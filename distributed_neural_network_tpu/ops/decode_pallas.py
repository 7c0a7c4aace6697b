"""Decode attention kernels (Pallas TPU, fwd-only): one query row a
sequence against its cached keys and values. Two entries, one a caller
each, and at the end of the file the two kernels of a LATENT pool (one row
`[c ; k_rope]` a position for all heads, serve/engine.py's latent
programs): `mla_decode_attention` (the absorbed form: all heads against a
fetch step's rows in one MXU product, the rows' first `rank` values also
the values) and `mla_prefill_attention` (the expanded form of a prefill
chunk: a head a grid step, a fetch step's rows up-projected for that head
in VMEM), both reading live pages through the block table as
`decode_paged_attention` does; after them the kernels of a pool whose row
holds every KV head's keys and values (grouped-query attention):
`gqa_decode_attention`, `split_gqa_decode_attention` (keys wider than
values) and its prefill sibling `split_gqa_prefill_attention`.

**`decode_paged_attention`** - the serving engine's decode step
(serve/engine.py `ServeEngine._decode_fn`, `decode_impl` "pallas", and
"auto" on a TPU). It takes the KV pools whole, as they lie in HBM at
``(L, slots, H, Dh)``, with the layer index, the block table and the
positions as scalar prefetch, and fetches each sequence's live pages
itself:

- **A page is the contiguous ``(block_size, H, Dh)`` tile of one block**
  (64 KiB at 16 rows of 16 heads of 128 in bfloat16). A fetch step
  copies up to `_FETCH_STEP_BYTES` of K pages and as many of V into one
  half of a double-buffered VMEM scratch, one async copy a page through
  the block table, while the step before it is computed on; the last
  step of a sequence starts the next sequence's first. A page wholly
  past ``pos[b]`` is neither copied nor computed on, so the traffic is
  the live positions rounded up to whole pages (`paged_read_positions`,
  the engine's ``serve_decode_positions_total{kind="read"}``), whatever
  the bucket's width.
- **All heads of a step in two MXU products**: a step's pages lie in
  VMEM as ``(pps, block_size, H, Dh)`` and are read as ONE matrix of
  ``pps * block_size * H`` rows of Dh, a position's H rows together (at
  16 heads of 128 in bfloat16 a position's rows are one tile, so the view
  costs no copy). ``S = q (H, Dh) . rows^T`` in the pool's dtype with
  float32 accumulation gives every head's score against every row, the
  positions along lanes; a select keeps the block diagonal (row r is
  head ``r mod H``'s) up to ``pos[b]``, the online softmax runs along
  lanes in float32 (m, l and acc are loop carries), and ``P . rows_V``,
  P in the pool's dtype, is every head's own value sum at once: P is
  nought off its diagonal. Nothing of a page is widened to float32.
  The boundary page is copied whole and a step's uncopied pages keep an
  older step's rows, so a sequence's last step sets its V rows past
  ``pos[b]`` to nought in VMEM before the product (0 x a stale NaN is
  NaN on the MXU); K's are dropped by the select.
- One `lax.fori_loop` over the batch's fetch steps, with the loops over
  a step's copies inside it, all on traced bounds: nothing is unrolled
  and every piece is traced once.
- **Measured on a TPU v5e** at the longdoc cell's shapes (24 layers,
  batch 16, tables of 128 pages): the 24 calls at 18,587 live positions
  take 5.03 ms, 89 % of the time HBM needs for the live K and V rows
  (91 % with every sequence at 2,047, 86 % at mixed page and step
  edges); its copies alone take 4.94 ms, so they bound it.
- It compiles where a pool row's ``(H, Dh)`` is whole tiles of a float
  pool (`paged_decode_ok`); an int8 pool, and heads of 64, take the
  engine's XLA route.

**`decode_cache_attention`** - `models/transformer.py generate`'s dense
``(B, H, total, Dh)`` cache, behind ``DNN_TPU_DECODE_IMPL=pallas`` (the
default there is the XLA chain). One fused pass over k blocks of up to
512 rows on a ``(B * H, total / bk)`` grid:

- **Dead-block skipping**: a block whose first column is past ``pos``
  skips compute under `pl.when` and clamps its index_map to the boundary
  block (already resident, no new DMA). ``pos`` rides scalar prefetch, a
  scalar (`generate`: every sequence at the same position) or ``(B,)``.
- **int8 K/V stream** (``k_scale``/``v_scale`` given): int8 caches with
  per-slot f32 scales, lane-replicated, dequantized in the k-block loop.
  No caller in the program since the engine reads its pool through
  `decode_paged_attention`; `tests/test_quant.py` holds its parity.
- **Single-row query on a (8, 128) grid**: the one real query row is
  lane-broadcast to 8 sublanes and row 0 of the output is read back.
- Numerics: f32 dot accumulation + f32 online-softmax recurrence
  (m/l/acc in VMEM scratch); parity with the XLA decode path is pinned
  by `tests/test_decode_pallas.py` up to blockwise reassociation.
- **Measured**: at the r5 probes' shapes (d512, cache <= 640) it LOSES
  to the XLA chain it replaces, 3.69 vs 2.59 ms/step at b16/hd64
  in-loop, which is why `generate` defaults to XLA. Under the
  engine's gathered bucket (until PR 30; Dh 128, 2,048 rows, batch 16)
  it took 14.8 ms of a 65.5 ms decode program at 32.5 % of its roofline
  (ledger, PR 29).

The reference framework has no attention at all (its model is the
5-layer CNN, `/root/reference/models/model.py:9-27`); these kernels are
part of the beyond-reference LM family's inference path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_pallas import _divisor_block, _struct

_LANES = 128
_SUBLANES = 8
_NEG_BIG = -1e30


def _dot_nt(a, b):
    """a (m, d) x b (n, d) -> (m, n), f32 accumulation (q @ k^T)."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dot_nn(a, b):
    """a (m, n) x b (n, d) -> (m, d), f32 accumulation (p @ v)."""
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc,
                   *, bk, scale, heads):
    bh, kj = pl.program_id(0), pl.program_id(1)
    n_k = pl.num_programs(1)
    pos = pos_ref[bh // heads]

    @pl.when(kj == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, _NEG_BIG, m_sc.dtype)
        l_sc[...] = jnp.zeros(l_sc.shape, l_sc.dtype)
        acc_sc[...] = jnp.zeros(acc_sc.shape, acc_sc.dtype)

    def _step():
        q = q_ref[0]  # (8, d) - row 0 real, rows 1-7 broadcast copies
        s = _dot_nt(q, k_ref[0]) * scale  # (8, bk) f32
        cols = kj * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols <= pos, s, _NEG_BIG)
        m = m_sc[...][:, :1]
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l_sc[...][:, :1] * alpha + p.sum(-1, keepdims=True)
        m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[...] = jnp.broadcast_to(l_new, l_sc.shape)
        acc_sc[...] = acc_sc[...] * alpha + _dot_nn(
            p.astype(v_ref.dtype), v_ref[0]
        )

    # a block whose first column is past pos is fully masked: skip it
    # (its index_map already re-points at the boundary block - no DMA)
    pl.when(kj * bk <= pos)(_step)

    @pl.when(kj == n_k - 1)
    def _finish():
        l = jnp.maximum(l_sc[...][:, :1], 1e-30)
        o_ref[0] = (acc_sc[...] / l).astype(o_ref.dtype)


def _decode_kernel_q8(pos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
                      m_sc, l_sc, acc_sc, *, bk, scale, heads):
    """int8-stream variant: k/v blocks arrive int8 with per-slot f32
    scales (lane-replicated); dequantization is fused into the k-block
    loop - the block is widened to the query dtype right before its dot,
    so the int8 bytes are all that ever crosses HBM for the cache."""
    bh, kj = pl.program_id(0), pl.program_id(1)
    n_k = pl.num_programs(1)
    pos = pos_ref[bh // heads]

    @pl.when(kj == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, _NEG_BIG, m_sc.dtype)
        l_sc[...] = jnp.zeros(l_sc.shape, l_sc.dtype)
        acc_sc[...] = jnp.zeros(acc_sc.shape, acc_sc.dtype)

    def _step():
        q = q_ref[0]  # (8, d) query dtype
        sk = ks_ref[0][:, :1]  # (bk, 1) f32 per-slot scales
        k_f = (k_ref[0].astype(jnp.float32) * sk).astype(q.dtype)
        s = _dot_nt(q, k_f) * scale  # (8, bk) f32
        cols = kj * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols <= pos, s, _NEG_BIG)
        m = m_sc[...][:, :1]
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l_sc[...][:, :1] * alpha + p.sum(-1, keepdims=True)
        m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[...] = jnp.broadcast_to(l_new, l_sc.shape)
        sv = vs_ref[0][:, :1]
        v_f = (v_ref[0].astype(jnp.float32) * sv).astype(q.dtype)
        acc_sc[...] = acc_sc[...] * alpha + _dot_nn(p.astype(q.dtype), v_f)

    pl.when(kj * bk <= pos)(_step)

    @pl.when(kj == n_k - 1)
    def _finish():
        l = jnp.maximum(l_sc[...][:, :1], 1e-30)
        o_ref[0] = (acc_sc[...] / l).astype(o_ref.dtype)


def decode_cache_attention(q, ck, cv, pos, *, block_k: int = 512,
                           interpret: bool = False,
                           k_scale=None, v_scale=None):
    """One cached decode step of attention for every (batch, head).

    q (B, H, Dh) - the current position's query rows;
    ck/cv (B, H, total, Dh) - the static KV caches, in q's dtype, OR
    int8 when ``k_scale``/``v_scale`` (B, H, total) f32 per-slot scales
    are given (the serving engine's quantized pool read: dequantization
    fuses into the k-block loop);
    pos - scalar int32 (every sequence at the same position - the
    `generate` loop) or (B,) int32 per-sequence positions (the serving
    engine's continuous batch; cols > pos[b] are dead for batch b).
    Returns o (B, H, Dh). `total` must admit a sublane-legal block
    (gate with `decode_kernel_ok(total)`; enforced here too, so a direct
    caller gets the documented ValueError instead of a Mosaic tiling
    failure deep in the compile); scale is 1/sqrt(Dh) applied here.
    """
    b, h, total, d = ck.shape
    quantized = k_scale is not None or v_scale is not None
    if quantized and (k_scale is None or v_scale is None):
        raise ValueError(
            "quantized decode needs BOTH k_scale and v_scale "
            "(per-slot f32, shape (B, H, total))"
        )
    bk = _divisor_block(block_k, total)
    if not decode_kernel_ok(total, block_k, quantized=quantized):
        raise ValueError(
            f"decode_cache_attention: cache size {total} admits no "
            f"sublane-legal k block at block_k={block_k} (largest "
            f"divisor {bk} is not a multiple of "
            f"{32 if quantized else 16}, the Mosaic sublane tile for "
            f"{'int8' if quantized else 'bf16'}) - pick a total with "
            "such a divisor (any multiple of 128 works) or fall back "
            "to the XLA decode path"
        )
    q8 = jnp.broadcast_to(
        q.reshape(b * h, 1, d), (b * h, _SUBLANES, d)
    )
    kf = ck.reshape(b * h, total, d)
    vf = cv.reshape(b * h, total, d)
    pos_arr = jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32).reshape(-1), (b,)
    ) if jnp.ndim(pos) <= 1 else None
    if pos_arr is None or pos_arr.shape != (b,):
        raise ValueError(
            f"pos must be a scalar or shape ({b},), got "
            f"{jnp.shape(pos)}"
        )

    def kv_index(b_, j, pos_ref):
        # skipped steps are the suffix (blocks past this sequence's
        # pos): re-point at the boundary block, which the last live
        # step left resident
        return (b_, jnp.minimum(j, pos_ref[b_ // h] // bk), 0)

    in_specs = [
        pl.BlockSpec((1, _SUBLANES, d), lambda b_, j, p_: (b_, 0, 0)),
        pl.BlockSpec((1, bk, d), kv_index),
        pl.BlockSpec((1, bk, d), kv_index),
    ]
    operands = [q8, kf, vf]
    if quantized:
        # per-slot scales ride lane-replicated (the flash lse layout):
        # a (total,) row vector is not a Mosaic-legal block
        ks_l = jnp.broadcast_to(
            k_scale.astype(jnp.float32).reshape(b * h, total)[..., None],
            (b * h, total, _LANES),
        )
        vs_l = jnp.broadcast_to(
            v_scale.astype(jnp.float32).reshape(b * h, total)[..., None],
            (b * h, total, _LANES),
        )
        in_specs += [
            pl.BlockSpec((1, bk, _LANES), kv_index),
            pl.BlockSpec((1, bk, _LANES), kv_index),
        ]
        operands += [ks_l, vs_l]
        kernel = functools.partial(
            _decode_kernel_q8, bk=bk, scale=1.0 / float(d) ** 0.5, heads=h
        )
    else:
        kernel = functools.partial(
            _decode_kernel, bk=bk, scale=1.0 / float(d) ** 0.5, heads=h
        )

    o = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * h, total // bk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, _SUBLANES, d), lambda b_, j, p_: (b_, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((_SUBLANES, _LANES), jnp.float32),  # running max
                pltpu.VMEM((_SUBLANES, _LANES), jnp.float32),  # denom
                pltpu.VMEM((_SUBLANES, d), jnp.float32),       # accumulator
            ],
        ),
        out_shape=_struct((b * h, _SUBLANES, d), q.dtype, q, ck, cv),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="decode_paged_attn",
    )(pos_arr, *operands)
    return o[:, 0].reshape(b, h, d)


def decode_kernel_ok(total: int, block_k: int = 512, *,
                     quantized: bool = False) -> bool:
    """True when the kernel's block constraints hold at this cache size:
    the chosen k block must be sublane-tileable for EVERY supported
    cache dtype - bf16's Mosaic tile is (16, 128), f32's is (8, 128),
    so the gate requires the stricter 16 (the head-dim block is always
    the full axis, which Mosaic accepts at any size); int8/fp8 caches
    (``quantized=True``) tile at (32, 128), so their gate requires 32.
    Pass the same block_k the kernel will run with - the gate validates
    the block actually used. Tiny or awkward totals fall back to the
    XLA path."""
    tile = 4 * _SUBLANES if quantized else 2 * _SUBLANES
    return _divisor_block(block_k, total) % tile == 0


# ------------------------------------------------- paged pool (serving)

# K bytes one fetch step moves into VMEM (and as many of V): a page alone
# is 64 KiB at the served shape, 0.08 us of HBM time, so a step gathers
# pages until the copy in flight covers the compute on the one before it
_FETCH_STEP_BYTES = 1 << 19
# the two scratches hold two fetch steps each, of at least a page: four
# pages have to fit VMEM beside everything else
_PAGE_BYTES_MAX = 1 << 20


def _pages_per_step(page_bytes: int, width: int) -> int:
    return max(1, min(width, _FETCH_STEP_BYTES // page_bytes))


def _paged_kernel(l_ref, table_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
                  k_buf, v_buf, sem, *, bs, pps, scale):
    """One loop over the batch's fetch steps, sequence after sequence, each
    of up to ``pps`` pages: iteration g starts the copies of step g (HBM
    -> one half of the VMEM scratch, a page a copy, through the block
    table) and computes on step g - 1 in the other half, so a sequence's
    last step overlaps the next sequence's first. A page wholly past
    ``pos[b]`` is neither copied nor computed on. Positions are never
    negative, so the divisions truncate (`lax.div`: a floor division's
    sign fix-up is a quarter of this kernel's lowering time)."""
    n_seq, h, d = q_ref.shape
    n_rows = pps * bs * h
    layer = l_ref[0]
    div = jax.lax.div

    def steps_of(b):
        return div(pos_ref[b], pps * bs) + 1

    def live_pages(b, i):
        return jnp.minimum(pps, div(pos_ref[b], bs) + 1 - i * pps)

    def copies(rows, slot, j):
        return (
            pltpu.make_async_copy(
                k_hbm.at[layer, rows], k_buf.at[slot, j], sem.at[0, slot]),
            pltpu.make_async_copy(
                v_hbm.at[layer, rows], v_buf.at[slot, j], sem.at[1, slot]),
        )

    n_steps = jax.lax.fori_loop(
        0, n_seq, lambda b, n: n + steps_of(b), jnp.int32(0))

    def step(g, carry):
        # (b, i): the step to fetch. (pb, pi), n_pages, last: the step
        # fetched last time round, its live pages and whether it ends its
        # sequence - the one to compute on. m/l/acc: the online softmax
        b, i, pb, pi, n_pages, last, m, l, acc = carry
        slot = jax.lax.rem(g, 2)
        bf = jnp.minimum(b, n_seq - 1)
        n_fetch = jnp.where(g < n_steps, live_pages(bf, i), 0)

        def start(j, c):
            blk = table_ref[bf, i * pps + j]
            for copy in copies(pl.ds(blk * bs, bs), slot, j):
                copy.start()
            return c

        jax.lax.fori_loop(0, n_fetch, start, 0)

        def wait(j, c):
            # a wait needs the copy's size and semaphore, not its source
            for copy in copies(pl.ds(0, bs), 1 - slot, j):
                copy.wait()
            return c

        jax.lax.fori_loop(0, n_pages, wait, 0)

        def compute():
            # the step's positions up to pos[pb]; past them lie the rest of
            # the boundary page and the pages not copied, which still hold
            # an older step: only a sequence's last step has any
            n_live = pos_ref[pb] - pi * pps * bs + 1

            @pl.when(last)
            def _clear_dead_values():
                # a dead row's p is 0, but 0 x a stale inf or nan is not
                v = v_buf[1 - slot]                     # (pps, bs, H, Dh)
                at = bs * jax.lax.broadcasted_iota(jnp.int32, v.shape, 0) + (
                    jax.lax.broadcasted_iota(jnp.int32, v.shape, 1))
                v_buf[1 - slot] = jnp.where(at < n_live, v, jnp.zeros_like(v))

            # the step as one matrix of rows, a position's H rows together:
            # row r is head r mod H's at the step's position r div H
            k = k_buf[1 - slot].reshape(n_rows, d)
            v = v_buf[1 - slot].reshape(n_rows, d)
            s = _dot_nt(q_ref[pb].astype(k.dtype), k) * scale  # (H, rows)
            r = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            own = jax.lax.rem(r, h) == jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            s = jnp.where(own & (r < n_live * h), s, _NEG_BIG)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            # P is nought off its block diagonal, so this one product is
            # every head's own value sum
            return (m_new, l * alpha + p.sum(axis=-1, keepdims=True),
                    acc * alpha + _dot_nn(p.astype(v.dtype), v))

        m, l, acc = jax.lax.cond(n_pages > 0, compute, lambda: (m, l, acc))

        @pl.when(last)
        def _write():
            o_ref[pb] = (acc / l).astype(o_ref.dtype)

        ends = i + 1 == steps_of(bf)
        return (
            jnp.where(ends, b + 1, b), jnp.where(ends, 0, i + 1),
            bf, i, n_fetch, jnp.logical_and(ends, g < n_steps),
            jnp.where(last, _NEG_BIG, m), jnp.where(last, 0.0, l),
            jnp.where(last, 0.0, acc),
        )

    zero = jnp.int32(0)
    jax.lax.fori_loop(0, n_steps + 1, step, (
        zero, zero, zero, zero, zero, False,
        jnp.full((h, 1), _NEG_BIG, jnp.float32),
        jnp.zeros((h, 1), jnp.float32),
        jnp.zeros((h, d), jnp.float32),
    ))


def decode_paged_attention(q, k_pool, v_pool, layer, table, pos, *,
                           block_size: int, interpret: bool = False):
    """One decode step of attention for every (sequence, head), read from
    the serving engine's paged KV pool where it lies.

    q (B, H, Dh) - the current position's query rows; k_pool/v_pool
    (L, slots, H, Dh) - the whole pools, left in HBM (a page is the
    contiguous ``(block_size, H, Dh)`` tile of one block); ``layer`` - the
    layer to read, a scalar that may be traced (the engine's layer scan);
    table (B, W) int32 - each sequence's block ids in order, entries past
    its live pages unread; pos (B,) int32 - positions 0..pos[b] are
    attended. Returns o (B, H, Dh) in q's dtype. Scores are the product
    of q and the K rows in the pool's dtype, accumulated in float32 and
    scaled by 1/sqrt(Dh) after it; the online softmax is float32; the
    value sum is the product of the probabilities, rounded to the pool's
    dtype, and the V rows, accumulated in float32 (a float32 pool keeps
    float32 operands throughout). Gate a compiled call with
    `paged_decode_ok`."""
    b, h, d = q.shape
    if v_pool.shape != k_pool.shape or k_pool.shape[2:] != (h, d):
        raise ValueError(
            f"pools {k_pool.shape} / {v_pool.shape} do not hold q's "
            f"(H, Dh) = ({h}, {d}) rows"
        )
    if table.shape[0] != b or pos.shape != (b,):
        raise ValueError(
            f"table {table.shape} and pos {pos.shape} do not describe "
            f"q's batch of {b}"
        )
    if not interpret and not paged_decode_ok(
            block_size, h, d, k_pool.dtype):
        raise ValueError(
            f"decode_paged_attention: a page of (block_size {block_size}, "
            f"H {h}, Dh {d}) {k_pool.dtype} rows is no tile this kernel "
            "compiles for (paged_decode_ok) - fall back to the XLA decode "
            "path"
        )
    page_bytes = block_size * h * d * k_pool.dtype.itemsize
    pps = _pages_per_step(page_bytes, table.shape[1])
    buf = pltpu.VMEM((2, pps, block_size, h, d), k_pool.dtype)
    return pl.pallas_call(
        functools.partial(
            _paged_kernel, bs=block_size, pps=pps,
            scale=1.0 / float(d) ** 0.5,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2))],
        ),
        out_shape=_struct((b, h, d), q.dtype, q, k_pool, v_pool),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="decode_paged_attn",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        table.astype(jnp.int32), pos.astype(jnp.int32),
        q, k_pool, v_pool,
    )


def paged_decode_ok(block_size: int, n_heads: int, head_dim: int,
                    dtype) -> bool:
    """True where `decode_paged_attention` compiles: a page is copied and
    computed on as it lies, so a pool row's (H, Dh) has to be tiles the
    compiler has for the pool's dtype - Dh whole 128-lane rows and H
    whole 8-sublane tiles (or the small tiles of 2 and 4 heads), float32
    or bfloat16 (tests/test_tpu_aot_compile.py compiles them for a
    described v5e) - and a page has to fit its share of VMEM. An int8
    pool is not read by this kernel: its per-(block, head) scales would
    need a copy of their own a page."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    page_bytes = block_size * n_heads * head_dim * dtype.itemsize
    return ((n_heads % _SUBLANES == 0 or n_heads in (2, 4))
            and head_dim % _LANES == 0 and page_bytes <= _PAGE_BYTES_MAX)


def paged_read_positions(pos, block_size: int) -> int:
    """The cache positions whose pages `decode_paged_attention` fetches
    for a batch at ``pos`` (host array): every sequence's live positions
    rounded up to whole pages."""
    return int(((pos // block_size + 1) * block_size).sum())


# ------------------------------------- latent pool (MLA, absorbed form)

# the positions one fetch step brings into VMEM: several pages, so that the
# probabilities' product with the values has a contraction worth the MXU's
# while (a page alone is 64 rows) and a copy in flight covers the compute
_MLA_STEP_POSITIONS = 512
# q, o, the two buffers and the accumulator at the served shape are 11 MiB:
# over the compiler's default scope, well inside the chip's 128 MiB
_MLA_VMEM_BYTES = 64 << 20


def _fetch_step_loop(l_ref, table_ref, pos_ref, q_ref, pool_hbm, o_ref, buf,
                     acc_ref, sem, *, bs, pps, scores, weigh, round_p):
    """`_paged_kernel`'s loop over the batch's fetch steps, for the kernels
    that read ONE pool of rows (latent, grouped-query): a step's pages land
    one under the other in a half of ``buf`` (pps * bs, row) while the step
    before is computed on from the other half. A step's products are the
    kernel's: ``scores(q, rows) -> (s, ctx)``, the sequence's query rows'
    scaled float32 scores (H', rows), and ``weigh(p, ctx) -> (H', v)``, the
    probabilities against the values; between them the online softmax over
    all query rows at once, its probabilities in the pool's dtype where
    ``round_p`` (the running sum then adds them as rounded). Pages past
    ``pos[b]`` are not copied; what a half still holds from an earlier step
    (or the zeros it starts with) lies past ``pos`` and weighs nought."""
    n_seq = q_ref.shape[0]
    layer = l_ref[0]
    div = jax.lax.div
    step_rows = pps * bs

    def steps_of(b):
        return div(pos_ref[b], step_rows) + 1

    def live_pages(b, i):
        return jnp.minimum(pps, div(pos_ref[b], bs) + 1 - i * pps)

    def copy(rows, slot, j):
        return pltpu.make_async_copy(
            pool_hbm.at[layer, rows], buf.at[slot, pl.ds(j * bs, bs)],
            sem.at[slot])

    buf[...] = jnp.zeros(buf.shape, buf.dtype)
    n_steps = jax.lax.fori_loop(
        0, n_seq, lambda b, n: n + steps_of(b), jnp.int32(0))

    def step(g, carry):
        # (b, i): the step to fetch; (pb, pi), n_pages, last: the step
        # fetched last time round - the one to compute on; m, l: the
        # online softmax's running maximum and sum (the weighted values'
        # sum is `acc_ref`)
        b, i, pb, pi, n_pages, last, m, l = carry
        slot = jax.lax.rem(g, 2)
        bf = jnp.minimum(b, n_seq - 1)
        n_fetch = jnp.where(g < n_steps, live_pages(bf, i), 0)

        def start(j, c):
            blk = table_ref[bf, i * pps + j]
            copy(pl.ds(blk * bs, bs), slot, j).start()
            return c

        jax.lax.fori_loop(0, n_fetch, start, 0)

        def wait(j, c):
            copy(pl.ds(0, bs), 1 - slot, j).wait()
            return c

        jax.lax.fori_loop(0, n_pages, wait, 0)
        first = pi == 0
        m = jnp.where(first, _NEG_BIG, m)
        l = jnp.where(first, 0.0, l)

        def compute():
            rows = buf[1 - slot]                          # (rows, row)
            s, ctx = scores(q_ref[pb], rows)           # (H', rows) f32
            at = pi * step_rows + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(at <= pos_ref[pb], s, _NEG_BIG)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            if round_p:
                p = p.astype(rows.dtype)
            alpha = jnp.exp(m - m_new)
            pv = weigh(p, ctx)
            acc_ref[...] = jnp.where(first, 0.0, acc_ref[...] * alpha) + pv
            return m_new, l * alpha + p.astype(jnp.float32).sum(
                axis=-1, keepdims=True)

        m, l = jax.lax.cond(n_pages > 0, compute, lambda: (m, l))

        @pl.when(last)
        def _write():
            o_ref[pb] = (acc_ref[...] / l).astype(o_ref.dtype)

        ends = i + 1 == steps_of(bf)
        return (
            jnp.where(ends, b + 1, b), jnp.where(ends, 0, i + 1),
            bf, i, n_fetch, jnp.logical_and(ends, g < n_steps), m, l,
        )

    zero = jnp.int32(0)
    h = q_ref.shape[1]
    jax.lax.fori_loop(0, n_steps + 1, step, (
        zero, zero, zero, zero, zero, False,
        jnp.full((h, 1), _NEG_BIG, jnp.float32),
        jnp.zeros((h, 1), jnp.float32),
    ))


def _mla_paged_kernel(*refs, bs, pps, rank, scale):
    """`_fetch_step_loop` over a pool of latent rows (pps * bs, W): all H
    heads score a step's rows in ONE product on the MXU, ``q (H, W) .
    rows^T``; the float32 probabilities weigh the rows' first ``rank``
    values, ``p (H, pps * bs) . rows[:, :rank]``: K and V are the same
    bytes, fetched once for all heads."""
    _fetch_step_loop(
        *refs, bs=bs, pps=pps, round_p=False,
        scores=lambda q, rows: (_dot_nt(q, rows) * scale, rows),
        weigh=lambda p, rows: _dot_nn(p.astype(rows.dtype), rows[:, :rank]))


def mla_decode_attention(q_lat, pool, layer, table, pos, *, block_size: int,
                         rank: int, scale: float, interpret: bool = False):
    """One decode step of absorbed-form latent attention for every
    sequence, all heads at once, read from the serving engine's latent pool
    where it lies.

    q_lat (B, H, W) - each head's query in the cache row's space (`W_uk
    q_nope` beside the rotated `q_rope`); pool (L, slots, W) - the whole
    pool, left in HBM (a page is the contiguous ``(block_size, W)`` tile of
    one block; a row is `[c ; k_rope]`, its first ``rank`` values also the
    values); ``layer`` a scalar that may be traced; table (B, W_blocks)
    int32, entries past a sequence's live pages unread; pos (B,) int32 -
    positions 0..pos[b] are attended. Returns the latent-space output (B,
    H, rank) in q_lat's dtype: `sum_j p(j) c(j)`, the `W_uv` and `W_o`
    products left to the caller. Scores, the online softmax and the
    accumulator are float32. Gate a compiled call with `mla_decode_ok`."""
    b, h, w = q_lat.shape
    if pool.ndim != 3 or pool.shape[2] != w or not 0 < rank <= w:
        raise ValueError(
            f"pool {pool.shape} does not hold q_lat's rows of {w} with "
            f"{rank} values")
    if table.shape[0] != b or pos.shape != (b,):
        raise ValueError(
            f"table {table.shape} and pos {pos.shape} do not describe "
            f"q_lat's batch of {b}")
    if not interpret and not mla_decode_ok(block_size, w, rank, pool.dtype):
        raise ValueError(
            f"mla_decode_attention: pages of {block_size} {pool.dtype} rows "
            f"of {w} ({rank} of them values) are no tile this kernel "
            "compiles for (mla_decode_ok) - fall back to the XLA decode path")
    pps = max(1, min(table.shape[1], _MLA_STEP_POSITIONS // block_size))
    return pl.pallas_call(
        functools.partial(_mla_paged_kernel, bs=block_size, pps=pps,
                          rank=rank, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((2, pps * block_size, w), pool.dtype),
                pltpu.VMEM((h, rank), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=_struct((b, h, rank), q_lat.dtype, q_lat, pool),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_MLA_VMEM_BYTES,
        ),
        interpret=interpret,
        name="mla_decode_attn",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        table.astype(jnp.int32), pos.astype(jnp.int32), q_lat, pool,
    )


def mla_decode_ok(block_size: int, width: int, rank: int, dtype) -> bool:
    """True where `mla_decode_attention` compiles: a page is copied under
    the step's other pages, so its rows have to be whole sublane tiles of
    the pool's dtype (16 rows of bfloat16, 8 of float32), and a row and its
    values whole 128-lane tiles: the device stores a pool's minor axis in
    such tiles whatever its length, and a copy cannot end inside one, so
    the engine pads the latent row (576 values) to 640
    (tests/test_tpu_aot_compile.py compiles the served shape for a
    described v5e)."""
    dtype = jnp.dtype(dtype)
    tile = {jnp.dtype(jnp.bfloat16): 2 * _SUBLANES,
            jnp.dtype(jnp.float32): _SUBLANES}.get(dtype)
    return (tile is not None and block_size % tile == 0
            and width % _LANES == 0 and rank % _LANES == 0)


# ------------------------------- latent pool, chunked prefill (expanded)

# cache positions one fetch step of the prefill kernel brings into VMEM and
# expands: a head's scores against them are (chunk, 1024) float32, 2 MiB
_MLA_PREFILL_KEYS = 1024


def _mla_prefill_kernel(l_ref, table_ref, span_ref, qn_ref, qr_ref, w_ref,
                        pool_hbm, o_ref, buf, sem, *, bs, pps, rank, nope,
                        scale):
    """One head a grid step: the chunk's queries of this head against the
    cache positions ``0 .. n_keys - 1``, a fetch step of ``pps`` pages at a
    time, double-buffered. A step's latent rows are EXPANDED in VMEM for
    this head alone (``rows[:, :rank] . W_kvb,h`` -> its k_nope and v), its
    scores are ``q_nope . k_nope^T + q_rope . k_rope^T`` (the rotary key
    read from the rows as it lies, lanes ``rank ..``, the row's padding
    against the query's), and an online softmax folds them into the
    float32 accumulator: neither the expanded keys and values nor a score
    leaves the chip. Pages past the last key are not copied."""
    layer, pos0, n_keys = l_ref[0], span_ref[0], span_ref[1]
    div = jax.lax.div
    step_rows = pps * bs
    n_steps = div(n_keys + step_rows - 1, step_rows)
    n_pages_all = div(n_keys + bs - 1, bs)
    c = qn_ref.shape[1]

    def live_pages(i):
        return jnp.clip(n_pages_all - i * pps, 0, pps)

    def copy(rows, slot, j):
        return pltpu.make_async_copy(
            pool_hbm.at[layer, rows], buf.at[slot, pl.ds(j * bs, bs)],
            sem.at[slot])

    def fetch(i, slot):
        def start(j, carry):
            blk = table_ref[i * pps + j]
            copy(pl.ds(blk * bs, bs), slot, j).start()
            return carry
        jax.lax.fori_loop(0, live_pages(i), start, 0)

    @pl.when(pl.program_id(0) == 0)
    def _clear():   # what no copy has written yet must be finite
        buf[...] = jnp.zeros(buf.shape, buf.dtype)

    fetch(0, 0)
    q_nope, q_rope = qn_ref[0], qr_ref[0]                   # (C, .)
    w = w_ref[...]                                          # (rank, nope+v)
    qpos = pos0 + jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)

    def step(i, carry):
        m, l, acc = carry
        slot = jax.lax.rem(i, 2)
        fetch(i + 1, 1 - slot)      # no page past the last key: no copy

        def wait(j, carry):
            copy(pl.ds(0, bs), slot, j).wait()
            return carry

        jax.lax.fori_loop(0, live_pages(i), wait, 0)
        rows = buf[slot]                                    # (rows, W)
        kv = _dot_nn(rows[:, :rank], w).astype(rows.dtype)  # (rows, nope+v)
        s = _dot_nt(q_nope, kv[:, :nope]) + _dot_nt(
            q_rope, rows[:, rank:rank + q_rope.shape[1]])
        kpos = i * step_rows + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(kpos <= qpos, s * scale, _NEG_BIG)    # (C, rows)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        acc = acc * alpha + _dot_nn(p.astype(rows.dtype), kv[:, nope:])
        return m_new, l * alpha + p.sum(axis=-1, keepdims=True), acc

    m, l, acc = jax.lax.fori_loop(0, n_steps, step, (
        jnp.full((c, 1), _NEG_BIG, jnp.float32),
        jnp.zeros((c, 1), jnp.float32),
        jnp.zeros((c, w.shape[1] - nope), jnp.float32),
    ))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def mla_prefill_attention(q_nope, q_rope, w_kvb, pool, layer, table, pos0,
                          n_keys, *, block_size: int, rank: int,
                          scale: float, interpret: bool = False):
    """Expanded-form latent attention of one sequence's prefill chunk over
    its cache, read from the serving engine's latent pool where it lies.

    q_nope (H, C, nope), q_rope (H, C, R) - the chunk's queries, heads
    first, the rotary part padded with noughts to whole 128-lane tiles;
    w_kvb (rank, H * (nope + v)) - a layer's up-projection as the tree
    holds it, head h's columns `[W_uk | W_uv]`; pool (L, slots, W) - the
    whole pool in HBM, a row `[c (rank) ; k_rope ; noughts]`, ``W >= rank
    + R``; ``layer``, ``pos0`` (the chunk's first position) and ``n_keys``
    (cache positions 0..n_keys - 1 are live; the chunk's own rows are
    among them) scalars that may be traced; table (W_blocks,) int32. Query
    i sees key positions <= pos0 + i. Returns o (H, C, v) in q's dtype.
    Gate a compiled call with `mla_prefill_ok`."""
    h, c, nope = q_nope.shape
    width = w_kvb.shape[1] // h
    if (q_rope.shape[:2] != (h, c) or w_kvb.shape[0] != rank
            or pool.ndim != 3 or pool.shape[2] < rank + q_rope.shape[2]):
        raise ValueError(
            f"q_nope {q_nope.shape}, q_rope {q_rope.shape}, w_kvb "
            f"{w_kvb.shape} and pool {pool.shape} do not describe one "
            f"chunk's heads over rows of {rank} + {q_rope.shape[2]}")
    if not interpret and not mla_prefill_ok(
            block_size, pool.shape[2], rank, nope, width - nope,
            q_rope.shape[2], pool.dtype):
        raise ValueError(
            f"mla_prefill_attention: pages of {block_size} {pool.dtype} "
            f"rows of {pool.shape[2]} with heads of {nope} + {width - nope} "
            "are no tiles this kernel compiles for (mla_prefill_ok)")
    pps = max(1, min(table.shape[0], _MLA_PREFILL_KEYS // block_size))
    return pl.pallas_call(
        functools.partial(_mla_prefill_kernel, bs=block_size, pps=pps,
                          rank=rank, nope=nope, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(h,),
            in_specs=[
                pl.BlockSpec((1, c, nope), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((1, c, q_rope.shape[2]),
                             lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((rank, width), lambda i, *_: (0, i)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, c, width - nope),
                                   lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pps * block_size, pool.shape[2]), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=_struct((h, c, width - nope), q_nope.dtype, q_nope, pool),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_MLA_VMEM_BYTES,
        ),
        interpret=interpret,
        name="mla_prefill_attn",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), table.astype(jnp.int32),
        jnp.stack([jnp.asarray(pos0, jnp.int32),
                   jnp.asarray(n_keys, jnp.int32)]),
        q_nope, q_rope, w_kvb, pool,
    )


def mla_prefill_ok(block_size: int, width: int, rank: int, nope: int,
                   v: int, rope: int, dtype) -> bool:
    """True where `mla_prefill_attention` compiles: `mla_decode_ok`'s pages,
    and every slice the kernel cuts (the latent, a head's keys and values,
    the rotary key with the row's padding) whole 128-lane tiles."""
    return (mla_decode_ok(block_size, width, rank, dtype)
            and all(n % _LANES == 0 for n in (nope, v, rope))
            and width >= rank + rope)


# --------------------------- grouped-query pool (K and V side by side)

# the positions one fetch step of a grouped-query kernel brings into VMEM (1
# MiB of bfloat16 rows of 1,024 values, 1.25 MiB of split rows of 1,280: a
# copy in flight covers the compute on the step before it)
_GQA_STEP_POSITIONS = 512


def _by_group(p, values):
    """Each group's probabilities (its run of ``p``'s rows) against its own
    values, the groups' results stacked: (H', v)."""
    sub = p.shape[0] // len(values)
    return jnp.concatenate([_dot_nn(p[k * sub:(k + 1) * sub], v)
                            for k, v in enumerate(values)], axis=0)


def _gqa_paged_kernel(*refs, bs, pps, groups, scale):
    """`_fetch_step_loop` over a pool whose row holds every KV head's keys
    and values side by side, one 128-lane tile a KV head (pps * bs, groups *
    128): KV head g's tile is read ONCE for its query heads, ``q_g (8, 128)
    . tile_g^T`` on the MXU (the queries lie in the tile's key lanes,
    noughts against its value lanes; a group's spare query rows are
    noughts), the online softmax over all groups' scores at once, then ``p_g
    (8, rows) . tile_g``, whose value lanes are the weighted values (the key
    lanes come out as well and the caller drops them: the tile is multiplied
    as it lies)."""
    q_ref = refs[3]
    sub = q_ref.shape[1] // groups      # query rows a group: 8 sublanes
    lanes = q_ref.shape[2]              # a KV head's [k ; v]: 128 lanes

    def scores(q, rows):
        tiles = [rows[:, k * lanes:(k + 1) * lanes] for k in range(groups)]
        return jnp.concatenate([
            _dot_nt(q[k * sub:(k + 1) * sub], tiles[k])
            for k in range(groups)], axis=0) * scale, tiles

    _fetch_step_loop(*refs, bs=bs, pps=pps, round_p=True, scores=scores,
                     weigh=_by_group)


def gqa_decode_attention(q, pool, layer, table, pos, *, block_size: int,
                         n_kv_heads: int, interpret: bool = False):
    """One decode step of grouped-query attention for every sequence, read
    from the serving engine's KV pool where it lies.

    q (B, H, Dh) - the current position's query rows, H a multiple of
    ``n_kv_heads`` (query head h reads KV head ``h // (H / n_kv_heads)``);
    pool (L, slots, n_kv_heads * 2 * Dh) - the whole pool, left in HBM: a
    row is ``[k_0 ; v_0 ; k_1 ; v_1 ; ...]`` (models/lfm2_moe.py `attn_in`),
    a page the contiguous ``(block_size, row)`` tile of one block; ``layer``
    a scalar that may be traced; table (B, W) int32, entries past a
    sequence's live pages unread; pos (B,) int32 - positions 0..pos[b] are
    attended. Returns o (B, H, Dh) in q's dtype. Scores are scaled by
    1/sqrt(Dh); scores, the online softmax and the accumulator are float32.
    Gate a compiled call with `gqa_decode_ok`.

    Around the one Mosaic call the queries are laid out as the kernel reads
    them - a group's heads on the first rows of an 8-sublane tile, in the
    key lanes of a KV head's 128 - and the value lanes of its output are
    cut out again: plain XLA on (B, H, 128) values."""
    b, h, d = q.shape
    if pool.ndim != 3 or pool.shape[2] != n_kv_heads * 2 * d or (
            h % n_kv_heads):
        raise ValueError(
            f"pool {pool.shape} does not hold K and V of {n_kv_heads} KV "
            f"heads of {d} for q's {h} heads")
    if table.shape[0] != b or pos.shape != (b,):
        raise ValueError(
            f"table {table.shape} and pos {pos.shape} do not describe "
            f"q's batch of {b}")
    per = h // n_kv_heads
    if not interpret and not gqa_decode_ok(
            block_size, n_kv_heads, per, d, pool.dtype):
        raise ValueError(
            f"gqa_decode_attention: pages of {block_size} {pool.dtype} rows "
            f"of {n_kv_heads} x 2 x {d} with {per} queries a KV head are no "
            "tiles this kernel compiles for (gqa_decode_ok) - fall back to "
            "the XLA decode path")
    sub = -(-per // _SUBLANES) * _SUBLANES
    qk = jnp.zeros((b, n_kv_heads, sub, 2 * d), q.dtype).at[
        :, :, :per, :d].set(q.reshape(b, n_kv_heads, per, d))
    pps = max(1, min(table.shape[1], _GQA_STEP_POSITIONS // block_size))
    o = pl.pallas_call(
        functools.partial(_gqa_paged_kernel, bs=block_size, pps=pps,
                          groups=n_kv_heads, scale=1.0 / float(d) ** 0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((2, pps * block_size, pool.shape[2]), pool.dtype),
                pltpu.VMEM((n_kv_heads * sub, 2 * d), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=_struct((b, n_kv_heads * sub, 2 * d), q.dtype, q, pool),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_MLA_VMEM_BYTES,
        ),
        interpret=interpret,
        name="gqa_decode_attn",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        table.astype(jnp.int32), pos.astype(jnp.int32),
        qk.reshape(b, n_kv_heads * sub, 2 * d), pool,
    )
    return o.reshape(b, n_kv_heads, sub, 2 * d)[:, :, :per, d:].reshape(
        b, h, d)


def gqa_decode_ok(block_size: int, n_kv_heads: int, per_kv: int,
                  head_dim: int, dtype) -> bool:
    """True where `gqa_decode_attention` compiles: `mla_decode_ok`'s pages
    (whole sublane tiles of the pool's dtype), a KV head's keys and values
    together one 128-lane tile (heads of 64), and a group's queries within
    one 8-sublane tile (tests/test_tpu_aot_compile.py compiles the served
    shape for a described v5e)."""
    return (mla_decode_ok(block_size, n_kv_heads * 2 * head_dim, _LANES,
                          dtype)
            and 2 * head_dim == _LANES and 1 <= per_kv <= _SUBLANES)


# ---------------- grouped-query pool, keys wider than values (split row)

def _split_query_lanes(qg, rope: int, tile: int, rows: int):
    """Queries (..., KV, per, qk), each head's rotated part first, laid out
    as the split-row kernels read them: (..., KV, rows, nope + tile), a KV
    head's query heads on its first ``per`` rows, each head's unrotated part
    in the first lanes and its rotated part in its own lanes of the
    ``tile`` lanes that hold the rotated keys (the lane tile that holds them,
    two heads' at 64, or all heads' where they fill less than one),
    noughts elsewhere."""
    *lead, kv, per, qk = qg.shape
    nope = qk - rope
    qa = jnp.zeros((*lead, kv, rows, nope + tile), qg.dtype).at[
        ..., :per, :nope].set(qg[..., rope:])
    for k in range(kv):
        at = nope + k * rope % tile
        qa = qa.at[..., k, :per, at:at + rope].set(qg[..., k, :, :rope])
    return qa


def _split_gqa_paged_kernel(*refs, bs, pps, groups, nope, rope, rope_tile,
                            vdim, scale):
    """`_fetch_step_loop` over a pool whose row keeps the KV heads' unrotated
    key parts, their rotated parts and their values apart, each part a run
    of whole lane tiles: `[k_nope_0 ; ... ; k_rope_0 ; ... ; v_0 ; ...]`. KV
    head g's three slices are read ONCE for its query rows (a group's rows
    are ``sub`` sublanes, two tiles at 16 queries a KV head): scores are
    ``q_nope_g . nope_g^T + q_rope_g . rope_tile^T`` (the group's rotated
    queries lie in their head's lanes of the ``rope_tile`` lanes that hold
    it, noughts against the other heads'), the online softmax over all
    groups' scores at once, then ``p_g . v_g``."""
    sub = refs[3].shape[1] // groups
    rope_at, v_at = groups * nope, groups * (nope + rope)

    def scores(q, rows):
        s, values = [], []
        for k in range(groups):
            qk = q[k * sub:(k + 1) * sub]
            at = rope_at + (k * rope // rope_tile) * rope_tile
            s.append(_dot_nt(qk[:, :nope], rows[:, k * nope:(k + 1) * nope])
                     + _dot_nt(qk[:, nope:], rows[:, at:at + rope_tile]))
            values.append(rows[:, v_at + k * vdim:v_at + (k + 1) * vdim])
        return jnp.concatenate(s, axis=0) * scale, values

    _fetch_step_loop(*refs, bs=bs, pps=pps, round_p=True, scores=scores,
                     weigh=_by_group)


def split_gqa_decode_attention(q, pool, layer, table, pos, *, block_size: int,
                               n_kv_heads: int, rope: int, v_dim: int,
                               interpret: bool = False):
    """One decode step of grouped-query attention whose keys are wider than
    its values, read from the serving engine's KV pool where it lies.

    q (B, H, qk) - the current position's query rows, each head's first
    ``rope`` values its rotated part (models/mimo_v2.py `qkv`), H a multiple
    of ``n_kv_heads`` (query head h reads KV head ``h // (H / n_kv_heads)``);
    pool (L, slots, n_kv_heads * (qk + v_dim)) - the whole pool, left in HBM:
    a row is every KV head's unrotated key part, then every head's rotated
    part, then every head's values (models/mimo_v2.py `to_row`), a page the
    contiguous ``(block_size, row)`` tile of one block; ``layer`` a scalar
    that may be traced; table (B, W) int32, entries past a sequence's live
    pages unread; pos (B,) int32 - positions 0..pos[b] are attended. Returns
    o (B, H, v_dim) in q's dtype. Scores are scaled by 1/sqrt(qk); scores
    and the value sum are MXU products in the pool's dtype with float32
    accumulation, the online softmax float32. Gate a compiled call with
    `split_gqa_decode_ok`.

    Around the one Mosaic call the queries are laid out as the kernel reads
    them - a group's heads on the first rows of its sublane tiles, the
    unrotated part in the first lanes and the rotated part in its head's
    lanes of the rotated keys' tile - and the group's spare rows are cut off
    the output again: plain XLA on (B, H, qk) values."""
    b, h, qk = q.shape
    nope = qk - rope
    row = n_kv_heads * (qk + v_dim)
    if pool.ndim != 3 or pool.shape[2] != row or h % n_kv_heads:
        raise ValueError(
            f"pool {pool.shape} does not hold keys of {qk} and values of "
            f"{v_dim} for {n_kv_heads} KV heads under q's {h} heads")
    if table.shape[0] != b or pos.shape != (b,):
        raise ValueError(
            f"table {table.shape} and pos {pos.shape} do not describe "
            f"q's batch of {b}")
    per = h // n_kv_heads
    if not interpret and not split_gqa_decode_ok(
            block_size, n_kv_heads, per, qk, rope, v_dim, pool.dtype):
        raise ValueError(
            f"split_gqa_decode_attention: pages of {block_size} {pool.dtype} "
            f"rows of {n_kv_heads} x ({qk} + {v_dim}) with {per} queries a KV "
            "head are no tiles this kernel compiles for (split_gqa_decode_ok)"
            " - fall back to the XLA decode path")
    sub = -(-per // _SUBLANES) * _SUBLANES
    tile = min(_LANES, n_kv_heads * rope)
    qa = _split_query_lanes(q.reshape(b, n_kv_heads, per, qk), rope, tile,
                            sub)
    pps = max(1, min(table.shape[1], _GQA_STEP_POSITIONS // block_size))
    o = pl.pallas_call(
        functools.partial(_split_gqa_paged_kernel, bs=block_size, pps=pps,
                          groups=n_kv_heads, nope=nope, rope=rope,
                          rope_tile=tile, vdim=v_dim,
                          scale=1.0 / float(qk) ** 0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((2, pps * block_size, row), pool.dtype),
                pltpu.VMEM((n_kv_heads * sub, v_dim), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=_struct((b, n_kv_heads * sub, v_dim), q.dtype, q, pool),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_MLA_VMEM_BYTES,
        ),
        interpret=interpret,
        name="split_gqa_decode_attn",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        table.astype(jnp.int32), pos.astype(jnp.int32),
        qa.reshape(b, n_kv_heads * sub, nope + tile), pool,
    )
    return o.reshape(b, n_kv_heads, sub, v_dim)[:, :, :per].reshape(
        b, h, v_dim)


def split_gqa_decode_ok(block_size: int, n_kv_heads: int, per_kv: int,
                        qk: int, rope: int, v_dim: int, dtype) -> bool:
    """True where `split_gqa_decode_attention` compiles: `mla_decode_ok`'s
    pages (whole sublane tiles of the pool's dtype), each KV head's
    unrotated key part and its values whole 128-lane tiles, the rotated
    parts whole tiles together with a head's never across two, and a
    group's queries within two 8-sublane tiles (tests/test_tpu_aot_compile.py
    compiles the served shape for a described v5e)."""
    nope = qk - rope
    rope_all = n_kv_heads * rope
    return (mla_decode_ok(block_size, n_kv_heads * (qk + v_dim), _LANES,
                          dtype)
            and nope % _LANES == 0 and v_dim % _LANES == 0
            and rope_all % _LANES == 0 and _LANES % rope == 0
            and 1 <= per_kv <= 2 * _SUBLANES)


# ------------------ split-row grouped-query pool, chunked prefill

# query rows one grid step of the split prefill kernel scores against a
# fetch step (a block of query positions, each with the query heads of a KV
# head), and the cache positions a fetch step brings into VMEM: a KV head's
# scores are (1,024, 1,024) float32, 4 MiB, and a step's rows 2.5 MiB. On a
# TPU v5e a chunk of 512 over 32k keys took 6.3 ms a layer at this tiling,
# 13.5 at 1,024 x 256 and 10.0 at 512 x 512: a longer step leaves fewer
# updates of the running maxima, sums and accumulators a key
_SPLIT_PREFILL_ROWS = 1024
_SPLIT_PREFILL_KEYS = 1024


def _split_prefill_tiles(chunk: int, per_kv: int, width: int,
                         block_size: int) -> tuple:
    """(query positions a grid step, pages a fetch step) of
    `split_gqa_prefill_attention` for a chunk of ``chunk`` positions over a
    table of ``width`` blocks."""
    return (min(chunk, max(1, _SPLIT_PREFILL_ROWS // per_kv)),
            max(1, min(width, _SPLIT_PREFILL_KEYS // block_size)))


def _split_gqa_prefill_kernel(l_ref, table_ref, span_ref, q_ref, pool_hbm,
                              o_ref, buf, m_sc, l_sc, acc_sc, sem, *, bs, pps,
                              per, nope, rope, rope_tile, vdim, scale):
    """One block of query positions a grid step, all KV heads: the cache
    positions the block's queries can see, ``0 .. min(its last position,
    n_keys - 1)``, a fetch step of ``pps`` pages at a time, double-buffered
    through the block table (pages past them are not copied). A step's rows
    serve every KV head in turn: head g's query rows (the block's positions,
    each with the head's ``per`` query heads) score its unrotated and
    rotated key slices, ``q_nope . k_nope^T + q_rope . rope_tile^T``, scaled
    in float32; an online softmax folds them into the head's float32
    accumulator with ``p . v``, p in the pool's dtype. Steps wholly at or
    before the block's first position need no mask; the later ones keep key
    position <= query position. A block with no token writes noughts."""
    layer, pos0, n_keys = l_ref[0], span_ref[0], span_ref[1]
    groups, tm = q_ref.shape[0], q_ref.shape[1]
    tq, step_rows = tm // per, pps * bs
    div = jax.lax.div
    rope_at, v_at = groups * nope, groups * (nope + rope)
    q_lo = pos0 + pl.program_id(0) * tq          # the block's first position
    live = q_lo < n_keys
    n_seen = jnp.where(live, jnp.minimum(q_lo + tq, n_keys), 0)
    n_steps = div(n_seen + step_rows - 1, step_rows)
    n_pages = div(n_seen + bs - 1, bs)
    n_plain = jnp.minimum(div(q_lo + 1, step_rows), n_steps)

    def live_pages(i):
        return jnp.clip(n_pages - i * pps, 0, pps)

    def copy(rows, slot, j):
        return pltpu.make_async_copy(
            pool_hbm.at[layer, rows], buf.at[slot, pl.ds(j * bs, bs)],
            sem.at[slot])

    def fetch(i, slot):
        def start(j, carry):
            blk = table_ref[i * pps + j]
            copy(pl.ds(blk * bs, bs), slot, j).start()
            return carry
        jax.lax.fori_loop(0, live_pages(i), start, 0)

    @pl.when(pl.program_id(0) == 0)
    def _clear():   # what no copy has written yet must be finite
        buf[...] = jnp.zeros(buf.shape, buf.dtype)

    m_sc[...] = jnp.full(m_sc.shape, _NEG_BIG, m_sc.dtype)
    l_sc[...] = jnp.zeros(l_sc.shape, l_sc.dtype)
    acc_sc[...] = jnp.zeros(acc_sc.shape, acc_sc.dtype)
    fetch(0, 0)

    def step(i, masked):
        slot = jax.lax.rem(i, 2)
        fetch(i + 1, 1 - slot)      # no page past the last key: no copy

        def wait(j, carry):
            copy(pl.ds(0, bs), slot, j).wait()
            return carry

        jax.lax.fori_loop(0, live_pages(i), wait, 0)
        rows = buf[slot]                                   # (rows, row)
        if masked:
            shape = (tm, step_rows)
            keep = i * step_rows + jax.lax.broadcasted_iota(
                jnp.int32, shape, 1) <= q_lo + div(
                    jax.lax.broadcasted_iota(jnp.int32, shape, 0), per)
        for g in range(groups):
            q = q_ref[g]                                   # (tm, nope + tile)
            at = rope_at + (g * rope // rope_tile) * rope_tile
            s = (_dot_nt(q[:, :nope], rows[:, g * nope:(g + 1) * nope])
                 + _dot_nt(q[:, nope:], rows[:, at:at + rope_tile])) * scale
            if masked:
                s = jnp.where(keep, s, _NEG_BIG)
            m = m_sc[g][:, :1]
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            m_sc[g] = jnp.broadcast_to(m_new, m_sc.shape[1:])
            l_sc[g] = jnp.broadcast_to(
                l_sc[g][:, :1] * alpha + p.sum(axis=-1, keepdims=True),
                l_sc.shape[1:])
            acc_sc[g] = acc_sc[g] * alpha + _dot_nn(
                p.astype(rows.dtype),
                rows[:, v_at + g * vdim:v_at + (g + 1) * vdim])

    def plain(i, carry):
        step(i, False)
        return carry

    def masked(i, carry):
        step(i, True)
        return carry

    jax.lax.fori_loop(0, n_plain, plain, 0)
    jax.lax.fori_loop(n_plain, n_steps, masked, 0)
    for g in range(groups):
        o_ref[g] = (acc_sc[g] / jnp.maximum(l_sc[g][:, :1], 1e-30)).astype(
            o_ref.dtype)


def split_gqa_prefill_attention(q, pool, layer, table, pos0, n_keys, *,
                                block_size: int, n_kv_heads: int, rope: int,
                                v_dim: int, interpret: bool = False):
    """Grouped-query attention of one sequence's prefill chunk whose keys
    are wider than its values, read from the serving engine's KV pool where
    it lies: `split_gqa_decode_attention`'s pool and rows, a chunk of
    queries.

    q (C, H, qk) - the chunk's queries, each head's first ``rope`` values
    its rotated part (models/mimo_v2.py `qkv`); pool (L, slots, n_kv_heads *
    (qk + v_dim)) - the whole pool in HBM, a row as `to_row` lays it out;
    ``layer``, ``pos0`` (the chunk's first position) and ``n_keys`` (cache
    positions 0..n_keys - 1 are live, the chunk's own rows among them)
    scalars that may be traced; table (W,) int32. Query i sees key positions
    <= pos0 + i; a query at or past ``n_keys`` (a bucket's dead tail) is
    not a token and its output is not one. Returns o (C, H, v_dim) in q's
    dtype. Scores are MXU products in the pool's dtype with float32
    accumulation, scaled by 1/sqrt(qk) after it; the online softmax is
    float32 and its probabilities weigh the values in the pool's dtype, as
    models/mimo_v2.py `prefill_attention` does. A fetched page serves every
    query head of its KV head for a whole block of query positions; a fetch
    step wholly after a block's last position is neither fetched nor
    scored, and a block wholly past ``n_keys`` fetches nothing
    (`split_gqa_prefill_pairs` counts what is scored). Gate a compiled call
    with `split_gqa_prefill_ok`.

    Around the one Mosaic call the queries are laid out as the kernel reads
    them - a KV head's rows the chunk's positions, each with its query
    heads, the unrotated part in the first lanes and the rotated part in its
    head's lanes of the rotated keys' tile - and the output is laid back:
    plain XLA on (C, H, qk) values."""
    c, h, qk = q.shape
    nope = qk - rope
    row = n_kv_heads * (qk + v_dim)
    if pool.ndim != 3 or pool.shape[2] != row or h % n_kv_heads:
        raise ValueError(
            f"pool {pool.shape} does not hold keys of {qk} and values of "
            f"{v_dim} for {n_kv_heads} KV heads under q's {h} heads")
    if table.ndim != 1:
        raise ValueError(f"table {table.shape}: one sequence's block ids")
    per = h // n_kv_heads
    if not interpret and not split_gqa_prefill_ok(
            block_size, n_kv_heads, per, qk, rope, v_dim, pool.dtype):
        raise ValueError(
            f"split_gqa_prefill_attention: pages of {block_size} "
            f"{pool.dtype} rows of {n_kv_heads} x ({qk} + {v_dim}) with {per} "
            "queries a KV head are no tiles this kernel compiles for "
            "(split_gqa_prefill_ok)")
    tq, pps = _split_prefill_tiles(c, per, table.shape[0], block_size)
    if c % tq:
        raise ValueError(
            f"a chunk of {c} is no whole number of blocks of {tq} queries")
    tm = tq * per
    tile = min(_LANES, n_kv_heads * rope)
    qa = _split_query_lanes(q.reshape(c, n_kv_heads, per, qk), rope, tile,
                            per).transpose(1, 0, 2, 3).reshape(
        n_kv_heads, c * per, nope + tile)
    o = pl.pallas_call(
        functools.partial(_split_gqa_prefill_kernel, bs=block_size, pps=pps,
                          per=per, nope=nope, rope=rope, rope_tile=tile,
                          vdim=v_dim, scale=1.0 / float(qk) ** 0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(c // tq,),
            in_specs=[
                pl.BlockSpec((n_kv_heads, tm, nope + tile),
                             lambda i, *_: (0, i, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((n_kv_heads, tm, v_dim),
                                   lambda i, *_: (0, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pps * block_size, row), pool.dtype),
                pltpu.VMEM((n_kv_heads, tm, _LANES), jnp.float32),
                pltpu.VMEM((n_kv_heads, tm, _LANES), jnp.float32),
                pltpu.VMEM((n_kv_heads, tm, v_dim), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=_struct((n_kv_heads, c * per, v_dim), q.dtype, q, pool),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_MLA_VMEM_BYTES,
        ),
        interpret=interpret,
        name="split_gqa_prefill_attn",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), table.astype(jnp.int32),
        jnp.stack([jnp.asarray(pos0, jnp.int32),
                   jnp.asarray(n_keys, jnp.int32)]),
        qa, pool,
    )
    return o.reshape(n_kv_heads, c, per, v_dim).transpose(1, 0, 2, 3).reshape(
        c, h, v_dim)


def split_gqa_prefill_ok(block_size: int, n_kv_heads: int, per_kv: int,
                         qk: int, rope: int, v_dim: int, dtype) -> bool:
    """True where `split_gqa_prefill_attention` compiles: the decode
    kernel's pages and lane slices (`split_gqa_decode_ok`: it reads the same
    rows), whatever the chunk (a block of query rows is a chunk's whole
    rows, or 1,024 of them; tests/test_tpu_aot_compile.py compiles the
    served chunks for a described v5e)."""
    return split_gqa_decode_ok(block_size, n_kv_heads, per_kv, qk, rope,
                               v_dim, dtype)


def split_gqa_prefill_pairs(pos0: int, n: int, chunk: int, per_kv: int,
                            width: int, block_size: int) -> int:
    """The (query, key) pairs one `split_gqa_prefill_attention` call scores
    for ``n`` tokens from ``pos0`` in a chunk bucket of ``chunk`` over a
    table of ``width`` blocks: every block of query positions that holds a
    token, whole, against the whole fetch steps it walks (host integers)."""
    tq, pps = _split_prefill_tiles(chunk, per_kv, width, block_size)
    step = pps * block_size
    return sum(tq * -(-min(pos0 + lo + tq, pos0 + n) // step) * step
               for lo in range(0, n, tq))
