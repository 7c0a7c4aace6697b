"""Single-query KV-cache decode attention kernel (Pallas TPU, fwd-only).

Why a dedicated kernel when `ops/flash_pallas.py` already exists: decode
attends ONE query row per step against a static-size cache, and the r5
probes put the XLA lowering of that step ~4x above its HBM-bandwidth
bound at batch (2.60 ms/step at b16/hd64/cache 640 vs ~0.4 ms of
unavoidable traffic; b1 IS at the bound, so the gap is the per-step
small-op chain, not cache size). The training flash kernel cannot help:
its q axis is a full sequence. This kernel is the decode-shaped
counterpart:

- **One fused pass**: scores, online softmax, and the value gather run
  in a single `pallas_call` per layer-step - no (B, H, total) f32 score
  tensor round-trips through HBM between three XLA ops.
- **Dead-block skipping**: the XLA path attends the FULL padded cache
  every step and masks (static shapes - the design is right, the work
  is not). Here the grid still covers total/bk blocks, but a block
  whose first column is past `pos` skips compute under `pl.when` and
  clamps its index_map to the boundary block (already resident, no new
  DMA) - per-step cache traffic is proportional to the LIVE prefix,
  not the allocation. `pos` rides scalar prefetch
  (`pltpu.PrefetchScalarGridSpec`) so index_maps can use it.
- **Per-sequence positions**: ``pos`` may be a scalar (the
  `models/transformer.py generate` path - every sequence at the same
  position) or a ``(B,)`` vector - the serving engine's continuous
  batch, where every slot sits at its own depth (serve/engine.py routes
  this kernel under the paged gather). The mask and the skip clamp
  resolve per (batch, head) lane from the prefetched vector.
- **int8 K/V stream** (`k_scale`/`v_scale` given): the caches arrive in
  int8 with per-slot f32 scales (lane-replicated, the same layout as
  flash's lse residual) and each k-block is dequantized IN the k-block
  loop right before its dot - HBM cache traffic is halved (decode's
  actual roofline; see the measured-outcome note below), the MXU dots
  stay in the query dtype. This is the serving int8 KV cache's fused
  read path (serve/kv_cache.py stores per-(block, head) scales; the
  engine expands them to per-slot at gather time).
- **Single-row query on a (8, 128) grid**: Mosaic blocks must tile
  (8, 128), so the one real query row is lane-broadcast to 8 sublanes
  by the caller and row 0 of the output is read back - 7 redundant rows
  cost nothing (the MXU pass is the same) and keep every block legal.
- Numerics: f32 dot accumulation + f32 online-softmax recurrence
  (m/l/acc in VMEM scratch), matching `flash_pallas` conventions;
  parity with the XLA decode path is pinned by
  `tests/test_decode_pallas.py` up to blockwise reassociation, and the
  int8 path by `tests/test_quant.py` against the dequantized oracle.

The reference framework has no attention at all (its model is the
5-layer CNN, `/root/reference/models/model.py:9-27`); this kernel is
part of the beyond-reference LM family's inference path
(`models/transformer.py generate`).

**Measured outcome (r5, TPU v5e, the honest negative result)**: at the
decode bench shapes (d512, cache <= 640) this kernel LOSES to the XLA
chain it replaces - 3.69 vs 2.59 ms/step at b16/hd64 in-loop, and
+~25% isolated at every block size. XLA lowers the einsum/softmax/
einsum step as one well-tiled batched matmul chain over all B*H heads;
a per-layer `pallas_call` costs more than the fusion saves, and
dead-block skipping cannot pay at 640-slot caches. `generate` therefore
defaults to the XLA path (`DNN_TPU_DECODE_IMPL=auto`); the kernel stays
selectable (`=pallas`) and parity-tested for the long-cache regime
where skipping's traffic advantage grows linearly - and the int8 stream
halves exactly the traffic that regime is bound by.
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
