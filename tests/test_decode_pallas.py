"""Decode-attention kernel (ops/decode_pallas.py) parity, interpret mode.

The kernel computes one cached decode step: softmax(q @ K^T / sqrt(d),
masked past `pos`) @ V per (batch, head). The oracle is the exact XLA
computation `models/transformer.py generate`'s layer_step performs.
Mosaic-compiled behavior is only truly covered on TPU (the decode bench
row runs it there); interpret mode pins the math.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_neural_network_tpu.models.transformer import masked_attention
from distributed_neural_network_tpu.ops import decode_pallas
from distributed_neural_network_tpu.ops.decode_pallas import (
    decode_cache_attention,
    decode_kernel_ok,
    decode_paged_attention,
    paged_decode_ok,
    paged_read_positions,
)


def _oracle(q, ck, cv, pos):
    # q (B, H, D); ck/cv (B, H, total, D)
    total = ck.shape[2]
    s = jnp.einsum("bhd,bhsd->bhs", q, ck).astype(jnp.float32)
    s = s / np.sqrt(q.shape[-1])
    live = (jnp.arange(total) <= pos)[None, None, :]
    p = jax.nn.softmax(jnp.where(live, s, -1e30), axis=-1)
    return jnp.einsum("bhs,bhsd->bhd", p.astype(cv.dtype), cv)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("pos", [0, 7, 255, 639])
def test_matches_xla_oracle(dtype, pos):
    b, h, total, d = 2, 4, 640, 64
    ks = jax.random.split(jax.random.key(pos + 1), 3)
    q = jax.random.normal(ks[0], (b, h, d), dtype)
    ck = jax.random.normal(ks[1], (b, h, total, d), dtype)
    cv = jax.random.normal(ks[2], (b, h, total, d), dtype)
    want = _oracle(q, ck, cv, pos)
    got = decode_cache_attention(q, ck, cv, pos, interpret=True)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


def test_pos_zero_is_first_token_only():
    """At pos=0 only cache slot 0 is live: the output must equal v[:, :, 0]
    exactly (softmax over one element), independent of garbage in the
    rest of the cache."""
    b, h, total, d = 1, 2, 128, 64
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
    ck = jax.random.normal(ks[1], (b, h, total, d), jnp.float32)
    cv = jax.random.normal(ks[2], (b, h, total, d), jnp.float32) * 100.0
    got = decode_cache_attention(q, ck, cv, 0, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(cv[:, :, 0]), rtol=1e-6, atol=1e-6
    )


def test_kernel_ok_gate():
    assert decode_kernel_ok(640)       # bk 128
    assert decode_kernel_ok(256)
    assert decode_kernel_ok(4096)
    assert not decode_kernel_ok(17)    # prime-ish: bk 17
    # bf16's Mosaic tile is (16, 128): a bk that is a multiple of 8 but
    # not 16 must be rejected (r5 review - e.g. total 1032 -> bk 344)
    assert not decode_kernel_ok(1032)
    # a multiple-of-8 total whose best divisor is not sublane-legal
    assert not decode_kernel_ok(1736)  # bk 434


def test_direct_call_enforces_kernel_contract():
    """Calling the kernel directly at a sublane-illegal cache size gets
    the documented ValueError from decode_cache_attention itself, not a
    Mosaic tiling failure (total 17: largest divisor 17, not a multiple
    of 16)."""
    b, h, total, d = 1, 1, 17, 64
    q = jnp.zeros((b, h, d), jnp.float32)
    ck = jnp.zeros((b, h, total, d), jnp.float32)
    cv = jnp.zeros((b, h, total, d), jnp.float32)
    assert not decode_kernel_ok(total)
    with pytest.raises(ValueError, match="sublane-legal"):
        decode_cache_attention(q, ck, cv, 0, interpret=True)


def test_generate_kernel_path_matches_xla(monkeypatch):
    """End-to-end: generate() with DNN_TPU_DECODE_IMPL=pallas-interpret
    produces the same greedy tokens as the XLA decode path (total = 32
    is kernel-legal: bk 32, and 32 % 16 == 0 - the block must tile
    bf16's (16, 128) Mosaic sublane rule, decode_kernel_ok's gate -
    asserted below)."""
    from distributed_neural_network_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(
        vocab_size=64, d_model=64, n_heads=2, n_layers=2, d_ff=128
    )
    params = tfm.init_params(jax.random.key(0), cfg)
    prompt = jax.random.randint(jax.random.key(1), (2, 8), 0, 64)
    monkeypatch.setenv("DNN_TPU_DECODE_IMPL", "xla")
    want = tfm.generate(params, prompt, cfg, max_new_tokens=24)
    monkeypatch.setenv("DNN_TPU_DECODE_IMPL", "pallas-interpret")
    got = tfm.generate(params, prompt, cfg, max_new_tokens=24)
    assert decode_kernel_ok(prompt.shape[1] + 24)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_generate_rejects_unknown_or_infeasible_impl(monkeypatch):
    """Unknown DNN_TPU_DECODE_IMPL raises (flash.py convention); an
    explicit kernel request at a kernel-illegal cache size raises
    instead of silently measuring the XLA path."""
    from distributed_neural_network_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(
        vocab_size=64, d_model=64, n_heads=2, n_layers=1, d_ff=128
    )
    params = tfm.init_params(jax.random.key(0), cfg)
    prompt = jnp.zeros((1, 8), jnp.int32)
    monkeypatch.setenv("DNN_TPU_DECODE_IMPL", "palas")
    with pytest.raises(ValueError, match="unknown decode impl"):
        tfm.generate(params, prompt, cfg, max_new_tokens=24)
    monkeypatch.setenv("DNN_TPU_DECODE_IMPL", "pallas-interpret")
    assert not decode_kernel_ok(8 + 9)
    with pytest.raises(ValueError, match="no sublane-legal"):
        tfm.generate(params, prompt, cfg, max_new_tokens=9)


# ---------------------------------------------------------- paged pool

# the paged cases' pool: pages of 4 rows, sequences of up to 8 pages,
# fetch steps of 2 pages (the step's byte target shrunk to two of these
# small pages, so that a sequence spans several fetch steps)
BS, W, PPS, LAYERS, BLOCKS, HEADS = 4, 8, 2, 3, 64, 4
# a row each: the first position; one under and at a page boundary; one
# under and at a fetch-step boundary; the last position of the bucket
PAGED_POS = [0, BS - 1, BS, PPS * BS - 1, PPS * BS, W * BS - 1]


def _paged_case(dtype, dh, monkeypatch):
    """(q, clean pools, poisoned pools, table, pos) at the shapes above,
    with the fetch step shrunk to `PPS` pages (`_poisoned_pools`)."""
    monkeypatch.setattr(
        decode_pallas, "_FETCH_STEP_BYTES",
        PPS * BS * HEADS * dh * jnp.dtype(dtype).itemsize,
    )
    return _poisoned_pools(dtype, HEADS, dh, BS, W, PAGED_POS, seed=dh)


def _poisoned_pools(dtype, heads, dh, bs, width, positions, *, seed,
                    layers=LAYERS, blocks=BLOCKS):
    """(q, clean pools, poisoned pools, table, pos): a shuffled,
    non-contiguous table whose entries past a sequence's live pages name
    the scratch block 0, and pools whose every row past ``pos`` (the dead
    rows of the boundary page, every block no live page names, the
    scratch block) is NaN in the poisoned copy."""
    b = len(positions)
    ks = jax.random.split(jax.random.key(seed), 3)
    shape = (layers, blocks * bs, heads, dh)
    k_pool = jax.random.normal(ks[0], shape, dtype)
    v_pool = jax.random.normal(ks[1], shape, dtype)
    q = jax.random.normal(ks[2], (b, heads, dh), dtype)
    pos = np.asarray(positions, np.int32)
    blocks_ = np.random.default_rng(seed).permutation(
        np.arange(1, blocks))[: b * width].reshape(b, width)
    pages = np.arange(width)[None, :] <= (pos // bs)[:, None]
    table = np.where(pages, blocks_, 0).astype(np.int32)
    rows = (table[..., None] * bs + np.arange(bs)).reshape(b, width * bs)
    live = np.arange(width * bs)[None, :] <= pos[:, None]
    dead = np.ones((blocks * bs,), bool)
    dead[rows[live]] = False
    poison = jnp.where(jnp.asarray(dead)[None, :, None, None], jnp.nan, 0.0)
    return (q, (k_pool, v_pool),
            (k_pool + poison.astype(dtype), v_pool + poison.astype(dtype)),
            jnp.asarray(table), jnp.asarray(pos))


def _paged_oracle(q, k_pool, v_pool, layer, table, pos, bs=BS):
    """The engine's `xla` route: gather the table's span, attend under
    the live mask."""
    b, w = table.shape
    rows = (table[..., None] * bs + jnp.arange(bs)).reshape(b, w * bs)
    live = (jnp.arange(w * bs)[None, :] <= pos[:, None])[:, None, None, :]
    return masked_attention(
        q[:, None], k_pool[layer][rows].transpose(0, 2, 1, 3),
        v_pool[layer][rows].transpose(0, 2, 1, 3), live, q.dtype,
    )[:, 0]


@pytest.mark.parametrize("dh", [64, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernel_matches_gather_and_masked_attention(dtype, dh,
                                                          monkeypatch):
    """Every row of `PAGED_POS` at once, on the poisoned pool: a NaN
    from a page or a row past ``pos`` would reach the output."""
    q, clean, poisoned, table, pos = _paged_case(dtype, dh, monkeypatch)
    assert decode_pallas._pages_per_step(
        BS * HEADS * dh * jnp.dtype(dtype).itemsize, W) == PPS
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    for layer in (0, LAYERS - 1):
        got = decode_paged_attention(
            q, *poisoned, layer, table, pos, block_size=BS, interpret=True)
        assert got.dtype == q.dtype
        np.testing.assert_allclose(
            np.asarray(got, np.float32),
            np.asarray(_paged_oracle(q, *clean, layer, table, pos),
                       np.float32),
            rtol=tol, atol=tol,
        )


def test_paged_kernel_reads_a_traced_layer_under_scan(monkeypatch):
    """The engine's call: the layer index is the layer scan's counter."""
    q, clean, poisoned, table, pos = _paged_case(jnp.float32, 64,
                                                 monkeypatch)

    @jax.jit
    def every_layer(k_pool, v_pool):
        def layer_step(_, layer):
            return None, decode_paged_attention(
                q, k_pool, v_pool, layer, table, pos, block_size=BS,
                interpret=True)
        return jax.lax.scan(layer_step, None, jnp.arange(LAYERS))[1]

    got = every_layer(*poisoned)
    for layer in range(LAYERS):
        np.testing.assert_allclose(
            np.asarray(got[layer]),
            np.asarray(_paged_oracle(q, *clean, layer, table, pos)),
            rtol=2e-6, atol=2e-6,
        )


@pytest.mark.parametrize("dtype,heads", [
    (jnp.bfloat16, 16),                       # the longdoc cell's pages
    (jnp.bfloat16, 2), (jnp.bfloat16, 4),     # the gate's small tiles
    (jnp.float32, 2), (jnp.float32, 4),
])
def test_paged_kernel_at_its_own_fetch_step(dtype, heads):
    """Pages of 16 rows of ``heads`` heads of 128 at the kernel's own fetch
    step (8 pages at the longdoc cell's 16 heads in bfloat16; 16 to 64
    where a position's rows are fewer than a sublane tile), on the
    poisoned pool: a sequence at position 0, one mid-page, one that ends
    a page, one that ends and one that starts a fetch step, one in the
    third step and one that fills a table of three steps and a page."""
    bs, dh = 16, 128
    pps = decode_pallas._pages_per_step(
        bs * heads * dh * jnp.dtype(dtype).itemsize, 1 << 20)
    width, step = 3 * pps + 1, pps * bs
    positions = [0, 7, bs - 1, step - 1, step, 2 * step + 7, width * bs - 1]
    q, clean, poisoned, table, pos = _poisoned_pools(
        dtype, heads, dh, bs, width, positions, seed=heads, layers=2,
        blocks=len(positions) * width + 1)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    got = decode_paged_attention(q, *poisoned, 1, table, pos, block_size=bs,
                                 interpret=True)
    assert got.dtype == q.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(_paged_oracle(q, *clean, 1, table, pos, bs), np.float32),
        rtol=tol, atol=tol,
    )


def test_paged_kernel_gate_and_read_count():
    """What compiles (tests/test_tpu_aot_compile.py holds the gate to the
    compiler): whole (H, Dh) tiles of a float pool; and the positions a
    batch's pages hold."""
    assert paged_decode_ok(16, 16, 128, jnp.bfloat16)   # the served shape
    assert paged_decode_ok(16, 8, 256, jnp.float32)
    assert paged_decode_ok(16, 2, 128, jnp.bfloat16)
    assert not paged_decode_ok(16, 8, 64, jnp.bfloat16)   # half a lane row
    assert not paged_decode_ok(16, 12, 128, jnp.bfloat16)
    assert not paged_decode_ok(16, 16, 128, jnp.int8)     # scales unread
    assert not paged_decode_ok(1024, 16, 128, jnp.bfloat16)  # a 4 MiB page
    pos = np.asarray(PAGED_POS)
    assert paged_read_positions(pos, BS) == sum(
        (p // BS + 1) * BS for p in PAGED_POS)
    assert int(pos.sum()) + len(pos) <= paged_read_positions(pos, BS)
    with pytest.raises(ValueError, match="paged_decode_ok"):
        decode_paged_attention(
            jnp.zeros((1, 8, 64)), jnp.zeros((1, 32, 8, 64)),
            jnp.zeros((1, 32, 8, 64)), 0, jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1,), jnp.int32), block_size=16)
