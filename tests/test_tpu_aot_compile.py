"""The main path's Pallas kernels compile for a described TPU v5e.

No chip is attached here: `jax.experimental.topologies` describes a
`v5e:2x2` host and the installed TPU compiler builds each kernel for it at
the widths `chip_smoke.py` runs (the `on-chip-measurement` guide, section
2). This catches what interpret mode cannot - a block that does not tile,
a kernel over its VMEM budget, a kernel that cannot be partitioned under
`shard_map` - at no chip time. Nothing executes, so nothing here is a
measurement.
"""

import functools
import math
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from distributed_neural_network_tpu.ops.decode_pallas import (
    decode_cache_attention,
    decode_paged_attention,
    gqa_decode_attention,
    gqa_decode_ok,
    mla_decode_attention,
    mla_decode_ok,
    mla_prefill_attention,
    mla_prefill_ok,
    paged_decode_ok,
    split_gqa_decode_attention,
    split_gqa_decode_ok,
    split_gqa_prefill_attention,
    split_gqa_prefill_ok,
)
from distributed_neural_network_tpu.ops.flash import tuned_blocks
from distributed_neural_network_tpu.ops.flash_pallas import flash_mha
from distributed_neural_network_tpu.ops.pallas_kernels import fused_mlp3
from distributed_neural_network_tpu.utils.tracing import mosaic_custom_calls

# the LM smoke's attention geometry (bench.py's flagship rows)
B, S, H = 16, 2048, 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")


@pytest.fixture(autouse=True)
def _compile_cache_off():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _on_one_chip(topo, shapes):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]


def _flash(topo, head_dim, *, grad, quant=None):
    blocks = tuned_blocks(S, head_dim, topo.devices[0].device_kind)
    attn = functools.partial(flash_mha, causal=True, blocks=blocks,
                             quant=quant)
    if grad:
        def loss(q, k, v):
            return attn(q, k, v).astype(jnp.float32).sum()

        fn = jax.grad(loss, argnums=(0, 1, 2))
    else:
        fn = attn
    x = ((B, S, H, head_dim), jnp.bfloat16)
    return fn, _on_one_chip(topo, [x, x, x])


def _decode(topo, batch, total, *, int8):
    # the dense-cache kernel (`generate`'s) at d512 / 8 heads -> Dh 64
    # with per-sequence positions; `total` = the cache's length
    dh = 64
    cache = ((batch, H, total, dh), jnp.int8 if int8 else jnp.bfloat16)
    shapes = [((batch, H, dh), jnp.bfloat16), cache, cache,
              ((batch,), jnp.int32)]
    if not int8:
        return decode_cache_attention, _on_one_chip(topo, shapes)
    scale = ((batch, H, total), jnp.float32)

    def fn(q, ck, cv, pos, ks, vs):
        return decode_cache_attention(q, ck, cv, pos, k_scale=ks,
                                      v_scale=vs)

    return fn, _on_one_chip(topo, shapes + [scale, scale])


def _decode_paged(topo, batch, width, *, heads=16, dh=128,
                  dtype=jnp.bfloat16, layers=24, blocks=1537):
    # the longdoc cell: 24 layers, 1,537 blocks of 16 rows of 16 heads of
    # 128 in bfloat16; the pools whole, the layer a traced scalar
    bs = 16
    assert paged_decode_ok(bs, heads, dh, dtype)
    pool = ((layers, blocks * bs, heads, dh), dtype)
    i32 = jnp.int32

    def fn(q, k_pool, v_pool, layer, table, pos):
        return decode_paged_attention(q, k_pool, v_pool, layer[0], table,
                                      pos, block_size=bs)

    return fn, _on_one_chip(topo, [
        ((batch, heads, dh), dtype), pool, pool, ((1,), i32),
        ((batch, width), i32), ((batch,), i32),
    ])


def _mla_decode(topo, batch, width, *, dtype=jnp.bfloat16):
    # the docqa cell: 5 layers, 6,145 blocks of 64 latent rows (576 values
    # padded to 640), 128 heads, tables of 256 blocks
    bs, row, rank = 64, 640, 512
    assert mla_decode_ok(bs, row, rank, dtype)
    i32 = jnp.int32

    def fn(q_lat, pool, layer, table, pos):
        return mla_decode_attention(q_lat, pool, layer[0], table, pos,
                                    block_size=bs, rank=rank,
                                    scale=192 ** -0.5)

    return fn, _on_one_chip(topo, [
        ((batch, 128, row), dtype), ((5, 6145 * bs, row), dtype),
        ((1,), i32), ((batch, width), i32), ((batch,), i32),
    ])


def _gqa_decode(topo, batch, width, *, dtype=jnp.bfloat16):
    # the reason-1k cell: 2 attention layers, 6,145 blocks of 64 rows of
    # [k ; v] of 8 KV heads of 64, 32 query heads, tables of 128 blocks
    bs, kv, per, dh = 64, 8, 4, 64
    assert gqa_decode_ok(bs, kv, per, dh, dtype)
    i32 = jnp.int32

    def fn(q, pool, layer, table, pos):
        return gqa_decode_attention(q, pool, layer[0], table, pos,
                                    block_size=bs, n_kv_heads=kv)

    return fn, _on_one_chip(topo, [
        ((batch, kv * per, dh), dtype), ((2, 6145 * bs, kv * 2 * dh), dtype),
        ((1,), i32), ((batch, width), i32), ((batch,), i32),
    ])


def _split_gqa_decode(topo, batch, width, *, dtype=jnp.bfloat16):
    # the mixed-32k cell's full layers: 2 layers, 12,289 blocks of 64 rows
    # of 4 KV heads' keys of 192 (128 unrotated + 64 rotated) and values of
    # 128, 64 query heads, tables of 1,024 blocks
    bs, kv, per, qk, rope, v = 64, 4, 16, 192, 64, 128
    assert split_gqa_decode_ok(bs, kv, per, qk, rope, v, dtype)
    i32 = jnp.int32

    def fn(q, pool, layer, table, pos):
        return split_gqa_decode_attention(
            q, pool, layer[0], table, pos, block_size=bs, n_kv_heads=kv,
            rope=rope, v_dim=v)

    return fn, _on_one_chip(topo, [
        ((batch, kv * per, qk), dtype),
        ((2, 12289 * bs, kv * (qk + v)), dtype),
        ((1,), i32), ((batch, width), i32), ((batch,), i32),
    ])


def _split_gqa_prefill(topo, chunk):
    # the mixed-32k cell's full layers' prefill attention: a chunk's 64
    # heads over the pool of `_split_gqa_decode`
    bs, kv, per, qk, rope, v = 64, 4, 16, 192, 64, 128
    dtype, i32 = jnp.bfloat16, jnp.int32
    assert split_gqa_prefill_ok(bs, kv, per, qk, rope, v, dtype)

    def fn(q, pool, layer, table, span):
        return split_gqa_prefill_attention(
            q, pool, layer[0], table, span[0], span[1], block_size=bs,
            n_kv_heads=kv, rope=rope, v_dim=v)

    return fn, _on_one_chip(topo, [
        ((chunk, kv * per, qk), dtype),
        ((2, 12289 * bs, kv * (qk + v)), dtype),
        ((1,), i32), ((1024,), i32), ((2,), i32),
    ])


def _mla_prefill(topo, chunk):
    # the docqa cell's prefill attention: a chunk's 128 heads over the
    # latent pool, a layer's `kv_b` as the tree holds it
    bs, row, rank = 64, 640, 512
    dtype, i32 = jnp.bfloat16, jnp.int32
    assert mla_prefill_ok(bs, row, rank, 128, 128, 128, dtype)

    def fn(q_nope, q_rope, w_kvb, pool, layer, table, span):
        return mla_prefill_attention(
            q_nope, q_rope, w_kvb, pool, layer[0], table, span[0], span[1],
            block_size=bs, rank=rank, scale=192 ** -0.5)

    return fn, _on_one_chip(topo, [
        ((128, chunk, 128), dtype), ((128, chunk, 128), dtype),
        ((rank, 128 * 256), dtype), ((5, 6145 * bs, row), dtype),
        ((1,), i32), ((256,), i32), ((2,), i32),
    ])


def _mlp3(topo):
    # the CNN's classifier head at the smoke's batch 16
    dims = [(16, 400), (400, 120), (120,), (120, 84), (84,), (84, 10),
            (10,)]

    def loss(*a):
        return fused_mlp3(*a, interpret=False).sum()

    return (jax.grad(loss, argnums=tuple(range(7))),
            _on_one_chip(topo, [(d, jnp.float32) for d in dims]))


def _flash_dp2_tp2(topo):
    """The kernel's vma-typed outputs under shard_map(check_vma=True) on
    the four described chips: batch over `data`, heads over `tensor` - the
    layout `lm_train.py --dp 2 --tp 2 --attn flash` gives attention."""
    mesh = Mesh(
        [[topo.devices[0], topo.devices[1]],
         [topo.devices[2], topo.devices[3]]],
        ("data", "tensor"),
    )
    blocks = tuned_blocks(S, 64, topo.devices[0].device_kind)
    spec = P("data", None, "tensor", None)

    def loss(q, k, v):
        o = flash_mha(q, k, v, causal=True, blocks=blocks)
        return jax.lax.psum(
            o.astype(jnp.float32).sum(), ("data", "tensor")
        )

    fn = jax.shard_map(
        jax.grad(loss, argnums=(0, 1, 2)), mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=(spec, spec, spec),
        check_vma=True,
    )
    x = jax.ShapeDtypeStruct(
        (B, S, H, 64), jnp.bfloat16, sharding=NamedSharding(mesh, spec)
    )
    return fn, [x, x, x]


CASES = {
    "flash_fwd_d64": lambda t: _flash(t, 64, grad=False),
    "flash_bwd_d64": lambda t: _flash(t, 64, grad=True),
    "flash_fwd_d128": lambda t: _flash(t, 128, grad=False),
    "flash_bwd_d128": lambda t: _flash(t, 128, grad=True),
    "flash_int8_fwd_d64": lambda t: _flash(t, 64, grad=False, quant="int8"),
    "flash_fp8_fwd_d64": lambda t: _flash(t, 64, grad=False, quant="fp8"),
    "flash_int8_fwd_d128": lambda t: _flash(t, 128, grad=False, quant="int8"),
    "flash_fp8_fwd_d128": lambda t: _flash(t, 128, grad=False, quant="fp8"),
    "decode_bf16_b1_w1": lambda t: _decode(t, 1, 16, int8=False),
    "decode_bf16_b8_w16": lambda t: _decode(t, 8, 256, int8=False),
    "decode_int8_b1_w2": lambda t: _decode(t, 1, 32, int8=True),
    "decode_int8_b8_w16": lambda t: _decode(t, 8, 256, int8=True),
    "mla_prefill_bf16_c512": lambda t: _mla_prefill(t, 512),
    "mla_prefill_bf16_c1": lambda t: _mla_prefill(t, 1),
    "mla_decode_bf16_b32_w256": lambda t: _mla_decode(t, 32, 256),
    "mla_decode_bf16_b1_w256": lambda t: _mla_decode(t, 1, 256),
    "mla_decode_f32_b4_w4": lambda t: _mla_decode(t, 4, 4,
                                                  dtype=jnp.float32),
    "gqa_decode_bf16_b64_w128": lambda t: _gqa_decode(t, 64, 128),
    "gqa_decode_bf16_b1_w128": lambda t: _gqa_decode(t, 1, 128),
    "gqa_decode_f32_b4_w4": lambda t: _gqa_decode(t, 4, 4,
                                                  dtype=jnp.float32),
    "split_gqa_decode_bf16_b48_w1024": lambda t: _split_gqa_decode(
        t, 48, 1024),
    "split_gqa_decode_bf16_b1_w1024": lambda t: _split_gqa_decode(
        t, 1, 1024),
    "split_gqa_decode_f32_b4_w4": lambda t: _split_gqa_decode(
        t, 4, 4, dtype=jnp.float32),
    "split_gqa_prefill_bf16_c512": lambda t: _split_gqa_prefill(t, 512),
    "split_gqa_prefill_bf16_c1": lambda t: _split_gqa_prefill(t, 1),
    "decode_paged_bf16_b16_w128": lambda t: _decode_paged(t, 16, 128),
    "decode_paged_bf16_b1_w1": lambda t: _decode_paged(t, 1, 1),
    # the serve smoke (chip_smoke.py: d512 / 4 heads of 128, 129 blocks)
    "decode_paged_bf16_h4_b8_w16": lambda t: _decode_paged(
        t, 8, 16, heads=4, layers=8, blocks=129),
    # the gate's other tiles (`paged_decode_ok`): two heads, float32
    "decode_paged_bf16_h2_b2_w4": lambda t: _decode_paged(
        t, 2, 4, heads=2, layers=16, blocks=64),
    "decode_paged_f32_h8_d256_b8_w16": lambda t: _decode_paged(
        t, 8, 16, heads=8, dh=256, dtype=jnp.float32, layers=8, blocks=129),
    "fused_mlp3_fwd_bwd": _mlp3,
    "flash_bwd_dp2_tp2_shard_map": _flash_dp2_tp2,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(topo, case):
    fn, args = CASES[case](topo)
    compiled = jax.jit(fn).lower(*args).compile()
    assert mosaic_custom_calls(compiled) > 0, (
        f"{case}: no Mosaic custom call in the program"
    )


@pytest.mark.parametrize("remat_policy,calls", [
    ("dots_saveable", 3), ("", 4),
])
def test_train_step_runs_the_forward_kernel_once_under_dots_saveable(
        topo, monkeypatch, remat_policy, calls):
    """A two-layer GPT-2 training step at `gpt2-medium.pretrain-1k`'s
    widths (16 heads of 64, 8 x 1024 tokens, Adam, bfloat16) on the
    described chip: under `dots_saveable` the layer loops hold three Mosaic
    call sites (forward, dQ, dK/dV) - the blocks keep the forward kernel's
    output with their matmuls' (`block_remat_policy`) - and under full
    recomputation four, the forward kernel again in the backward loop. The
    dispatch asks the runtime whether it is on a TPU: here the test answers
    for it."""
    from distributed_neural_network_tpu.models import transformer as tfm
    from distributed_neural_network_tpu.ops import flash as flash_mod
    from distributed_neural_network_tpu.train import lm

    monkeypatch.setattr(flash_mod, "on_tpu", lambda: True)
    cfg = tfm.TransformerConfig(
        vocab_size=50257, d_model=1024, n_heads=16, n_layers=2, d_ff=4096,
        dtype=jnp.bfloat16, remat=True, remat_policy=remat_policy,
    )
    mesh = Mesh([[[topo.devices[0]]]],
                (lm.DATA_AXIS, lm.SEQ_AXIS, lm.TP_AXIS))
    step = lm.make_lm_train_step(cfg, mesh, lr=3e-4, attn_impl="flash",
                                 optimizer="adam")
    state = jax.tree.map(
        lambda x, sharding: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                 sharding=sharding),
        lm.abstract_lm_state(cfg, mesh, "adam"),
        lm.make_lm_shardings(cfg, mesh, "adam")[1:],
    )
    tok = jax.ShapeDtypeStruct(
        (8, 1024), jnp.int32,
        sharding=NamedSharding(mesh, P(lm.DATA_AXIS, lm.SEQ_AXIS)))
    compiled = step.lower(*state, tok, tok).compile()
    assert mosaic_custom_calls(compiled) == calls


@pytest.mark.parametrize("family,n", [
    ("decode", 1), ("decode", 2), ("prefill", 1), ("prefill", 8),
])
def test_serve_program_holds_no_slab_on_v5e(topo, family, n):
    """The TPU compiler's side of tests/test_serve_pool_inplace.py, in the
    chip's own pool dtype: 16 layers, so the layer scan stays a loop (a
    short one unrolls whole and hides a pool threaded as xs/ys, which cost
    0.45-0.7 GB of temporaries here), and batch / chunk 1 as well, whose
    single-row scatter the compiler turns into a dynamic-update-slice."""
    from distributed_neural_network_tpu.models import transformer as tfm
    from distributed_neural_network_tpu.serve.engine import (
        EngineConfig,
        ServeEngine,
    )

    cfg = tfm.TransformerConfig(
        vocab_size=256, d_model=256, n_heads=2, n_layers=16, d_ff=512,
        dtype=jnp.bfloat16,
    )
    width = 4
    eng = ServeEngine(
        tfm.init_params(jax.random.key(0), cfg), cfg,
        EngineConfig(max_batch=2, num_blocks=1024, block_size=16,
                     max_seq_len=width * 16, prefill_chunk=8,
                     decode_impl="xla"),
    )
    i32 = jnp.int32
    if family == "decode":
        fn = eng._decode_fn(n, width)
        tail = [((n,), i32), ((n,), i32), ((n, width), i32),
                ((n,), jnp.float32), ((n, 2), jnp.uint32)]
    else:
        fn = eng._prefill_fn(n, width)
        tail = [((n,), i32), ((), i32), ((width,), i32), ((), i32)]
    one_chip = SingleDeviceSharding(topo.devices[0])
    params, k_pool, v_pool = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        (eng.params, eng.k_pool, eng.v_pool),
    )
    mem = fn.lower(
        params, k_pool, v_pool, *_on_one_chip(topo, tail)
    ).compile().memory_analysis()
    assert mem.temp_size_in_bytes < eng.k_pool[0].nbytes
    assert mem.alias_size_in_bytes >= 2 * eng.k_pool.nbytes


@pytest.mark.parametrize("family", ["decode", "prefill"])
def test_latent_serve_program_holds_no_slab_on_v5e(topo, family):
    """The same for the one pool of a module with a latent cache
    (tests/test_pangu_ultra_moe.py has the CPU's side): twelve expert
    layers after a dense one, so both stacks' scans stay loops, the pool on
    their carry; the `xla` route (the kernels' own compiles are `CASES`)."""
    from distributed_neural_network_tpu.models import pangu_ultra_moe as pm
    from distributed_neural_network_tpu.serve.engine import (
        EngineConfig,
        ServeEngine,
    )

    cfg = pm.PanguUltraMoEConfig(n_dense=2, n_moe=12, dtype=jnp.bfloat16)
    eng = ServeEngine(
        jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                     pm.init_params(jax.random.key(0), cfg)), cfg,
        EngineConfig(max_batch=2, num_blocks=1024, block_size=16,
                     max_seq_len=64, prefill_chunk=8, decode_impl="xla"),
    )
    i32, width = jnp.int32, eng._bucket_widths()[0]
    if family == "decode":
        fn = eng._decode_fn(2, width)
        tail = [((2,), i32), ((2,), i32), ((2, width), i32),
                ((2,), jnp.float32), ((2, 2), jnp.uint32)]
    else:
        fn = eng._prefill_fn(8, width)
        tail = [((8,), i32), ((), i32), ((width,), i32), ((), i32)]
    one_chip = SingleDeviceSharding(topo.devices[0])
    params, pool = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        (eng.params, eng.k_pool),
    )
    mem = fn.lower(params, pool, *_on_one_chip(topo, tail)).compile(
        ).memory_analysis()
    assert mem.temp_size_in_bytes < eng.k_pool[0].nbytes
    assert mem.alias_size_in_bytes >= eng.k_pool.nbytes


@pytest.mark.parametrize("n", [1, 16])
def test_serve_decode_program_reads_the_pool_through_the_table_on_v5e(
        topo, monkeypatch, n):
    """`decode_impl="pallas"` on the described chip, at the longdoc cell's
    widths (16 heads of 128, 16 of its 24 layers, its pool, the 128-block
    bucket): the decode program's layer loop holds one Mosaic call, the
    paged kernel, and neither a gathered bucket nor a copy of a pool
    beside it - what the program holds at its peak beyond its arguments
    and outputs stays under one bucket's K rows (the gather -> transpose
    -> dense kernel route held three, and `temp_size_in_bytes` alone read
    0 for it at some batch sizes), and both pools are aliased to the
    outputs. The weights are shapes (nothing runs, so the engine is built
    around a placeholder tree), and the engine asks the runtime whether
    it is on a TPU: here the test answers for it."""
    from distributed_neural_network_tpu.models import transformer as tfm
    from distributed_neural_network_tpu.serve import engine as engine_mod

    monkeypatch.setattr(engine_mod, "on_tpu", lambda: True)
    cfg = tfm.TransformerConfig(
        vocab_size=50257, d_model=2048, n_heads=16, n_layers=16, d_ff=8192,
        dtype=jnp.bfloat16,
    )
    width, bs, blocks = 128, 16, 1537
    eng = engine_mod.ServeEngine(
        {"placeholder": jnp.zeros(())}, cfg,
        engine_mod.EngineConfig(
            max_batch=16, num_blocks=width + 1, block_size=bs,
            max_seq_len=width * bs, prefill_chunk=128,
            decode_impl="pallas"),
    )
    assert eng.decode_route() == "pallas"
    one_chip = SingleDeviceSharding(topo.devices[0])
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, cfg.dtype,
                                       sharding=one_chip),
        jax.eval_shape(lambda: tfm.init_params(jax.random.key(0), cfg)),
    )
    i32 = jnp.int32
    pool = ((cfg.n_layers, blocks * bs, cfg.n_heads, cfg.head_dim),
            cfg.dtype)
    compiled = eng._decode_fn(n, width).lower(
        params, *_on_one_chip(topo, [
            pool, pool, ((n,), i32), ((n,), i32), ((n, width), i32),
            ((n,), jnp.float32), ((n, 2), jnp.uint32),
        ])
    ).compile()
    assert mosaic_custom_calls(compiled) == 1
    mem = compiled.memory_analysis()
    bucket = n * width * bs * cfg.n_heads * cfg.head_dim * 2
    pools = 2 * math.prod(pool[0]) * 2
    held = mem.peak_memory_in_bytes - (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        - mem.alias_size_in_bytes)
    assert max(held, mem.temp_size_in_bytes) < bucket
    assert mem.alias_size_in_bytes >= pools


@pytest.mark.parametrize("family,n", [("decode", 48), ("prefill", 512)])
def test_mimo_serve_programs_compile_on_v5e(topo, monkeypatch, family, n):
    """The served programs of `mimo-v2.5.serve-mixed-32k` at its widths and
    pools (7 layers: 2 full, 5 window; 16 held experts; 12,289 blocks of 64;
    the rings of 48 sequences), `decode_impl="auto"` on the described chip:
    each program's only Mosaic calls are the full layers' kernel, one a full
    layer (decode `split_gqa_decode_attn`, prefill `split_gqa_prefill_attn`;
    the window layers' attention is plain XLA); both pools are aliased to
    the outputs and what a program holds beyond them stays under a
    gigabyte. The weights are shapes (nothing
    runs, so the engine is built around a placeholder tree with a small
    pool of its own), and the engine asks the runtime whether it is on a
    TPU: here the test answers for it."""
    import json

    from distributed_neural_network_tpu.models import mimo_v2
    from distributed_neural_network_tpu.serve import engine as engine_mod

    monkeypatch.setattr(engine_mod, "on_tpu", lambda: True)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "mimo-v2.5.json")) as f:
        cfg = mimo_v2.from_published(json.load(f), dtype=jnp.bfloat16)
    eng = engine_mod.ServeEngine(
        {"placeholder": jnp.zeros(())}, cfg,
        engine_mod.EngineConfig(
            max_batch=2, num_blocks=65, block_size=64, max_seq_len=34304,
            prefill_chunk=512, decode_impl="auto"),
    )
    assert eng.decode_route() == eng._prefill_route() == "pallas"
    one_chip = SingleDeviceSharding(topo.devices[0])
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, jnp.float32 if x.ndim < 2 else cfg.dtype,
            sharding=one_chip),
        jax.eval_shape(lambda: mimo_v2.init_params(jax.random.key(0), cfg)),
    )
    kv = ((cfg.n_full, 12289 * 64, cfg.row("full")), cfg.dtype)
    rings = ((cfg.n_window, 48 + 1, cfg.window, cfg.row("window")),
             cfg.dtype)
    width = eng._bucket_widths()[0]
    tail = [jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=one_chip)
            for t in eng.bucket_tail(family, n, width)]
    fn = (eng._decode_fn if family == "decode" else eng._prefill_fn)(
        n, width)
    compiled = fn.lower(params, *_on_one_chip(topo, [kv, rings]),
                        *tail).compile()
    assert mosaic_custom_calls(compiled) == cfg.n_full
    mem = compiled.memory_analysis()
    pools = sum(math.prod(s) * 2 for s, _ in (kv, rings))
    held = mem.peak_memory_in_bytes - (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        - mem.alias_size_in_bytes)
    assert max(held, mem.temp_size_in_bytes) < 1 << 30
    assert mem.alias_size_in_bytes >= pools


def test_lfm2_prefill_program_takes_no_kernel_on_v5e(topo, monkeypatch):
    """LFM2's module declares no prefill kernel: on the described chip,
    `decode_impl="auto"` lowers its hybrid prefill program with no Mosaic
    call, to the text of the `xla` route, while its decode program takes
    its kernel. The weights are shapes; the engine asks the runtime whether
    it is on a TPU, and the test answers for it."""
    import sys

    from distributed_neural_network_tpu.serve import engine as engine_mod

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "benchmark"))
    try:
        from lib import harness

        family = harness.load_family("lfm2_moe", "serve")
        model = harness.load_json("families", "lfm2_moe", "tiny.json")
    finally:
        sys.path.remove(os.path.join(root, "benchmark"))
    cfg = family.program.config(model, {}, jnp.bfloat16)
    monkeypatch.setattr(engine_mod, "on_tpu", lambda: True)
    one_chip = SingleDeviceSharding(topo.devices[0])
    text = {}
    for impl in ("auto", "xla"):
        eng = engine_mod.ServeEngine(
            {"placeholder": jnp.zeros(())}, cfg, engine_mod.EngineConfig(
                max_batch=2, num_blocks=64, block_size=16, max_seq_len=256,
                prefill_chunk=16, decode_impl=impl))
        assert eng._cache.prefill_kernel_ok is None
        assert eng._prefill_route() == "xla"
        params = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, jnp.float32 if x.ndim < 2 else cfg.dtype,
                sharding=one_chip),
            jax.eval_shape(lambda: cfg.module.init_params(
                jax.random.key(0), cfg)))
        pools = [jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one_chip)
                 for p in eng._pools()]
        width = eng._bucket_widths()[0]
        tail = [jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=one_chip)
                for t in eng.bucket_tail("prefill", 16, width)]
        text[impl] = eng._prefill_fn(16, width).lower(
            params, *pools, *tail).as_text()
    assert "tpu_custom_call" not in text["auto"]
    assert text["auto"] == text["xla"]
