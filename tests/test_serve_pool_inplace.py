"""The bucket programs serve from the KV pool IN PLACE (serve/engine.py).

Donating the pools is not enough: handed to the layer scan as xs and
taken back as ys they are two buffers of pool size, and the compiler
copies the pool whole (and slices a layer's slab out and back) to alias
them - which a jaxpr walk (analysis/serve_trace.py) cannot see. With
the pools LOOP-CARRIED and every access a gather or a scatter of rows at
``l * slots + idx`` (`_read_rows` / `_write_rows`), a compiled program
holds no temporary of a layer's slab. Pinned here on the compiled
programs of a model whose pool dwarfs everything else in the program;
the live figure is the ``serve_program_temp_bytes{family}`` gauge.

float32 ``cfg.dtype`` on purpose: the CPU backend widens a bfloat16
scatter through a pool-sized temporary of its own, which says nothing
about the TPU.
"""

import jax
import jax.numpy as jnp
import pytest

from distributed_neural_network_tpu.models import transformer as tfm
from distributed_neural_network_tpu.serve.engine import (
    EngineConfig,
    ServeEngine,
)
from distributed_neural_network_tpu.serve.scheduler import (
    SchedulerConfig,
    ServeScheduler,
)
from distributed_neural_network_tpu.utils.obs import MetricsRegistry

CFG = tfm.TransformerConfig(
    vocab_size=64, d_model=32, n_heads=2, n_layers=4, d_ff=64,
    dtype=jnp.float32,
)
B, W, CHUNK, SPEC_K = 2, 4, 8, 3


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(jax.random.key(0), CFG)


def _engine(params, kv_dtype, **kw):
    # 1,024 blocks of 16: 16,384 slots a layer, a slab of 2 MiB (float32)
    # or 512 KiB (int8) against a gathered bucket of 16 KiB
    return ServeEngine(params, CFG, EngineConfig(
        max_batch=B, num_blocks=1024, block_size=16, max_seq_len=W * 16,
        prefill_chunk=CHUNK, kv_dtype=kv_dtype, decode_impl="xla", **kw,
    ))


def _program(eng, family):
    i32 = jnp.int32
    if family == "decode":
        return eng._decode_fn(B, W), (
            jnp.zeros((B,), i32), jnp.zeros((B,), i32),
            jnp.zeros((B, W), i32), jnp.zeros((B,), jnp.float32),
            jnp.zeros((B, 2), jnp.uint32))
    if family == "prefill":
        return eng._prefill_fn(CHUNK, W), (
            jnp.zeros((CHUNK,), i32), i32(0), jnp.zeros((W,), i32), i32(0))
    return eng._verify_fn(B, W), (
        jnp.zeros((B, SPEC_K + 1), i32), jnp.zeros((B,), i32),
        jnp.zeros((B, W), i32))


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("family", ["decode", "prefill", "verify"])
def test_bucket_program_holds_no_slab(params, family, kv_dtype):
    eng = _engine(params, kv_dtype,
                  spec_decode=SPEC_K if family == "verify" else 0)
    fn, tail = _program(eng, family)
    pools = (eng.k_pool, eng.v_pool) + (
        (eng.k_scale, eng.v_scale) if eng.quantized else ())
    mem = fn.lower(eng.params, *pools, *tail).compile().memory_analysis()
    slab = eng.k_pool[0].nbytes
    donated = sum(p.nbytes for p in pools)
    assert mem.temp_size_in_bytes < slab, (
        f"{family}/{kv_dtype}: {mem.temp_size_in_bytes} B of temporaries "
        f"against a layer's slab of {slab} B - the program moves the pool, "
        "not its rows"
    )
    assert mem.alias_size_in_bytes >= donated, (
        f"{family}/{kv_dtype}: {mem.alias_size_in_bytes} B aliased of "
        f"{donated} B donated - an output pool is a second buffer"
    )


def test_warmup_publishes_program_temp_bytes(params):
    eng = _engine(params, "bf16")
    assert eng.program_temp_bytes == {}
    n = eng.warmup()
    assert n == eng.compiled_programs()["total"]  # the asking built none
    assert set(eng.program_temp_bytes) == {"decode", "prefill"}
    # the family's LARGEST: at least its (B, W) program's own. (No bound
    # by the slab here: the grid holds batch / chunk 1, whose single-row
    # scatter the CPU backend - not the TPU's, tests/test_tpu_aot_compile.py
    # - lowers through a copy of the pool.)
    fn, tail = _program(eng, "decode")
    own = fn.lower(
        eng.params, eng.k_pool, eng.v_pool, *tail
    ).compile().memory_analysis().temp_size_in_bytes
    assert eng.program_temp_bytes["decode"] >= own > 0
    registry = MetricsRegistry()
    scheduler = ServeScheduler(eng, SchedulerConfig(), registry=registry)
    try:
        text = registry.render()
    finally:
        scheduler.close()
    for family, nbytes in eng.program_temp_bytes.items():
        line = f'serve_program_temp_bytes{{family="{family}"}} {nbytes}'
        assert line in text, text
