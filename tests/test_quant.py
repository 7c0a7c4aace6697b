"""The low-precision fast path (ops/quant.py + the quantized kernels +
int8 KV serving + the cost/lint surfaces).

Bars, mirroring the honesty rails the PR ships:
- quantize/dequantize round-trip error is BOUNDED (per-format relative
  bounds, per-block scale isolation, fp8 saturation clamps instead of
  NaN), and the quantized matmuls really accumulate wide;
- the Pallas quant kernels match the XLA reference
  (`quantized_attention`) near-bitwise, and the int8 decode stream
  matches the dequantized oracle;
- the int8 KV serving engine agrees with the bf16 oracle per token
  across block sizes, replays preemptions byte-identically, and its
  chunked prefill equals token-at-a-time;
- the quantized-footprint cost pricing and the quantized-dtype lint
  hold both directions (undeclared int8 is an error; a declared
  quantized config whose path fell back is an error).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_neural_network_tpu.models import transformer as tfm
from distributed_neural_network_tpu.ops import quant
from distributed_neural_network_tpu.ops.decode_pallas import (
    decode_cache_attention,
    decode_kernel_ok,
)
from distributed_neural_network_tpu.ops.flash_pallas import flash_mha
from distributed_neural_network_tpu.parallel.ring import attention
from distributed_neural_network_tpu.serve.engine import (
    EngineConfig,
    Sequence,
    ServeEngine,
)

CFG = tfm.TransformerConfig(
    vocab_size=32, d_model=32, n_heads=4, n_layers=2, d_ff=64
)


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(jax.random.key(0), CFG)


def _prompt(key, n):
    return list(
        np.asarray(jax.random.randint(jax.random.key(key), (n,), 2, 32))
    )


def _oracle(params, prompt, n_new):
    return [int(x) for x in np.asarray(tfm.generate(
        params, jnp.asarray([prompt], jnp.int32), CFG,
        max_new_tokens=n_new,
    ))[0, len(prompt):]]


def _drain(eng, max_ticks=1000):
    t = 0
    while eng.has_work() and t < max_ticks:
        eng.step()
        t += 1
    assert not eng.has_work()


# ------------------------------------------------- quantize / dequantize


@pytest.mark.parametrize("fmt,rel_bound", [("int8", 1 / 64), ("fp8", 0.1)])
def test_roundtrip_error_bounded(fmt, rel_bound):
    """Per-row symmetric round trip: relative error within the format's
    resolution (int8 ~2^-7 per step, one bound-width of slack; fp8-e4m3
    ~2^-3 mantissa)."""
    x = jax.random.normal(jax.random.key(0), (16, 64)) * 5.0
    err = quant.roundtrip_error(x, fmt)
    assert err["rel"] <= rel_bound, err
    assert err["mae"] <= err["max_abs"]


def test_per_block_scale_isolates_outliers():
    """One huge outlier must not destroy the OTHER blocks' resolution:
    blockwise scales confine it to its own block."""
    x = np.array(
        jax.random.normal(jax.random.key(1), (4, 64)), np.float32,
        copy=True,
    )
    x[0, 0] = 1000.0
    x = jnp.asarray(x)
    err_row = quant.roundtrip_error(x, "int8")          # one scale/row
    err_blk = quant.roundtrip_error(x, "int8", block=16)
    # row 0's non-outlier entries under per-row scaling carry ~1000/127
    # absolute error; per-block scaling keeps the clean blocks clean
    q, s = quant.quantize(x, "int8", block=16)
    back = quant.dequantize(q, s, block=16)
    clean = jnp.abs(back[0, 16:] - x[0, 16:])
    assert float(clean.max()) < 0.1
    assert err_blk["mae"] < err_row["mae"]


def test_zero_block_is_exact():
    x = jnp.zeros((4, 32))
    q, s = quant.quantize(x, "int8")
    assert np.all(np.asarray(q) == 0)
    assert float(jnp.max(jnp.abs(quant.dequantize(q, s)))) == 0.0


def test_fp8_saturation_clamps_not_nan():
    """Values at the block amax land exactly at e4m3's 448 max finite;
    nothing becomes NaN/inf (an unclamped cast beyond 448 would)."""
    x = jnp.asarray([[1e6, -1e6, 3.0, 0.5]])
    q, s = quant.quantize(x, "fp8")
    assert np.all(np.isfinite(np.asarray(q, np.float32)))
    back = quant.dequantize(q, s)
    assert np.all(np.isfinite(np.asarray(back)))
    # the amax element round-trips exactly (scale maps it onto 448)
    assert back[0, 0] == pytest.approx(1e6, rel=1e-6)


def test_asymmetric_roundtrip_one_sided():
    """Zero-point variant: a one-sided distribution keeps ~2x the
    symmetric resolution (symmetric wastes half its codes on the
    never-used negative range)."""
    x = jax.random.uniform(jax.random.key(2), (8, 64)) * 3.0 + 1.0
    q, s, z = quant.quantize_asymmetric(x)
    back = quant.dequantize_asymmetric(q, s, z)
    sym_err = quant.roundtrip_error(x, "int8")["mae"]
    asym_err = float(jnp.mean(jnp.abs(back - x)))
    assert asym_err < sym_err


def test_quantize_validation():
    with pytest.raises(ValueError, match="unknown quantized format"):
        quant.quantize(jnp.zeros((2, 4)), "int4")
    with pytest.raises(ValueError, match="must divide"):
        quant.quantize(jnp.zeros((2, 10)), "int8", block=4)


@pytest.mark.parametrize("fmt", ["int8", "fp8"])
def test_quantized_matmul_accumulates_wide(fmt):
    """k=512 all-max-code rows would overflow an int8/int16 (or lose an
    fp8) accumulator by orders of magnitude; the wide accumulation
    (int32 / f32 preferred_element_type) keeps the result exact-ish."""
    a = jnp.ones((4, 512))
    b = jnp.ones((512, 4))
    out = quant.quantized_matmul(a, b, fmt)
    assert np.allclose(np.asarray(out), 512.0, rtol=0.05)


@pytest.mark.parametrize("fmt,tol", [("int8", 0.05), ("fp8", 0.15)])
def test_quantized_attention_close_to_exact(fmt, tol):
    ks = jax.random.split(jax.random.key(3), 3)
    q, k, v = (jax.random.normal(kk, (2, 16, 2, 8)) for kk in ks)
    ref = attention(q, k, v, causal=True)
    out = quant.quantized_attention(q, k, v, causal=True, fmt=fmt)
    assert float(jnp.max(jnp.abs(out - ref))) < tol


# ------------------------------------------------- Pallas quant kernels


@pytest.mark.parametrize("fmt", ["int8", "fp8"])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_quant_kernel_matches_xla_reference(n_devices, fmt, causal):
    """The quantized flash forward implements the same math as
    `quantized_attention` - same per-row scales, same fold-v-into-p
    trick - so they agree to float slop, not just to quantization
    tolerance."""
    ks = jax.random.split(jax.random.key(4), 3)
    q, k, v = (jax.random.normal(kk, (2, 64, 2, 16)) for kk in ks)
    out = flash_mha(q, k, v, causal=causal, interpret=True, quant=fmt)
    ref = quant.quantized_attention(q, k, v, causal=causal, fmt=fmt)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


def test_flash_quant_grads_flow_and_stay_close(n_devices):
    """Straight-through backward: gradients are the bf16 kernel's on
    the original residuals - finite, and near the unquantized grads."""
    ks = jax.random.split(jax.random.key(5), 3)
    q, k, v = (jax.random.normal(kk, (1, 64, 2, 16)) for kk in ks)

    def loss(q, k, v, quant_fmt):
        return jnp.sum(flash_mha(
            q, k, v, causal=True, interpret=True, quant=quant_fmt
        ) ** 2)

    gq = jax.grad(loss, argnums=(0, 1, 2))(q, k, v, "int8")
    gr = jax.grad(loss, argnums=(0, 1, 2))(q, k, v, None)
    for a, b in zip(gq, gr):
        assert bool(jnp.all(jnp.isfinite(a)))
        assert float(jnp.max(jnp.abs(a - b))) < 0.2


def test_flash_quant_rejects_unknown_format(n_devices):
    with pytest.raises(ValueError, match="unknown quant format"):
        flash_mha(
            jnp.zeros((1, 16, 1, 8)), jnp.zeros((1, 16, 1, 8)),
            jnp.zeros((1, 16, 1, 8)), quant="int4", interpret=True,
        )


def _xla_decode_ref(q, ck, cv, pos_vec):
    b, h, total, d = ck.shape
    scores = jnp.einsum("bhd,bhsd->bhs", q, ck) / np.sqrt(d)
    live = jnp.arange(total)[None, None, :] <= pos_vec[:, None, None]
    p = jax.nn.softmax(jnp.where(live, scores, -1e30), axis=-1)
    return jnp.einsum("bhs,bhsd->bhd", p, cv)


def test_decode_kernel_per_sequence_positions(n_devices):
    """The serving extension: every (batch, head) lane masks at ITS
    sequence's depth from the prefetched pos vector."""
    ks = jax.random.split(jax.random.key(6), 3)
    q = jax.random.normal(ks[0], (3, 2, 16))
    ck = jax.random.normal(ks[1], (3, 2, 64, 16))
    cv = jax.random.normal(ks[2], (3, 2, 64, 16))
    pos = jnp.asarray([3, 31, 63], jnp.int32)
    out = decode_cache_attention(q, ck, cv, pos, block_k=32,
                                 interpret=True)
    ref = _xla_decode_ref(q, ck, cv, pos)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


def test_decode_kernel_int8_stream_matches_dequant_oracle(n_devices):
    """int8 K/V + per-slot scales with dequant fused in the k-block
    loop == dequantize-then-attend, to float slop."""
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (2, 2, 16))
    ck = jax.random.normal(ks[1], (2, 2, 64, 16))
    cv = jax.random.normal(ks[2], (2, 2, 64, 16))
    ck_q, ksc = quant.quantize(ck, "int8")
    cv_q, vsc = quant.quantize(cv, "int8")
    pos = jnp.asarray([10, 63], jnp.int32)
    out = decode_cache_attention(
        q, ck_q, cv_q, pos, block_k=32, interpret=True,
        k_scale=ksc, v_scale=vsc,
    )
    ref = _xla_decode_ref(
        q, quant.dequantize(ck_q, ksc), quant.dequantize(cv_q, vsc), pos
    )
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


def test_decode_kernel_quantized_gate():
    # int8 tiles at (32, 128): a 48-slot cache has a 16-divisor block
    # (bf16-legal) but no 32-multiple
    assert decode_kernel_ok(48, 16)
    assert not decode_kernel_ok(48, 16, quantized=True)
    assert decode_kernel_ok(64, 32, quantized=True)
    with pytest.raises(ValueError, match="sublane-legal"):
        decode_cache_attention(
            jnp.zeros((1, 1, 8)),
            jnp.zeros((1, 1, 48, 8), jnp.int8),
            jnp.zeros((1, 1, 48, 8), jnp.int8),
            jnp.int32(0), block_k=16, interpret=True,
            k_scale=jnp.ones((1, 1, 48)), v_scale=jnp.ones((1, 1, 48)),
        )
    with pytest.raises(ValueError, match="BOTH k_scale and v_scale"):
        decode_cache_attention(
            jnp.zeros((1, 1, 64, 8)), jnp.zeros((1, 1, 64, 8)),
            jnp.zeros((1, 1, 64, 8)), jnp.int32(0), interpret=True,
            k_scale=jnp.ones((1, 1, 64)),
        )


# ------------------------------------------------------ int8 KV serving


def test_engine_config_validation():
    with pytest.raises(ValueError, match="kv_dtype"):
        EngineConfig(kv_dtype="int4")
    with pytest.raises(ValueError, match="decode_impl"):
        EngineConfig(decode_impl="triton")


@pytest.mark.parametrize("block_size", [4, 8, 16])
def test_int8_kv_paged_decode_agrees_with_bf16_oracle(params, n_devices,
                                                      block_size):
    """THE accuracy pin: a mixed batch on the quantized pool produces
    the bf16 `generate()` oracle's tokens, across block sizes (block
    size changes scale granularity AND requant cadence)."""
    eng = ServeEngine(params, CFG, EngineConfig(
        max_batch=4, num_blocks=64, block_size=block_size,
        max_seq_len=64, kv_dtype="int8", decode_impl="xla",
    ))
    seqs = [Sequence(i, _prompt(20 + i, 3 + 4 * i), 12) for i in range(3)]
    for s in seqs:
        eng.add(s)
    _drain(eng)
    agree = tot = exact = 0
    for s in seqs:
        oracle = _oracle(params, s.prompt, s.max_new_tokens)
        m = sum(a == b for a, b in zip(s.out, oracle))
        agree += m
        tot += len(oracle)
        exact += int(m == len(oracle))
    assert tot == 36
    # this TINY random model is the adversarial case for a top-1
    # metric: near-uniform logits make argmax ties int8-noise-thin, and
    # one flipped token feeds back into full divergence of the greedy
    # rollout. Most sequences must still be token-exact and overall
    # agreement high; the production-shaped >= 99% bar is enforced on
    # the bench/CI smoke workload (measure_serving's gate), where the
    # measured agreement is 100%.
    assert exact >= 2, f"only {exact}/3 sequences token-exact"
    assert agree / tot >= 0.85, (
        f"int8-KV top-1 agreement {agree}/{tot} vs the bf16 oracle"
    )


def test_int8_kv_preemption_replay_is_byte_identical(params, n_devices):
    """Preempted-and-replayed sequences re-derive EXACTLY the tokens
    already streamed (scale state of freed blocks is reset, so replay
    quantization is history-free), and never re-stream them."""
    eng = ServeEngine(params, CFG, EngineConfig(
        max_batch=3, num_blocks=7, block_size=4, max_seq_len=32,
        kv_dtype="int8", decode_impl="xla",
    ))
    streamed = {}

    def on_token(seq, tok, done):
        streamed.setdefault(seq.seq_id, []).append(tok)

    seqs = [
        Sequence(i, _prompt(30 + i, 4), 10, on_token=on_token)
        for i in (1, 2, 3)
    ]
    for s in seqs:
        eng.add(s)
    t = 0
    while eng.has_work() and t < 400:
        eng.step()
        t += 1
        while eng.preempted and len(eng.active) < 3:
            s = eng.preempted[0]
            if not eng.kv.can_fit(s.prompt_len + 1):
                break
            eng.preempted.popleft()
            eng.add(s)
    assert not eng.has_work()
    assert sum(s.preemptions for s in seqs) > 0, "no preemption induced"
    for s in seqs:
        # solo run on a fresh quantized engine = the replay oracle
        solo_eng = ServeEngine(params, CFG, EngineConfig(
            max_batch=1, num_blocks=16, block_size=4, max_seq_len=32,
            kv_dtype="int8", decode_impl="xla",
        ))
        solo = Sequence(99, list(s.prompt), s.max_new_tokens)
        solo_eng.add(solo)
        _drain(solo_eng)
        assert s.out == solo.out, f"seq {s.seq_id} replay diverged"
        assert streamed[s.seq_id] == s.out, (
            f"seq {s.seq_id} re-streamed or dropped tokens"
        )


def test_int8_kv_chunked_prefill_matches_token_at_a_time(params,
                                                         n_devices):
    outs = []
    for chunk in (1, 8):
        eng = ServeEngine(params, CFG, EngineConfig(
            max_batch=2, num_blocks=32, block_size=4, max_seq_len=64,
            prefill_chunk=chunk, kv_dtype="int8", decode_impl="xla",
        ))
        s = Sequence(0, _prompt(40, 21), 8)
        eng.add(s)
        _drain(eng)
        outs.append(s.out)
    assert outs[0] == outs[1]


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_pallas_route_matches_xla_route(params, n_devices, kv_dtype):
    """decode_impl='pallas' (the paged kernel reading the pool through
    the block table) produces the xla route's greedy tokens over a float
    pool; an int8 pool it does not read (its scales), so asking for it
    there is refused where the route is first asked for."""
    outs = {}
    for impl in ("xla", "pallas"):
        eng = ServeEngine(params, CFG, EngineConfig(
            max_batch=2, num_blocks=8, block_size=32, max_seq_len=64,
            kv_dtype=kv_dtype, decode_impl=impl,
        ))
        s = Sequence(0, _prompt(50, 5), 10)
        eng.add(s)
        if kv_dtype == "int8" and impl == "pallas":
            with pytest.raises(ValueError, match="decode_impl 'pallas'"):
                _drain(eng)
            return
        _drain(eng)
        outs[impl] = s.out
    assert outs["pallas"] == outs["xla"]


def test_pallas_route_rejects_illegal_bucket(params, n_devices):
    eng = ServeEngine(params, CFG, EngineConfig(
        max_batch=2, num_blocks=16, block_size=4, max_seq_len=32,
        kv_dtype="int8", decode_impl="pallas",
    ))
    s = Sequence(0, _prompt(51, 4), 4)
    eng.add(s)
    with pytest.raises(ValueError, match="does not read a int8 pool"):
        eng.step()


def test_int8_engine_auto_routes_xla_off_tpu(params, n_devices):
    eng = ServeEngine(params, CFG, EngineConfig(
        max_batch=2, num_blocks=8, block_size=32, max_seq_len=64,
        kv_dtype="int8", decode_impl="auto",
    ))
    assert eng._attn_route() == "xla"  # off-TPU auto never interprets


def test_warmup_leaves_quantized_state_clean(params, n_devices):
    eng = ServeEngine(params, CFG, EngineConfig(
        max_batch=2, num_blocks=16, block_size=8, max_seq_len=64,
        prefill_chunk=4, kv_dtype="int8", decode_impl="xla",
    ))
    n = eng.warmup()
    assert n > 0
    assert float(jnp.max(jnp.abs(eng.k_scale))) == 0.0
    assert float(jnp.max(jnp.abs(eng.v_scale))) == 0.0
    s = Sequence(0, _prompt(60, 4), 6)
    eng.add(s)
    _drain(eng)
    assert s.out == _oracle(params, s.prompt, 6)


# ----------------------------------------------- bytes, metrics, gates


def test_kv_byte_accounting():
    from distributed_neural_network_tpu.analysis.cost import (
        dtype_bytes,
        kv_block_bytes,
        kv_capacity_sequences,
        quantized_bytes,
    )

    assert dtype_bytes("bf16") == 2 and dtype_bytes("int8") == 1
    with pytest.raises(ValueError, match="unknown dtype"):
        dtype_bytes("int3")
    # int8 charges its scales: never a free 4x vs f32
    assert quantized_bytes(64, "int8", quant_block=64) == 64 + 4
    assert quantized_bytes(64, "bf16") == 128
    bb16 = kv_block_bytes(8, 8, 64, 16, "bf16")
    bb8 = kv_block_bytes(8, 8, 64, 16, "int8")
    assert bb16 == 2 * 8 * 16 * 8 * 64 * 2
    assert bb8 == 2 * 8 * 16 * 8 * 64 + 2 * 8 * 8 * 4
    assert 1.8 <= bb16 / bb8 <= 2.0  # the capacity multiplier
    assert kv_capacity_sequences(128, 16, 256) == 8


def test_engine_reports_quantized_bytes(params):
    e16 = ServeEngine(params, CFG, EngineConfig(num_blocks=16))
    e8 = ServeEngine(params, CFG, EngineConfig(
        num_blocks=16, kv_dtype="int8"
    ))
    assert e16.kv_dtype_name() == "f32"  # CFG dtype is float32
    assert e8.kv_dtype_name() == "int8"
    assert e8.kv_block_bytes() < e16.kv_block_bytes()


def test_scheduler_publishes_kv_dtype_and_capacity(params):
    from distributed_neural_network_tpu.serve.scheduler import (
        SchedulerConfig,
        ServeScheduler,
    )
    from distributed_neural_network_tpu.utils.obs import MetricsRegistry

    eng = ServeEngine(params, CFG, EngineConfig(
        max_batch=2, num_blocks=16, block_size=4, max_seq_len=32,
        kv_dtype="int8",
    ))
    reg = MetricsRegistry()
    sch = ServeScheduler(eng, SchedulerConfig(), registry=reg)
    try:
        text = reg.render()
        assert 'serve_kv_dtype{dtype="int8"} 1' in text
        assert f"serve_kv_bytes_total {15 * eng.kv_block_bytes()}" in text
        assert "serve_kv_capacity_sequences 1" in text
    finally:
        sch.close(finalize=False)


def test_measured_kv_capacity_ratio_meets_bar():
    """The capacity half of the serving gate at the BENCH row's
    geometry (d512/L8/H8): equal HBM budget, real allocator, >= 1.8x."""
    from distributed_neural_network_tpu.analysis.cost import (
        kv_block_bytes,
    )
    from distributed_neural_network_tpu.train.measure import (
        measure_kv_capacity,
    )

    bb16 = kv_block_bytes(8, 8, 64, 16, "bf16")
    bb8 = kv_block_bytes(8, 8, 64, 16, "int8")
    budget = 128 * bb16
    cap16 = measure_kv_capacity(129, 16, 256)
    cap8 = measure_kv_capacity(budget // bb8 + 1, 16, 256)
    assert cap8 / cap16 >= 1.8


def test_quant_parity_row_gates(n_devices):
    """The training parity row end to end (reduced steps): runs the
    three variants, asserts its own tolerances, reports both formats."""
    from distributed_neural_network_tpu.train.measure import (
        measure_quant_parity,
    )

    row = measure_quant_parity(steps=10)
    assert set(row["formats"]) == {"int8", "fp8"}
    for fmt, r in row["formats"].items():
        assert r["loss_delta"] <= r["loss_delta_tol"]
        assert r["logit_mae"] <= r["logit_mae_tol"]


# ------------------------------------------------ analysis: lint + cost


def test_quantized_dtype_lint_both_directions(n_devices):
    from distributed_neural_network_tpu.analysis.configs import (
        build_program,
    )
    from distributed_neural_network_tpu.analysis.runner import (
        analyze_program,
    )

    r = analyze_program(build_program("lm_quant_fp8"))
    assert r.facts.quant_dtypes.get("fp8", 0) > 0
    assert "float8_e4m3fn->float32" in r.facts.upcasts  # the wide accum
    assert not r.errors

    # undeclared: same program with the declaration stripped
    p = build_program("lm_quant_fp8")
    object.__setattr__(p, "meta", dict(p.meta, quant=None))
    r = analyze_program(p)
    assert [f.code for f in r.errors] == ["quant-undeclared"]

    # declared-but-missing: a full-precision step claiming quant
    p = build_program("lm_dp")
    object.__setattr__(p, "meta", dict(p.meta, quant="int8"))
    r = analyze_program(p)
    assert [f.code for f in r.errors] == ["quant-missing"]


def test_manifest_pins_quant_dtypes(n_devices):
    from distributed_neural_network_tpu.analysis.configs import (
        build_program,
    )
    from distributed_neural_network_tpu.analysis.manifest import (
        diff_manifests,
    )
    from distributed_neural_network_tpu.analysis.runner import (
        analyze_program,
    )

    r = analyze_program(build_program("lm_quant_int8"))
    man = r.manifest
    assert man["quant_dtypes"] == {"int8": r.facts.quant_dtypes["int8"]}
    # a fallen-back path (no int8 anywhere) must diff
    degraded = dict(man, quant_dtypes={})
    msgs = diff_manifests(man, degraded)
    assert any("quantized dtypes changed" in m for m in msgs)
    # legacy manifests without the key compare as empty, not as a diff
    legacy = {k: v for k, v in man.items() if k != "quant_dtypes"}
    assert not diff_manifests(legacy, dict(man, quant_dtypes={}))


def test_cost_precision_pricing_trades_precision_for_parallelism(
    n_devices,
):
    """An int8-priced param footprint fits a budget the bf16 pricing
    prunes - the autoshard precision/parallelism trade, end to end on a
    real traced program."""
    from distributed_neural_network_tpu.analysis.configs import (
        build_program,
    )
    from distributed_neural_network_tpu.analysis.cost import (
        CostWeights,
        score_program,
        sharded_leaf_bytes,
    )
    from distributed_neural_network_tpu.analysis.trace import (
        collect_trace,
    )

    program = build_program("lm_dp")
    facts = collect_trace(program.make_jaxpr())
    full = score_program(program, facts)
    mesh_axes = {str(k): int(v) for k, v in program.mesh.shape.items()}
    p_int8 = sharded_leaf_bytes(
        program.abstract_args[0], program.specs["params"], mesh_axes,
        precision="int8",
    )
    assert p_int8 < full.param_bytes_per_device / 3  # f32 -> int8+scales
    # a budget between the two footprints flips feasibility
    budget = (full.param_bytes_per_device + full.opt_bytes_per_device
              + full.scan_carry_bytes) - 1
    tight = score_program(
        program, facts, CostWeights(hbm_bytes=budget)
    )
    assert not tight.feasible
    quantized = score_program(
        program, facts,
        CostWeights(hbm_bytes=budget, param_precision="int8"),
    )
    assert quantized.feasible
    assert quantized.param_precision == "int8"
    assert "@int8" in quantized.why()
