"""Shared benchmark harness for bench.py, report.py and lm_train.py.

One implementation of "train the data-parallel CIFAR workload and time the
train+sync phases" so the entry points cannot drift: split loading,
warm-up policy, the fused-span fast path with its outside-the-timer final
eval (mirroring the reference's child train-time metric, which excludes the
parent's eval - SURVEY.md section 6), and the phase accounting. Also the LM
throughput/MFU measurement (`measure_lm_training`) and the MFU accounting
(`model_flops_per_token`, `peak_flops`) shared by lm_train.py and bench.py.
"""

from __future__ import annotations

import os
import time

import jax

from ..data.cifar10 import load_split
from ..runtime import on_tpu
from ..utils import timers as T
from .engine import Engine, TrainConfig

# peak TFLOP/s by device kind for the MFU denominator. An unknown kind is
# None off-TPU (CPU tests) and an error on TPU: a chip run must not print
# a null MFU.
# bf16 is the MXU-native rate; f32 matmuls run at roughly half of it on
# TPU (the MXU computes f32 via bf16x3-style passes), so MFU for f32 runs
# is reported against the halved peak (ADVICE r2: quoting the bf16 peak
# silently understated f32 utilization).
PEAK_TFLOPS_BF16 = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}
F32_PEAK_FACTOR = 0.5

# peak HBM bandwidth (bytes/s) by device kind - the decode-utilization
# denominator (decode streams every parameter once per generation step).
# Kept next to PEAK_TFLOPS_BF16 so a new device generation is added to
# both tables in one place.
PEAK_HBM_BYTES = {
    "TPU v4": 1228e9,
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
    "TPU v5p": 2765e9,
    "TPU v6 lite": 1640e9,
    "TPU v6e": 1640e9,
}


def peak_flops(device_kind: str, dtype: str = "bfloat16") -> float | None:
    """Per-device peak FLOP/s for the MFU denominator, dtype-adjusted."""
    peak = PEAK_TFLOPS_BF16.get(device_kind)
    if peak is None:
        if on_tpu():
            raise ValueError(
                f"no peak FLOP/s entry for device kind {device_kind!r}: "
                "add it to PEAK_TFLOPS_BF16 (and PEAK_HBM_BYTES)"
            )
        return None
    return peak * (F32_PEAK_FACTOR if dtype == "float32" else 1.0)


def peak_hbm_bandwidth(device_kind: str) -> float | None:
    """Per-device peak HBM bandwidth (bytes/s); None for unknown kinds."""
    return PEAK_HBM_BYTES.get(device_kind)


def model_flops_per_token(cfg, seq_len: int) -> float:
    """Model FLOPs per trained token (fwd + 2x bwd), PaLM-appendix style.

    Per layer, per token (forward): 8*d^2 (QKV+out projections) +
    4*seq*d (attention scores+values, causal NOT halved - the standard
    convention) + 4*d*ff (MLP; for MoE, the top-k activated experts).
    Plus 2*d*vocab for the LM head. Backward = 2x forward; remat recompute
    is excluded (MFU counts model FLOPs, not hardware FLOPs).
    """
    d, f, v, L = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.n_layers
    mlp = 4 * d * f * (cfg.moe_top_k if cfg.n_experts else 1)
    per_layer = 8 * d * d + 4 * seq_len * d + mlp
    return 3.0 * (L * per_layer + 2 * d * v)


# set after the first real span execution in this process: the backend
# init it absorbs is session-level, not per-program (r5 measurement)
_session_warm = False


def measure_dp_training(
    *,
    nb_proc: int | None = None,
    batch_size: int = 16,
    epochs: int = 25,
    data: str = "auto",
    synthetic_size: int | None = None,
    sync_mode: str = "epoch",
    compute_dtype: str = "float32",
    kernels: str = "xla",
    fused: bool = True,
    input_mode: str = "hbm",
    stream_prefetch: int = 2,
) -> dict:
    """Run the data-parallel regime and return measured results.

    Returns {devices, batch_size, epochs, val_acc, val_loss, train_s,
    source}. train_s = training + parameter-sync wall-clock (compile time
    excluded via AOT warm-up; eval outside), the reference-comparable
    metric.
    """
    # requested size passes through; the engine rejects infeasible counts
    # with a clear error rather than silently measuring a smaller mesh
    n = nb_proc if nb_proc else jax.device_count()
    train_split = load_split(
        True, source=data, synthetic_size=synthetic_size,
        # stream mode keeps uint8 host storage; the native kernel
        # normalizes per batch (data/stream.py)
        normalize_images=input_mode != "stream",
    )
    test_split = load_split(
        False, source=data,
        synthetic_size=max(1, synthetic_size // 5) if synthetic_size else None,
    )
    cfg = TrainConfig(
        batch_size=batch_size, epochs=epochs, nb_proc=n,
        regime="data_parallel", sync_mode=sync_mode,
        compute_dtype=compute_dtype, kernels=kernels,
        input_mode=input_mode, stream_prefetch=stream_prefetch,
    )
    timers = T.PhaseTimers()
    engine = Engine(cfg, train_split, test_split)
    if input_mode == "stream":
        fused = False  # streaming supports the per-epoch path only
    if fused:
        # one dispatch for the whole run; AOT compile, then measure.
        # The 1-epoch warm-up span absorbs SESSION-level first-execution
        # cost (measured r5: ~22 s of backend/runtime init landed inside
        # whichever row ran first in a claim session - the headline bs16
        # row read 18.7 s first-in-session vs 3.2 s after any prior real
        # execution; AOT compile alone does not trigger the init, a real
        # execution does). Once per process: the init is session-level,
        # so later rows in the same worker skip the throwaway epoch.
        engine.compile_span(epochs, eval_inside=False)
        global _session_warm
        if not _session_warm:
            engine.compile_span(1, eval_inside=False)
            engine.run_span(0, 1, eval_inside=False, timers=T.PhaseTimers())
            engine.reset_state()
            _session_warm = True
        engine.run_span(0, epochs, eval_inside=False, timers=timers)
        vl, va = engine._eval_fn(
            engine.params, engine.test_images, engine.test_labels,
            engine.test_weights,
        )
        final = engine.history[-1]
        final.val_loss, final.val_acc = float(vl), float(va)
    else:
        # per-epoch dispatch: warm up one epoch, rewind, measure
        engine.run_epoch(0, timers=T.PhaseTimers())
        engine.reset_state()
        for epoch in range(epochs):
            engine.run_epoch(epoch, timers=timers)
        final = engine.history[-1]
    return {
        "devices": n,
        "platform": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "batch_size": batch_size,
        "epochs": epochs,
        "val_acc": final.val_acc,
        "val_loss": final.val_loss,
        "train_s": timers.get(T.TRAINING) + timers.get(T.COMMUNICATION),
        "train_phase_s": round(timers.get(T.TRAINING), 3),
        "sync_phase_s": round(timers.get(T.COMMUNICATION), 3),
        "source": train_split.source,
    }


def measure_dp_scaling(
    *,
    ns=(1, 2, 4, 8),
    batch_size: int = 16,
    epochs: int = 3,
    synthetic_size: int = 4096,
) -> dict:
    """Relative data-parallel scaling curve on the virtual CPU mesh
    (r3 VERDICT missing item 3: multi-device performance evidence is
    single-device only; one chip is all the environment provides, so the
    sync-cost SHAPE is characterized on the mesh the tests use).

    Fixed total work (same dataset, same global batch sequence), mesh
    size n swept: each device trains total//n contiguous rows per epoch
    with epoch-edge pmean sync - the reference's own Table 1 experiment
    (/root/reference/data_parallelism_train.py:49-53,238-244). On this
    host the n virtual devices share ONE core, so ideal wall-clock is
    FLAT in n (the same total FLOPs, serialized); any growth of
    t_n / t_1 is parallelization overhead - per-device dispatch,
    collective sync, and the padded last batch per shard. That overhead
    curve is the transferable signal: on real n-chip hardware wall-clock
    divides by n modulo exactly this overhead (plus ICI latency the CPU
    mesh cannot see; stated in the row note). The per-epoch (unfused)
    path is measured so the training/sync phase split is attributable.

    Contrast with the reference's Table 1, where time GROWS 375 -> 1642 s
    from 3 -> 8 procs (oversubscribed cores + serialized parent sync):
    here the same sweep holds near-flat, which IS the framework's
    scaling story expressed within a one-core environment.
    """
    if not ns or ns[0] != 1:
        raise ValueError(
            f"ns must start at 1 (the overhead_vs_n1 baseline), got {ns}"
        )
    points = []
    for n in ns:
        if n > jax.device_count():
            continue  # skip just this point; ns need not be sorted
        r = measure_dp_training(
            nb_proc=n, batch_size=batch_size, epochs=epochs,
            data="synthetic", synthetic_size=synthetic_size, fused=False,
        )
        points.append({
            "n": n,
            "train_s": round(r["train_s"], 3),
            "train_phase_s": r["train_phase_s"],
            "sync_phase_s": r["sync_phase_s"],
        })
    t1 = points[0]["train_s"]
    for p in points:
        p["overhead_vs_n1"] = round(p["train_s"] / max(t1, 1e-9), 3)
        p["sync_frac"] = round(
            p["sync_phase_s"] / max(p["train_s"], 1e-9), 4
        )
    return {
        "devices": jax.device_count(),
        "platform": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "batch_size": batch_size,
        "epochs": epochs,
        "rows_total": synthetic_size,
        "host_cores": os.cpu_count(),
        "points": points,
        "overhead_vs_n1_max": max(p["overhead_vs_n1"] for p in points),
        "note": (
            "fixed total work on one shared host core: ideal wall is flat "
            "in n; overhead_vs_n1 is the measured parallelization+sync "
            "cost. Real n-chip wall divides by n modulo this curve (ICI "
            "latency not visible on a CPU mesh)."
        ),
    }


def _lm_axis_sweep(
    sizes, *, cfg, make_mesh, axis_key, batch, seq_len, vocab, steps,
    attn_impl="ring", point_extras=None,
):
    """Shared body of the sp/ep scaling sweeps: per mesh size, build the
    mesh and a fresh sharded model, compile one LM train step, hard-fence
    a warm-up, time `steps` steps, and normalize wall against the size-1
    baseline (the first sweep entry, enforced). Returns the points list;
    each point carries `{axis_key: n, wall_s, tokens_per_s, final_loss,
    overhead_vs_{axis_key}1}` plus `point_extras(n)` if given.
    (`measure_dp_scaling` stays engine-based: the CNN regime times the
    train/sync phase split, which this LM-step loop has no notion of.)"""
    from ..models import transformer as tfm
    from ..utils.timers import hard_block
    from . import lm as lmtrain

    if not sizes or sizes[0] != 1:
        raise ValueError(
            f"{axis_key} sweep must start at 1 (the "
            f"overhead_vs_{axis_key}1 baseline), got {sizes}"
        )
    points = []
    for n in sizes:
        if n > jax.device_count():
            continue
        mesh = make_mesh(n)
        params, _ = lmtrain.shard_params(
            tfm.init_params(jax.random.key(0), cfg), cfg, mesh
        )
        mom = lmtrain.init_lm_momentum(params, mesh)
        step = lmtrain.make_lm_train_step(cfg, mesh, lr=0.01,
                                          attn_impl=attn_impl)
        tokens, targets = lmtrain.make_copy_task(
            jax.random.key(1), batch=batch, seq_len=seq_len, vocab=vocab
        )
        if attn_impl == "zigzag" and n > 1:
            # zigzag consumes tokens in zigzag SHARD order (the caller
            # permutes - parallel/ring.py zigzag_order; pinned by
            # tests/test_transformer.py): without this each sp trains a
            # differently-permuted objective and the loss column - the
            # sweep's semantics check - drifts per sp
            from ..parallel.ring import zigzag_order

            perm = zigzag_order(seq_len, n)
            tokens, targets = tokens[:, perm], targets[:, perm]
        params, mom, loss = step(params, mom, tokens, targets)  # compile
        hard_block(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            params, mom, loss = step(params, mom, tokens, targets)
        hard_block(loss)
        dt = time.perf_counter() - t0
        point = {
            axis_key: n,
            "wall_s": round(dt, 3),
            "tokens_per_s": round(batch * seq_len * steps / dt),
            "final_loss": round(float(loss), 4),
        }
        if point_extras:
            point.update(point_extras(n))
        points.append(point)
    t1 = points[0]["wall_s"]
    for p in points:
        p[f"overhead_vs_{axis_key}1"] = round(
            p["wall_s"] / max(t1, 1e-9), 3)
    return points


def measure_sp_scaling(
    *,
    sps=(1, 2, 4, 8),
    d_model: int = 128,
    n_layers: int = 4,
    n_heads: int = 8,
    d_ff: int = 512,
    vocab: int = 2048,
    seq_len: int = 2048,
    batch: int = 2,
    steps: int = 3,
    attn_impl: str = "ring",
) -> dict:
    """Ring-attention sequence-parallel scaling shape on the virtual CPU
    mesh - the SP analog of `measure_dp_scaling` (long-context evidence
    beyond the single-chip hardware this environment provides).

    Fixed GLOBAL sequence, sp swept: each device holds seq_len/sp tokens
    and the ring rotates K/V blocks sp-1 times per attention
    (parallel/ring.py). On n virtual devices sharing ONE host core,
    total model FLOPs are identical at every sp, so ideal wall-clock is
    flat; growth of t_sp / t_1 is the sequence-parallel overhead
    (per-device dispatch, ring permutes, per-hop softmax-merge). On real
    chips wall divides by sp modulo exactly this curve plus ICI latency
    (which a CPU mesh cannot see - stated in the row note).
    """
    from ..models import transformer as tfm
    from . import lm as lmtrain

    cfg = tfm.TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, d_ff=d_ff,
    )
    # at sp=1 the step builder drops the sequence axis (lm.py: seq axis
    # None) and the same attn_impl runs as plain local attention - the
    # baseline is the identical program minus the ring, exactly the
    # overhead being measured
    points = _lm_axis_sweep(
        sps, cfg=cfg, make_mesh=lambda sp: lmtrain.create_lm_mesh(1, sp, 1),
        axis_key="sp", batch=batch, seq_len=seq_len, vocab=vocab,
        steps=steps, attn_impl=attn_impl,
    )
    return {
        "devices": jax.device_count(),
        "platform": jax.default_backend(),
        "attn_impl": attn_impl,
        "d_model": d_model, "n_layers": n_layers, "seq_len": seq_len,
        "batch": batch, "steps": steps,
        "host_cores": os.cpu_count(),
        "points": points,
        "overhead_vs_sp1_max": max(p["overhead_vs_sp1"] for p in points),
        "note": (
            "fixed global sequence on one shared host core: ideal wall "
            f"is flat in sp; overhead_vs_sp1 is the measured {attn_impl} "
            "sequence-parallel cost. Real sp-chip wall divides by sp "
            "modulo this curve (ICI latency not visible on a CPU mesh)."
        ),
    }


def fit_tick_model(results, *, n_layers, mb_rows, seq_len, steps,
                   pp_n: int = 4) -> dict:
    """Fit T = ticks * (w*c + o) to measured pp-bubble configs.

    Separates the schedule bubble from per-tick dispatch overhead: w =
    layers/tick, c = per-layer cost, o = fixed per-tick overhead - two
    unknowns over len(results) configs, least squares. Annotates each
    result with `bubble_overhead_adjusted` = 1 - (v*M useful ticks of
    model time) / MEASURED time (dividing model useful by model total
    would cancel the fit and always reproduce the analytic number -
    review r3 caught exactly that tautology), and returns the tick_model
    dict.

    The physical model requires c, o >= 0: when the unconstrained
    optimum has a negative component, the constrained (NNLS) optimum is
    one of the two single-parameter boundary fits (o=0 c-only, c=0
    o-only) - the lower-SSE one is chosen rather than assuming which
    coordinate went negative, and both optima are reported
    (`boundary_solution`). A slightly negative unconstrained o is
    expected on a shared host (later ticks run warmer caches), so the
    o=0 boundary is a finding - per-tick overhead statistically zero -
    not a fallback. Pure function of the measured configs: unit-tested
    in tests/test_pipeline.py without running a measurement."""
    import numpy as np

    ticks = np.array([r["interleave"] * r["microbatches"] + pp_n - 1
                      for r in results], np.float64)
    work = np.array([n_layers / (r["interleave"] * pp_n)
                     for r in results], np.float64)
    t_meas = np.array([
        r["microbatches"] * mb_rows * seq_len * steps / r["tokens_per_s"]
        for r in results
    ])
    A = np.stack([ticks * work, ticks], axis=1)
    (c_un, o_un), res, *_ = np.linalg.lstsq(A, t_meas, rcond=None)
    c_fit, o_fit = float(c_un), float(o_un)
    boundary = None
    if o_fit < 0 or c_fit < 0:
        tw = ticks * work
        cands = [(max(float(tw @ t_meas / (tw @ tw)), 0.0), 0.0),
                 (0.0, max(float(ticks @ t_meas / (ticks @ ticks)), 0.0))]
        c_fit, o_fit = min(
            cands, key=lambda co: float(
                ((A @ np.array(co)) - t_meas) ** 2 @ np.ones_like(t_meas)))
        boundary = {"per_layer_s_unconstrained": round(float(c_un), 6),
                    "per_tick_overhead_s_unconstrained": round(
                        float(o_un), 6)}
    pred = A @ np.array([c_fit, o_fit])
    fit_err = float(np.abs(pred - t_meas).max() / t_meas.max())
    for r, w, t_i in zip(results, work, t_meas):
        useful = r["interleave"] * r["microbatches"] * (w * c_fit + o_fit)
        r["bubble_overhead_adjusted"] = round(1.0 - useful / t_i, 4)
    return {
        "per_layer_s": round(float(c_fit), 6),
        "per_tick_overhead_s": round(float(o_fit), 6),
        "rel_fit_err": round(fit_err, 4),
        "n_configs": len(results),
        **({"boundary_solution": boundary} if boundary else {}),
    }


def measure_pp_bubble(
    *,
    d_model: int = 256,
    n_layers: int = 8,
    n_heads: int = 8,
    d_ff: int = 1024,
    vocab: int = 512,
    seq_len: int = 128,
    mb_rows: int = 2,
    steps: int = 6,
    warmup: int = 1,
) -> dict:
    """Measure the pp=4 pipeline bubble empirically (VERDICT r2 item 4).

    Runs the pipeline train step at fixed microbatch SIZE (mb_rows rows)
    and varying (M microbatches, v interleave), so tokens/s is
    proportional to 1 - bubble: every config does identical per-token
    work and differs only in how many bubble ticks the schedule pays.
    Reports per-config tokens/s plus the empirically derived bubble
    (1 - tok/s / ideal, where ideal extrapolates the best config by its
    own analytic bubble). Needs >= 4 devices - meant for the 4-device
    virtual CPU mesh (the bench row sets JAX_PLATFORMS=cpu); relative
    throughput, not absolute, is the measurement.
    """
    import jax.numpy as jnp

    from ..models import transformer as tfm
    from ..parallel import pipeline as ppl

    cfg = tfm.TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, d_ff=d_ff,
    )
    mesh = ppl.create_pp_mesh(1, 4, 1)
    base = tfm.init_params(jax.random.key(0), cfg)
    from ..train import lm as lmtrain
    from ..utils.timers import hard_block

    results = []
    # 7 configs over 2 fit parameters (r4 VERDICT weak #6: 4 points for
    # a 2-parameter model was underdetermined and the clamp kicked in);
    # spans analytic bubble 0.158 (M=16,v=1) .. 0.6 (M=2,v=1). v=4 is
    # infeasible at L=8/pp=4 (half a layer per chunk) and v=2 needs
    # M % 4 == 0 (parallel/pipeline.py), so extra spread comes from the
    # M axis at v=1 plus M=16 at v=2.
    for m, v in ((2, 1), (4, 1), (8, 1), (16, 1), (4, 2),
                 (8, 2), (16, 2)):
        batch = m * mb_rows
        # copy per config: the donated train step consumes its params, and
        # device_put aliases (rather than copies) leaves whose placement
        # already matches - donating an alias would delete `base`'s leaf
        params, _ = ppl.shard_pp_params(
            jax.tree.map(jnp.array, base), cfg, mesh, interleave=v
        )
        mom = jax.tree.map(jnp.zeros_like, params)
        step = ppl.make_pp_train_step(
            cfg, mesh, n_microbatches=m, lr=0.01, interleave=v
        )
        tokens, targets = lmtrain.make_copy_task(
            jax.random.key(1), batch=batch, seq_len=seq_len, vocab=vocab
        )
        for _ in range(max(warmup, 1)):  # >=1: the fence needs a loss
            params, mom, loss = step(params, mom, tokens, targets)
        hard_block(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            params, mom, loss = step(params, mom, tokens, targets)
        hard_block(loss)
        dt = time.perf_counter() - t0
        pp_n = 4
        results.append({
            "microbatches": m, "interleave": v,
            "tokens_per_s": round(batch * seq_len * steps / dt),
            "bubble_analytic": round((pp_n - 1) / (v * m + pp_n - 1), 4),
        })
    best = max(results, key=lambda r: r["tokens_per_s"])
    ideal = best["tokens_per_s"] / (1.0 - best["bubble_analytic"])
    for r in results:
        r["bubble_measured"] = round(1.0 - r["tokens_per_s"] / ideal, 4)

    tick_model = fit_tick_model(
        results, n_layers=n_layers, mb_rows=mb_rows, seq_len=seq_len,
        steps=steps,
    )
    return {
        "pp": 4, "d_model": d_model, "n_layers": n_layers,
        "seq_len": seq_len, "mb_rows": mb_rows,
        "devices": jax.device_count(), "platform": jax.default_backend(),
        "configs": results,
        "tick_model": tick_model,
        "note": (
            "bubble_measured compares raw tokens/s against the best "
            "config extrapolated by its analytic bubble; CPU-mesh "
            "per-tick dispatch overhead inflates it for long schedules "
            "(high M at v=1). bubble_overhead_adjusted = 1 - (model "
            "time of the v*M useful ticks, from the fitted T*(w*c+o) "
            "tick model) / MEASURED time: it tracks bubble_analytic "
            "only if the schedule really pays v*M+P-1 ticks "
            "(rel_fit_err is the model's residual)."
        ),
    }


def measure_lm_decode(
    *,
    d_model: int = 512,
    n_layers: int = 8,
    n_heads: int = 8,
    d_ff: int = 2048,
    vocab: int = 32768,
    batch: int = 16,
    prompt_len: int = 128,
    gen_short: int = 128,
    gen_long: int = 512,
    dtype: str = "bfloat16",
    repeats: int = 3,
) -> dict:
    """KV-cache decode throughput (models/transformer.py `generate`).

    `generate` scans prompt_len + max_new_tokens cached steps over a
    STATIC cache of that total size - every step attends the full padded
    cache - so per-step cost is a function of the total length, and an
    honest rate is the per-step AVERAGE at a stated cache size, not a
    cross-length "marginal" (a two-length diff mixes c(short) and
    c(long) and understates throughput). Reported: average ms/step and
    tokens/s at each of the two cache sizes (prompt + gen_short /
    gen_long); the spread IS the measured cache-length scaling. Compile
    time is excluded by a jitted warm-up per static length.

    Decode is HBM-bandwidth-bound, not FLOP-bound: each step streams
    every parameter once (the batch shares the read), so the utilization
    lens is bytes/s against peak HBM bandwidth - `hbm_util_pct`
    (params_bytes * steps/s / peak_bw) at the LONG cache size. MFU
    against the MXU peak would be misleadingly tiny here and is
    deliberately not reported.
    """
    import numpy as np

    import jax.numpy as jnp

    from ..models import transformer as tfm
    from ..utils.timers import hard_block

    cfg = tfm.TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, d_ff=d_ff,
        dtype=jnp.bfloat16 if dtype == "bfloat16" else jnp.float32,
    )
    params = tfm.init_params(jax.random.key(0), cfg)
    prompt = jax.random.randint(
        jax.random.key(1), (batch, prompt_len), 0, vocab, jnp.int32
    )

    def timed(n_new: int) -> float:
        # jit per static length: generate re-traces on every bare call
        # (~seconds of host time); under jit the repeats are cache hits
        # measuring device time only
        g = jax.jit(
            lambda p, pr: tfm.generate(p, pr, cfg, max_new_tokens=n_new)
        )
        out = g(params, prompt)
        hard_block(out)  # warm-up: compile for this static length
        best = float("inf")
        for _ in range(max(repeats, 1)):
            t0 = time.perf_counter()
            out = g(params, prompt)
            hard_block(out)
            best = min(best, time.perf_counter() - t0)
        return max(best, 1e-9)

    def stats(n_new: int, t: float) -> dict:
        steps = prompt_len + n_new  # the scan length (generate)
        return {
            "cache_len": steps,
            "wall_s": round(t, 3),
            "ms_per_step": round(t / steps * 1e3, 3),
            "tokens_per_s": round(batch * steps / t),
        }

    short = stats(gen_short, timed(gen_short))
    long_ = stats(gen_long, timed(gen_long))
    steps_s = 1e3 / long_["ms_per_step"]

    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    bytes_per_param = 2 if dtype == "bfloat16" else 4
    dev = jax.devices()[0]
    # decode streams params once per step, so params_bytes * steps/s
    # bounds achievable throughput (PEAK_HBM_BYTES table above)
    hbm_bw = peak_hbm_bandwidth(dev.device_kind)
    hbm_util = (
        round(n_params * bytes_per_param * steps_s / hbm_bw * 100.0, 2)
        if hbm_bw else None
    )
    return {
        "d_model": d_model, "n_layers": n_layers, "n_heads": n_heads,
        "vocab": vocab, "batch": batch, "prompt_len": prompt_len,
        "gen_short": gen_short, "gen_long": gen_long, "dtype": dtype,
        "device_kind": dev.device_kind,
        "platform": jax.default_backend(),
        # provenance: which per-step attention path produced this row -
        # merge-by-id would otherwise let a DNN_TPU_DECODE_IMPL=pallas
        # run silently replace the XLA numbers under the same row id
        "decode_impl": (
            "pallas" if os.environ.get("DNN_TPU_DECODE_IMPL", "auto")
            in ("pallas", "pallas-interpret") else "xla"
        ),
        # headline decode rate: per-step average at the LONG cache size
        # (conservative; the short-cache row shows the scaling)
        "decode_tokens_per_s": long_["tokens_per_s"],
        "decode_steps_per_s": round(steps_s, 1),
        "ms_per_step": long_["ms_per_step"],
        "at_cache_short": short,
        "at_cache_long": long_,
        "n_params": n_params,
        "hbm_util_pct": hbm_util,
    }


def measure_lm_training(
    *,
    d_model: int = 512,
    n_layers: int = 8,
    n_heads: int = 8,
    d_ff: int = 2048,
    vocab: int = 32768,
    seq_len: int = 2048,
    batch: int = 16,
    steps: int = 20,
    warmup: int = 2,
    attn: str = "flash",
    dtype: str = "bfloat16",
    remat: bool = False,
    remat_attn: bool = False,
    remat_policy: str = "",
    loss_chunks: int = 0,
    lr: float = 0.01,
    accum_steps: int = 1,
    grad_sync: str = "end",
    bucket_mb: float = 4.0,
    tracer=None,
    step_stats=None,
) -> dict:
    """Single-mesh LM throughput: tokens/s and MFU over `steps` timed steps.

    attn='flash' uses the tuned Pallas kernel on TPU (falls back to plain
    attention elsewhere - the returned dict records which path ran, so
    callers can fail loudly when the compiled kernel was required:
    VERDICT r2 weak #7). MFU follows `model_flops_per_token` with the
    dtype-adjusted peak; `hw_flops_per_step` adds the compiled
    executable's own cost_analysis() FLOPs when the backend reports them
    (None otherwise - utils/tracing.py compiled_flops).

    `tracer` (utils/tracing.py Tracer) records per-step `train_step` spans
    inside the timed loop WITHOUT fencing (dispatch time; fencing each
    step would change the measurement) plus a fenced `steady_window` span
    around the whole loop; `step_stats` (StepStats) gets one steady
    record per timed step from the same unfenced walls - trend data, not
    the headline (which stays the fenced-window tokens/s below).

    The row also carries the run's own goodput accounting
    (utils/goodput.py: a private ledger over setup -> warmup -> timed
    window): ``goodput_ratio`` and the non-zero ``badput_breakdown``
    seconds, so the bench matrix reports not just how fast the steady
    state is but how much of the measurement's wall-clock WAS steady
    state (init/compile being the honest overhead of short benches).
    """
    import jax.numpy as jnp

    from ..models import transformer as tfm
    from ..utils.goodput import GOODPUT_CAUSE, GoodputLedger
    from . import lm as lmtrain

    # a private ledger (never the process singleton: rows must not leak
    # accounting into each other when several run in one process)
    ledger = GoodputLedger().start()

    cfg = tfm.TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, d_ff=d_ff,
        dtype=jnp.bfloat16 if dtype == "bfloat16" else jnp.float32,
        remat=remat,
        remat_attn=remat_attn,
        remat_policy=remat_policy,
    )
    mesh = lmtrain.create_lm_mesh(1, 1, 1)
    params0 = tfm.init_params(jax.random.key(0), cfg)
    params, _ = lmtrain.shard_params(params0, cfg, mesh)
    mom = lmtrain.init_lm_momentum(params, mesh)
    step = lmtrain.make_lm_train_step(
        cfg, mesh, lr=lr, attn_impl=attn, loss_chunks=loss_chunks,
        accum_steps=accum_steps, grad_sync=grad_sync, bucket_mb=bucket_mb,
    )
    tokens, targets = lmtrain.make_copy_task(
        jax.random.key(1), batch=batch, seq_len=seq_len, vocab=vocab
    )
    from ..utils import tracing as tracing_mod
    from ..utils.timers import hard_block

    if tracer is None:
        tracer = tracing_mod.NULL_TRACER
    hw_flops = tracing_mod.compiled_flops(step, params, mom, tokens, targets)

    # static cross-check (shardlint, analysis/): abstractly trace THE
    # compiled step being benched and total its collective payload, so
    # the bench row carries both the runtime ring estimate and the
    # analyzer's logical-payload count side by side (they use different
    # conventions; the point is that a schedule regression moves one
    # without the other). Trace-only - never affects the timed loop.
    static_comm = None
    try:
        from ..analysis.trace import collect_trace

        static_comm = collect_trace(
            jax.make_jaxpr(step)(params, mom, tokens, targets)
        ).total_collective_bytes()
    except Exception:
        pass
    if step_stats is not None and static_comm is not None:
        step_stats.static_comm_bytes_per_step = static_comm

    with tracer.span("warmup", track="train", steps=max(warmup, 1)):
        t_warm = time.perf_counter()
        for _ in range(max(warmup, 1)):
            params, mom, loss = step(params, mom, tokens, targets)
        hard_block(loss)
        # the warmup window absorbs compilation: one compile span on the
        # ledger (it also closes the setup-side init interval)
        ledger.step_span(0, time.perf_counter() - t_warm, is_compile=True)
    timed = step
    if tracer.enabled or step_stats is not None:
        from . import lm as _lm

        # compile_first=False: the warm-up above absorbed compilation, so
        # every unfenced loop record is a steady-state dispatch wall
        timed = _lm.make_traced_step(
            step, tracer=tracer, step_stats=step_stats,
            items_per_step=batch * seq_len, fence=False,
            compile_first=False,
        )
    with tracer.span("steady_window", track="train", steps=steps):
        t0 = time.perf_counter()
        for _ in range(steps):
            params, mom, loss = timed(params, mom, tokens, targets)
        hard_block(loss)
        dt = max(time.perf_counter() - t0, 1e-9)
    # the fenced steady window is the goodput; everything around it
    # (model build, warmup/compile, fences) is the bench's own badput
    ledger.step_span(
        1, dt, tokens=batch * seq_len * steps, is_compile=False
    )
    goodput_rec = ledger.finalize()
    tok_s = batch * seq_len * steps / dt
    flops_tok = model_flops_per_token(cfg, seq_len)
    dev = jax.devices()[0]
    peak = peak_flops(dev.device_kind, dtype)
    mfu = flops_tok * tok_s / peak * 100.0 if peak else None
    if step_stats is not None:
        step_stats.set_flops(
            hw_flops if hw_flops is not None
            else flops_tok * batch * seq_len,
            "cost_analysis" if hw_flops is not None else "analytic",
        )
        if step_stats.peak_flops_per_device is None:
            step_stats.peak_flops_per_device = peak
        step_stats.capture_memory(tracer)
    # committed-memory delta column for the grad_sync variant rows: the
    # overlap schedule's shard-carry should show up here (CPU returns None)
    snap = tracing_mod.device_memory_snapshot()
    mem_peak = (
        max(
            s.get("peak_bytes_in_use", s.get("bytes_in_use", 0))
            for s in snap.values()
        )
        if snap else None
    )
    return {
        "d_model": d_model, "n_layers": n_layers, "n_heads": n_heads,
        "d_ff": d_ff, "seq_len": seq_len,
        "vocab": vocab, "batch": batch, "steps": steps, "dtype": dtype,
        "attn": attn, "remat": remat, "remat_attn": remat_attn,
        "remat_policy": remat_policy,
        "accum_steps": accum_steps, "grad_sync": grad_sync,
        "mem_peak_bytes": mem_peak,
        # shardlint static logical payload per step (None when the trace
        # failed); the bench row's cross-check against StepStats'
        # comm_bytes_per_step runtime ring estimate
        "static_collective_bytes": static_comm,
        # provenance: WHICH flash kernel measured this row (r3's numbers
        # were the library kernel; r4+ defaults to the own kernels)
        "attn_kernel": (
            ("pallas-flash-"
             + os.environ.get("DNN_TPU_FLASH_IMPL", "own"))
            if attn == "flash" and on_tpu()
            else "xla"
        ),
        "device_kind": dev.device_kind,
        "tokens_per_s": round(tok_s),
        "wall_s": round(dt, 3),
        # goodput accounting of this measurement's own wall-clock
        # (utils/goodput.py; steady window / total incl. setup+compile)
        "goodput_ratio": goodput_rec["goodput_ratio"],
        "badput_breakdown": {
            k: round(v, 3)
            for k, v in goodput_rec["badput_s"].items()
            if v > 0 and k != GOODPUT_CAUSE
        },
        "model_tflops_per_s": round(flops_tok * tok_s / 1e12, 2),
        "mfu_pct": round(mfu, 2) if mfu is not None else None,
        # provenance: hardware FLOPs per step straight from the compiled
        # executable's cost_analysis() (includes remat recompute, unlike
        # the model-FLOPs MFU numerator above); None where unreported
        "hw_flops_per_step": hw_flops,
        "final_loss": float(loss),
    }


def measure_guard_overhead(
    *,
    d_model: int = 512,
    n_layers: int = 8,
    n_heads: int = 8,
    d_ff: int = 2048,
    vocab: int = 32768,
    seq_len: int = 2048,
    batch: int = 16,
    steps: int = 20,
    warmup: int = 2,
    attn: str = "flash",
    dtype: str = "bfloat16",
    budget_pct: float = 1.0,
) -> dict:
    """Guard-overhead A/B: the identical LM config with guard off vs
    ``--guard warn`` (health bundle compiled into the step + one-step-
    lagged host observation, train/guard.py HealthPipe).

    Two claims, both asserted into the returned row:
    - ``within_budget``: the warn-mode steady-state step-time overhead is
      under `budget_pct` (default 1%) - the health bundle costs one O(1)
      finite-check on scalars the step already computes (plus one global
      grad-norm reduction when clipping is off, as here - the honest
      worst case) and the observation never fences the dispatch pipeline.
    - ``final_loss_bitwise_equal``: warn mode is observation-only - the
      guarded run's final loss is BIT-IDENTICAL to the unguarded run's
      (same seeds, same data, same update math).
    """
    import jax.numpy as jnp

    from ..models import transformer as tfm
    from . import lm as lmtrain
    from .guard import GuardConfig, HealthPipe, TrainingGuard

    cfg = tfm.TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, d_ff=d_ff,
        dtype=jnp.bfloat16 if dtype == "bfloat16" else jnp.float32,
    )
    mesh = lmtrain.create_lm_mesh(1, 1, 1)
    params0 = tfm.init_params(jax.random.key(0), cfg)
    tokens, targets = lmtrain.make_copy_task(
        jax.random.key(1), batch=batch, seq_len=seq_len, vocab=vocab
    )
    from ..utils.timers import hard_block

    def run(guard_on: bool):
        params, _ = lmtrain.shard_params(params0, cfg, mesh)
        mom = lmtrain.init_lm_momentum(params, mesh)
        step = lmtrain.make_lm_train_step(
            cfg, mesh, lr=0.01, attn_impl=attn, with_health=guard_on,
        )
        pipe = None
        if guard_on:
            pipe = HealthPipe(TrainingGuard(
                GuardConfig(policy="warn"), log=lambda *_: None,
            ))
        loss = None
        for i in range(max(warmup, 1)):
            out = step(params, mom, tokens, targets)
            params, mom, loss = out[0], out[1], out[2]
        hard_block(loss)
        t0 = time.perf_counter()
        for i in range(steps):
            out = step(params, mom, tokens, targets)
            params, mom, loss = out[0], out[1], out[2]
            if pipe is not None:
                pipe.push(i, out[3])
        if pipe is not None:
            pipe.flush()
        hard_block(loss)
        dt = max(time.perf_counter() - t0, 1e-9)
        return dt, float(loss)

    base_dt, base_loss = run(False)
    guard_dt, guard_loss = run(True)
    overhead_pct = (guard_dt / base_dt - 1.0) * 100.0
    tok = batch * seq_len * steps
    return {
        "d_model": d_model, "n_layers": n_layers, "seq_len": seq_len,
        "batch": batch, "steps": steps, "dtype": dtype, "attn": attn,
        "device_kind": jax.devices()[0].device_kind,
        "base_tokens_per_s": round(tok / base_dt),
        "guard_tokens_per_s": round(tok / guard_dt),
        "overhead_pct": round(overhead_pct, 3),
        "budget_pct": budget_pct,
        "within_budget": overhead_pct < budget_pct,
        "final_loss": guard_loss,
        "final_loss_bitwise_equal": base_loss == guard_loss,
    }


def measure_dynamics_overhead(
    *,
    d_model: int = 512,
    n_layers: int = 8,
    n_heads: int = 8,
    d_ff: int = 2048,
    vocab: int = 32768,
    seq_len: int = 2048,
    batch: int = 16,
    steps: int = 20,
    warmup: int = 2,
    attn: str = "flash",
    dtype: str = "bfloat16",
    budget_pct: float = 1.0,
) -> dict:
    """Dynamics-observatory A/B: the identical LM config with
    ``--dynamics`` off vs on (per-layer norm bundle compiled into the
    step + the one-step-lagged DynamicsSink decode, train/dynamics.py).

    Two claims, both asserted into the returned row:
    - ``within_budget``: the steady-state step-time overhead is under
      `budget_pct` (default 1%) - the per-leaf squared-norm reductions
      are O(params) elementwise flops over tensors the backward already
      produced (vs the O(params * seq * batch) matmuls of the step), and
      the sink's decode rides the same lagged fetch cadence as the
      guard, never fencing the dispatch pipeline.
    - ``final_loss_bitwise_equal``: dynamics is observation-only - the
      bundle is an extra OUTPUT of the step, the update math is
      untouched, so the final loss is BIT-IDENTICAL to the plain run's.
    """
    import jax.numpy as jnp

    from ..models import transformer as tfm
    from ..parallel.rules import named_leaves
    from . import lm as lmtrain
    from .dynamics import DynamicsSink

    cfg = tfm.TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, d_ff=d_ff,
        dtype=jnp.bfloat16 if dtype == "bfloat16" else jnp.float32,
    )
    mesh = lmtrain.create_lm_mesh(1, 1, 1)
    params0 = tfm.init_params(jax.random.key(0), cfg)
    tokens, targets = lmtrain.make_copy_task(
        jax.random.key(1), batch=batch, seq_len=seq_len, vocab=vocab
    )
    from ..utils.timers import hard_block

    def run(dyn_on: bool):
        params, _ = lmtrain.shard_params(params0, cfg, mesh)
        mom = lmtrain.init_lm_momentum(params, mesh)
        step = lmtrain.make_lm_train_step(
            cfg, mesh, lr=0.01, attn_impl=attn, dynamics=dyn_on,
        )
        sink = None
        if dyn_on:
            sink = DynamicsSink([p for p, _ in named_leaves(params)])
        loss = None
        for i in range(max(warmup, 1)):
            out = step(params, mom, tokens, targets)
            params, mom, loss = out[0], out[1], out[2]
        hard_block(loss)
        t0 = time.perf_counter()
        for i in range(steps):
            out = step(params, mom, tokens, targets)
            params, mom, loss = out[0], out[1], out[2]
            if sink is not None:
                sink.push(i, out[3])
        if sink is not None:
            sink.flush()
        hard_block(loss)
        dt = max(time.perf_counter() - t0, 1e-9)
        return dt, float(loss)

    base_dt, base_loss = run(False)
    dyn_dt, dyn_loss = run(True)
    overhead_pct = (dyn_dt / base_dt - 1.0) * 100.0
    tok = batch * seq_len * steps
    return {
        "d_model": d_model, "n_layers": n_layers, "seq_len": seq_len,
        "batch": batch, "steps": steps, "dtype": dtype, "attn": attn,
        "device_kind": jax.devices()[0].device_kind,
        "base_tokens_per_s": round(tok / base_dt),
        "dynamics_tokens_per_s": round(tok / dyn_dt),
        "overhead_pct": round(overhead_pct, 3),
        "budget_pct": budget_pct,
        "within_budget": overhead_pct < budget_pct,
        "final_loss": dyn_loss,
        "final_loss_bitwise_equal": base_loss == dyn_loss,
    }


def measure_watchdog_overhead(
    *,
    d_model: int = 512,
    n_layers: int = 8,
    n_heads: int = 8,
    d_ff: int = 2048,
    vocab: int = 32768,
    seq_len: int = 2048,
    batch: int = 16,
    steps: int = 20,
    warmup: int = 2,
    attn: str = "flash",
    dtype: str = "bfloat16",
    budget_pct: float = 1.0,
) -> dict:
    """Live-observability overhead A/B: the identical LM config with no
    monitoring vs the full ``--metrics-port`` stack live - metrics
    registry, /metrics + /healthz HTTP server thread, stall/recompile
    watchdog thread, the per-step publish sites (heartbeat, step
    counter, step-time histogram, one ``_cache_size()`` read), PLUS the
    fleet-observability extras a supervised worker carries: the
    heartbeat-FILE writer thread and the armed write-through crash
    flight recorder (`utils/obs.py HeartbeatFileWriter` / `FLIGHT`),
    PLUS the armed goodput ledger (`utils/goodput.py LEDGER`: per-step
    interval recording, registry export, and the write-through run
    record) - the FULL supervised-worker observability surface under
    the same <1% steady-step budget.

    Two claims, both asserted into the returned row:
    - ``within_budget``: steady-step overhead under `budget_pct` (default
      1%). The per-step cost is a handful of host-side float stores on
      pre-resolved metric children (utils/obs.py's lock-free fast path);
      the server and watchdog live on their own daemon threads, off the
      step loop's critical path.
    - ``final_loss_bitwise_equal``: monitoring is observation-only - the
      monitored run's final loss is BIT-IDENTICAL to the bare run's.
    """
    import jax.numpy as jnp

    from ..models import transformer as tfm
    from . import lm as lmtrain
    from .monitor import WatchdogConfig, attach_monitor

    cfg = tfm.TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, d_ff=d_ff,
        dtype=jnp.bfloat16 if dtype == "bfloat16" else jnp.float32,
    )
    mesh = lmtrain.create_lm_mesh(1, 1, 1)
    params0 = tfm.init_params(jax.random.key(0), cfg)
    tokens, targets = lmtrain.make_copy_task(
        jax.random.key(1), batch=batch, seq_len=seq_len, vocab=vocab
    )
    from ..utils.timers import hard_block

    def run(monitored: bool):
        params, _ = lmtrain.shard_params(params0, cfg, mesh)
        mom = lmtrain.init_lm_momentum(params, mesh)
        step = lmtrain.make_lm_train_step(
            cfg, mesh, lr=0.01, attn_impl=attn
        )
        monitor = None
        tmpdir = None
        env_keys = ("DNN_TPU_HEARTBEAT_FILE", "DNN_TPU_FLIGHT_FILE",
                    "DNN_TPU_RUN_RECORD")
        if monitored:
            # the FULL fleet stack: registry + server + watchdog as
            # before, PLUS the supervised-worker extras - heartbeat-file
            # writer thread, the armed (write-through) crash flight
            # recorder, and the armed goodput ledger with its run-record
            # write-through - so the <1% budget covers the whole
            # observability surface a supervised worker carries
            import tempfile

            from ..utils.goodput import LEDGER

            tmpdir = tempfile.mkdtemp(prefix="dnn_fleet_obs_bench_")
            os.environ["DNN_TPU_HEARTBEAT_FILE"] = os.path.join(
                tmpdir, "hb.json"
            )
            os.environ["DNN_TPU_FLIGHT_FILE"] = os.path.join(
                tmpdir, "flight.json"
            )
            os.environ["DNN_TPU_RUN_RECORD"] = os.path.join(
                tmpdir, "run_record.json"
            )
            LEDGER.reset()
            LEDGER.start()
            monitor = attach_monitor(
                metrics_port=0, config=WatchdogConfig(),
                log=lambda *_: None,
            )
            monitor.recompiles.swap(step)
        reg = monitor.registry if monitor is not None else None
        m_steps = m_wall = led = None
        if reg is not None:
            from ..utils.goodput import LEDGER as led

            m_steps = reg.counter("train_steps_total")
            m_wall = reg.histogram("train_step_seconds")
        loss = None
        try:
            for i in range(max(warmup, 1)):
                params, mom, loss = step(params, mom, tokens, targets)[:3]
            hard_block(loss)
            t0 = time.perf_counter()
            for i in range(steps):
                ts = time.perf_counter()
                params, mom, loss = step(params, mom, tokens, targets)[:3]
                if reg is not None:
                    # the exact per-step publish set --metrics-port wires
                    step_dt = time.perf_counter() - ts
                    reg.beat(i)
                    reg.mark_ready()
                    m_steps.inc()
                    m_wall.observe(step_dt)
                    monitor.recompiles.observe(i)
                    led.step_span(i, step_dt, tokens=batch * seq_len,
                                  is_compile=False)
            hard_block(loss)
            dt = max(time.perf_counter() - t0, 1e-9)
        finally:
            if monitor is not None:
                monitor.close()
            if tmpdir is not None:
                from ..utils.goodput import LEDGER
                from ..utils.obs import FLIGHT

                LEDGER.finalize()
                LEDGER.reset()  # disarm the process-global ledger
                FLIGHT.reset()  # disarm the process-global recorder
                for k in env_keys:
                    os.environ.pop(k, None)
        return dt, float(loss)

    base_dt, base_loss = run(False)
    mon_dt, mon_loss = run(True)
    overhead_pct = (mon_dt / base_dt - 1.0) * 100.0
    tok = batch * seq_len * steps
    return {
        "d_model": d_model, "n_layers": n_layers, "seq_len": seq_len,
        "batch": batch, "steps": steps, "dtype": dtype, "attn": attn,
        "device_kind": jax.devices()[0].device_kind,
        "base_tokens_per_s": round(tok / base_dt),
        "monitored_tokens_per_s": round(tok / mon_dt),
        "overhead_pct": round(overhead_pct, 3),
        "budget_pct": budget_pct,
        "within_budget": overhead_pct < budget_pct,
        "final_loss": mon_loss,
        "final_loss_bitwise_equal": base_loss == mon_loss,
    }


def measure_zero_memory(
    *,
    d_model: int = 256,
    n_layers: int = 4,
    n_heads: int = 8,
    d_ff: int = 1024,
    vocab: int = 4096,
    seq_len: int = 256,
    batch: int = 8,
) -> dict:
    """Measured per-device optimizer-state footprint: replicated Adam vs
    ZeRO-1 Adam over the full data axis.

    The memory claim that motivates ZeRO-1 (`parallel/zero.py`: each
    device owns 1/dp of the O(params) optimizer state) is pinned here by
    counting the bytes of the ACTUAL committed device buffers
    (`Array.addressable_shards`), not shapes-on-paper - and counted
    again after one real compiled train step, so the artifact proves the
    state *stays* sharded through the jitted update (a lost
    out-sharding would silently re-replicate it). The reference has no
    counterpart: each of its MPI workers holds a full private optimizer
    (`data_parallelism_train.py:187` recreates torch SGD per epoch per
    rank), so its optimizer memory grows with replica count - this
    measurement shows the opposite slope on a mesh.

    Expected bytes are derived exactly (per-leaf ceil-padded shards,
    `parallel/zero.py leaf_shard_size`, f32 m+v plus the step counter) -
    measured == expected is the pass condition, asserted by
    tests/test_zero.py rather than here so the bench row still reports
    honest numbers if the invariant ever breaks.
    """
    from ..models import transformer as tfm
    from ..parallel.zero import leaf_shard_size
    from . import lm as lmtrain

    dp = jax.device_count()
    cfg = tfm.TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, d_ff=d_ff,
    )
    mesh = lmtrain.create_lm_mesh(dp, 1, 1)

    def fresh_params():
        # per-optimizer: the compiled step donates params/state, so each
        # measurement needs its own live copies
        p, _ = lmtrain.shard_params(
            tfm.init_params(jax.random.key(0), cfg), cfg, mesh
        )
        return p

    tokens, targets = lmtrain.make_copy_task(
        jax.random.key(1), batch=batch, seq_len=seq_len, vocab=vocab
    )

    def bytes_per_device(tree) -> int:
        """Max committed bytes on any one device (replicated leaves count
        their full copy on every device; sharded leaves their shard)."""
        per: dict = {}
        for leaf in jax.tree.leaves(tree):
            for sh in leaf.addressable_shards:
                key = getattr(sh.device, "id", sh.device)
                per[key] = per.get(key, 0) + sh.data.nbytes
        return max(per.values()) if per else 0

    probe = fresh_params()
    param_bytes = bytes_per_device(probe)
    sizes = [int(p.size) for p in jax.tree.leaves(probe)]
    n_params = sum(sizes)
    del probe  # a memory-measuring utility should not hold a spare copy
    # exact expected ZeRO per-device state: f32 m+v shards per leaf
    # (ceil-padded), plus the replicated (): int32 step counter
    expected_zero = 2 * 4 * sum(
        leaf_shard_size(s, dp) for s in sizes
    ) + 4

    out = {}
    for optimizer in ("adam", "zero-adam"):
        params = fresh_params()
        mom = lmtrain.init_lm_momentum(params, mesh, optimizer)
        init_b = bytes_per_device(mom)
        step = lmtrain.make_lm_train_step(
            cfg, mesh, lr=0.01, optimizer=optimizer
        )
        p2, mom2, loss = step(params, mom, tokens, targets)
        jax.block_until_ready(loss)
        out[optimizer] = {
            "state_bytes_per_device": init_b,
            "state_bytes_per_device_post_step": bytes_per_device(mom2),
            "final_loss": round(float(loss), 4),
        }
    adam_b = out["adam"]["state_bytes_per_device"]
    zero_b = out["zero-adam"]["state_bytes_per_device"]
    return {
        "devices": dp,
        "platform": jax.default_backend(),
        "d_model": d_model, "n_layers": n_layers, "n_params": n_params,
        "param_bytes_per_device": param_bytes,
        "optimizers": out,
        "expected_zero_bytes_per_device": expected_zero,
        "reduction_x": round(adam_b / max(zero_b, 1), 2),
        "note": (
            "bytes are committed device buffers (addressable_shards), "
            "measured at init and again after one compiled step; "
            "reduction_x ~ dp modulo per-leaf ceil padding and the "
            "replicated step counter. The reference's optimizer memory "
            "multiplies with workers; this divides."
        ),
    }


def measure_fault_tolerance(
    *,
    probs=(0.0, 0.3, 0.6),
    epochs: int = 8,
    batch_size: int = 16,
    synthetic_size: int = 2000,
    lr: float = 0.05,
    seed: int = 0,
    straggler_duration: float = 0.25,
) -> dict:
    """The fault experiment the reference implemented but never ran
    (its report section 6.2: `simulate_failure` exists at
    `data_parallelism_train.py:41-46` yet no fault numbers were ever
    published). Sweeps `--failure-probability` at a fixed seed on the
    full mesh and measures what drop-and-continue actually costs.

    Two claims, both measured rather than asserted:

    - **Wall-clock is flat in p.** A dropped device is excluded from the
      epoch-edge parameter average by the live-mask (`parallel/fault.py`;
      weighted pmean over survivors) - nobody waits for it. In the
      reference the same event is a straggler sleep that stalls the
      WHOLE epoch behind the blocking recv
      (`data_parallelism_train.py:227`): its cost is p * duration *
      epochs of pure wall-clock, unmeasured in its report.
    - **Convergence survives.** Dropped devices discard their epoch's
      contribution (mean_live_frac is the surviving fraction), yet the
      run reaches the control's accuracy at the default settings even at
      p=0.6, and never diverges or deadlocks - including all-dead epochs
      (the mask degrades to keeping current params).

    Same seed everywhere: p=0 is the exact control (identical shuffles,
    identical init), so deltas are attributable to the masking alone.
    """
    n = jax.device_count()
    train_split = load_split(True, source="synthetic",
                             synthetic_size=synthetic_size)
    test_split = load_split(False, source="synthetic",
                            synthetic_size=max(1, synthetic_size // 5))
    # ONE engine, ONE compile for the whole sweep: failure_probability
    # only feeds the host-built live-masks run_span passes as runtime
    # arguments (engine.py run_span), so the compiled span is identical
    # at every p - the sweep mutates the config and resets state
    # (same seed -> same init/shuffles: p=0 stays the exact control).
    # This is also why the sweep cannot just call measure_dp_training
    # per point (each call would rebuild + re-AOT-compile its engine).
    cfg = TrainConfig(
        lr=lr, batch_size=batch_size, epochs=epochs, nb_proc=n,
        regime="data_parallel", seed=seed,
    )
    engine = Engine(cfg, train_split, test_split)
    engine.compile_span(epochs, eval_inside=False)
    points = []
    for p in probs:
        cfg.failure_probability = float(p)
        engine.reset_state()
        timers = T.PhaseTimers()
        engine.run_span(0, epochs, eval_inside=False, timers=timers)
        vl, va = engine._eval_fn(
            engine.params, engine.test_images, engine.test_labels,
            engine.test_weights,
        )
        lives = [h.n_live for h in engine.history]
        points.append({
            "failure_probability": float(p),
            "val_acc": round(float(va), 2),
            "val_loss": round(float(vl), 4),
            "train_s": round(
                timers.get(T.TRAINING) + timers.get(T.COMMUNICATION), 3),
            "epochs_degraded": sum(1 for v in lives if v < n),
            "min_live_devices": min(lives),
            "mean_live_frac": round(sum(lives) / (len(lives) * n), 3),
        })
    # baseline = the actual p=0 control. A custom sweep without one gets
    # wall_vs_p0=None plus wall_vs_first (ratio to its first point) - the
    # field name promises p=0 and must not silently mean something else
    t0 = next((c["train_s"] for c in points
               if c["failure_probability"] == 0.0), None)
    for c in points:
        c["wall_vs_p0"] = (None if t0 is None
                           else round(c["train_s"] / max(t0, 1e-9), 3))
        if t0 is None:
            c["wall_vs_first"] = round(
                c["train_s"] / max(points[0]["train_s"], 1e-9), 3)

    # the reference's ACTUAL failure semantics, priced: --failure-duration
    # sleeps the epoch (straggler_sleep; one sleep per degraded epoch,
    # like the reference's overlapping worker sleeps behind the blocking
    # recv). Same seed and p, per-epoch path, duration 0 vs d: identical
    # masks and compute, so the wall delta IS the stall - compared to the
    # predicted epochs_degraded * duration.
    straggler = None
    if straggler_duration > 0 and max(probs) > 0:
        import contextlib
        import io

        cfg.failure_probability = float(max(probs))
        walls = {}
        first = True
        for dur in (0.0, float(straggler_duration)):
            cfg.failure_duration = dur
            engine.reset_state()
            if first:  # compile the per-epoch path outside the timing
                engine.run_epoch(0, timers=T.PhaseTimers(), do_eval=False)
                engine.reset_state()
                first = False
            # stdout redirected SYMMETRICALLY on both sides: the dur>0
            # run prints two fail/wake lines per failed device per epoch
            # (parallel/fault.py straggler_sleep) and that I/O must not
            # bias the delta; eval is skipped - the stall is the quantity
            t_w = time.perf_counter()
            with contextlib.redirect_stdout(io.StringIO()):
                for e in range(epochs):
                    engine.run_epoch(e, timers=T.PhaseTimers(),
                                     do_eval=False)
            walls[dur] = time.perf_counter() - t_w
        degraded = sum(1 for h in engine.history if h.n_live < n)
        cfg.failure_duration = 0.0
        straggler = {
            "failure_probability": float(max(probs)),
            "duration_s": float(straggler_duration),
            "epochs_degraded": degraded,
            "predicted_stall_s": round(degraded * straggler_duration, 3),
            "measured_stall_s": round(
                walls[float(straggler_duration)] - walls[0.0], 3),
        }
    return {
        "devices": n,
        "platform": jax.default_backend(),
        "epochs": epochs, "batch_size": batch_size,
        "synthetic_size": synthetic_size, "seed": seed,
        "points": points,
        "straggler": straggler,
        "note": (
            "fixed seed: p=0 is the exact control. wall_vs_p0 ~ 1.0 is "
            "the drop-and-continue claim (no one waits for dead "
            "devices); the reference's straggler-sleep design stalls "
            "every epoch behind its blocking recv instead, and its "
            "report ran no fault experiment at all (section 6.2)."
        ),
    }


def measure_ep_scaling(
    *,
    eps=(1, 2, 4, 8),
    d_model: int = 128,
    n_layers: int = 2,
    n_heads: int = 8,
    d_ff: int = 256,
    vocab: int = 2048,
    seq_len: int = 256,
    batch: int = 8,
    steps: int = 3,
    n_experts: int = 8,
    top_k: int = 2,
) -> dict:
    """Expert-parallel scaling shape on the virtual CPU mesh - the EP
    analog of `measure_sp_scaling`, completing the measured-artifact set
    for every parallelism axis the framework carries (dp / sp / pp / ep).

    Fixed GLOBAL batch and data, expert axis swept: experts shard over
    the data axis (`train/lm.py`: ep rides dp), so at ep=1 one device
    holds all experts and no dispatch collective runs; at ep>1 each
    device holds n_experts/ep experts and every MoE layer pays one
    all_to_all each way (`parallel/moe.py`). Total model FLOPs are
    identical at every ep on the shared host core, so ideal wall is flat
    and overhead_vs_ep1 is the measured expert-parallel dispatch cost.

    `moe_capacity_factor` is pinned to n_experts/top_k, which makes
    per-expert capacity equal the device's token count - the no-drop
    regime, where routing is load-independent and every ep computes the
    same model step (the loss column is the semantics check; it agrees
    to blockwise-reduction tolerance - the psum association varies with
    ep. With a smaller factor, capacity is per-device and drop patterns
    would legitimately vary with ep).
    """
    from ..models import transformer as tfm
    from . import lm as lmtrain

    cfg = tfm.TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, d_ff=d_ff, n_experts=n_experts,
        moe_top_k=top_k, moe_capacity_factor=n_experts / top_k,
    )
    points = _lm_axis_sweep(
        eps, cfg=cfg, make_mesh=lambda ep: lmtrain.create_lm_mesh(ep, 1, 1),
        axis_key="ep", batch=batch, seq_len=seq_len, vocab=vocab,
        steps=steps,
        point_extras=lambda ep: {"experts_per_device": n_experts // ep},
    )
    return {
        "devices": jax.device_count(),
        "platform": jax.default_backend(),
        "d_model": d_model, "n_layers": n_layers, "seq_len": seq_len,
        "batch": batch, "steps": steps,
        "n_experts": n_experts, "top_k": top_k,
        "host_cores": os.cpu_count(),
        "points": points,
        "overhead_vs_ep1_max": max(p["overhead_vs_ep1"] for p in points),
        "note": (
            "fixed global batch and data on one shared host core: ideal "
            "wall is flat in ep; overhead_vs_ep1 is the measured "
            "expert-parallel dispatch cost (one all_to_all each way per "
            "MoE layer at ep>1, none at ep=1). capacity_factor = "
            "E/top_k pins the no-drop regime, so the loss column agrees "
            "at every ep to blockwise-reduction tolerance - the "
            "semantics check."
        ),
    }


def measure_native_batcher(
    *,
    n_rows: int = 20000,
    batch: int = 4096,
    reps: int = 5,
) -> dict:
    """Host-side input-pipeline kernels: the C++ batcher (`native/`) vs
    its own pure-numpy fallback, per kernel, best-of-`reps` wall.

    The native layer exists for the runtime *around* the XLA compute
    path (SURVEY.md section 2: the reference's native layer is external
    libmpi + ATen; here it is XLA plus these host kernels). This row
    prices that choice on the actual host: fused single-pass C++
    (decode+transpose+normalize; gather+normalize) against the multi-
    pass numpy chain the wrappers fall back to - the exact same
    functions (`native.fallback_*`), so the baseline cannot drift from
    the shipped fallback. Parity of outputs is pinned by
    tests/test_native.py; this measures only speed. Purely host CPU:
    no jax, no chip claim.
    """
    import numpy as np

    from .. import native

    rng = np.random.default_rng(0)
    rows = rng.integers(0, 256, (n_rows, 3072), dtype=np.uint8)
    idx = rng.integers(0, n_rows, batch).astype(np.int64)

    def best(f):
        f()  # warm-up (first native call builds/loads the library)
        b = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            f()
            b = min(b, time.perf_counter() - t0)
        return b

    kernels = {
        "cifar_decode_normalize": (
            lambda: native.cifar_decode_normalize(rows, 0.5, 0.5),
            lambda: native.fallback_cifar_decode_normalize(rows, 0.5, 0.5),
            n_rows,
        ),
        "gather_normalize_u8": (
            lambda: native.gather_normalize_u8(rows, idx, 0.5, 0.5),
            lambda: native.fallback_gather_normalize_u8(
                rows, idx, 0.5, 0.5),
            batch,
        ),
    }
    out = {}
    for name, (nat, fb, images) in kernels.items():
        tn, tf = best(nat), best(fb)
        out[name] = {
            "native_ms": round(tn * 1e3, 2),
            "fallback_ms": round(tf * 1e3, 2),
            "speedup_x": round(tf / max(tn, 1e-9), 2),
            "native_images_per_s": round(images / max(tn, 1e-9)),
        }
    return {
        "native_available": native.available(),
        "host_cores": os.cpu_count(),
        "n_rows": n_rows, "batch": batch, "reps": reps,
        "kernels": out,
        "note": (
            "best-of-reps wall per kernel, native C++ vs the SAME "
            "pure-numpy fallback the wrappers ship (native.fallback_*); "
            "host-only, no chip claim. Speedup on one core is pure "
            "fusion (single pass, no float32 intermediate churn); "
            "multi-core hosts add the pthread fan-out on top."
        ),
    }


def measure_serving(
    *,
    d_model: int = 512,
    n_layers: int = 8,
    n_heads: int = 8,
    d_ff: int = 2048,
    vocab: int = 256,
    dtype: str = "bfloat16",
    rate: float = 4.0,
    requests: int = 24,
    prompt_lens=(16, 64, 128),
    max_new: int = 32,
    max_batch: int = 8,
    num_blocks: int = 129,
    block_size: int = 16,
    max_seq_len: int = 256,
    prefill_chunk: int = 16,
    seed: int = 0,
    kv_dtype: str = "bf16",
    weight_dtype: str = "bf16",
    spec_decode: int = 0,
    spec_draft_layers: int = 0,
    min_capacity_ratio: float = 1.8,
    min_top1_agreement: float = 0.99,
    min_accepted_per_step: float = 1.5,
) -> dict:
    """The serving row: sustained requests/s + TTFT / inter-token
    latency under the open-loop load generator (tools/loadgen.py)
    against a real in-process server (serve/ stack end to end: HTTP,
    SSE streaming, admission, continuous batching, paged KV).

    Open loop means offered load never slows to match the server -
    queueing shows up in TTFT, which is the number a capacity plan
    needs. The serving goodput ledger's breakdown (decode = goodput,
    prefill, queue_wait, batch_formation_idle, kv_alloc_stall) rides
    along, so the row says not just how fast but WHERE the wall-clock
    went (docs/SERVING.md).

    ``kv_dtype="int8"`` runs the same workload on the quantized KV pool
    and GATES the two claims that make quantization honest
    (docs/MEASUREMENT.md "Low-precision parity gates"):

    - capacity: the concurrent-sequence capacity of an int8 pool sized
      to the SAME HBM byte budget as the bf16 pool, MEASURED by
      admitting max-length sequences into both allocators until
      OutOfBlocks, must be >= ``min_capacity_ratio`` x bf16's;
    - accuracy: per-token top-1 agreement of every completed stream vs
      the offline bf16 ``generate()`` oracle must be >=
      ``min_top1_agreement``.

    ``weight_dtype="int8"`` serves with int8-quantized weights and
    applies the same per-token top-1 agreement gate (the capacity claim
    is the pool's, so only the accuracy half applies).

    ``spec_decode=k`` runs speculative decoding (early-exit drafter +
    one k+1-position verify per tick) and gates the two claims that
    make the mode worth shipping:

    - accepted tokens per speculative slot-step (the guaranteed token
      plus accepted drafts) must be > ``min_accepted_per_step``;
    - end-to-end tokens/s must be STRICTLY greater than a paired
      non-spec run at the same offered load (measured here, same
      engine geometry, spec off).

    Greedy spec streams are token-exact vs ``generate()``, so the
    oracle gate composes rather than weakening.
    """
    import sys as _sys

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models.transformer import TransformerConfig, init_params
    from ..serve import (
        EngineConfig,
        SchedulerConfig,
        ServeEngine,
        ServeScheduler,
    )
    from ..serve.http import ServeServer
    from ..utils.obs import MetricsRegistry

    tools_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)
        ))), "tools",
    )
    if tools_dir not in _sys.path:
        _sys.path.insert(0, tools_dir)
    import loadgen

    cfg = TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, d_ff=d_ff,
        dtype=jnp.bfloat16 if dtype == "bfloat16" else jnp.float32,
    )
    params = init_params(jax.random.key(seed), cfg)

    def _run(spec_k: int):
        """One end-to-end serving run (engine -> scheduler -> HTTP ->
        loadgen) at the shared geometry and offered load."""
        eng = ServeEngine(params, cfg, EngineConfig(
            max_batch=max_batch, num_blocks=num_blocks,
            block_size=block_size, max_seq_len=max_seq_len,
            prefill_chunk=prefill_chunk, kv_dtype=kv_dtype,
            weight_dtype=weight_dtype, spec_decode=spec_k,
            spec_draft_layers=spec_draft_layers,
        ))
        # pre-compile the bucket grid: a bench row measures serving,
        # not first-request XLA compiles (production pays these at
        # deploy time)
        n = eng.warmup()
        reg = MetricsRegistry()
        sched = ServeScheduler(
            eng, SchedulerConfig(max_queue=max(requests, 8)),
            registry=reg,
        ).start()
        srv = ServeServer(sched, reg, port=0)
        try:
            summ = loadgen.run_load(
                srv.url, rate=rate, n_requests=requests, duration=None,
                prompt_lens=list(prompt_lens), max_new=max_new,
                vocab=vocab, seed=seed, api_keys=["bench"],
                temperature=0.0, burst=0, cancel_one=False,
                timeout=600.0, poisson=False,
            )
        finally:
            rec = sched.close()
            srv.close()
        return eng, summ, rec, n

    spec = {}
    if spec_decode:
        # paired baseline first: the SAME workload at the same offered
        # load, spec off - the throughput gate compares against it
        _, base_summary, _, _ = _run(0)
        spec["baseline_tokens_per_s"] = base_summary["tokens_per_s"]
    engine, summary, record, n_compiled = _run(spec_decode)
    if spec_decode:
        slot_steps = max(
            engine.spec_proposed_tokens // max(spec_decode, 1), 1
        )
        accepted_per_step = (
            engine.spec_accepted_tokens + slot_steps
        ) / slot_steps
        spec.update({
            "k": spec_decode,
            "draft_layers": engine.draft_layers,
            "proposed_tokens": engine.spec_proposed_tokens,
            "accepted_tokens": engine.spec_accepted_tokens,
            "acceptance_rate": round(
                engine.spec_accepted_tokens
                / max(engine.spec_proposed_tokens, 1), 4
            ),
            # emitted tokens per speculative slot-step: the guaranteed
            # token + accepted drafts (1.0 == plain decode's ceiling)
            "accepted_tokens_per_step": round(accepted_per_step, 4),
            "tokens_per_s": summary["tokens_per_s"],
        })
        assert accepted_per_step > min_accepted_per_step, (
            f"spec-decode acceptance gate: {accepted_per_step:.3f} "
            f"emitted tokens per slot-step <= {min_accepted_per_step} "
            f"(k={spec_decode}, acceptance "
            f"{spec['acceptance_rate']:.1%}) - the drafter is not "
            "beating the one-token-per-slot ceiling"
        )
        assert summary["tokens_per_s"] > spec["baseline_tokens_per_s"], (
            f"spec-decode throughput gate: {summary['tokens_per_s']} "
            f"tokens/s with k={spec_decode} is not strictly greater "
            f"than the paired non-spec run's "
            f"{spec['baseline_tokens_per_s']} at the same offered load"
        )
    total = float(record.get("wall_s") or 0.0)
    bad = record.get("badput_s") or {}
    dev = jax.devices()[0]

    quant = {}
    if kv_dtype == "int8" or weight_dtype == "int8":
        # --- accuracy gate (int8 KV pool and/or int8 weights): every
        # completed stream vs the offline full-precision oracle (the
        # seeded-model contract), per-token top-1 agreement
        from ..models.transformer import generate

        agree = tot_toks = 0
        for r in summary["results"]:
            if r.status != "completed" or not r.tokens:
                continue
            oracle = np.asarray(generate(
                params, jnp.asarray([r.prompt], jnp.int32), cfg,
                max_new_tokens=len(r.tokens),
            ))[0, len(r.prompt):]
            agree += int(sum(
                int(a) == int(b) for a, b in zip(r.tokens, oracle)
            ))
            tot_toks += len(r.tokens)
        agreement = agree / max(tot_toks, 1)
        quant = {
            "oracle_top1_agreement": round(agreement, 6),
            "oracle_tokens_compared": tot_toks,
        }
        assert agreement >= min_top1_agreement, (
            f"low-precision accuracy gate (kv {kv_dtype}, weights "
            f"{weight_dtype}): per-token top-1 agreement "
            f"{agreement:.4f} < {min_top1_agreement} vs the "
            f"full-precision oracle over {tot_toks} tokens"
        )
    if kv_dtype == "int8":
        # --- capacity gate: equal-HBM-budget pools, MEASURED by
        # admitting max-length sequences into the real allocator
        from ..analysis.cost import kv_block_bytes

        bf16_name = "bf16" if dtype == "bfloat16" else "f32"
        bb_bf16 = kv_block_bytes(
            n_layers, n_heads, cfg.head_dim, block_size, bf16_name
        )
        bb_int8 = kv_block_bytes(
            n_layers, n_heads, cfg.head_dim, block_size, "int8"
        )
        budget = (num_blocks - 1) * bb_bf16  # the bf16 pool's bytes
        int8_blocks = budget // bb_int8 + 1  # + scratch
        cap_bf16 = measure_kv_capacity(
            num_blocks, block_size, max_seq_len
        )
        cap_int8 = measure_kv_capacity(
            int8_blocks, block_size, max_seq_len
        )
        ratio = cap_int8 / max(cap_bf16, 1)
        quant["kv_capacity"] = {
            "hbm_budget_bytes": int(budget),
            "bf16": {"blocks": num_blocks - 1,
                     "bytes_per_block": bb_bf16,
                     "max_seq_sequences": cap_bf16},
            "int8": {"blocks": int(int8_blocks - 1),
                     "bytes_per_block": bb_int8,
                     "max_seq_sequences": cap_int8},
            "measured_capacity_ratio": round(ratio, 4),
        }
        assert ratio >= min_capacity_ratio, (
            f"int8-KV capacity gate: measured concurrent-sequence "
            f"capacity ratio {ratio:.3f} < {min_capacity_ratio} at equal "
            f"HBM budget ({cap_int8} vs {cap_bf16} max-len sequences)"
        )

    # the servelint cost model's figure for THIS engine, next to the
    # measured one, so static-vs-measured drift is tracked per bench
    # run (tools/servelint.py --validate gates the same pair within the
    # documented tolerance - analysis/serve_trace.py)
    from ..analysis.serve_trace import static_decode_tokens_per_s

    static_pred = static_decode_tokens_per_s(engine, "cpu-host")

    return {
        "devices": f"1x {dev.device_kind}",
        "model": f"d{d_model}/L{n_layers}/H{n_heads} vocab {vocab} {dtype}",
        "kv_dtype": kv_dtype,
        "weight_dtype": weight_dtype,
        **({"spec_decode": spec} if spec else {}),
        **quant,
        "offered_rps": summary["offered_rps"],
        "sustained_rps": summary["achieved_rps"],
        "requests_completed": summary["by_status"].get("completed", 0),
        "requests_total": summary["requests"],
        "tokens_per_s": summary["tokens_per_s"],
        "static_predicted_tokens_per_s": round(
            static_pred["tokens_per_s"], 2
        ),
        "static_prediction": {
            "bucket": static_pred["bucket"],
            "hw": static_pred["hw"],
            "bound": static_pred["bound"],
            "tick_s": static_pred["tick_s"],
        },
        "ttft_p50_s": summary["ttft_p50_s"],
        "ttft_p99_s": summary["ttft_p99_s"],
        "intertoken_p50_s": summary["intertoken_p50_s"],
        "intertoken_p99_s": summary["intertoken_p99_s"],
        "engine": {
            "max_batch": max_batch, "block_size": block_size,
            "num_blocks": num_blocks, "prefill_chunk": prefill_chunk,
            "warmup_programs": n_compiled,
        },
        "serve_goodput_ratio": record.get("goodput_ratio"),
        "serve_breakdown_share": {
            c: round(v / total, 4) for c, v in bad.items() if total > 0
        },
        "note": (
            "open-loop load (tools/loadgen.py) against the in-process "
            "serve/ stack over real HTTP+SSE; sustained_rps counts "
            "COMPLETED requests over the whole window, TTFT includes "
            "queue wait (docs/SERVING.md)"
        ),
    }


def measure_fleet_serving(
    *,
    d_model: int = 256,
    n_layers: int = 4,
    n_heads: int = 8,
    d_ff: int = 1024,
    vocab: int = 256,
    dtype: str = "bfloat16",
    rate: float = 3.0,
    requests: int = 12,
    prompt_lens=(16, 64),
    max_new: int = 24,
    max_batch: int = 8,
    num_blocks: int = 129,
    block_size: int = 16,
    max_seq_len: int = 256,
    prefill_chunk: int = 16,
    seed: int = 0,
    kill_after_s: float = 1.5,
    min_scaling_ratio: float = 0.9,
) -> dict:
    """The serving-fleet row (serve/fleet.py, docs/SERVING.md "Serving
    fleet"): two replicas behind the failover router, three legs, all
    gates ASSERTED in the row.

    1. single-replica baseline at offered rate r (the denominator);
    2. healthy 2-replica fleet at 2r: sustained rps must be >=
       ``min_scaling_ratio`` x 2 x the single-replica sustained rps -
       the router's least-loaded dispatch must actually deliver the
       second replica's capacity, not just its existence;
    3. chaos failover at 2r: one replica is killed abruptly mid-run
       (scheduler torn down under live streams, then the listener -
       in-flight SSE streams break, new dispatches get connection
       refused). Every request must still COMPLETE, at least one must
       arrive via failover re-dispatch, and every retried stream must
       be per-token identical to the offline ``generate()`` oracle -
       the deterministic-replay contract, measured, not assumed.

    Per-replica serving goodput records from both fleet legs fold
    through `serve.fleet.aggregate_serve_records`, which asserts
    goodput + badput == wall conservation per replica AND on the
    aggregate (including the killed replica's partial record)."""
    import sys as _sys
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models.transformer import (
        TransformerConfig,
        generate,
        init_params,
    )
    from ..serve import (
        EngineConfig,
        SchedulerConfig,
        ServeEngine,
        ServeScheduler,
    )
    from ..serve.fleet import FleetRouter, aggregate_serve_records
    from ..serve.http import ServeServer
    from ..utils.obs import MetricsRegistry

    tools_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)
        ))), "tools",
    )
    if tools_dir not in _sys.path:
        _sys.path.insert(0, tools_dir)
    import loadgen

    cfg = TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, d_ff=d_ff,
        dtype=jnp.bfloat16 if dtype == "bfloat16" else jnp.float32,
    )
    params = init_params(jax.random.key(seed), cfg)

    def _replica(rid: str):
        eng = ServeEngine(params, cfg, EngineConfig(
            max_batch=max_batch, num_blocks=num_blocks,
            block_size=block_size, max_seq_len=max_seq_len,
            prefill_chunk=prefill_chunk,
        ))
        eng.warmup()
        reg = MetricsRegistry()
        sched = ServeScheduler(
            eng, SchedulerConfig(max_queue=max(4 * requests, 8)),
            registry=reg,
        ).start()
        srv = ServeServer(sched, reg, port=0, replica_id=rid)
        return sched, srv

    def _load(url: str, offered: float, n: int):
        return loadgen.run_load(
            url, rate=offered, n_requests=n, duration=None,
            prompt_lens=list(prompt_lens), max_new=max_new,
            vocab=vocab, seed=seed, api_keys=["bench"],
            temperature=0.0, burst=0, cancel_one=False,
            timeout=600.0, poisson=False,
        )

    # --- leg 1: single-replica baseline at offered rate r
    sched, srv = _replica("solo")
    try:
        base = _load(srv.url, rate, requests)
    finally:
        sched.close()
        srv.close()
    single_rps = base["achieved_rps"]

    def _fleet_leg(chaos: bool):
        s0, v0 = _replica("rank0")
        s1, v1 = _replica("rank1")
        reg = MetricsRegistry()
        router = FleetRouter(reg, replicas=[
            ("rank0", v0.url), ("rank1", v1.url),
        ])
        recs: dict = {}
        killer = None
        if chaos:
            def _kill():
                # abrupt replica death under live streams: in-flight
                # requests get torn down (SSE error frames / broken
                # pipes), then the listener goes away so re-dispatch
                # sees connection refused - the router must fail both
                # over to rank1 with streams intact
                recs["rank0"] = s0.close()
                v0.close()

            killer = threading.Timer(kill_after_s, _kill)
            killer.start()
        try:
            summ = _load(router.url, 2 * rate, 2 * requests)
        finally:
            if killer is not None:
                killer.join()
            if "rank0" not in recs:
                recs["rank0"] = s0.close()
                v0.close()
            recs["rank1"] = s1.close()
            v1.close()
            failures = int(
                reg.counter("fleet_replica_failures_total").value
            )
            router.close()
        return summ, [recs["rank0"], recs["rank1"]], failures

    # --- leg 2: healthy 2-replica fleet at 2r - the scaling gate
    healthy, healthy_recs, _ = _fleet_leg(chaos=False)
    fleet_rps = healthy["achieved_rps"]
    assert fleet_rps >= min_scaling_ratio * 2.0 * single_rps, (
        f"fleet scaling gate: 2-replica sustained {fleet_rps:.3f} rps "
        f"< {min_scaling_ratio} x 2 x single-replica "
        f"{single_rps:.3f} rps - the router is not delivering the "
        "second replica's capacity"
    )
    healthy_agg = aggregate_serve_records(healthy_recs)

    # --- leg 3: chaos failover at 2r - the robustness gates
    chaos, chaos_recs, failures = _fleet_leg(chaos=True)
    completed = chaos["by_status"].get("completed", 0)
    assert completed == chaos["requests"], (
        f"fleet failover gate: {completed}/{chaos['requests']} "
        "requests completed - a replica SIGKILL must be invisible to "
        f"clients (statuses: {chaos['by_status']})"
    )
    assert chaos["requests_retried"] >= 1, (
        "fleet failover gate: killing a replica mid-run produced zero "
        "failover re-dispatches - the chaos leg did not exercise the "
        "failover path"
    )
    assert failures >= 1, (
        "fleet failover gate: router observed no replica failure "
        "(fleet_replica_failures_total == 0) after the kill"
    )
    # deterministic-replay oracle: every RETRIED stream (prompt replayed
    # with streamed tokens suppressed on a survivor) must match the
    # offline greedy oracle token for token
    checked = mismatched = 0
    for r in chaos["results"]:
        if r.status != "completed" or not r.router_retries:
            continue
        oracle = np.asarray(generate(
            params, jnp.asarray([r.prompt], jnp.int32), cfg,
            max_new_tokens=len(r.tokens),
        ))[0, len(r.prompt):]
        checked += 1
        if list(map(int, r.tokens)) != [int(t) for t in oracle]:
            mismatched += 1
    assert checked >= 1 and mismatched == 0, (
        f"fleet failover oracle gate: {mismatched}/{checked} retried "
        "streams diverged from the offline generate() oracle - "
        "deterministic replay is broken"
    )
    chaos_agg = aggregate_serve_records(chaos_recs)

    dev = jax.devices()[0]
    return {
        "devices": f"1x {dev.device_kind}",
        "model": f"d{d_model}/L{n_layers}/H{n_heads} vocab {vocab} {dtype}",
        "replicas": 2,
        "single_replica_sustained_rps": single_rps,
        "offered_rps": healthy["offered_rps"],
        "sustained_rps": fleet_rps,
        "scaling_ratio_vs_2x_single": round(
            fleet_rps / max(2.0 * single_rps, 1e-9), 4
        ),
        "ttft_p50_s": healthy["ttft_p50_s"],
        "ttft_p99_s": healthy["ttft_p99_s"],
        "by_replica": healthy.get("by_replica"),
        "failover": {
            "kill_after_s": kill_after_s,
            "requests_completed": completed,
            "requests_total": chaos["requests"],
            "requests_retried": chaos["requests_retried"],
            "retry_episodes": chaos["router_retry_episodes"],
            "replica_failures_observed": failures,
            "oracle_checked_streams": checked,
            "oracle_mismatched_streams": mismatched,
            "sustained_rps": chaos["achieved_rps"],
        },
        "fleet_goodput_ratio": healthy_agg["goodput_ratio"],
        "fleet_goodput_ratio_under_failure": chaos_agg["goodput_ratio"],
        "note": (
            "2 in-process replicas behind serve/fleet.py FleetRouter "
            "over real HTTP+SSE; scaling gate >= "
            f"{min_scaling_ratio} x 2 x single-replica sustained rps, "
            "chaos leg kills a replica under live streams and gates "
            "zero client-visible failures + per-token oracle equality "
            "of every failed-over stream (docs/SERVING.md)"
        ),
    }


def measure_kv_capacity(num_blocks: int, block_size: int,
                        max_seq_len: int) -> int:
    """MEASURED concurrent-sequence capacity of a paged-KV pool: admit
    max-length sequences into the real allocator (serve/kv_cache.py)
    until `OutOfBlocks`. The capacity half of the int8-KV gate runs on
    this, not on arithmetic - if the allocator's scratch-block reserve,
    ceil-div block math, or scale bookkeeping changed, the measured
    ratio moves with it."""
    from ..serve.kv_cache import KVCacheConfig, OutOfBlocks, PagedKVCache

    pool = PagedKVCache(KVCacheConfig(
        num_blocks=int(num_blocks), block_size=int(block_size),
        max_seq_len=int(max_seq_len),
    ))
    n = 0
    while True:
        try:
            pool.ensure_range(n, max_seq_len - 1)
        except OutOfBlocks:
            return n
        n += 1


# documented accuracy contract of the quantized training forward
# (docs/MEASUREMENT.md "Low-precision parity gates"): per-row symmetric
# int8 carries ~2^-7 relative error per operand, fp8-e4m3 ~2^-3; the
# bounds below are the end-to-end budget those translate to at the
# parity row's shapes, with headroom against seed/backend jitter. A
# kernel change that breaks numerics blows through them by orders of
# magnitude - a softmax-scale bug shows up as MAE ~ O(1), not O(0.1).
QUANT_PARITY_TOLERANCES = {
    #        (final-loss delta, logit MAE)
    "int8": (0.05, 0.05),
    "fp8": (0.10, 0.25),
}


def measure_quant_parity(
    *,
    d_model: int = 64,
    n_layers: int = 2,
    n_heads: int = 4,
    d_ff: int = 128,
    vocab: int = 64,
    seq_len: int = 32,
    batch: int = 8,
    steps: int = 40,
    lr: float = 0.05,
    seed: int = 0,
    formats: tuple = ("int8", "fp8"),
    tolerances: dict | None = None,
) -> dict:
    """The training parity row: quantized-vs-bf16 loss/logit drift,
    GATED (ROADMAP item 3's honesty rail).

    Trains the same tiny LM three times from identical init/data -
    full precision, ``attn_quant="int8"``, ``attn_quant="fp8"``
    (ops/quant.py: real low-precision QK^T/PV dots, straight-through
    backward) - and asserts the documented tolerances on

    - ``loss_delta``: |final quantized loss - final full-precision loss|
      (did quantization change what was learned), and
    - ``logit_mae``: mean |logit difference| on a held-out batch at the
      final parameters (how far individual predictions moved).

    Single-device on purpose: the quantized forward is sharding-
    agnostic (per-token scales are local math), so parity here is
    parity everywhere the spec lint lets it run; single-device also
    keeps the gate executable on any jax generation the serving CI
    runs (the mesh step needs modern shard_map).
    """
    import jax.numpy as jnp
    import numpy as np

    from ..models import transformer as tfm

    tol = dict(QUANT_PARITY_TOLERANCES)
    tol.update(tolerances or {})

    def build(fmt: str):
        return tfm.TransformerConfig(
            vocab_size=vocab, d_model=d_model, n_heads=n_heads,
            n_layers=n_layers, d_ff=d_ff, attn_quant=fmt,
        )

    # fixed synthetic next-token workload: every variant sees byte-
    # identical batches (seeded PRNG, regenerated per variant)
    def batches(n):
        key = jax.random.key(seed + 1)
        for _ in range(n):
            key, k = jax.random.split(key)
            yield jax.random.randint(k, (batch, seq_len), 0, vocab)

    def train(fmt: str):
        cfg = build(fmt)
        params = tfm.init_params(jax.random.key(seed), cfg)

        def loss_fn(p, toks):
            logits, _ = tfm.apply_with_aux(p, toks, cfg)
            tgt = toks[:, 1:]
            lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
            nll = -jnp.take_along_axis(
                lp, tgt[..., None], axis=-1
            )[..., 0]
            return nll.mean()

        @jax.jit
        def step(p, toks):
            loss, g = jax.value_and_grad(loss_fn)(p, toks)
            p = jax.tree.map(lambda w, gw: w - lr * gw, p, g)
            return p, loss

        loss = None
        for toks in batches(steps):
            params, loss = step(params, toks)
        eval_toks = jax.random.randint(
            jax.random.key(seed + 2), (batch, seq_len), 0, vocab
        )
        logits, _ = tfm.apply_with_aux(params, eval_toks, cfg)
        return float(loss), np.asarray(logits, np.float32)

    base_loss, base_logits = train("")
    rows = {}
    for fmt in formats:
        q_loss, q_logits = train(fmt)
        loss_delta = abs(q_loss - base_loss)
        logit_mae = float(np.mean(np.abs(q_logits - base_logits)))
        d_tol, m_tol = tol[fmt]
        rows[fmt] = {
            "final_loss": round(q_loss, 6),
            "loss_delta": round(loss_delta, 6),
            "loss_delta_tol": d_tol,
            "logit_mae": round(logit_mae, 6),
            "logit_mae_tol": m_tol,
        }
        assert loss_delta <= d_tol, (
            f"quant parity gate [{fmt}]: final-loss delta "
            f"{loss_delta:.4f} > {d_tol} vs full precision "
            f"(base {base_loss:.4f}, quantized {q_loss:.4f})"
        )
        assert logit_mae <= m_tol, (
            f"quant parity gate [{fmt}]: logit MAE {logit_mae:.4f} > "
            f"{m_tol} vs full precision on the held-out batch"
        )
    dev = jax.devices()[0]
    return {
        "devices": f"1x {dev.device_kind}",
        "model": f"d{d_model}/L{n_layers}/H{n_heads} vocab {vocab}",
        "steps": steps,
        "baseline_final_loss": round(base_loss, 6),
        "formats": rows,
        "note": (
            "same init + byte-identical batches per variant; quantized "
            "attention forward (ops/quant.py), straight-through "
            "backward; gates assert the documented tolerances "
            "(docs/MEASUREMENT.md)"
        ),
    }
