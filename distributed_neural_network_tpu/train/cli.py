"""Shared CLI runner behind the three reference entry points.

CLI parity (SURVEY.md section 5.6): the three top-level scripts keep the
reference's names and flag surface - `--lr --momentum --batch-size --epochs
--nb-proc --failure-probability --failure-duration`
(`data_parallelism_train.py:259-271`) - with properly *typed* flags (the
reference passed raw strings to SGD, so non-default `--lr` crashed it;
SURVEY.md section 2 quirks). Framework-specific extensions are added behind
new flags, defaults preserving reference behaviour.
"""

from __future__ import annotations

import argparse
import json
import os
import time

from ..data.cifar10 import load_split
from ..utils import timers as T
from ..utils import tracing as TR
from ..utils.logfiles import write_phase_logs
from ..utils.metrics import init_run
from .engine import Engine, TrainConfig


def add_common_flags(p: argparse.ArgumentParser, *, epochs: int, batch_size: int):
    p.add_argument("--lr", dest="lr", type=float, default=0.001)
    p.add_argument("--momentum", dest="momentum", type=float, default=0.9)
    p.add_argument("--batch-size", dest="bs", type=int, default=batch_size)
    p.add_argument("--epochs", dest="epochs", type=int, default=epochs)
    # framework extensions (not in the reference CLI)
    p.add_argument("--seed", type=int, default=0, help="PRNG seed (reference was unseeded)")
    p.add_argument(
        "--sync-mode",
        choices=("epoch", "step"),
        default="epoch",
        help="epoch = faithful local SGD + epoch-edge parameter averaging "
        "(reference semantics); step = per-step gradient pmean (idiomatic DP)",
    )
    p.add_argument(
        "--no-momentum-reset",
        action="store_true",
        help="keep momentum across epochs (reference re-creates SGD per epoch)",
    )
    p.add_argument(
        "--grad-sync",
        choices=("end", "overlap"),
        default="end",
        help="per-step gradient-sync granularity under --sync-mode step: "
        "end = one pmean per leaf; overlap = one pmean per size-capped "
        "leaf bucket (--bucket-mb), independent collectives XLA can "
        "overlap with backward compute (no effect in epoch mode)",
    )
    p.add_argument(
        "--bucket-mb",
        type=float,
        default=4.0,
        help="gradient-bucket payload cap in MiB for --grad-sync overlap",
    )
    p.add_argument(
        "--precision",
        choices=("bf16", "fp8", "int8", "int8-kv"),
        default="bf16",
        help="low-precision fast path selector (shared flag surface with "
        "lm_train.py / the serve CLI). The CNN engine itself has no "
        "quantized kernels - only 'bf16' (the full-precision contract) "
        "runs here; 'fp8'/'int8' quantize the LM's attention matmuls "
        "(lm_train.py --precision) and 'int8-kv' the serving KV cache "
        "(python -m distributed_neural_network_tpu.serve --precision)",
    )
    p.add_argument(
        "--compilation-cache-dir",
        default=None,
        help="persistent XLA compilation cache directory, used only while "
        "JAX_COMPILATION_CACHE_DIR is unset (the variable wins; default "
        "<checkout>/.jax_cache): repeat runs of the same program "
        "deserialize instead of recompiling - the --step-stats compile "
        "field then records the cache-hit time, and the StepStats "
        "summary carries the cache dir for provenance",
    )
    p.add_argument(
        "--input-mode",
        choices=("hbm", "stream"),
        default="hbm",
        help="hbm = dataset uploaded to device memory once, whole epochs "
        "compiled (default); stream = dataset stays in host RAM (uint8), "
        "batches assembled per step by the native C++ kernel - for "
        "datasets larger than HBM",
    )
    p.add_argument(
        "--stream-prefetch",
        type=int,
        default=2,
        help="stream mode: batches assembled this many steps ahead on a "
        "background thread (2 = double buffering, 0 = synchronous)",
    )
    p.add_argument("--data", choices=("auto", "pickle", "npz", "synthetic"), default="auto")
    p.add_argument("--data-root", default=None, help="dataset dir (default ./data)")
    p.add_argument(
        "--synthetic-size",
        type=int,
        default=None,
        help="synthetic train rows (test = 1/5 of it); default: CIFAR-10 sizes",
    )
    p.add_argument("--log-dir", default="log", help="phase-time log directory")
    p.add_argument("--metrics-jsonl", default=None, help="metrics JSONL path")
    p.add_argument(
        "--run-record",
        default=None,
        metavar="RECORD.json",
        help="write the goodput run record here (utils/goodput.py: "
        "goodput ratio + per-cause badput seconds; written through "
        "during the run; render/diff/gate with tools/goodput.py). "
        "Defaults to the DNN_TPU_RUN_RECORD env the elastic supervisor "
        "exports; a GOODPUT summary line is printed either way",
    )
    p.add_argument("--neptune", action="store_true", help="also log to Neptune (env creds)")
    p.add_argument("--eval-batch-size", type=int, default=None)
    p.add_argument(
        "--compute-dtype", choices=("float32", "bfloat16"), default="float32"
    )
    p.add_argument(
        "--kernels",
        choices=("xla", "pallas"),
        default="xla",
        help="pallas = fused Pallas classifier-head kernel (VMEM-resident "
        "weights; equivalent plain-jnp math off-TPU)",
    )
    p.add_argument("--eval-every", type=int, default=1)
    p.add_argument(
        "--checkpoint-dir",
        default=None,
        help="save params+momentum+history at epoch edges (SURVEY.md sec. 5.4)",
    )
    p.add_argument("--checkpoint-every", type=int, default=1, help="epochs between saves")
    p.add_argument("--checkpoint-keep", type=int, default=3, help="checkpoints retained")
    p.add_argument(
        "--checkpoint-backend", choices=("auto", "orbax", "npz"), default="auto"
    )
    p.add_argument(
        "--resume",
        action="store_true",
        help="resume from the latest checkpoint in --checkpoint-dir",
    )
    p.add_argument(
        "--elastic",
        action="store_true",
        help="elastic resume (parallel/reshard.py, docs/ROBUSTNESS.md): "
        "accept a checkpoint written under a DIFFERENT --nb-proc and "
        "reshard the per-device momentum stack onto this mesh (shrink: "
        "surviving workers keep their buffers; grow: new workers start "
        "with zero momentum). Without it a worker-count mismatch is a "
        "hard error",
    )
    p.add_argument(
        "--fused",
        action="store_true",
        help="run multi-epoch compiled spans (one dispatch per span) instead "
        "of one dispatch per phase per epoch - the fast path; phase timing "
        "then reports train+sync(+eval at --eval-every 1) as one TRAINING "
        "number. Silently downgraded to the per-epoch path when combined "
        "with --failure-duration > 0 (straggler sleeps can only interleave "
        "between epochs) or --input-mode stream",
    )
    # training-dynamics observatory (train/dynamics.py,
    # docs/OBSERVABILITY.md "Training dynamics")
    p.add_argument(
        "--dynamics",
        action="store_true",
        help="measure replica-divergence at each parameter-averaging "
        "sync (max/mean per-layer parameter distance across workers, "
        "in-jit, just before the average collapses it): live "
        "dynamics_replica_div_* gauges, dynamics/* metrics series, and "
        "a 'dynamics' trace track; disables --fused (the divergence "
        "rides the per-epoch sync dispatch)",
    )
    # self-healing guard layer (train/guard.py, docs/ROBUSTNESS.md)
    p.add_argument(
        "--guard",
        choices=("off", "warn", "skip", "rollback", "abort"),
        default="off",
        help="per-epoch training guard: warn = count/log anomalies "
        "(non-finite loss, EMA loss spikes); skip = drop an anomalous "
        "epoch's update (pre-epoch snapshot restored); rollback = restore "
        "the rolling snapshot and retry with LR backoff (bounded by "
        "--max-retries); abort = stop with an actionable error",
    )
    p.add_argument(
        "--guard-spike-zscore",
        type=float,
        default=6.0,
        help="loss-spike threshold in EMA standard deviations "
        "(anomaly when loss > mean + z*sigma; non-finite always counts)",
    )
    p.add_argument(
        "--snapshot-every",
        type=int,
        default=1,
        help="epochs between the guard's rolling in-memory host snapshots "
        "(a rollback rewinds at most this far)",
    )
    p.add_argument(
        "--max-retries",
        type=int,
        default=3,
        help="guard rollback budget before abort (refills after a stretch "
        "of healthy epochs)",
    )
    p.add_argument(
        "--on-sigterm",
        choices=("checkpoint", "ignore"),
        default="checkpoint",
        help="checkpoint = on SIGTERM/SIGINT finish the current epoch, "
        "write an emergency checkpoint (when --checkpoint-dir is set) and "
        "exit cleanly for exact resume; ignore = default signal behavior",
    )
    p.add_argument(
        "--profile-dir",
        default=None,
        help="capture a jax.profiler trace of the training run into this dir "
        "(SURVEY.md sec. 5.1 - the reference had only wall-clock brackets)",
    )
    # step-level telemetry (utils/tracing.py, docs/OBSERVABILITY.md)
    p.add_argument(
        "--trace-out",
        default=None,
        metavar="TRACE.json",
        help="write a Chrome trace-event JSON of the run (span per "
        "train_step/sync/eval, one track per phase) - open in Perfetto or "
        "chrome://tracing, summarize with tools/trace_summary.py",
    )
    p.add_argument(
        "--step-stats",
        action="store_true",
        help="collect per-step StepStats (compile vs steady-state step "
        "time, images/s, device memory, collective bytes, MFU), print the "
        "summary, and emit step/* series to --metrics-jsonl",
    )
    # live runtime observability (utils/obs.py + train/monitor.py,
    # docs/OBSERVABILITY.md "Live monitoring")
    p.add_argument(
        "--metrics-port",
        type=int,
        default=None,
        metavar="PORT",
        help="serve live Prometheus metrics on http://127.0.0.1:PORT"
        "/metrics plus a /healthz JSON liveness/readiness endpoint "
        "(0 = ephemeral port, printed at startup); also starts the "
        "stall/recompile/checkpoint watchdog unless --watchdog off",
    )
    p.add_argument(
        "--metrics-linger",
        type=float,
        default=0.0,
        metavar="SEC",
        help="keep the metrics server up this many seconds after the run "
        "finishes (final scrape window for CI / external scrapers)",
    )
    p.add_argument(
        "--watchdog",
        choices=("on", "off"),
        default="on",
        help="with --metrics-port: background watchdog flagging stalled "
        "steps (no heartbeat for N x steady p95 step time), recompile "
        "storms, and checkpoint staleness as watchdog/* trace events + "
        "watchdog_*_total counters (train/monitor.py)",
    )
    p.add_argument(
        "--watchdog-escalate",
        choices=("none", "preempt"),
        default="none",
        help="preempt = a persistent stall requests the cooperative "
        "SIGTERM-style preemption path (emergency checkpoint at the next "
        "step boundary, then clean exit) instead of burning the "
        "reservation wedged; requires --on-sigterm checkpoint",
    )
    return p


def add_distributed_flags(p: argparse.ArgumentParser, *, nb_proc: int = 4):
    p.add_argument(
        "--nb-proc",
        dest="nb_proc",
        type=int,
        default=nb_proc,
        help="mesh data-axis size (reference: MPI world size)",
    )
    p.add_argument(
        "--failure-probability",
        dest="failure_probability",
        type=float,
        default=0.0,
        help="Probability of simulated process failure at each epoch",
    )
    p.add_argument(
        "--failure-duration",
        dest="failure_duration",
        type=float,
        default=0.0,
        help="Duration of simulated process failure in seconds",
    )
    p.add_argument(
        "--reference-compat",
        action="store_true",
        help="N-1 compute workers at --nb-proc N, as the reference's idle-parent "
        "topology (default: all N devices train)",
    )
    p.add_argument(
        "--sharding",
        choices=("manual", "auto"),
        default="manual",
        help="auto derives --nb-proc statically instead of taking it as "
        "given: the largest worker count that fits the visible devices "
        "AND divides the global batch (the engine's divisibility "
        "contract; analysis/autoshard.py auto_nb_proc) - the CNN "
        "engine's one free sharding choice, decided by the same "
        "declarative layer the LM mesh search uses",
    )
    return p


def config_from_args(args, regime: str) -> TrainConfig:
    return TrainConfig(
        lr=args.lr,
        momentum=args.momentum,
        batch_size=args.bs,
        epochs=args.epochs,
        nb_proc=getattr(args, "nb_proc", None),
        regime=regime,
        sync_mode=args.sync_mode,
        reset_momentum=not args.no_momentum_reset,
        failure_probability=getattr(args, "failure_probability", 0.0),
        failure_duration=getattr(args, "failure_duration", 0.0),
        seed=args.seed,
        eval_batch_size=args.eval_batch_size,
        compute_dtype=args.compute_dtype,
        kernels=getattr(args, "kernels", "xla"),
        reference_compat=getattr(args, "reference_compat", False),
        input_mode=getattr(args, "input_mode", "hbm"),
        stream_prefetch=getattr(args, "stream_prefetch", 2),
        grad_sync=getattr(args, "grad_sync", "end"),
        bucket_mb=getattr(args, "bucket_mb", 4.0),
        dynamics=getattr(args, "dynamics", False),
    )


def run_training(args, regime: str, *, log=print) -> Engine:
    """Load data, train, write phase logs - the shared main() body.

    Owns the live-observability lifecycle (`train/monitor.py`): the
    preemption guard and `--metrics-port` monitor (registry + /metrics +
    /healthz server + watchdog) are created up front, threaded through
    the engine/guard/checkpointer, and closed on every exit path - after
    an optional `--metrics-linger` window so external scrapers can read
    the final counters.
    """
    # goodput wall clock zero: before data load / rendezvous / compile so
    # the init bucket owns them (utils/goodput.py; no-op when it is the
    # process ledger already started by an outer harness)
    from ..utils.goodput import LEDGER as G_LEDGER

    G_LEDGER.reset()  # one ledger per run (tests reuse the process)
    G_LEDGER.start()
    if getattr(args, "run_record", None):
        G_LEDGER.arm(args.run_record)

    precision = getattr(args, "precision", "bf16")
    if precision != "bf16":
        raise SystemExit(
            f"--precision {precision}: the CNN engine has no quantized "
            "kernels (its conv/dense matmuls are full precision); the "
            "fp8/int8 fast path lives in the LM stack - lm_train.py "
            "--precision fp8|int8 for training, python -m "
            "distributed_neural_network_tpu.serve --precision int8-kv "
            "for the serving KV cache (docs/MEASUREMENT.md)"
        )

    from ..parallel.distributed import initialize as distributed_initialize
    from ..runtime import enable_compile_cache, route

    cache_dir = enable_compile_cache(
        getattr(args, "compilation_cache_dir", None)
    )
    log(f"(Persistent compilation cache: {cache_dir})")
    if distributed_initialize():
        import jax

        log(
            f"(Multi-host: process {jax.process_index()}/{jax.process_count()}, "
            f"{jax.device_count()} global devices)"
        )
    if getattr(args, "kernels", "xla") == "pallas":
        log(f"(kernels=pallas -> {route()})")
    if getattr(args, "sharding", "manual") == "auto":
        import jax

        from ..analysis.autoshard import auto_nb_proc

        chosen = auto_nb_proc(args.bs, jax.device_count())
        log(
            f"(--sharding auto: nb_proc {getattr(args, 'nb_proc', None)} "
            f"-> {chosen}: largest worker count dividing batch {args.bs} "
            f"on {jax.device_count()} device(s))"
        )
        args.nb_proc = chosen
    cfg = config_from_args(args, regime)
    timers = T.PhaseTimers()

    trace_out = getattr(args, "trace_out", None)
    want_stats = getattr(args, "step_stats", False)
    tracer = TR.Tracer(enabled=bool(trace_out))
    # fleet identity (multi-process groups, e.g. under the elastic
    # supervisor): rank-stamped process metadata + per-rank trace shards
    # tools/trace_merge.py can merge (utils/tracing.py)
    rank = TR.detect_rank()
    if rank is not None:
        import socket as _socket

        tracer.set_process(rank=rank, hostname=_socket.gethostname())
        if trace_out:
            trace_out = TR.rank_trace_path(trace_out, rank)
            args.trace_out = trace_out
            log(f"(per-rank trace shard: {trace_out})")

    from .guard import PreemptionGuard
    from .monitor import WatchdogConfig, attach_monitor

    preemption = None
    if getattr(args, "on_sigterm", "ignore") == "checkpoint":
        preemption = PreemptionGuard(log=log).install()
    monitor = attach_monitor(
        metrics_port=getattr(args, "metrics_port", None),
        tracer=tracer,
        preemption=preemption,
        watchdog=getattr(args, "watchdog", "on") == "on",
        config=WatchdogConfig(
            escalate_after_polls=(
                5
                if getattr(args, "watchdog_escalate", "none") == "preempt"
                and preemption is not None
                else 0
            ),
        ),
        # on-demand /profile captures land next to the Chrome trace; the
        # whole-run --profile-dir capture is a separate (exclusive) path
        profile_dir=(
            os.path.dirname(os.path.abspath(trace_out)) if trace_out
            else None
        ),
        rank=rank,
        log=log,
    )
    try:
        return _run_training_body(
            args, regime, log=log, cfg=cfg, timers=timers, tracer=tracer,
            preemption=preemption, monitor=monitor, cache_dir=cache_dir,
            trace_out=trace_out, want_stats=want_stats,
        )
    finally:
        linger = getattr(args, "metrics_linger", 0.0) or 0.0
        if monitor.server is not None and linger > 0:
            log(f"(metrics server lingering {linger:g}s for final scrapes)")
            time.sleep(linger)
        if preemption is not None:
            preemption.uninstall()
        monitor.close()


def _run_training_body(
    args, regime: str, *, log, cfg, timers, tracer, preemption, monitor,
    cache_dir, trace_out, want_stats,
) -> Engine:
    registry = monitor.registry
    syn = getattr(args, "synthetic_size", None)
    with tracer.span(TR.DATA_LOADING, track="host"), timers.phase(T.DATA_LOADING):
        train_split = load_split(
            True,
            root=args.data_root,
            source=args.data,
            seed=args.seed,
            synthetic_size=syn,
            # streaming keeps the train split as uint8 in host RAM; the
            # native kernel normalizes per batch
            normalize_images=cfg.input_mode != "stream",
        )
        test_split = load_split(
            False,
            root=args.data_root,
            source=args.data,
            seed=args.seed,
            synthetic_size=max(1, syn // 5) if syn else None,
        )
    log(
        f"(Loaded train dataset of length {len(train_split)} "
        f"[source={train_split.source}], test length {len(test_split)})"
    )

    run = init_run(jsonl_path=args.metrics_jsonl, neptune=args.neptune)
    run["parameters"] = {
        "learning_rate": cfg.lr,
        "optimizer": "SGD",
        "model_name": {"single": "nodistmodel"}.get(regime, "distmodel"),
        "epochs": cfg.epochs,
        "batch_size": cfg.batch_size,
        "regime": regime,
        "sync_mode": cfg.sync_mode,
        "nb_proc": cfg.nb_proc,
        "seed": cfg.seed,
    }

    t0 = time.perf_counter()
    engine = Engine(
        cfg, train_split, test_split, tracer=tracer, registry=registry
    )
    from ..utils.goodput import LEDGER as G_LEDGER

    G_LEDGER.describe(
        config={
            "regime": regime, "epochs": cfg.epochs,
            "batch_size": cfg.batch_size, "lr": cfg.lr,
            "nb_proc": cfg.nb_proc, "sync_mode": cfg.sync_mode,
            "seed": cfg.seed, "compute_dtype": cfg.compute_dtype,
            "input_mode": cfg.input_mode, "kernels": cfg.kernels,
        },
        mesh={
            "axes": {"data": engine.n_workers},
            "devices": engine.n_workers,
            "desc": f"data{engine.n_workers}",
            "optimizer": "sgd",
        },
    )

    stats = None
    if want_stats or trace_out:
        import jax

        from .measure import peak_flops

        flops, flops_src = engine.flops_per_epoch()
        stats = TR.StepStats(
            item_label="images",
            # step/* series ride the existing metrics sinks; without
            # --step-stats the trace still embeds the aggregate summary
            sink=run if want_stats else None,
            n_devices=engine.n_workers,
            comm_bytes_per_step=TR.collective_bytes_per_sync(
                engine.params, engine.n_workers
            ),
            flops_per_step=flops,
            flops_source=flops_src,
            peak_flops_per_device=peak_flops(
                jax.devices()[0].device_kind, cfg.compute_dtype
            ),
            grad_sync=cfg.grad_sync if cfg.sync_mode == "step" else None,
            compilation_cache_dir=cache_dir,
            registry=registry,
        )
        engine.step_stats = stats
        if cfg.sync_mode == "step" and cfg.grad_sync == "overlap":
            # put the bucket plan in-band in the trace (the collectives
            # run inside the compiled epoch where spans can't see them)
            from ..parallel.collectives import plan_buckets

            layout = plan_buckets(
                engine.params, bucket_bytes=int(cfg.bucket_mb * 2**20)
            )
            stats.comm_bucket_bytes = [int(b) for b in layout.bucket_bytes()]
            TR.record_bucket_plan(
                tracer, stats.comm_bucket_bytes, schedule="overlap",
                op="pmean", axis_size=engine.n_workers,
            )

    checkpointer = None
    start_epoch = 0
    if getattr(args, "resume", False) and not getattr(args, "checkpoint_dir", None):
        raise SystemExit("--resume requires --checkpoint-dir")
    if getattr(args, "checkpoint_dir", None):
        from ..utils.checkpoint import Checkpointer

        checkpointer = Checkpointer(
            args.checkpoint_dir,
            every=args.checkpoint_every,
            keep=args.checkpoint_keep,
            backend=args.checkpoint_backend,
            registry=registry,
        )
        if args.resume:
            start_epoch = checkpointer.restore_latest(
                engine, elastic=getattr(args, "elastic", False), log=log
            )
            if start_epoch:
                log(f"(Resumed from checkpoint: next epoch {start_epoch})")
            else:
                log(
                    f"(WARNING: --resume found no checkpoint in "
                    f"{args.checkpoint_dir} [backend={checkpointer.backend_name}]; "
                    "starting from scratch - check the dir and "
                    "--checkpoint-backend match the original run)"
                )

    # self-healing layer (train/guard.py): per-epoch policy guard; the
    # cooperative preemption guard was installed by run_training before
    # the monitor (its escalation path needs it)
    from .guard import GuardConfig, TrainingGuard

    guard = None
    if getattr(args, "guard", "off") != "off":
        guard = TrainingGuard(
            GuardConfig(
                policy=args.guard,
                spike_zscore=getattr(args, "guard_spike_zscore", 6.0),
                snapshot_every=getattr(args, "snapshot_every", 1),
                max_retries=getattr(args, "max_retries", 3),
                # one observation per epoch: arm the spike detector after
                # a few epochs rather than the step-scale default
                warmup_steps=3,
            ),
            tracer=tracer, step_stats=stats, registry=registry, log=log,
        )

    profile_dir = getattr(args, "profile_dir", None)
    if profile_dir:
        import jax

        jax.profiler.start_trace(profile_dir)
    if monitor.recompiles is not None:
        # cache-miss counting on the engine's compiled epoch step: the
        # watchdog turns a burst of misses into the recompile-storm flag
        monitor.recompiles.swap(engine._train_fn)
        engine.recompiles = monitor.recompiles

    try:
        engine.run(
            timers=timers,
            run=run,
            log=log,
            eval_every=args.eval_every,
            checkpointer=checkpointer,
            start_epoch=start_epoch,
            fused=getattr(args, "fused", False),
            guard=guard,
            preemption=preemption,
        )
    finally:
        if profile_dir:
            import jax

            try:
                # a failed fused dispatch may have consumed (donated) params;
                # never let the fence mask the original exception or skip
                # stop_trace/close below
                from ..utils.timers import hard_block

                hard_block(engine.params)
            except Exception:
                pass
            jax.profiler.stop_trace()
            log(f"(Profiler trace written to {profile_dir})")
        if checkpointer is not None:
            checkpointer.close()
    wall = time.perf_counter() - t0

    if guard is not None:
        log(f"(guard summary: {json.dumps(guard.summary())})")

    # goodput close-out: conservation-asserted breakdown + run record
    goodput_rec = G_LEDGER.finalize(metrics={
        "final_train_loss": engine.history[-1].train_loss
        if engine.history else None,
        "final_val_acc": engine.history[-1].val_acc
        if engine.history else None,
        "epochs": cfg.epochs,
        "preempted": bool(preemption.requested) if preemption else False,
    })
    log("GOODPUT " + json.dumps({
        "goodput_ratio": goodput_rec["goodput_ratio"],
        "wall_s": goodput_rec["wall_s"],
        "goodput_s": goodput_rec["goodput_s"],
        "badput_s": {k: v for k, v in goodput_rec["badput_s"].items()
                     if v > 0},
        "steps": goodput_rec["steps"],
        "record": G_LEDGER.path,
    }))

    if stats is not None and want_stats:
        for line in stats.report().splitlines():
            log(line)
    if trace_out:
        tracer.export(trace_out, step_stats=stats, goodput=goodput_rec)
        log(
            f"(Chrome trace written to {trace_out}; open in Perfetto / "
            "chrome://tracing, or summarize with tools/trace_summary.py)"
        )
    run.stop()

    # the reference's five epoch-phase accumulators, live on /metrics as
    # phase_seconds_total{phase=...} (utils/obs.py) - not just log/*.txt
    from ..utils.obs import publish_phase_timers

    publish_phase_timers(registry, timers)

    # the canonical phase-summary block (utils/timers.py report(); the
    # reference's stdout phrasing, shared with every other entry point)
    for line in timers.report().splitlines():
        log(line)
    log(f"Total wall-clock: {wall:.3f} s")

    if args.log_dir:
        nb_proc = getattr(args, "nb_proc", None) or 1
        parent, children = write_phase_logs(
            args.log_dir,
            bs=cfg.batch_size,
            epochs=cfg.epochs,
            nb_proc=nb_proc,
            timers=timers,
        )
        log(f"(Phase logs written: {parent}, {children})")

    best = max(
        (m for m in engine.history if m.val_acc is not None),
        key=lambda m: m.val_acc,
        default=None,
    )
    summary = {
        "regime": regime,
        "epochs": cfg.epochs,
        "guard": getattr(args, "guard", "off"),
        "preempted": bool(preemption.requested) if preemption else False,
        "final_train_loss": engine.history[-1].train_loss if engine.history else None,
        "final_val_acc": engine.history[-1].val_acc if engine.history else None,
        "best_val_acc": best.val_acc if best else None,
        "wall_clock_s": round(wall, 3),
        "data_source": train_split.source,
    }
    log("SUMMARY " + json.dumps(summary))
    return engine


def main(argv=None) -> int:
    """`python -m distributed_neural_network_tpu.train.cli` - the smoke /
    telemetry harness behind the three top-level scripts.

    Same flag surface plus `--regime`; defaults are deliberately tiny
    (synthetic data, 2048 rows, all available devices) so a bare
    `python -m ... --epochs 1 --trace-out trace.json --step-stats` runs in
    seconds on a CPU host. Full-scale runs use the top-level entry points
    (single_proc_train.py / model_replication_train.py /
    data_parallelism_train.py), whose defaults mirror the reference.
    """
    import argparse as _argparse

    parser = _argparse.ArgumentParser(
        prog="python -m distributed_neural_network_tpu.train.cli",
        description=main.__doc__,
        formatter_class=_argparse.RawDescriptionHelpFormatter,
    )
    add_common_flags(parser, epochs=2, batch_size=16)
    add_distributed_flags(parser, nb_proc=None)
    parser.add_argument(
        "cmd",
        nargs="?",
        choices=("smoke",),
        default=None,
        help="optional subcommand alias: 'smoke' names the default tiny "
        "synthetic run explicitly (CI: python -m ...train.cli smoke "
        "--metrics-port 0)",
    )
    parser.add_argument(
        "--regime",
        choices=("single", "data_parallel", "replication"),
        default="data_parallel",
    )
    # tiny-by-default: the module runner is for smoke runs and telemetry
    # capture, not baseline numbers (--data/--synthetic-size override)
    parser.set_defaults(data="synthetic", synthetic_size=2048)
    args = parser.parse_args(argv)
    run_training(args, args.regime)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
