#!/usr/bin/env python
"""Train the transformer LM with any mesh factorization from the CLI.

The reference has exactly one model (the CIFAR CNN) and one parallelism
axis; this entry point exposes the framework's multi-axis portfolio -
data / sequence (ring or Ulysses attention) / tensor / expert parallelism
and the ZeRO-1 sharded optimizer - on a dp x sp x tp mesh, or pipeline
parallelism on a dp x pp x tp mesh. The task is the built-in synthetic
copy task (second half of each sequence repeats the first), so convergence
is observable without a corpus: loss should fall toward ~0.

Examples (8 devices - real or XLA_FLAGS=--xla_force_host_platform_device_count=8):
  python lm_train.py --dp 2 --sp 2 --tp 2 --attn ring --steps 100
  python lm_train.py --dp 8 --optimizer zero --steps 100
  python lm_train.py --dp 4 --tp 2 --experts 8 --steps 100
  python lm_train.py --pp 4 --dp 2 --microbatches 2 --steps 100
  python lm_train.py --dp 2 --sp 4 --attn ulysses --seq-len 512 --steps 50
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# checkpoint momentum-layout version: "tree" = per-leaf momentum trees for
# both sgd and zero (the round-2 layout); bump on any layout change so
# resume rejects old checkpoints with a clear message
MOM_FORMAT = "tree"

def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    p.add_argument("--dp", type=int, default=1, help="data-parallel axis size")
    p.add_argument("--sp", type=int, default=1, help="sequence-parallel axis size")
    p.add_argument("--tp", type=int, default=1, help="tensor-parallel axis size")
    p.add_argument("--pp", type=int, default=1,
                   help="pipeline stages (uses the dp x pp x tp mesh; "
                   "exclusive with --sp; composes with --experts (experts "
                   "shard over dp); zero optimizers compose with --dp, "
                   "not --tp/--experts)")
    p.add_argument("--sharding", default="manual", metavar="MODE",
                   help="how the partition layout is chosen (dp x sp x tp "
                   "mesh path): 'manual' (default) shards per "
                   "--dp/--sp/--tp with the built-in partition-rule table "
                   "(parallel/rules.py); 'auto' runs the static cost-model "
                   "search (analysis/autoshard.py) over every mesh "
                   "factorization of --dp*--sp*--tp devices (or all "
                   "visible devices when those are 1) and adopts the "
                   "winning plan - pure abstract tracing, nothing "
                   "executes; 'rules:<file>' loads a custom ordered "
                   "[regex, spec] JSON rule list for the param layout "
                   "(every leaf must match)")
    p.add_argument("--microbatches", type=int, default=2)
    p.add_argument(
        "--pp-interleave", type=int, default=1,
        help="virtual pipeline stages per device (circular schedule): "
        "cuts the bubble from (P-1)/(M+P-1) to (P-1)/(v*M+P-1) at the "
        "cost of v-times-finer layer chunks; needs pp*v | layers and "
        "pp | microbatches",
    )
    p.add_argument(
        "--attn", choices=("ring", "ulysses", "zigzag", "flash"),
        default="ring",
        help="sequence-parallel attention; zigzag = load-balanced causal "
        "ring (~2x ring's causal throughput; tokens are fed in zigzag "
        "shard order automatically); flash = Pallas TPU kernel for the "
        "local sp=1 case",
    )
    p.add_argument("--experts", type=int, default=0,
                   help="MoE expert count (0 = dense FFN)")
    p.add_argument(
        "--optimizer", choices=("sgd", "adam", "zero", "zero-adam"),
        default="sgd",
        help="sgd/adam = replicated state; zero/zero-adam = ZeRO-1 state "
        "sharded over the data axis (adam state is 2x params, so sharding "
        "it saves the most)",
    )
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--stop-at-step", type=int, default=None, metavar="N",
                   help="ABSOLUTE step to stop before (end_step = N), "
                   "overriding the relative '--steps more' semantics on "
                   "resume - the supervisor (tools/launch.py) passes this "
                   "so every relaunch of an elastic group trains to the "
                   "same target instead of adding --steps per restart")
    p.add_argument("--batch-size", type=int, default=32, help="global batch")
    p.add_argument("--seq-len", type=int, default=64)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--n-heads", type=int, default=8)
    p.add_argument("--n-layers", type=int, default=4)
    p.add_argument("--d-ff", type=int, default=512)
    p.add_argument("--dtype", choices=("float32", "bfloat16"), default="float32")
    p.add_argument("--model-config", default="", metavar="FILE",
                   help="train the model a configuration file of the "
                   "benchmark's form describes (benchmark/configs/*.json, "
                   "benchmark/families/*/tiny.json) in place of the "
                   "--d-model/--n-heads/... transformer. Its \"family\" "
                   "picks the model module: 'nemotron_h' (Mamba-2, expert "
                   "and attention layers in one pattern, "
                   "models/nemotron_h.py; --dp only, attention by the flash kernels). "
                   "--vocab, --d-model, --n-heads, --n-layers, --d-ff and "
                   "--experts are then unused")
    p.add_argument(
        "--precision", choices=("bf16", "fp8", "int8", "int8-kv"),
        default="bf16",
        help="low-precision fast path (ops/quant.py): 'fp8'/'int8' run "
        "the attention QK^T/PV matmuls quantized with per-token scales "
        "and wide accumulation (forward only - backward stays full "
        "precision; the bench parity row gates the loss/logit drift, "
        "docs/MEASUREMENT.md); 'bf16' (default) is the unquantized "
        "path ('bf16' names the ACCUMULATION contract, not --dtype). "
        "'int8-kv' is the serving-side KV-cache quantization - use "
        "python -m distributed_neural_network_tpu.serve --precision "
        "int8-kv",
    )
    p.add_argument("--loss-chunks", type=int, default=0,
                   help="compute the CE loss in this many sequence chunks "
                   "so full (B, S, vocab) logits never materialize "
                   "(0 = auto-pick by a 64 MB logits budget, 1 = single pass)")
    p.add_argument("--remat", action="store_true",
                   help="rematerialize blocks in backward (jax.checkpoint): "
                   "~1/3 more FLOPs for far less activation memory")
    p.add_argument("--remat-policy", default="",
                   help="jax.checkpoint_policies name applied with --remat "
                   "(e.g. dots_saveable: store matmul outputs and the "
                   "flash kernel's, recompute only elementwise - a few "
                   "percent FLOP tax instead of full remat's ~1/3); '' = "
                   "save nothing")
    p.add_argument("--remat-attn", action="store_true",
                   help="rematerialize ONLY the attention scores/softmax in "
                   "backward: avoids storing the (B,H,S,S) tensor for a few "
                   "percent extra FLOPs - the cheap alternative to --remat "
                   "for the XLA attention path (no-op with --remat)")
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--lr-schedule", choices=("constant", "cosine"),
                   default="constant",
                   help="cosine = linear warmup (--warmup-steps) then "
                   "half-cosine decay over --steps to --min-lr-frac * lr")
    p.add_argument("--warmup-steps", type=int, default=0)
    p.add_argument("--min-lr-frac", type=float, default=0.0,
                   help="cosine floor as a fraction of --lr")
    p.add_argument("--clip-norm", type=float, default=0.0,
                   help="clip gradients to this global L2 norm before the "
                   "optimizer (0 = off); sharding-aware across dp/sp/tp/pp")
    p.add_argument("--accum-steps", type=int, default=1,
                   help="gradient accumulation: scan this many sequential "
                   "fwd/bwd micro-batches per optimizer step (batch-size "
                   "must divide by dp * accum-steps; under --pp also by "
                   "microbatches per pass - prefer raising --microbatches "
                   "until activation memory binds, then accumulate)")
    p.add_argument("--grad-sync", choices=("end", "overlap"), default="end",
                   help="gradient-sync schedule under --accum-steps k>1: "
                   "end = one bulk sync after the accumulation scan "
                   "(existing behavior); overlap = one collective per "
                   "size-capped leaf bucket (--bucket-mb) PER MICROBATCH "
                   "inside the scan, so the interconnect works while the "
                   "next microbatch's backward runs - with zero/zero-adam "
                   "the scan carries only this device's 1/dp gradient "
                   "shard (reduce-scatter), shrinking the accumulator "
                   "from O(D) to O(D/dp). Same result up to float "
                   "reassociation; identical at --accum-steps 1. Not "
                   "compatible with --experts at dp>1")
    p.add_argument("--bucket-mb", type=float, default=4.0,
                   help="gradient-bucket payload cap in MiB for "
                   "--grad-sync overlap")
    p.add_argument("--compilation-cache-dir", default=None,
                   help="persistent XLA compilation cache dir, used only "
                   "while JAX_COMPILATION_CACHE_DIR is unset (the variable "
                   "wins; default <checkout>/.jax_cache): repeat runs "
                   "deserialize instead of recompiling; the --step-stats "
                   "compile field then shows the cache-hit time")
    p.add_argument("--ema-decay", type=float, default=0.0,
                   help="track an exponential moving average of params "
                   "(e.g. 0.999) and use it for --eval-every/--generate; "
                   "0 = off. Not checkpointed: resume restarts the average "
                   "from the restored params")
    p.add_argument("--weight-decay", type=float, default=0.0,
                   help="decoupled (AdamW-style) weight decay; applied by "
                   "every optimizer on both the mesh and pipeline paths")
    p.add_argument("--momentum", type=float, default=0.9,
                   help="SGD momentum; for adam/zero-adam this is b1 "
                   "(the first-moment decay, Adam's momentum analog)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--data-path", default=None,
                   help="token corpus (.npy, raw .bin of uint16 tokens, or "
                   ".txt byte-tokenized as uint8 - one flat stream): each "
                   "step samples fresh (B, S) windows; default = the fixed "
                   "synthetic copy-task batch")
    p.add_argument("--eval-every", type=int, default=0,
                   help="every N steps report held-out loss/perplexity "
                   "over --eval-batches windows (requires --data-path; "
                   "the stream tail is the eval split)")
    p.add_argument("--eval-batches", type=int, default=8)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--metrics-jsonl", default=None,
                   help="append train/loss (+ val/loss on --eval-every) "
                   "series to this JSONL file - the reference's metric "
                   "channel (utils/metrics.py), shared with the CNN engine")
    p.add_argument("--run-record", default=None, metavar="RECORD.json",
                   help="write the goodput run record here (wall-clock "
                   "efficiency accounting, utils/goodput.py: goodput "
                   "ratio + per-cause badput seconds, config fingerprint, "
                   "mesh, step/token counts; written through during the "
                   "run so even a SIGKILL leaves the accounting on disk; "
                   "render/diff/gate with tools/goodput.py). Defaults to "
                   "the DNN_TPU_RUN_RECORD env the elastic supervisor "
                   "exports; the breakdown is always printed as a "
                   "GOODPUT line either way")
    p.add_argument("--trace-out", default=None, metavar="TRACE.json",
                   help="write a Chrome trace-event JSON of the run (one "
                   "train_step span per step, fenced - adds one scalar "
                   "device fetch per step); open in Perfetto or summarize "
                   "with tools/trace_summary.py (docs/OBSERVABILITY.md)")
    p.add_argument("--step-stats", action="store_true",
                   help="collect per-step StepStats (compile vs steady "
                   "step time, tokens/s, device memory, collective bytes, "
                   "MFU from cost_analysis with analytic fallback), print "
                   "the summary, and emit step/* series to --metrics-jsonl")
    p.add_argument("--dynamics", action="store_true",
                   help="training-dynamics telemetry (train/dynamics.py, "
                   "docs/OBSERVABILITY.md): the compiled step emits one "
                   "extra mesh-reduced bundle - per-layer grad/param/"
                   "update-to-weight norms, the gradient-noise scale "
                   "(with --accum-steps >= 2 and --grad-sync end), and "
                   "the first non-finite layer index for provenance - "
                   "decoded one step behind like the guard's health "
                   "bundle; streams to --dynamics-jsonl, dynamics_* "
                   "gauges, and the 'dynamics' trace track. Mesh path "
                   "only (not --pp)")
    p.add_argument("--dynamics-jsonl", default=None, metavar="DYN.jsonl",
                   help="append the per-step dynamics rows here (one JSON "
                   "object per step: global + per-layer norms, GNS "
                   "readout, bad_layer); render/diff/gate with "
                   "tools/dynamics.py")
    p.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                   help="serve live Prometheus metrics on http://127.0.0.1"
                   ":PORT/metrics plus a /healthz JSON liveness/readiness "
                   "endpoint (0 = ephemeral port, printed at startup); "
                   "also starts the stall/recompile/checkpoint watchdog "
                   "unless --watchdog off (utils/obs.py, train/monitor.py, "
                   "docs/OBSERVABILITY.md; watch live with "
                   "tools/live_top.py http://127.0.0.1:PORT)")
    p.add_argument("--metrics-linger", type=float, default=0.0,
                   metavar="SEC",
                   help="keep the metrics server up this many seconds "
                   "after the run finishes (final scrape window)")
    p.add_argument("--profile-dir", default=None, metavar="DIR",
                   help="with --metrics-port: serve /profile?steps=N - "
                   "an on-demand jax.profiler capture of the next N "
                   "steps, written under DIR (default: next to "
                   "--trace-out when set; without either the endpoint "
                   "answers 501)")
    p.add_argument("--watchdog", choices=("on", "off"), default="on",
                   help="with --metrics-port: background watchdog flagging "
                   "stalled steps (no heartbeat for N x steady p95 step "
                   "time), recompile storms, and checkpoint staleness as "
                   "watchdog/* trace events + watchdog_*_total counters")
    p.add_argument("--watchdog-escalate", choices=("none", "preempt"),
                   default="none",
                   help="preempt = a persistent stall requests the "
                   "cooperative preemption path (emergency checkpoint at "
                   "the next step boundary, clean exit); requires "
                   "--on-sigterm checkpoint")
    p.add_argument("--checkpoint-dir", default=None,
                   help="save params+momentum every --checkpoint-every steps")
    p.add_argument("--checkpoint-every", type=int, default=50)
    p.add_argument("--resume", action="store_true",
                   help="resume from the latest checkpoint in --checkpoint-dir")
    p.add_argument("--elastic", action="store_true",
                   help="elastic resume (parallel/reshard.py, docs/"
                   "ROBUSTNESS.md): accept a checkpoint saved under a "
                   "DIFFERENT mesh shape or optimizer layout and reshard "
                   "it onto this run's mesh - dp/sp/tp may all change, "
                   "ZeRO shards re-pad for the new dp, and sgd<->zero / "
                   "adam<->zero-adam convert bitwise; the global batch "
                   "stays fixed (grad accumulation is re-sliced) so the "
                   "exact-resume data cursor still holds")
    p.add_argument("--guard", choices=("off", "warn", "skip", "rollback",
                                       "abort"),
                   default="off",
                   help="self-healing step guard (train/guard.py, "
                   "docs/ROBUSTNESS.md): the compiled step emits a health "
                   "bundle (loss, global grad-norm, all-finite flag) "
                   "observed one step behind the dispatch pipeline. "
                   "warn = count/log anomalies; skip = additionally drop "
                   "non-finite updates INSIDE the compiled step (params/"
                   "momentum pass through unchanged); rollback = restore "
                   "the rolling in-memory snapshot (or newest checkpoint) "
                   "and retry with LR backoff; abort = stop with an "
                   "actionable error. Mesh path only (not --pp)")
    p.add_argument("--guard-spike-zscore", type=float, default=6.0,
                   help="loss-spike threshold in EMA standard deviations; "
                   "non-finite steps always count as anomalies")
    p.add_argument("--snapshot-every", type=int, default=50,
                   help="steps between the guard's rolling host snapshots "
                   "(one device_get of params+momentum each; a rollback "
                   "rewinds at most this many steps)")
    p.add_argument("--max-retries", type=int, default=3,
                   help="guard rollback budget before abort (refills after "
                   "a stretch of healthy steps)")
    p.add_argument("--on-sigterm", choices=("checkpoint", "ignore"),
                   default="checkpoint",
                   help="checkpoint = on SIGTERM/SIGINT finish the current "
                   "step, write an emergency checkpoint (when "
                   "--checkpoint-dir is set) and exit cleanly; resume "
                   "replays from the exact batch, bit-identical. "
                   "ignore = default signal behavior")
    p.add_argument("--chaos-nan-step", type=int, action="append",
                   default=None, metavar="N",
                   help="fault injection (parallel/fault.py): NaN the "
                   "gradient tree at step N inside the compiled step "
                   "(repeatable); exercises the guard's in-jit skip path")
    p.add_argument("--chaos-nan-layer", default=None, metavar="REGEX",
                   help="restrict --chaos-nan-step to gradient leaves whose "
                   "/-joined tree path matches this regex (parallel/"
                   "fault.py nan_layer; e.g. 'blocks/3/.*'): with "
                   "--dynamics the non-finite provenance must name one of "
                   "the matched layers in the guard anomaly, flight "
                   "recorder, and postmortem")
    p.add_argument("--chaos-spike-step", type=int, action="append",
                   default=None, metavar="N",
                   help="fault injection: multiply the OBSERVED loss at "
                   "step N by 100 (host-side, fires once, so a rollback "
                   "replay sees a healthy step)")
    p.add_argument("--chaos-sigterm-after", type=int, default=None,
                   metavar="N",
                   help="fault injection: deliver a real SIGTERM to this "
                   "process after step N completes (drives the emergency-"
                   "checkpoint -> exact-resume path end to end)")
    p.add_argument("--chaos-stall-step", type=int, action="append",
                   default=None, metavar="N",
                   help="fault injection: sleep --chaos-stall-seconds "
                   "inside the host step callback after step N completes "
                   "(repeatable; host-side, works under --pp too) - the "
                   "heartbeat stops, which the --metrics-port watchdog "
                   "must flag as a watchdog/stall event within one "
                   "detection window")
    p.add_argument("--chaos-stall-seconds", type=float, default=2.0,
                   metavar="SEC",
                   help="stall duration for --chaos-stall-step")
    p.add_argument("--chaos-stall-rank", type=int, default=None,
                   metavar="R",
                   help="restrict --chaos-stall-step to process rank R "
                   "of a multi-process group (every rank runs the same "
                   "argv under tools/launch.py, so without this the "
                   "whole fleet stalls in lockstep); single-process runs "
                   "treat their rank as 0. Drives the supervisor's "
                   "straggler attribution validation "
                   "(fleet_straggler_rank)")
    p.add_argument("--chaos-shrink-at-step", type=int, default=None,
                   metavar="N",
                   help="fault injection (parallel/fault.py): after step N "
                   "raise a cooperative SHRINK preemption - the elastic "
                   "driver writes an emergency checkpoint, rebuilds the "
                   "mesh from the first --chaos-shrink-to devices, "
                   "reshards params+optimizer state onto it "
                   "(parallel/reshard.py) and CONTINUES training in this "
                   "process: the full preempt -> checkpoint -> reshard -> "
                   "resume path. Requires --checkpoint-dir and "
                   "--on-sigterm checkpoint; mesh path only (not --pp)")
    p.add_argument("--chaos-shrink-to", type=int, default=None,
                   metavar="DP",
                   help="data-parallel size the SHRINK preemption drops to "
                   "(default dp//2); sp/tp are kept, the global batch is "
                   "preserved by re-slicing gradient accumulation")
    p.add_argument("--gen-temperature", type=float, default=0.0,
                   help="sampling temperature for --generate (0 = greedy)")
    p.add_argument("--gen-top-k", type=int, default=0,
                   help="restrict --generate sampling to the k most likely "
                   "tokens (0 = no restriction)")
    p.add_argument("--gen-top-p", type=float, default=0.0,
                   help="nucleus sampling for --generate: restrict to the "
                   "smallest token set with cumulative probability >= p "
                   "(0 = no restriction; composes after --gen-top-k)")
    p.add_argument("--generate", type=int, default=0, metavar="N",
                   help="after training, greedy-decode N tokens from the "
                   "first sequences' prompts through the KV-cache path and "
                   "print prompt/completion pairs (single-device decode)")
    args = p.parse_args(argv)
    if args.steps < 1:
        p.error("--steps must be >= 1")
    if args.checkpoint_every < 1:
        p.error("--checkpoint-every must be >= 1")
    if args.resume and not args.checkpoint_dir:
        p.error("--resume requires --checkpoint-dir")
    if args.remat_policy and not args.remat:
        p.error("--remat-policy only applies with --remat (the policy "
                "picks WHAT checkpointed blocks save); the name is "
                "validated against jax.checkpoint_policies after startup")
    if args.eval_every and not args.data_path:
        p.error("--eval-every requires --data-path (the held-out split "
                "is the token stream's tail)")
    if args.gen_top_k and args.gen_temperature <= 0:
        p.error("--gen-top-k only applies when sampling; set "
                "--gen-temperature > 0 (temperature 0 is greedy and "
                "ignores top-k)")
    if args.gen_temperature < 0:
        p.error(f"--gen-temperature must be >= 0, got "
                f"{args.gen_temperature}")
    if not 0.0 <= args.gen_top_p <= 1.0:
        p.error(f"--gen-top-p must be in [0, 1], got {args.gen_top_p}")
    if args.gen_top_p and args.gen_temperature <= 0:
        p.error("--gen-top-p only applies when sampling; set "
                "--gen-temperature > 0 (temperature 0 is greedy and "
                "ignores top-p)")
    if args.generate <= 0 and (args.gen_temperature > 0 or args.gen_top_k
                               or args.gen_top_p):
        p.error("--gen-temperature/--gen-top-k/--gen-top-p configure "
                "--generate N, which was not requested - add "
                "--generate N or drop the sampling flags")
    if args.sharding not in ("manual", "auto") and not args.sharding.startswith(
        "rules:"
    ):
        p.error(
            f"--sharding must be 'manual', 'auto', or 'rules:<file>', got "
            f"{args.sharding!r}"
        )
    if args.sharding == "rules:":
        p.error("--sharding rules: needs a file path (rules:<file>)")
    if args.sharding != "manual" and args.pp > 1:
        p.error(
            "--sharding auto/rules:<file> drive the dp x sp x tp mesh "
            "path's partition layer (parallel/rules.py); the pipeline "
            "path's stage sharding is fixed by --pp - drop --pp or use "
            "--sharding manual"
        )
    if args.ema_decay and args.pp > 1:
        p.error("--ema-decay is unused under --pp (the pipeline path has "
                "no --eval-every/--generate consumer for the averaged "
                "weights); drop it or use the dp x sp x tp mesh")
    if args.loss_chunks > 1 and (
        args.seq_len // max(args.sp, 1)
    ) % args.loss_chunks:
        p.error(
            f"--loss-chunks {args.loss_chunks} must divide the per-shard "
            f"sequence length {args.seq_len // max(args.sp, 1)} "
            f"(--seq-len / --sp; the CE is chunked along the local "
            "sequence axis)"
        )
    if args.attn == "zigzag" and args.sp > 1 and args.seq_len % (2 * args.sp):
        p.error(
            f"--attn zigzag needs --seq-len divisible by 2*sp "
            f"({2 * args.sp}); got {args.seq_len}"
        )
    if args.attn == "flash" and args.sp > 1:
        p.error(
            "--attn flash is the local (per-device) kernel and composes "
            "with --dp/--tp (own vma-typed Pallas kernels, round 4); a "
            "sequence axis needs --attn ring/ulysses/zigzag"
        )
    if args.precision == "int8-kv":
        p.error(
            "--precision int8-kv quantizes the SERVING KV cache (paged "
            "pool + per-block scales); it is a flag of python -m "
            "distributed_neural_network_tpu.serve. Training's quantized "
            "paths are --precision fp8|int8"
        )
    if args.precision != "bf16" and args.sp > 1:
        p.error(
            f"--precision {args.precision} quantizes the LOCAL attention "
            "matmuls; a sequence axis (ring/ulysses/zigzag) has no "
            "quantized path - drop --sp or --precision"
        )
    if args.precision != "bf16" and args.pp > 1:
        p.error(
            f"--precision {args.precision} is wired through the "
            "dp x sp x tp mesh step; the pipeline path does not thread "
            "attn_quant - drop --pp or --precision"
        )
    if args.grad_sync == "overlap" and args.experts and args.dp > 1:
        p.error(
            "--grad-sync overlap psums gradient buckets over the data "
            "axis; expert-sharded leaves (--experts with --dp > 1) vary "
            "over that axis - use --grad-sync end"
        )
    if args.bucket_mb <= 0:
        p.error(f"--bucket-mb must be > 0, got {args.bucket_mb}")
    # --chaos-stall-step is deliberately NOT in this set: it is a pure
    # host-side sleep (no health bundle involved), so it works under --pp
    chaos_injected = bool(
        args.chaos_nan_step or args.chaos_spike_step
        or args.chaos_sigterm_after is not None
    )
    if args.pp > 1 and (args.guard != "off" or chaos_injected):
        p.error(
            "--guard / --chaos-* are wired through the dp x sp x tp mesh "
            "step's health bundle (train/lm.py make_lm_train_step); the "
            "pipeline path has no health output yet - drop --pp or the "
            "guard flags"
        )
    if args.chaos_stall_seconds <= 0:
        p.error(f"--chaos-stall-seconds must be > 0, got "
                f"{args.chaos_stall_seconds}")
    if args.chaos_stall_rank is not None and not args.chaos_stall_step:
        p.error("--chaos-stall-rank restricts --chaos-stall-step, which "
                "was not given")
    if args.chaos_nan_layer is not None and not args.chaos_nan_step:
        p.error("--chaos-nan-layer restricts --chaos-nan-step, which "
                "was not given")
    if args.dynamics and args.pp > 1:
        p.error("--dynamics is wired through the dp x sp x tp mesh step's "
                "telemetry bundle (train/lm.py make_lm_train_step); the "
                "pipeline path has no dynamics output - drop --pp")
    if args.dynamics_jsonl and not args.dynamics:
        p.error("--dynamics-jsonl is the sink for --dynamics, which "
                "was not given")
    if args.elastic and not args.resume and args.chaos_shrink_at_step is None:
        p.error("--elastic configures how --resume (or a SHRINK "
                "preemption) maps a checkpoint onto this mesh; add "
                "--resume with --checkpoint-dir, or --chaos-shrink-at-step")
    if args.stop_at_step is not None and args.stop_at_step < 1:
        p.error(f"--stop-at-step must be >= 1, got {args.stop_at_step}")
    if args.chaos_shrink_at_step is not None:
        if args.pp > 1:
            p.error("--chaos-shrink-at-step shrinks the dp x sp x tp mesh "
                    "in process; drop --pp")
        if not args.checkpoint_dir:
            p.error("--chaos-shrink-at-step drives the preempt -> "
                    "checkpoint -> reshard -> resume path; it requires "
                    "--checkpoint-dir")
        if args.on_sigterm != "checkpoint":
            p.error("--chaos-shrink-at-step rides the cooperative "
                    "preemption guard; it requires --on-sigterm checkpoint")
        if args.eval_every:
            p.error("--chaos-shrink-at-step cannot rebuild the --eval-every "
                    "evaluator mid-run; drop one of the two")
        if args.chaos_shrink_to is None:
            args.chaos_shrink_to = max(args.dp // 2, 1)
        if not 1 <= args.chaos_shrink_to < args.dp:
            p.error(f"--chaos-shrink-to must be in [1, dp) = "
                    f"[1, {args.dp}), got {args.chaos_shrink_to}")
        if args.batch_size % args.chaos_shrink_to:
            p.error(f"--batch-size {args.batch_size} must divide over "
                    f"--chaos-shrink-to {args.chaos_shrink_to} (the global "
                    "batch is preserved across the shrink)")
    if args.watchdog_escalate == "preempt" and args.on_sigterm != "checkpoint":
        p.error("--watchdog-escalate preempt rides the cooperative "
                "preemption path; it requires --on-sigterm checkpoint")
    if args.snapshot_every < 1:
        p.error(f"--snapshot-every must be >= 1, got {args.snapshot_every}")
    if args.max_retries < 0:
        p.error(f"--max-retries must be >= 0, got {args.max_retries}")

    # the goodput ledger's wall clock starts BEFORE the jax import and
    # distributed rendezvous so the init bucket owns them honestly
    # (utils/goodput.py; docs/OBSERVABILITY.md "Goodput accounting")
    from distributed_neural_network_tpu.utils.goodput import (
        LEDGER as G_LEDGER,
    )

    G_LEDGER.reset()  # one ledger per run (a harness may call main twice)
    G_LEDGER.start()
    if args.run_record:
        G_LEDGER.arm(args.run_record)

    from distributed_neural_network_tpu.runtime import (
        enable_compile_cache,
        route,
    )

    args.compilation_cache_dir = enable_compile_cache(
        args.compilation_cache_dir
    )
    print(f"(persistent compilation cache: {args.compilation_cache_dir})")
    import jax
    import jax.numpy as jnp

    if args.remat_policy and not hasattr(
        jax.checkpoint_policies, args.remat_policy
    ):
        raise SystemExit(
            f"--remat-policy {args.remat_policy!r} is not a "
            "jax.checkpoint_policies name"
        )

    from distributed_neural_network_tpu.models import transformer as tfm
    from distributed_neural_network_tpu.parallel import pipeline as ppl
    from distributed_neural_network_tpu.parallel.distributed import initialize
    from distributed_neural_network_tpu.train import lm as lmtrain

    initialize()
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    if args.model_config:
        with open(args.model_config) as f:
            published = json.load(f)
        from distributed_neural_network_tpu import models

        try:
            family = models.family_module(published.get("family"))
        except KeyError:
            raise SystemExit(
                f"--model-config {args.model_config}: family "
                f"{published.get('family')!r} has no model module here "
                f"(there is {sorted(models.FAMILIES)}; GPT-2's block is the "
                "--d-model/--n-heads/... flags)"
            ) from None
        if not hasattr(family, "apply_hidden"):
            raise SystemExit(
                f"--model-config {args.model_config}: family "
                f"{published['family']!r} is served, not trained (python -m "
                "distributed_neural_network_tpu.serve --model-config): its "
                "module has no training forward"
            )
        args.attn = "flash"  # no sequence axis: the local kernels
        args.vocab = published["vocab_size"]
        cfg = family.from_published(
            published, dtype=dtype, remat=args.remat,
            remat_policy=args.remat_policy,
        )
    else:
        cfg = tfm.TransformerConfig(
            vocab_size=args.vocab,
            d_model=args.d_model,
            n_heads=args.n_heads,
            n_layers=args.n_layers,
            d_ff=args.d_ff,
            dtype=dtype,
            remat=args.remat,
            remat_attn=args.remat_attn,
            remat_policy=args.remat_policy,
            n_experts=args.experts,
            attn_quant="" if args.precision == "bf16" else args.precision,
        )
    # the analytic FLOP count is the transformer's (train/measure.py); a
    # --model-config model's is kept with the benchmark
    # (benchmark/families/*/arith.py) and its MFU line is left out
    from distributed_neural_network_tpu.train.measure import (
        model_flops_per_token,
    )

    flops_tok = (0.0 if args.model_config
                 else model_flops_per_token(cfg, args.seq_len))
    if not args.model_config and args.n_heads % max(args.tp, 1):
        raise SystemExit(f"--n-heads {args.n_heads} must divide by --tp {args.tp}")

    from jax.sharding import NamedSharding, PartitionSpec as P

    # --sharding: the declarative partition layer (parallel/rules.py +
    # analysis/autoshard.py). 'auto' searches every mesh factorization of
    # the device budget with the static cost model (abstract traces only
    # - scoring happens before anything is placed or compiled) and
    # rewrites --dp/--sp/--tp to the winning plan; 'rules:<file>' swaps
    # the built-in rule table for a custom one, threaded through every
    # spec-derivation site (shard_params / make_lm_train_step / the
    # elastic reshard path).
    shard_rules = None
    if args.sharding.startswith("rules:"):
        from distributed_neural_network_tpu.parallel.rules import load_rules

        rules_path = args.sharding[len("rules:"):]
        shard_rules = load_rules(rules_path)
        print(f"(sharding rules: {rules_path}, {len(shard_rules)} rule(s))")
    elif args.sharding == "auto":
        from distributed_neural_network_tpu.analysis.autoshard import (
            search_plans,
        )

        budget = args.dp * args.sp * args.tp
        if budget == 1:
            budget = jax.device_count()
        result = search_plans(
            "lm", cfg=cfg, devices=budget, batch=args.batch_size,
            seq_len=args.seq_len, optimizer=args.optimizer,
            kwargs=dict(
                accum_steps=args.accum_steps, grad_sync=args.grad_sync,
                bucket_mb=args.bucket_mb, loss_chunks=args.loss_chunks,
                attn_impl=args.attn,
            ),
            config=f"auto@{budget}dev",
        )
        if result.chosen is None:
            raise SystemExit(
                "--sharding auto found no feasible plan over "
                f"{budget} device(s):\n" + "\n".join(
                    f"  {pl.label}: {pl.infeasible_reason}"
                    for pl in result.infeasible
                )
            )
        print(result.explain(top_k=3))
        dims = result.chosen.dims
        args.dp, args.sp, args.tp = dims["dp"], dims["sp"], dims["tp"]
        print(
            f"(sharding auto: adopted mesh dp{args.dp} x sp{args.sp} x "
            f"tp{args.tp}, optimizer {result.chosen.optimizer})"
        )

    params = cfg.module.init_params(jax.random.key(args.seed), cfg)
    pipe = args.pp > 1
    # guard defaults for the pipeline branch (pp + guard/chaos is rejected
    # at argparse; these keep the shared loop code below uniform)
    guard_on = False
    fault_plan = None
    build_step = None
    if pipe:
        if args.sp > 1:
            raise SystemExit(
                "--pp composes with --dp/--tp/--experts and any "
                "--optimizer (zero/zero-adam shard state over dp per "
                "stage; not with --experts or --tp); --sp runs on the "
                "dp x sp x tp mesh (drop --pp)"
            )
        if args.optimizer.startswith("zero") and (
                args.tp > 1 or (args.experts and args.dp > 1)):
            raise SystemExit(
                "--pp with zero optimizers composes with --dp only "
                "(tensor- and expert-sharded leaves are out of the "
                "per-leaf ZeRO layout's scope, same rule as the mesh "
                "path; --experts with --dp 1 keeps experts replicated "
                "and is fine)"
            )
        mesh = ppl.create_pp_mesh(args.dp, args.pp, args.tp)
        params, specs = ppl.shard_pp_params(
            params, cfg, mesh, interleave=args.pp_interleave
        )
        if args.optimizer == "adam":
            from distributed_neural_network_tpu.ops.adam import init_adam

            mom = init_adam(params)
        elif args.optimizer.startswith("zero"):
            mom = ppl.init_pp_zero_state(params, specs, mesh, args.optimizer)
        else:
            from distributed_neural_network_tpu.ops.sgd import init_momentum

            mom = init_momentum(params)
        mom_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            ppl.pp_optimizer_state_specs(args.optimizer, specs),
        )
        import functools

        from distributed_neural_network_tpu.ops import schedule as sched

        pp_lr_schedule = None
        if args.lr_schedule == "cosine":
            pp_lr_schedule = functools.partial(
                sched.warmup_cosine, base_lr=args.lr,
                total_steps=args.steps, warmup_steps=args.warmup_steps,
                min_lr_frac=args.min_lr_frac,
            )
        step = ppl.make_pp_train_step(
            cfg, mesh, n_microbatches=args.microbatches,
            lr=args.lr, momentum=args.momentum,
            loss_chunks=args.loss_chunks, interleave=args.pp_interleave,
            lr_schedule=pp_lr_schedule, clip_norm=args.clip_norm,
            weight_decay=args.weight_decay, optimizer=args.optimizer,
            accum_steps=args.accum_steps, grad_sync=args.grad_sync,
            bucket_mb=args.bucket_mb,
        )
    else:
        mesh = lmtrain.create_lm_mesh(args.dp, args.sp, args.tp)
        params, specs = lmtrain.shard_params(
            params, cfg, mesh, rules=shard_rules
        )
        mom = lmtrain.init_lm_momentum(params, mesh, args.optimizer)
        mom_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            lmtrain.optimizer_state_specs(args.optimizer, specs),
        )
        import functools

        from distributed_neural_network_tpu.ops import schedule as sched

        guard_on = args.guard != "off"
        if args.chaos_nan_step:
            from distributed_neural_network_tpu.parallel.fault import (
                StepFaultPlan,
            )

            fault_plan = StepFaultPlan(
                nan_grads_at=tuple(args.chaos_nan_step),
                nan_layer=args.chaos_nan_layer,
            )

        def build_step(lr_scale: float = 1.0):
            """The compiled mesh step at `lr * lr_scale` - the guard's LR
            backoff rebuilds it (one recompile per rollback retry, bounded
            by --max-retries; the schedule's base LR scales too)."""
            lr_schedule = None
            if args.lr_schedule == "cosine":
                lr_schedule = functools.partial(
                    sched.warmup_cosine, base_lr=args.lr * lr_scale,
                    total_steps=args.steps, warmup_steps=args.warmup_steps,
                    min_lr_frac=args.min_lr_frac,
                )
            return lmtrain.make_lm_train_step(
                cfg, mesh, lr=args.lr * lr_scale, momentum=args.momentum,
                attn_impl=args.attn, optimizer=args.optimizer,
                loss_chunks=args.loss_chunks, lr_schedule=lr_schedule,
                clip_norm=args.clip_norm, accum_steps=args.accum_steps,
                weight_decay=args.weight_decay, grad_sync=args.grad_sync,
                bucket_mb=args.bucket_mb,
                with_health=guard_on,
                skip_nonfinite=args.guard == "skip",
                fault_plan=fault_plan,
                rules=shard_rules,
                dynamics=args.dynamics,
            )

        step = build_step()

    param_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)

    def place_batch(tok, tgt):
        """Host batch -> the mesh's data sharding. Single-process: one
        `device_put` onto the step's input sharding, so the batch is
        split across the mesh before dispatch and not parked on device 0.
        Multi-process (a supervisor group, real multi-host): each process
        uploads only its addressable slices via `distribute_host_data` -
        the compiled step's in_specs span devices this host cannot see,
        so host arrays must become global jax.Arrays BEFORE dispatch."""
        spec = P("data") if pipe else P("data", "seq")
        if jax.process_count() == 1:
            return jax.device_put((tok, tgt), NamedSharding(mesh, spec))
        import numpy as _np

        from distributed_neural_network_tpu.parallel.distributed import (
            distribute_host_data,
        )

        return (
            distribute_host_data(_np.asarray(tok), mesh, spec),
            distribute_host_data(_np.asarray(tgt), mesh, spec),
        )

    mesh_desc = "x".join(
        f"{k}{v}" for k, v in mesh.shape.items() if v > 1
    ) or "single"

    # run-record identity: the config fingerprint hashes everything that
    # shapes the training computation; output paths/ports are excluded so
    # the same run in a different directory fingerprints identically
    _volatile = {
        "run_record", "metrics_port", "metrics_linger", "trace_out",
        "profile_dir", "metrics_jsonl", "checkpoint_dir",
        "compilation_cache_dir", "log_every",
    }
    G_LEDGER.describe(
        config={k: v for k, v in sorted(vars(args).items())
                if k not in _volatile},
        mesh={"axes": {k: int(v) for k, v in mesh.shape.items()},
              "devices": int(mesh.devices.size), "desc": mesh_desc,
              "optimizer": args.optimizer},
    )

    # live observability (utils/obs.py + train/monitor.py): the tracer,
    # preemption guard, and --metrics-port monitor exist BEFORE the
    # checkpointer/guard/step wiring so every layer can publish into the
    # same registry (docs/OBSERVABILITY.md "Live monitoring")
    from distributed_neural_network_tpu.train import guard as G
    from distributed_neural_network_tpu.train.monitor import (
        WatchdogConfig,
        attach_monitor,
    )
    from distributed_neural_network_tpu.utils import tracing as TRC

    tracer = TRC.Tracer(enabled=bool(args.trace_out))
    # fleet identity: under a supervised / multi-process group every rank
    # runs this same argv, so the tracer stamps rank{N} process metadata
    # and --trace-out becomes a per-rank shard (trace_rank{N}.json) that
    # tools/trace_merge.py reassembles into one aligned timeline
    rank = TRC.detect_rank()
    if rank is None and jax.process_count() > 1:
        rank = jax.process_index()
    if rank is not None:
        import socket as _socket

        tracer.set_process(rank=rank, hostname=_socket.gethostname())
        if args.trace_out:
            args.trace_out = TRC.rank_trace_path(args.trace_out, rank)
            print(f"(per-rank trace shard: {args.trace_out})")
    preempt = None
    if args.on_sigterm == "checkpoint":
        preempt = G.PreemptionGuard().install()
    profile_dir = args.profile_dir or (
        os.path.dirname(os.path.abspath(args.trace_out))
        if args.trace_out else None
    )
    monitor = attach_monitor(
        metrics_port=args.metrics_port,
        tracer=tracer,
        preemption=preempt,
        watchdog=args.watchdog == "on",
        config=WatchdogConfig(
            escalate_after_polls=(
                5 if args.watchdog_escalate == "preempt"
                and preempt is not None else 0
            ),
        ),
        profile_dir=profile_dir,
        rank=rank,
    )
    registry = monitor.registry
    m_loss_gauge = registry.gauge(
        "train_loss", "Training loss at the last logged step"
    )

    from distributed_neural_network_tpu.train.guard import (
        check_cursor,
        resume_cursor,
    )

    from distributed_neural_network_tpu.train import elastic as EL

    def current_mesh_meta():
        """Save-time topology of the CURRENT mesh (re-read after an
        in-process shrink: mesh/specs/accum are rebound locals)."""
        return EL.lm_mesh_meta(
            mesh, specs, args.optimizer,
            batch=args.batch_size, accum_steps=args.accum_steps,
            pp_interleave=args.pp_interleave,
        )

    def ckpt_meta(i: int, loss_val):
        """Checkpoint meta incl. the versioned exact-resume cursor: every
        batch/PRNG stream here is a pure function of (seed, step), so the
        cursor pins the continuation's data order bit-exactly. mesh_meta
        records the save-time topology so a restore into a different
        mesh/optimizer is detected and - with --elastic - resharded
        (parallel/reshard.py) instead of crashing inside pjit."""
        return {"mesh": mesh_desc, "optimizer": args.optimizer,
                "mom_format": MOM_FORMAT, "loss": loss_val,
                "pp_interleave": args.pp_interleave,
                "mesh_meta": current_mesh_meta(),
                **resume_cursor(step=i, seed=args.seed)}

    ck = None
    step0 = 0
    if args.checkpoint_dir:
        from distributed_neural_network_tpu.utils.checkpoint import (
            TreeCheckpointer,
        )

        ck = TreeCheckpointer(args.checkpoint_dir, registry=registry)
        if not args.resume and ck.latest_step() is not None:
            raise SystemExit(
                f"--checkpoint-dir {args.checkpoint_dir} already contains "
                f"checkpoints (latest step {ck.latest_step()}); pass "
                "--resume to continue that run or use a fresh directory "
                "(saves at existing step numbers would be silently skipped)"
            )
        if args.resume and args.elastic:
            restored = EL.elastic_restore(
                ck, cfg=cfg, mesh=mesh, specs=specs,
                optimizer=args.optimizer,
                param_shardings=param_shardings,
                mom_shardings=mom_shardings,
                current_meta=current_mesh_meta(),
                tracer=tracer, registry=registry,
            )
            if restored is None:
                print(
                    f"(WARNING: --resume found no checkpoint in "
                    f"{args.checkpoint_dir}; starting from scratch)"
                )
            else:
                state, meta, last, resharded = restored
                try:
                    check_cursor(meta, seed=args.seed)
                except ValueError as e:
                    raise SystemExit(str(e))
                params, mom = state["params"], state["mom"]
                step0 = last + 1
                if resharded and not pipe:
                    new_accum = EL.rescaled_accum_steps(
                        meta.get("mesh_meta") or {}, batch=args.batch_size,
                        new_dp=args.dp, accum_steps=args.accum_steps,
                    )
                    if new_accum != args.accum_steps:
                        print(
                            f"(elastic: accum-steps {args.accum_steps} -> "
                            f"{new_accum} keeps the global batch "
                            f"{args.batch_size} - and with it the data "
                            "cursor - exact across the dp change)"
                        )
                        args.accum_steps = new_accum
                        step = build_step()
                print(f"(Resumed from step {last}; continuing at {step0})")
        elif args.resume:
            restored = ck.restore_latest(
                {"params": params, "mom": mom},
                {"params": param_shardings, "mom": mom_shardings},
            )
            if restored is None:
                print(
                    f"(WARNING: --resume found no checkpoint in "
                    f"{args.checkpoint_dir}; starting from scratch)"
                )
            if restored is not None:
                state, meta, last = restored
                # mom_format guards against checkpoints from before the
                # ZeRO momentum layout change (flat buffer -> per-leaf
                # tree): the mesh/optimizer checks pass on those but
                # restore then dies on an opaque tree-structure mismatch,
                # so reject with a clear message instead. Only the 'zero'
                # layout ever changed - sgd checkpoints without the key
                # (written before the key existed) restore fine and are
                # accepted.
                checks = [("mesh", mesh_desc), ("optimizer", args.optimizer)]
                if args.optimizer.startswith("zero"):
                    checks.append(("mom_format", MOM_FORMAT))
                if pipe:
                    # interleave permutes the layer axis on device
                    # (interleave_layer_order), so a checkpoint written at
                    # a different v holds a different layer order. Old
                    # checkpoints without the key were written at v=1.
                    meta.setdefault("pp_interleave", 1)
                    checks.append(("pp_interleave", args.pp_interleave))
                for key_, want in checks:
                    if meta.get(key_) != want:
                        raise SystemExit(
                            f"checkpoint was written with {key_}="
                            f"{meta.get(key_)!r}, this run has {want!r} - "
                            "momentum/param shards don't map across layouts; "
                            "resume with the original flags, or pass "
                            "--elastic to reshard the checkpoint onto this "
                            "run's layout (parallel/reshard.py)"
                            + (
                                " (or restart training: this checkpoint "
                                "predates the current momentum layout)"
                                if key_ == "mom_format" else ""
                            )
                        )
                try:
                    check_cursor(meta, seed=args.seed)
                except ValueError as e:
                    raise SystemExit(str(e))
                params, mom = state["params"], state["mom"]
                step0 = last + 1
                print(f"(Resumed from step {last}; continuing at {step0})")

    zperm = None
    if not pipe and args.attn == "zigzag" and args.sp > 1:
        # zigzag layout: permute the sequence axis so each device's shard
        # holds one early + one late chunk; next-token loss is a mean over
        # positions, so a consistent permutation of (tokens, targets)
        # leaves it unchanged
        from distributed_neural_network_tpu.parallel.ring import zigzag_order

        zperm = zigzag_order(args.seq_len, args.sp)

    stream = None
    if args.data_path:
        from distributed_neural_network_tpu.data.tokens import (
            load_token_stream,
            sample_batch,
        )

        stream = load_token_stream(args.data_path, vocab_size=args.vocab)
        print(f"(token stream: {len(stream.tokens):,} tokens "
              f"[{stream.source}], {stream.n_eval:,} held out)")

        def batch_at(i, split="train"):
            tok, tgt = sample_batch(
                stream, batch=args.batch_size, seq_len=args.seq_len,
                step=i, seed=args.seed, split=split,
            )
            tok, tgt = jnp.asarray(tok), jnp.asarray(tgt)
            if zperm is not None:
                tok, tgt = tok[:, zperm], tgt[:, zperm]
            return place_batch(tok, tgt)

        tokens, targets = batch_at(0)
    else:
        tokens, targets = lmtrain.make_copy_task(
            jax.random.key(args.seed + 1),
            batch=args.batch_size, seq_len=args.seq_len, vocab=args.vocab,
        )
        if zperm is not None:
            tokens, targets = tokens[:, zperm], targets[:, zperm]
        tokens, targets = place_batch(tokens, targets)

    eval_fn = None
    if args.eval_every and pipe:
        # held-out eval through the same microbatch schedule, no grad
        # (r3 ADVICE: --eval-every used to be silently ignored under --pp)
        eval_fn = ppl.make_pp_eval_fn(
            cfg, mesh, n_microbatches=args.microbatches,
            loss_chunks=args.loss_chunks, interleave=args.pp_interleave,
        )
    elif args.eval_every:
        from jax.sharding import PartitionSpec as _P

        from distributed_neural_network_tpu import compat as _compat

        tp_ax = lmtrain.TP_AXIS if args.tp > 1 else None
        sp_ax = lmtrain.SEQ_AXIS if args.sp > 1 else None
        sync = tuple(a for a in (lmtrain.DATA_AXIS, lmtrain.SEQ_AXIS)
                     if a in mesh.axis_names)
        eval_fn = jax.jit(
            _compat.shard_map(
                lambda p, tok, tgt: lmtrain.lm_loss(
                    p, tok, tgt, cfg, seq_axis=sp_ax, tp_axis=tp_ax,
                    ep_axis=lmtrain._ep_axis(cfg, mesh),
                    attn_impl=args.attn, axes=sync,
                ),
                mesh=mesh,
                in_specs=(specs, _P(lmtrain.DATA_AXIS, lmtrain.SEQ_AXIS),
                          _P(lmtrain.DATA_AXIS, lmtrain.SEQ_AXIS)),
                out_specs=_P(),
                # the own flash kernels are vma-typed (r4); only the
                # library kernel (lib impl, single-device-gated) needs
                # the checker off
                check_vma=not (
                    args.attn == "flash"
                    and os.environ.get("DNN_TPU_FLASH_IMPL") == "lib"
                ),
            )
        )
    # --attn flash runs the Pallas kernel only where Mosaic compiles
    attn_route = route() if args.attn == "flash" else None
    print(
        f"(LM {tfm.param_count(params):,} params, mesh {mesh_desc}, "
        f"attn={args.attn if args.sp > 1 or args.attn == 'flash' else 'full'}"
        + (f" -> {attn_route}, " if attn_route else ", ")
        + (f"precision={args.precision}, " if args.precision != "bf16" else "")
        + f"experts={args.experts or 'dense'}, optimizer={args.optimizer})"
    )

    first_loss = first_step_s = param_devices = batch_devices = None
    t_compile = time.perf_counter()
    t0 = None
    from distributed_neural_network_tpu.utils import metrics as M

    run = M.init_run(jsonl_path=args.metrics_jsonl) if args.metrics_jsonl \
        else M.MetricsRun([])
    run["parameters"] = {
        "mesh": mesh_desc, "optimizer": args.optimizer, "lr": args.lr,
        "lr_schedule": args.lr_schedule, "batch_size": args.batch_size,
        "seq_len": args.seq_len, "d_model": args.d_model,
        "n_layers": args.n_layers, "dtype": args.dtype,
    }
    # step-level telemetry (utils/tracing.py; docs/OBSERVABILITY.md).
    # The traced wrapper fences each step (hard_block on the loss), so the
    # tokens/s this run reports includes one device->host fetch per step -
    # opt-in observability, not the measurement path (train/measure.py).
    # The tracer itself was created up front with the monitor.
    stats = None
    mosaic_calls = None  # counted from the compiled step under --step-stats
    if args.trace_out or args.step_stats:
        from distributed_neural_network_tpu.train.measure import (
            peak_flops as _peakf,
        )

        step_extra = (
            (jnp.int32(step0),)
            if args.lr_schedule != "constant" or fault_plan is not None
            else ()
        )
        compiled = TRC.compile_step(
            step, params, mom, tokens, targets, *step_extra
        )
        hw_flops = TRC.flops_from_compiled(compiled)
        if compiled is not None:
            mosaic_calls = TRC.mosaic_custom_calls(compiled)
            print(f"(compiled step: {mosaic_calls} Mosaic custom call(s))")
        # shardlint static cross-check: the analyzer's logical collective
        # payload for THIS compiled step, reported next to the runtime
        # ring estimate below (tools/trace_summary.py --lint compares a
        # recorded trace against the checked-in manifests the same way)
        static_comm = None
        try:
            from distributed_neural_network_tpu.analysis.trace import (
                collect_trace,
            )

            static_comm = collect_trace(
                jax.make_jaxpr(step)(params, mom, tokens, targets,
                                     *step_extra)
            ).total_collective_bytes()
        except Exception:
            pass
        # gradient sync rides the data (and seq) axes; tensor-sharded
        # leaves keep local grads - this over-counts those, an estimate
        n_sync = mesh.shape.get("data", 1) * mesh.shape.get("seq", 1)
        overlap = args.grad_sync == "overlap" and args.accum_steps > 1
        bucket_bytes_list = None
        if overlap:
            # the same deterministic plan the compiled step uses (leaf
            # buckets grouped by PartitionSpec) - per-bucket bytes go to
            # the StepStats summary and, below, in-band into the trace
            from distributed_neural_network_tpu.parallel.collectives import (
                plan_buckets,
            )

            layout = plan_buckets(
                params, bucket_bytes=int(args.bucket_mb * 2**20),
                group_keys=[
                    str(s) for s in jax.tree.leaves(
                        specs, is_leaf=lambda s: isinstance(s, P)
                    )
                ],
            )
            bucket_bytes_list = [int(b) for b in layout.bucket_bytes()]
            comm_bytes = TRC.overlapped_collective_bytes(
                bucket_bytes_list, n_sync, args.accum_steps
            )
        else:
            comm_bytes = TRC.collective_bytes_per_sync(params, n_sync)
        stats = TRC.StepStats(
            item_label="tokens",
            sink=run if args.step_stats else None,
            registry=registry,
            n_devices=mesh.devices.size,
            comm_bytes_per_step=comm_bytes,
            static_comm_bytes_per_step=static_comm,
            grad_sync=args.grad_sync,
            comm_bucket_bytes=bucket_bytes_list,
            compilation_cache_dir=args.compilation_cache_dir,
            flops_per_step=(
                hw_flops if hw_flops is not None
                else flops_tok * args.batch_size * args.seq_len
            ),
            flops_source="cost_analysis" if hw_flops is not None else "analytic",
            peak_flops_per_device=_peakf(
                jax.devices()[0].device_kind, args.dtype
            ),
        )
        if overlap and tracer.enabled:
            TRC.record_bucket_plan(
                tracer, bucket_bytes_list, schedule="overlap",
                op=("reduce_scatter" if args.optimizer.startswith("zero")
                    else "psum"),
                axis_size=n_sync, accum_steps=args.accum_steps,
            )

    # telemetered = the traced wrapper (and with it the goodput ledger's
    # per-step feed) is active; the bare fast path attributes coarsely at
    # run end instead (fencing every step just to time it would change
    # the run being accounted)
    telemetered = (
        stats is not None or monitor.server is not None
        or monitor.heartbeat is not None
    )

    def wrap_step(fn, first_step):
        """Span tracing + StepStats + live registry publishing around a
        compiled step (identity when all telemetry is off); re-applied
        after a guard LR-backoff rebuild. The recompile detector is
        re-baselined on the (new) fn so deliberate rebuilds never count
        as cache misses."""
        if monitor.recompiles is not None:
            monitor.recompiles.swap(fn)
        if not telemetered:
            return fn
        return lmtrain.make_traced_step(
            fn, tracer=tracer, step_stats=stats,
            items_per_step=args.batch_size * args.seq_len,
            fence=True, first_step=first_step,
            registry=registry, recompiles=monitor.recompiles,
        )

    step = wrap_step(step, step0)

    # self-healing layer (train/guard.py; docs/ROBUSTNESS.md)
    monkey = None
    stall_at = tuple(args.chaos_stall_step or ())
    if stall_at and args.chaos_stall_rank is not None \
            and (rank if rank is not None else 0) != args.chaos_stall_rank:
        stall_at = ()  # this rank is not the designated straggler
    if (args.chaos_spike_step or stall_at
            or args.chaos_sigterm_after is not None
            or args.chaos_shrink_at_step is not None):
        from distributed_neural_network_tpu.parallel.fault import ChaosMonkey

        monkey = ChaosMonkey(
            spike_at=tuple(args.chaos_spike_step or ()),
            sigterm_after=args.chaos_sigterm_after,
            stall_at=stall_at,
            stall_s=args.chaos_stall_seconds,
            shrink_at=args.chaos_shrink_at_step,
            preempt=preempt,
            tracer=tracer,
        )
    # training-dynamics observatory (train/dynamics.py): the sink decodes
    # the step's extra telemetry bundle one step behind (same cadence as
    # the guard's HealthPipe) and doubles as the guard's non-finite
    # provenance source, so it is built BEFORE the guard
    dsink = None
    if args.dynamics:
        from distributed_neural_network_tpu.parallel.rules import (
            named_leaves,
        )
        from distributed_neural_network_tpu.train.dynamics import (
            DynamicsSink,
        )

        want_gns = args.grad_sync == "end" and args.accum_steps >= 2
        dsink = DynamicsSink(
            [p_ for p_, _ in named_leaves(params)],
            jsonl_path=args.dynamics_jsonl,
            registry=registry, tracer=tracer,
            # GNS batch sizes in tokens: per-microbatch vs accumulated
            b_small=(args.batch_size * args.seq_len / args.accum_steps
                     if want_gns else None),
            b_big=(args.batch_size * args.seq_len if want_gns else None),
        )
    guard = hpipe = None
    if guard_on:
        guard = G.TrainingGuard(
            G.GuardConfig(
                policy=args.guard,
                spike_zscore=args.guard_spike_zscore,
                snapshot_every=args.snapshot_every,
                max_retries=args.max_retries,
            ),
            tracer=tracer, step_stats=stats, registry=registry,
            provenance=dsink.bad_layer if dsink is not None else None,
        )
        hpipe = G.HealthPipe(
            guard, perturb=monkey.perturb if monkey is not None else None
        )

    ema = ema_fn = None
    if args.ema_decay:
        from distributed_neural_network_tpu.ops.schedule import (
            make_ema_update,
        )

        ema_fn = make_ema_update(args.ema_decay)
        ema = jax.tree.map(jnp.array, params)
    scheduled = args.lr_schedule != "constant"
    takes_step = scheduled or fault_plan is not None
    last_eval = None
    eval_s = 0.0
    preempted = False
    timed_steps = 0
    end_step = (
        args.stop_at_step if args.stop_at_step is not None
        else step0 + args.steps
    )
    if end_step <= step0:
        # a supervised relaunch after the target step was already reached
        # (e.g. the group shrank on the very last checkpoint): nothing to
        # train, exit cleanly so the supervisor records completion
        print(f"(stop-at-step {end_step} already reached - resumed at "
              f"step {step0}; nothing to do)")
        if preempt is not None:
            preempt.uninstall()
        if ck is not None:
            ck.close()
        run.stop()
        G_LEDGER.finalize(metrics={"last_step": step0 - 1,
                                   "nothing_to_do": True})
        monitor.close()
        return 0
    i = last_step = step0

    def handle_verdict(v) -> bool:
        """Apply a guard verdict; True = rolled back (the loop restarts at
        the snapshot step with the rebuilt backed-off step fn)."""
        nonlocal params, mom, step, i
        if v is None or v.action in ("ok", "warn", "skip"):
            return False
        # at_step sizes the ledger's rollback_recompute window (the
        # replayed steps are lost progress being re-earned, not goodput);
        # raises GuardAbort when the retry budget is exhausted
        rb = guard.rollback(at_step=i)
        if rb is None and ck is not None:
            # no in-memory snapshot yet: fall back to the newest on-disk
            # checkpoint (same exact-resume contract)
            restored = ck.restore_latest(
                {"params": params, "mom": mom},
                {"params": param_shardings, "mom": mom_shardings},
            )
            if restored is not None:
                state, _meta, last = restored
                rb = (last + 1, state)
                if i > last + 1:
                    G_LEDGER.mark_recompute(i - (last + 1))
                print(f"(guard: no snapshot yet; restored the on-disk "
                      f"checkpoint at step {last})")
        if rb is None:
            raise G.GuardAbort(
                "guard rollback requested before any snapshot or on-disk "
                "checkpoint exists - lower the LR, enable --checkpoint-dir,"
                " or start with --guard warn to observe first"
            )
        snap_step, state = rb
        params = jax.device_put(state["params"], param_shardings)
        mom = jax.device_put(state["mom"], mom_shardings)
        step = wrap_step(build_step(guard.lr_scale), snap_step)
        print(f"(guard: resuming from step {snap_step} at "
              f"lr_scale={guard.lr_scale:g} [one recompile])")
        hpipe.clear()
        if dsink is not None:
            dsink.clear()  # the stashed step's update never retired
        i = snap_step
        return True

    def do_elastic_shrink(new_dp: int, at_step: int) -> None:
        """Answer a SHRINK preemption in process: the emergency checkpoint
        is already on disk; rebuild the mesh from the surviving device
        prefix, reshard the checkpoint onto it (the same elastic_restore
        path a fresh process would take - ZeRO shards re-pad for the new
        dp), re-slice gradient accumulation so the global batch and data
        cursor stay exact, and rebuild+rewrap the compiled step."""
        nonlocal mesh, specs, param_shardings, mom_shardings, mesh_desc
        nonlocal params, mom, step, ema, tokens, targets
        from distributed_neural_network_tpu.parallel.reshard import (
            place_tree,
            rescale_accum,
        )

        old_dp = mesh.shape.get("data", 1)
        mesh = lmtrain.create_lm_mesh(new_dp, args.sp, args.tp)
        specs, param_shardings, mom_shardings = lmtrain.make_lm_shardings(
            cfg, mesh, args.optimizer, rules=shard_rules
        )
        args.accum_steps = rescale_accum(
            args.batch_size, old_dp, new_dp, args.accum_steps
        )
        args.dp = new_dp
        mesh_desc = "x".join(
            f"{k}{v}" for k, v in mesh.shape.items() if v > 1
        ) or "single"
        restored = EL.elastic_restore(
            ck, cfg=cfg, mesh=mesh, specs=specs, optimizer=args.optimizer,
            param_shardings=param_shardings, mom_shardings=mom_shardings,
            current_meta=current_mesh_meta(), tracer=tracer,
            registry=registry,
        )
        state, _meta, _last, _resharded = restored
        params, mom = state["params"], state["mom"]
        tokens, targets = place_batch(tokens, targets)
        step = wrap_step(
            build_step(guard.lr_scale if guard is not None else 1.0),
            at_step + 1,
        )
        if ema is not None:
            ema = place_tree(ema, param_shardings)
        if guard is not None:
            # rolling snapshots hold the pre-shrink layout; a later
            # rollback must not restore them - the next cadence retakes
            guard.drop_snapshot()
        if hpipe is not None:
            hpipe.clear()
        if dsink is not None:
            dsink.clear()
            # the shrink re-sliced accumulation: the GNS per-microbatch
            # token count follows (the rebuilt step stops emitting
            # msq_small entirely if accum collapsed to 1)
            if dsink.b_small is not None and args.accum_steps >= 2:
                dsink.b_small = (
                    args.batch_size * args.seq_len / args.accum_steps
                )
        print(
            f"(elastic: continuing at step {at_step + 1} on mesh "
            f"{mesh_desc}, accum_steps={args.accum_steps})"
        )

    # the dynamics bundle rides after the health bundle when the guard is
    # on (train/lm.py make_lm_train_step); a model's routing counts, where
    # its step hands any out, come after every other output
    dyn_idx = 4 if guard_on else 3
    routing_pub = (None if cfg.module.AUX_IS_LOSS
                   else lmtrain.RoutingCounters(registry))
    while i < end_step:
        if guard is not None and (i - step0) % args.snapshot_every == 0:
            # settle the in-flight observation BEFORE snapshotting, so the
            # rolling snapshot only ever captures guard-verified state
            # (dynamics first: the guard's provenance lookup for the
            # settled step reads the sink's decoded row)
            if dsink is not None:
                dsink.flush()
            if handle_verdict(hpipe.flush()):
                continue
            guard.maybe_snapshot(
                i, {"params": params, "mom": mom}, first_step=step0
            )
        if stream is not None:
            # refresh at EVERY step (including step0): on resume the
            # pre-loop batch is batch_at(0), not batch_at(step0), and a
            # continuous run must see the same stream as a fresh one.
            # Host-side sampling blocks the dispatch - data_wait badput
            with G_LEDGER.interval("data_wait"):
                tokens, targets = batch_at(i)
        if takes_step:
            out = step(params, mom, tokens, targets, jnp.int32(i))
        else:
            out = step(params, mom, tokens, targets)
        params, mom, loss = out[0], out[1], out[2]
        if routing_pub is not None:
            routing_pub.push(out[-1])
        if dsink is not None:
            # BEFORE the health pipe: both are one-step lagged, so when
            # the guard judges step i-1 below, the sink must already have
            # decoded i-1's bundle for the bad_layer provenance lookup
            dsink.push(i, out[dyn_idx])
        if hpipe is not None and handle_verdict(hpipe.push(i, out[3])):
            continue
        if ema_fn is not None:
            ema = ema_fn(ema, params)
        if eval_fn is not None and (i + 1) % args.eval_every == 0:
            import numpy as _np

            t_ev = time.perf_counter()
            eval_params = ema if ema is not None else params
            ev = float(_np.mean([
                float(eval_fn(eval_params, *batch_at(j, "eval")))
                for j in range(args.eval_batches)
            ]))
            # excluded from the throughput window: only training tokens
            # are counted, so eval wall time must not deflate tokens/s.
            # Evals during the warmup/compile step (t0 unset) are outside
            # the window entirely - counting them would inflate tokens/s
            if t0 is not None:
                eval_s += time.perf_counter() - t_ev
            last_eval = {"step": i, "eval_loss": round(ev, 4),
                         "ppl": round(float(_np.exp(min(ev, 30.0))), 2)}
            print(f"step {i:>5}  eval_loss {ev:.4f}  "
                  f"ppl {last_eval['ppl']:.2f}")
            run.append(M.VAL_LOSS, ev)
        if i == step0 and first_loss is None:
            jax.block_until_ready(loss)
            first_loss = float(loss)
            first_step_s = time.perf_counter() - t_compile
            print(f"(first step incl. compile: {first_step_s:.1f}s)")
            # where the state really lives: the fewest distinct devices
            # any parameter leaf (and the batch) has shards on
            param_devices = min(
                len({sh.device for sh in leaf.addressable_shards})
                for leaf in jax.tree.leaves(params)
            )
            batch_devices = len(
                {sh.device for sh in tokens.addressable_shards}
            )
            print(f"(placement: params on {param_devices} device(s), "
                  f"batch on {batch_devices} device(s))")
            t0 = time.perf_counter()
        elif t0 is not None:
            timed_steps += 1
        if (i - step0) % args.log_every == 0 or i == end_step - 1:
            print(f"step {i:>5}  loss {float(loss):.4f}")
            run.append(M.TRAIN_LOSS, float(loss))
            m_loss_gauge.set(float(loss))
        if ck is not None and (i + 1) % args.checkpoint_every == 0:
            ck.save(i, {"params": params, "mom": mom},
                    ckpt_meta(i, float(loss)))
        last_step = i
        if monkey is not None:
            monkey.after_step(i)
        if preempt is not None and preempt.requested:
            if ck is not None:
                ck.save(i, {"params": params, "mom": mom},
                        ckpt_meta(i, float(loss)))
            if (preempt.signame == "SHRINK" and ck is not None
                    and args.chaos_shrink_to is not None):
                # elastic path: the emergency checkpoint above is the
                # hand-off; reshard it onto the shrunken mesh and keep
                # training instead of dying with the lost devices
                print(f"(emergency checkpoint at step {i}; SHRINK "
                      "preemption -> resharding onto the surviving "
                      "devices)")
                do_elastic_shrink(args.chaos_shrink_to, i)
                preempt.requested = False
                preempt.signame = None
                i += 1
                continue
            preempted = True
            if ck is not None:
                print(f"(emergency checkpoint at step {i}; resume with "
                      "--resume to continue bit-exactly)")
            else:
                print(f"({preempt.signame}: stopping after step {i}; no "
                      "--checkpoint-dir, progress is lost)")
            break
        i += 1
    jax.block_until_ready(loss)
    if not telemetered and t0 is not None:
        # coarse goodput attribution for the bare fast path: the first
        # dispatch (incl. XLA compile) and the post-compile window, as a
        # low-priority FILL so checkpoint saves recorded inside it keep
        # their own bucket (utils/goodput.py fill_ending_now)
        now_l, pc = G_LEDGER.now(), time.perf_counter()
        G_LEDGER.add("compile", now_l - (pc - t_compile),
                     now_l - (pc - t0))
        G_LEDGER.fill_ending_now(
            "steady_step", max(pc - t0 - eval_s, 0.0)
        )
        G_LEDGER.note_steps(
            timed_steps,
            tokens=float(args.batch_size * args.seq_len * timed_steps),
        )
    if preempt is not None:
        preempt.uninstall()
    if routing_pub is not None:
        routing_pub.flush()
    if dsink is not None:
        # settle before the health pipe's final flush (provenance for the
        # last judged step), then close the JSONL stream
        dsink.flush()
    if hpipe is not None:
        # settle the last step's observation (counters/trace completeness;
        # a final-step rollback has nothing left to re-run, and the abort
        # policy still raises from here)
        hpipe.flush()
    if dsink is not None:
        dsink.close()
    if ck is not None:
        if not preempted:
            ck.save(last_step, {"params": params, "mom": mom},
                    ckpt_meta(last_step, float(loss)))
        ck.close()
    from distributed_neural_network_tpu.train.measure import peak_flops

    # timed_steps counts post-compile steps actually executed (guard
    # replays included, preempted tails excluded), so tokens/s stays
    # honest under rollbacks and early exits
    dt = time.perf_counter() - t0 - eval_s if timed_steps else 0.0
    tok_s = args.batch_size * args.seq_len * timed_steps / dt if dt else 0.0
    model_flops_s = flops_tok * tok_s
    n_dev = mesh.devices.size
    peak = peak_flops(jax.devices()[0].device_kind, args.dtype)
    mfu = (model_flops_s / (peak * n_dev) * 100.0
           if peak and flops_tok else None)
    if mfu is not None:
        peak_label = (
            "bf16" if args.dtype == "bfloat16" else "f32 (0.5x bf16 MXU)"
        )
        print(
            f"MFU {mfu:.1f}% = {model_flops_s / 1e12:.1f} model TFLOP/s / "
            f"({peak / 1e12:.0f} peak {peak_label} TFLOP/s x {n_dev} dev); "
            f"FLOPs/token = 3*(L*(8d^2 + 4sd + 4d*ff) + 2d*V) "
            f"= {flops_tok / 1e6:.1f}M"
        )
    if args.generate > 0:
        if not hasattr(cfg.module, "generate"):
            print("(--generate skipped: decoding a --model-config model "
                  "needs its recurrent state beside the KV cache, which "
                  "generate() does not carry)")
        elif pipe:
            print("(--generate skipped: decode needs the non-pipeline "
                  "param layout; rerun without --pp)")
        else:
            import numpy as np

            # decode on replicated single-device params (gather the tree);
            # EMA weights when tracked - the eval-side parameters
            host_params = jax.tree.map(
                lambda x: jax.device_put(np.asarray(x), jax.devices()[0]),
                ema if ema is not None else params,
            )
            # fresh unpermuted prompts (zigzag feeds permuted tokens)
            ptoks, _ = lmtrain.make_copy_task(
                jax.random.key(args.seed + 1),
                batch=args.batch_size, seq_len=args.seq_len, vocab=args.vocab,
            )
            half = args.seq_len // 2
            prompt = ptoks[:2, : half + 1]
            out = tfm.generate(
                host_params, prompt, cfg, max_new_tokens=args.generate,
                temperature=args.gen_temperature, top_k=args.gen_top_k,
                top_p=args.gen_top_p,
                key=(jax.random.key(args.seed + 2)
                     if args.gen_temperature > 0 else None),
            )
            for i, row in enumerate(np.asarray(out)):
                cut = half + 1
                print(f"gen[{i}] prompt={row[:cut].tolist()} "
                      f"completion={row[cut:].tolist()}")

    # goodput accounting close-out: finalize ASSERTS conservation (the
    # taxonomy buckets + goodput partition total wall-clock), writes the
    # run record through when armed, and updates the registry export
    goodput_rec = G_LEDGER.finalize(metrics={
        "final_loss": float(loss), "first_loss": first_loss,
        "last_step": last_step, "preempted": preempted,
        "tokens_per_s": round(tok_s),
        "mfu_pct": round(mfu, 2) if mfu is not None else None,
    })

    if stats is not None:
        stats.capture_memory(tracer)
        if args.step_stats:
            print(stats.report())
    if args.trace_out:
        tracer.export(args.trace_out, step_stats=stats,
                      goodput=goodput_rec)
        print(f"(Chrome trace written to {args.trace_out}; open in "
              "Perfetto / chrome://tracing, or summarize with "
              "tools/trace_summary.py)")
    run.stop()
    # pipeline bubble: (P-1)/(v*M+P-1) of tick-time processes garbage;
    # raise --microbatches or --pp-interleave to shrink it (the head is
    # not paid per tick)
    bubble = (
        round(
            (args.pp - 1)
            / (args.pp_interleave * args.microbatches + args.pp - 1),
            4,
        )
        if pipe else None
    )
    if guard is not None:
        print("(guard summary: " + json.dumps(guard.summary()) + ")")
    print("GOODPUT " + json.dumps({
        "goodput_ratio": goodput_rec["goodput_ratio"],
        "wall_s": goodput_rec["wall_s"],
        "goodput_s": goodput_rec["goodput_s"],
        "badput_s": {k: v for k, v in goodput_rec["badput_s"].items()
                     if v > 0},
        "steps": goodput_rec["steps"],
        "record": G_LEDGER.path,
    }))
    print("SUMMARY " + json.dumps({
        "mesh": mesh_desc, "steps": args.steps, "start_step": step0,
        "last_step": last_step, "preempted": preempted,
        "guard": args.guard,
        "guard_summary": guard.summary() if guard is not None else None,
        "dtype": args.dtype, "pp_bubble_frac": bubble,
        "grad_sync": args.grad_sync, "accum_steps": args.accum_steps,
        "dynamics": (
            {"rows": dsink.rows_written, "jsonl": args.dynamics_jsonl}
            if dsink is not None else None
        ),
        "data_source": stream.source if stream is not None else "copy-task",
        "eval": last_eval,
        "first_loss": first_loss, "final_loss": float(loss),
        "attn_route": attn_route,
        "mosaic_custom_calls": mosaic_calls,
        "first_step_s": (
            round(first_step_s, 3) if first_step_s is not None else None
        ),
        "param_devices": param_devices, "batch_devices": batch_devices,
        "tokens_per_s": round(tok_s), "wall_s_post_compile": round(dt, 3),
        "model_tflops_per_s": round(model_flops_s / 1e12, 2),
        "mfu_pct": round(mfu, 2) if mfu is not None else None,
    }))
    from distributed_neural_network_tpu.utils.obs import flight_event

    flight_event("run_end", step=last_step, preempted=preempted)
    if monitor.server is not None and args.metrics_linger > 0:
        print(f"(metrics server lingering {args.metrics_linger:g}s for "
              "final scrapes)")
        time.sleep(args.metrics_linger)
    monitor.close()
    if preempted and os.environ.get("DNN_TPU_SUPERVISOR"):
        # tell the supervisor (train/supervisor.py) this is a clean
        # PREEMPTION, not workload completion: the emergency checkpoint
        # is on disk and the group should restart from it. os._exit skips
        # the jax distributed-runtime shutdown barrier - on a preemption
        # the OTHER ranks are usually still mid-step, and waiting for
        # them would hold the exit (and the supervisor's restart) for the
        # barrier's multi-minute timeout.
        from distributed_neural_network_tpu.train.supervisor import (
            PREEMPT_RC,
        )

        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(PREEMPT_RC)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SystemExit:
        raise
    except Exception as e:
        from distributed_neural_network_tpu.train.guard import GuardAbort

        if isinstance(e, GuardAbort):
            # actionable one-liner instead of a traceback: the message
            # already says what happened and what to do next
            raise SystemExit(f"GUARD ABORT: {e}")
        raise
