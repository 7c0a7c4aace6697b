#!/usr/bin/env python
"""Generate REPORT.md: this framework's numbers against the reference's.

Reproduces the reference report's two experiment tables (Project_Report.pdf
Tables 1-2, mirrored in BASELINE.md / SURVEY.md section 6) on this
machine's devices and writes a markdown report with side-by-side
comparison:

- Table 1: device-count sweep (reference: 3-8 MPI procs, 25 epochs, bs 16)
- Table 2: batch-size sweep (reference: 4 procs, bs 1-64, 25 epochs)

Usage:
  python report.py                    # full sweeps, real data if present
  python report.py --quick            # 2-epoch smoke sweeps on synthetic
  python report.py --epochs 25 --data auto --out REPORT.md

The reference numbers are CPU wall-clock on an 8-core i7-9800X; `speedup`
is reference_train_s / ours on whatever devices are visible here. Accuracy
is only comparable when real CIFAR-10 is on disk (`data_source` is
recorded; synthetic accuracy is near-100% and NOT comparable).
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import re
import sys

# SURVEY.md section 6 (report Tables 1-2 + measured child train logs)
REF_PROC = {  # procs -> (acc %, train_s)
    3: (64.4, 375.0), 4: (63.05, 794.0), 5: (60.93, 1127.0),
    6: (59.41, 1386.0), 7: (57.95, 1528.0), 8: (55.28, 1642.0),
}
# Train-time source of truth is bench.py's REFERENCE_BS_SWEEP_S (the
# measured child logs, e.g. bs16_log_epochs25_proc4_children.txt:2 =
# 701.8 s), NOT the reference report's published Table 2 (761 s at bs16)
# - the two differ because the published table includes overhead outside
# the child train metric; both artifacts must quote the SAME denominator
# or REPORT.md and BENCH_MATRIX.json contradict each other for one
# measurement. Accuracy has no child-log counterpart, so it stays from
# the published table.
from bench import REFERENCE_BS_SWEEP_S as _REF_BS_S

# artifact root: BENCH_MATRIX.json and tools/ tune files live beside
# this script; module-level so tests can point it at a synthetic tree
REPO = os.path.dirname(os.path.abspath(__file__))

_REF_BS_ACC = {1: 56.54, 2: 61.3, 4: 63.48, 8: 65.19, 16: 63.59,
               32: 57.68, 64: 50.86}
REF_BS = {bs: (_REF_BS_ACC[bs], _REF_BS_S[bs]) for bs in _REF_BS_ACC}


def run_one(nb_proc, batch_size, epochs, data, synthetic_size):
    from distributed_neural_network_tpu.train.measure import measure_dp_training

    return measure_dp_training(
        nb_proc=nb_proc, batch_size=batch_size, epochs=epochs,
        data=data, synthetic_size=synthetic_size,
    )


def fmt_row(cells):
    return "| " + " | ".join(str(c) for c in cells) + " |"


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--epochs", type=int, default=25)
    p.add_argument("--data", default="auto")
    p.add_argument("--synthetic-size", type=int, default=None)
    p.add_argument("--quick", action="store_true",
                   help="2 epochs, 2000 synthetic rows, reduced sweep points")
    p.add_argument("--from-matrix", action="store_true",
                   help="render the CNN tables from BENCH_MATRIX.json's "
                   "25-epoch cnn rows instead of re-measuring (one bench "
                   "run feeds both artifacts; saves ~10 min of chip time)")
    p.add_argument("--out", default="REPORT.md")
    args = p.parse_args()

    epochs = 2 if args.quick else args.epochs
    syn = 2000 if args.quick else args.synthetic_size
    data = "synthetic" if args.quick else args.data

    if args.from_matrix:
        # NEVER touch the jax backend on this path: a chip belongs to one
        # process, and rendering a report must not be the one that takes
        # it. Device identity comes from the measured rows themselves.
        proc_rows, bs_rows, pending_bs = _rows_from_matrix(epochs)
        any_row = (proc_rows or bs_rows or [None])[0]
        if any_row is None:
            # still render: the LM/bubble/scaling sections and the
            # accuracy-parity wording carry their own evidence, and the
            # CNN tables show honest pending cells rather than the whole
            # report going missing when the chip was unavailable
            print("note: no measured 25-epoch cnn rows in "
                  "BENCH_MATRIX.json; CNN tables render as pending",
                  file=sys.stderr)
            ndev, bs_devices = 1, 1
            device_desc = ("device pending (no measured cnn rows in "
                           "BENCH_MATRIX.json)")
        else:
            # device identity / data source come from whichever sweep has
            # measured rows (the headline bs16 row may be the missing one)
            ndev = any_row.get("devices", 1)
            bs_devices = bs_rows[0]["devices"] if bs_rows else min(4, ndev)
            device_desc = (
                f"{ndev}x "
                f"{any_row.get('device_kind', 'unknown device')} "
                f"({any_row.get('platform', '?')}, from matrix rows)"
            )
    else:
        import jax

        ndev = jax.device_count()
        dev0 = jax.devices()[0]
        device_desc = f"{ndev}x {dev0.device_kind} ({dev0.platform})"
        procs = sorted({d for d in REF_PROC if d <= ndev} | {min(ndev, 8)})
        bss = [4, 16, 64] if args.quick else list(REF_BS)

        proc_rows, bs_rows, pending_bs = [], [], []
        for n in procs:
            r = run_one(n, 16, epochs, data, syn)
            r["ref"] = REF_PROC.get(n)
            proc_rows.append(r)
            print(json.dumps(r), file=sys.stderr)
        bs_devices = min(4, ndev)
        for bs in bss:
            r = run_one(bs_devices, bs, epochs, data, syn)
            r["ref"] = REF_BS.get(bs)
            bs_rows.append(r)
            print(json.dumps(r), file=sys.stderr)

    src_row = (proc_rows or bs_rows or [{}])[0]
    src = src_row.get("source", "synthetic")
    lines = [
        "# REPORT - measured results vs the reference",
        "",
        f"Generated {datetime.datetime.now():%Y-%m-%d %H:%M} by `report.py` "
        f"on {device_desc}; "
        f"data source: **{src}**; {epochs} epochs per run.",
        "",
        "Reference numbers: Project_Report.pdf Tables 1-2 (8-core i7-9800X,"
        " 25 epochs; SURVEY.md section 6). `speedup` = reference train time /"
        " ours. Accuracy columns are only comparable on real CIFAR-10"
        " (synthetic accuracy is near-100% by construction)."
        if src != "synthetic" else
        "**Synthetic data run** - wall-clock comparable (identical shapes"
        " and FLOPs), accuracy NOT comparable to the reference.",
        "",
        "## Table 1 - device-count sweep (bs=16)",
        "",
    ]
    base = max(proc_rows, key=lambda r: r["devices"], default=None)
    if base and base["train_s"] > 0:
        ref8 = REF_PROC[8]
        lines += [
            f"Headline: {epochs} epochs at bs=16 on {base['devices']} "
            f"device(s) = **{base['train_s']:.2f} s** vs the reference's "
            f"8-process run ({ref8[1]:.0f} s at 25 ep) -> "
            f"**{ref8[1] * epochs / 25.0 / base['train_s']:.0f}x** "
            "(epoch-prorated).",
            "",
        ]
    lines += [
        fmt_row(["devices", "val acc %", "train s",
                 "ref acc % (N procs)", "ref train s", "speedup"]),
        fmt_row(["---"] * 6),
    ]
    def ref_cells(r):
        """Reference acc/time cells + epoch-prorated speedup (ref is 25 ep)."""
        ref = r["ref"]
        if not ref or r["train_s"] <= 0:
            return ["-", "-", "-"]
        prorated = ref[1] * epochs / 25.0
        return [f"{ref[0]:.2f}", f"{ref[1]:.0f}",
                f"{prorated / r['train_s']:.0f}x"]

    for r in proc_rows:
        lines.append(fmt_row([
            r["devices"], f"{r['val_acc']:.2f}", f"{r['train_s']:.2f}",
            *ref_cells(r),
        ]))
    if not proc_rows:
        lines.append(fmt_row(
            ["*pending measurement (chip unavailable)*"] + ["-"] * 5
        ))
    lines += [
        "",
        f"## Table 2 - batch-size sweep ({bs_devices} device"
        f"{'s' if bs_devices != 1 else ''}; reference used 4 MPI procs)",
        "",
        fmt_row(["batch size", "val acc %", "train s",
                 "ref acc %", "ref train s", "speedup"]),
        fmt_row(["---"] * 6),
    ]
    # measured and pending rows merged in bs order so the sweep column
    # stays monotonic whichever subset measured
    merged = sorted(
        [("row", r["batch_size"], r) for r in bs_rows]
        + [("pending", bs, None) for bs in pending_bs],
        key=lambda t: t[1],
    )
    field_notes = []
    for kind, bs, r in merged:
        if kind == "row":
            note = r.get("field_note")
            if note:
                field_notes.append(f"bs {bs}: {note}")
            lines.append(fmt_row([
                f"{bs}*" if note else bs,
                f"{r['val_acc']:.2f}", f"{r['train_s']:.2f}",
                *ref_cells(r),
            ]))
        else:
            # unmeasured stub row: show the reference cells so the
            # sweep's full bs range stays visible, value cells pending
            ref = REF_BS.get(bs)
            lines.append(fmt_row([
                bs, "*pending*", "*pending (not yet measured)*",
                f"{ref[0]:.2f}" if ref else "-",
                f"{ref[1]:.0f}" if ref else "-", "-",
            ]))
    if not bs_rows and not pending_bs:
        lines.append(fmt_row(
            ["*pending measurement (chip unavailable)*"] + ["-"] * 5
        ))
    for n in field_notes:  # provenance of any id<->field repair, visible
        lines.append(f"\n\\* {n}")
    lines += [
        "",
        "Notes: the reference's N procs = 1 idle parent + N-1 workers over "
        "1/(N-1) data shards; here all N devices train on 1/N shards "
        "(SURVEY.md section 7, topology remap). Train time here is the "
        "fused multi-epoch span (training + parameter sync; eval outside), "
        "matching the reference's child train-time metric.",
        "",
        (
            "Accuracy parity: this run used real CIFAR-10 "
            f"(data source: {src}), so the accuracy columns above compare "
            "directly against the reference's 63-66% band "
            "(Project_Report.pdf Tables 1-2). Semantic fidelity is "
            "additionally proven by `tests/test_oracle.py`: the engine's "
            "faithful path matches an independent pure-numpy "
            "implementation of the reference algorithm "
            "(`tests/oracle_numpy.py`) step-for-step."
            if src != "synthetic"
            else
            "Accuracy parity: no real CIFAR-10 exists in this "
            "environment, so the accuracy claim is worded as "
            "*algorithm-identical; band pending real data*, verified "
            "three ways. (1) Semantic fidelity: `tests/test_oracle.py` "
            "proves the engine's faithful path computes the reference's "
            "exact algorithm (contiguous shards, per-epoch momentum-reset "
            "SGD, epoch-edge parameter averaging) step-for-step against "
            "an independent pure-numpy implementation "
            "(`tests/oracle_numpy.py`) - params and global train loss "
            "match epoch-by-epoch, and the test fails if any semantic "
            "knob (e.g. momentum reset) is changed. "
            f"(2) Reference-scale trajectory: {_oracle_fullscale_line()} "
            "(3) Ready-to-run real-data path: drop "
            "`cifar-10-batches-py/` (or `cifar10.npz`) under `./data` "
            "and run `python report.py --data pickle --epochs 25` - the "
            "same engine is then expected to land in the reference's "
            "63-66% accuracy band (Project_Report.pdf Tables 1-2)."
        ),
        "",
    ]
    lines += _bench_matrix_sections()
    lines += _flash_tune_sections()
    lines += _mfu_ceiling_section()
    with open(args.out, "w") as f:
        f.write("\n".join(lines))
    print(f"wrote {args.out}")
    return 0


def _mfu_ceiling_section() -> list[str]:
    """Arithmetic MFU ceiling for the flagship LM row, from measured data.

    VERDICT r3 item 2 asks for >=40% MFU or a written ablation proving
    the ceiling. This derives the ceiling directly: the tune file's best
    own-kernel fwd+bwd wall-clock is EXACTLY one layer's attention at
    the flagship step shape (B16 x H8 x S2048 x Dh64), so

        step_time >= L * attn_wall + (non-attention FLOPs) / peak

    even if every matmul ran at 100% MXU. Ceiling MFU = step FLOPs /
    (peak * that bound). Rendered only when both the tune file and the
    flagship matrix row exist; all inputs are cited measured artifacts.
    """
    import glob

    from distributed_neural_network_tpu.models.transformer import (
        TransformerConfig,
    )
    from distributed_neural_network_tpu.train.measure import (
        model_flops_per_token,
        peak_flops,
    )

    here = REPO
    # the ceiling is only published for a flagship row that actually
    # exists in the matrix, with the model read FROM that row (a
    # hardcoded config could silently diverge from the bench spec)
    try:
        with open(os.path.join(here, "BENCH_MATRIX.json")) as f:
            rows = json.load(f).get("rows", [])
    except (OSError, json.JSONDecodeError):
        return []
    flag = next((r for r in rows
                 if r.get("id") == "lm_flash_d512_L8_seq2048_bf16"
                 and "tokens_per_s" in r), None)
    if flag is None:
        return []
    # older-format rows (r3) may lack some fields; fall back to the bench
    # spec's defaults for exactly this row id
    flag.setdefault("n_heads", 8)
    flag.setdefault("d_ff", 2048)
    flag.setdefault("vocab", 32768)
    seq, batch = flag["seq_len"], flag["batch"]
    head_dim = flag["d_model"] // flag["n_heads"]
    # matching tune file: same seq; shape must match the row's geometry
    paths = sorted(glob.glob(
        os.path.join(here, "tools", f"flash_tune_*_s{seq}*.json")))
    tune = None
    for p in paths:
        try:
            with open(p) as f:
                cand = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        s = cand.get("shape", {})
        if (s.get("seq") == seq and s.get("batch") == batch
                and s.get("heads") == flag["n_heads"]
                and s.get("head_dim") == head_dim
                and cand.get("best_own_ms")):
            tune, tune_path = cand, p
            break
    if tune is None:
        return []
    attn_ms = tune["best_own_ms"]
    kind = str(tune.get("device", "")).replace("_", " ")
    peak = peak_flops(kind, "bfloat16")
    if not peak:
        return []
    cfg = TransformerConfig(
        vocab_size=flag["vocab"], d_model=flag["d_model"],
        n_heads=flag["n_heads"], n_layers=flag["n_layers"],
        d_ff=flag.get("d_ff", 2048),
    )
    L = cfg.n_layers
    flops_tok = model_flops_per_token(cfg, seq)
    step_flops = flops_tok * batch * seq
    # attention share of the model-FLOP count (the 4*S*d term, x3 fwd+bwd)
    attn_flops = 3.0 * L * 4 * seq * cfg.d_model * batch * seq
    non_attn = step_flops - attn_flops
    attn_wall = L * attn_ms / 1e3
    bound = attn_wall + non_attn / peak
    ceiling = step_flops / (peak * bound) * 100.0
    ideal = step_flops / peak
    target_attn_ms = (step_flops / (0.40 * peak) - non_attn / peak) / L * 1e3
    achieved = flag.get("mfu_pct")
    ach = (f"measured {achieved}% on that row, " if achieved else "")
    # the config-level route past the d512 ceiling: best measured MFU
    # over ALL LM rows (r5: d1024/hd128/dots_saveable landed 53.73%)
    best = max((r for r in rows
                if r.get("id", "").startswith("lm_")
                and isinstance(r.get("mfu_pct"), (int, float))),
               key=lambda r: r["mfu_pct"], default=None)
    if best is not None and best["mfu_pct"] >= 40.0 \
            and best["id"] != flag["id"]:
        # the kernel-budget clause must track the actual comparison -
        # this branch is selected on best-row MFU alone (r5 review)
        kernel_clause = (
            "the tuned kernel is UNDER it, and the remaining gap on "
            "this row is matmul-side efficiency (d512 matmuls are "
            "narrow for the MXU)"
            if attn_ms <= target_attn_ms else
            f"the tuned kernel ({attn_ms:.1f} ms/layer) is still OVER it"
        )
        tail = (
            f"The 40% target at this shape implies an attention budget "
            f"of <= {target_attn_ms:.1f} ms/layer; {kernel_clause}. The "
            "config-level route closes it: the target is MET at "
            f"**{best['mfu_pct']}% measured MFU** on `{best['id']}` "
            f"(d{best.get('d_model')}, Dh="
            f"{best.get('d_model', 0) // max(best.get('n_heads', 1), 1)} "
            "head geometry"
            + (", dots_saveable remat" if best.get("remat_policy") else "")
            + " - the LM table row)."
        )
    elif attn_ms <= target_attn_ms:
        # the (re-)tuned kernel fits the 40% attention budget: the
        # ceiling no longer binds at the target - what remains is
        # matmul-side efficiency plus re-measuring the row with these
        # blocks (the measured row predates the tune that got here)
        tail = (
            f"The 40% target at this shape implies an attention budget "
            f"of <= {target_attn_ms:.1f} ms/layer, and the tuned kernel "
            f"is now UNDER it - the kernel ceiling no longer rules out "
            "the target. What stands between the measured row (which "
            "predates this kernel tuning) and the ceiling is matmul-side "
            "efficiency plus re-measuring the flagship row with these "
            "blocks (queued for the next healthy-chip session); "
            "larger-d_model rows (attention is a smaller FLOP fraction) "
            "remain the config-level route to even higher MFU."
        )
    else:
        tail = (
            "Reaching the 40% target at this shape requires attention "
            f"at <= {target_attn_ms:.1f} ms/layer "
            f"({attn_ms / max(target_attn_ms, 1e-9):.1f}x faster than "
            "measured) - the kernel, not the surrounding program, is "
            "the binding constraint; larger-d_model rows (attention is "
            "a smaller FLOP fraction) are the config-level route past "
            "it."
        )
    return [
        "## MFU ceiling - flagship LM row, derived from measured kernels",
        "",
        f"At d{cfg.d_model}/L{L}/seq{seq}/bs{batch} the step computes "
        f"{step_flops / 1e12:.2f} model TFLOP "
        f"(ideal {ideal * 1e3:.0f} ms at the {peak / 1e12:.0f} TF/s bf16 "
        f"peak). The tuned own flash kernel measures {attn_ms:.1f} ms "
        "fwd+bwd for ONE layer's attention at exactly this shape "
        f"(`{os.path.basename(tune_path)}`, best_own_ms), so attention "
        f"alone costs {attn_wall * 1e3:.0f} ms/step across {L} layers. "
        "Even with every non-attention matmul at 100% MXU utilization, "
        f"step time >= {bound * 1e3:.0f} ms -> **MFU <= {ceiling:.0f}%** "
        f"with the current kernel ({ach}the gap to the ceiling is the "
        f"matmul side). {tail}",
        "",
    ]


def _oracle_fullscale_line() -> str:
    """One sentence summarizing tools/oracle_fullscale_result.json."""

    path = os.path.join(REPO, "tools", "oracle_fullscale_result.json")
    pending = ("`tools/oracle_fullscale.py` runs the same parity check at "
               "the reference's full scale (25 epochs x 50k rows x 8 "
               "workers); artifact pending.")
    try:
        with open(path) as f:
            r = json.load(f)
        s = r["scale"]
        s["epochs"], s["rows"], s["workers"]
        r["worst_loss_abs_diff"], r["worst_param_max_rel_err"], r["wall_s"]
    except (OSError, json.JSONDecodeError, KeyError, TypeError):
        return pending
    # never render a smoke-scale or failed artifact as the full-scale
    # verification claim
    full = (s["epochs"] >= 25 and s["rows"] >= 50000 and s["workers"] >= 8)
    if not r.get("ok") or not full:
        return (pending[:-1] +
                f" (current artifact: ok={r.get('ok')}, {s['epochs']} "
                f"epochs x {s['rows']} rows - not the full-scale claim).")
    return (
        f"`tools/oracle_fullscale_result.json` (ok={r['ok']}) matches the "
        f"engine against the f64 numpy oracle at the reference's full "
        f"scale - {s['epochs']} epochs x {s['rows']} rows x "
        f"{s['workers']} workers, bs {s['batch_size']}: worst per-epoch "
        f"loss diff {r['worst_loss_abs_diff']:.1e}, worst param rel err "
        f"{r['worst_param_max_rel_err']:.1e} over the whole horizon "
        f"(float-precision drift of the same algorithm, "
        f"{r['wall_s'] / 60:.0f} min wall)."
    )


def _rows_from_matrix(epochs: int):
    """(proc_rows, bs_rows, pending_bs) from BENCH_MATRIX.json cnn rows.

    The bench matrix's cnn_dp_ep{epochs}_bs{N} rows carry exactly the
    fields `run_one` returns (devices/batch_size/val_acc/train_s/source),
    measured by the same `measure_dp_training` - so the report can render
    from one bench run instead of re-measuring the whole sweep.
    """

    path = os.path.join(REPO, "BENCH_MATRIX.json")
    try:
        with open(path) as f:
            rows = json.load(f).get("rows", [])
    except (OSError, json.JSONDecodeError):
        return [], [], []
    by_bs = {}
    pending_bs = []
    for r in rows:
        rid = r.get("id", "")
        if (rid == f"cnn_dp_ep{epochs}_bs{r.get('batch_size')}"
                and "train_s" in r):
            by_bs[r["batch_size"]] = dict(r)
        else:
            # error/skipped stubs of the plain bs sweep (no kernel/dtype
            # suffix): Table 2 must show the reference's bs values as
            # pending rather than silently shrinking the sweep
            m = re.fullmatch(rf"cnn_dp_ep{epochs}_bs(\d+)", rid)
            if m and "train_s" not in r:
                pending_bs.append(int(m.group(1)))
            elif m:
                # measured, but the batch_size field is missing or
                # disagrees with the id: render it (bs from the id)
                # instead of silently dropping a measured row - the
                # silent-shrink this function exists to prevent
                fixed = dict(r)
                fixed["batch_size"] = int(m.group(1))
                fixed["field_note"] = (
                    f"batch_size field was {r.get('batch_size')!r}; "
                    "bs taken from the row id")
                by_bs[int(m.group(1))] = fixed
    proc_rows = []
    if 16 in by_bs:
        r = dict(by_bs[16])
        r["ref"] = REF_PROC.get(8)  # headline comparison: the 8-proc run
        proc_rows.append(r)
    bs_rows = []
    for bs in sorted(by_bs):
        r = dict(by_bs[bs])
        r["ref"] = REF_BS.get(bs)
        bs_rows.append(r)
    pending_bs = sorted(b for b in set(pending_bs) if b not in by_bs)
    return proc_rows, bs_rows, pending_bs


def _unmeasured_cell(r: dict) -> str:
    """One cell for a row without a measured value: states the fact and
    carries the recorded error - no claim about queue state (whether a
    re-measure is scheduled lives in ROADMAP.md, not in the row)."""
    why = str(r.get("error", r.get("skipped", "no measurement")))
    # strip ANSI color codes (backend error strings embed them) and
    # collapse whitespace (multi-line tracebacks break the markdown
    # table at the first newline - r5 review) before truncating
    why = re.sub(r"\x1b\[[0-9;]*m", "", why)
    why = " ".join(why.split())
    return f"no measured value (error: {why[:60].rstrip('; (')})"



def _hd_suffix(r: dict) -> str:
    """Head-geometry label, shown only for the non-default Dh (suffixing
    every row would split the r3/r4 A/B pairs that share the hd64
    default). Used by both the LM and decode tables - decode per-step
    cost and LM MFU are both geometry-bound."""
    if r.get("n_heads") and r["d_model"] // r["n_heads"] != 64:
        return f"/hd{r['d_model'] // r['n_heads']}"
    return ""


def _bench_matrix_sections() -> list[str]:
    """LM-throughput/MFU + pipeline-bubble sections from BENCH_MATRIX.json.

    bench.py writes the matrix incrementally on every run; rendering it
    here (rather than hand-editing REPORT.md) keeps the report
    regenerable in one command. Rows with errors are listed as such -
    an honest artifact beats a silently dropped row.
    """

    path = os.path.join(REPO, "BENCH_MATRIX.json")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        matrix = json.load(f)
    rows = matrix.get("rows", [])
    out = []

    # CNN kernel/dtype/input variants of the headline row: without this
    # section the bs16_{pallas,bf16,stream} rows render nowhere (Table 2
    # matches only the suffix-free bs-sweep ids)
    variants = []
    for r in rows:
        m = re.fullmatch(r"cnn_dp_ep(\d+)_bs16_(pallas|bf16|stream)",
                         r.get("id", ""))
        if m:
            variants.append((r, m.group(2), int(m.group(1))))
    # headline per epoch count: rows from other --epochs runs persist in
    # the matrix, and a cross-epoch "vs headline" ratio would be bogus
    heads = {}
    for r in rows:
        m = re.fullmatch(r"cnn_dp_ep(\d+)_bs16", r.get("id", ""))
        if m and "train_s" in r:
            heads[int(m.group(1))] = r
    if variants:
        desc = {
            "pallas": "fused Pallas CNN head (`ops/pallas_kernels.py`)",
            "bf16": "bfloat16 compute dtype",
            "stream": "host-streaming input, double-buffered prefetch",
        }
        eps = sorted({ep for _, _, ep in variants})
        out += [
            "## CNN variants - headline shape "
            f"({'/'.join(str(e) for e in eps)} ep, bs 16), one knob "
            "each",
            "",
            fmt_row(["variant", "epochs", "val acc %", "train s",
                     "vs same-epoch headline (hbm/f32)"]),
            fmt_row(["---"] * 5),
        ]
        stream_measured = False
        for r, kind, ep in variants:
            head = heads.get(ep)
            if "train_s" in r:
                stream_measured |= kind == "stream"
                # sub-0.01 ratios (e.g. headline 4 s vs stream 964 s)
                # rounded to "0.00x" - print the inverse as "Nx slower"
                # so the comparison stays recoverable (r5 review)
                if head and r["train_s"] > 0:
                    ratio = head["train_s"] / r["train_s"]
                    vs = (f"{ratio:.2f}x" if ratio >= 0.01
                          else f"{1 / ratio:.0f}x slower")
                else:
                    vs = "-"
                out.append(fmt_row([
                    desc[kind], ep, f"{r['val_acc']:.2f}",
                    f"{r['train_s']:.2f}", vs,
                ]))
            else:
                out.append(fmt_row(
                    [desc[kind], ep, "-", _unmeasured_cell(r), "-"]))
        out.append("")
        if stream_measured:
            out += [
                "The stream row runs the per-epoch engine path: "
                "streaming input has no fused multi-epoch span "
                "(`train/engine.py run` downgrades with a log line), and "
                "every batch is a host->device transfer, where the "
                "HBM-resident rows pay one for the whole dataset (~78k "
                "transfers at 25 ep/bs 16). Attribute only the remainder "
                "to the input pipeline itself.",
                "",
            ]

    lm = [r for r in rows if r.get("id", "").startswith("lm_")
          and not r.get("id", "").startswith("lm_decode")
          and "_scaling_" not in r.get("id", "")]
    if lm:
        out += [
            "## LM throughput - single chip (beyond-reference model family)",
            "",
            "Transformer LM (`lm_train.py`), synthetic copy task, "
            "steady-state tokens/s over the timed steps, fenced with "
            "`block_until_ready` (`utils/timers.py hard_block`). MFU = model "
            "FLOPs/token x tokens/s / dtype-adjusted peak "
            "(`train/measure.py`; PaLM-appendix convention - causal "
            "attention counted at full S, not halved. The flash kernel "
            "skips fully-masked blocks, so at attention-dominated "
            "lengths the convention credits that skipped work: this is "
            "why MFU RISES with seq in the long-context rows; hardware "
            "MXU occupancy is lower there, and cross-seq comparisons "
            "hold on the stated convention, as published MFU numbers "
            "do). Kernel provenance: `pallas-flash` "
            "(no suffix) = the LIBRARY kernel (rows measured in r3, "
            "before the own kernels existed); `pallas-flash-own` / "
            "`pallas-flash-lib` = this framework's vma-typed 3-D-grid "
            "kernels vs the library A/B baseline (r4+).",
            "",
            fmt_row(["config", "attn", "remat", "batch", "seq",
                     "tokens/s", "MFU %"]),
            fmt_row(["---"] * 7),
        ]
        # measured rows first (best MFU at the top); unmeasured stubs below
        for r in sorted(lm, key=lambda r: ("tokens_per_s" not in r,
                                           -(r.get("mfu_pct") or 0))):
            if "tokens_per_s" not in r:
                out.append(fmt_row([
                    r["id"], "-", "-", "-", "-", _unmeasured_cell(r), "-",
                ]))
                continue
            cfgs = (f"d{r['d_model']}/L{r['n_layers']}{_hd_suffix(r)}"
                    f"/voc{r['vocab']//1000}k/{r['dtype']}")
            # a remat policy qualifies block remat (dots_saveable stores
            # matmul outputs; recompute is elementwise-only, so its FLOP
            # tax is a few percent, not full remat's ~1/3)
            remat = ("block/" + r["remat_policy"].replace("_saveable", "")
                     if r.get("remat") and r.get("remat_policy")
                     else "block" if r.get("remat")
                     else "attn" if r.get("remat_attn") else "none")
            out.append(fmt_row([
                cfgs, r.get("attn_kernel", r["attn"]), remat,
                r["batch"], r["seq_len"], f"{r['tokens_per_s']:,}",
                r.get("mfu_pct", "-"),
            ]))
        out.append("")

    dec = [r for r in rows if r.get("id", "").startswith("lm_decode")]
    if dec:
        out += [
            "## KV-cache decode throughput - single chip (inference path)",
            "",
            "Autoregressive generation (`models/transformer.py generate`): "
            "per-step AVERAGE cost at a stated static cache size "
            "(`train/measure.py measure_lm_decode`; every cached step "
            "attends the full padded cache, so the rate is a function of "
            "cache length - both sizes are shown, their spread is the "
            "measured cache-length scaling). Decode streams every "
            "parameter once per step, so utilization is reported against "
            "peak HBM BANDWIDTH (the binding resource), not the MXU peak.",
            "",
            fmt_row(["config", "batch", "cache len", "tok/s", "ms/step",
                     "HBM util %"]),
            fmt_row(["---"] * 6),
        ]
        # measured rows first, same as the LM table
        for r in sorted(dec, key=lambda r: "decode_tokens_per_s" not in r):
            if "decode_tokens_per_s" not in r:
                out.append(fmt_row([
                    r["id"], "-", "-", _unmeasured_cell(r), "-", "-",
                ]))
                continue
            cfgs = (f"d{r['d_model']}/L{r['n_layers']}{_hd_suffix(r)}"
                    f"/voc{r['vocab'] // 1000}k/{r['dtype']}")
            caches = [c for c in (r.get("at_cache_short"),
                                  r.get("at_cache_long")) if c]
            if not caches:
                # row measured under an older measure_lm_decode format
                # (top-level fields only) - render it rather than drop it
                caches = [{
                    "cache_len": "-",
                    "tokens_per_s": r["decode_tokens_per_s"],
                    "ms_per_step": r.get("ms_per_step", "-"),
                }]
            for i, c in enumerate(caches):
                is_last = i == len(caches) - 1
                out.append(fmt_row([
                    cfgs, r["batch"], c["cache_len"],
                    f"{c['tokens_per_s']:,}", c["ms_per_step"],
                    r.get("hbm_util_pct", "-") if is_last else "-",
                ]))
        out.append("")

    pb = [r for r in rows if r.get("id", "").startswith("pp4_bubble")
          and "configs" in r]
    if pb:
        r = pb[-1]
        out += [
            "## Pipeline bubble - measured at pp=4 "
            f"({r['devices']}x {r['platform']} mesh)",
            "",
            "Fixed microbatch size, varying (M microbatches, v interleave):"
            " tokens/s tracks 1 - bubble since per-token work is identical"
            " across configs (`train/measure.py measure_pp_bubble`). The"
            " interleaved (circular) schedule cuts the bubble to"
            " (P-1)/(v*M+P-1) (`parallel/pipeline.py`).",
            "",
            fmt_row(["microbatches", "interleave", "tokens/s",
                     "bubble (analytic)", "bubble (measured)",
                     "bubble (overhead-adjusted)"]),
            fmt_row(["---"] * 6),
        ]
        for c in r["configs"]:
            out.append(fmt_row([
                c["microbatches"], c["interleave"],
                f"{c['tokens_per_s']:,}", c["bubble_analytic"],
                c["bubble_measured"],
                c.get("bubble_overhead_adjusted", "-"),
            ]))
        tm = r.get("tick_model") or {}
        fit = (f" Tick-model fit over {tm.get('n_configs', '?')} "
               f"configs: per-layer {tm.get('per_layer_s')}s, "
               f"per-tick overhead {tm.get('per_tick_overhead_s')}s, "
               f"relative residual {tm.get('rel_fit_err')}. A NEGATIVE "
               "overhead-adjusted cell means that config ran faster than "
               "the fitted tick model predicts (fit residual, not a "
               "physical negative bubble) - read those cells as ~0."
               if tm else "")
        bnd = tm.get("boundary_solution")
        if bnd and tm.get("per_tick_overhead_s") == 0:
            fit += (
                " The overhead component sits on the o=0 boundary of "
                "the constrained (non-negative) fit - the unconstrained "
                "optimum is slightly negative "
                f"({bnd.get('per_tick_overhead_s_unconstrained')}s; "
                "later ticks run warmer caches on this host), i.e. "
                "per-tick overhead is statistically ZERO here, not "
                "clamped away."
            )
        elif bnd:
            fit += (
                " The fit sits on a boundary of the constrained "
                "(non-negative) model - unconstrained optimum "
                f"(c={bnd.get('per_layer_s_unconstrained')}s, "
                f"o={bnd.get('per_tick_overhead_s_unconstrained')}s); "
                "read the constrained parameters as the physical fit."
            )
        out += ["", (r.get("note", "") + fit).strip(), ""]

    sc = [r for r in rows if r.get("id", "").startswith("cnn_dp_scaling")
          and "points" in r]
    if sc:
        r = sc[-1]
        out += [
            "## Data-parallel scaling shape - "
            f"{r['devices']}-device {r['platform']} mesh, "
            f"{r['host_cores']} host core(s)",
            "",
            "The reference's Table 1 sweep (fixed 50k-row dataset, more "
            "workers) re-run on the virtual mesh: fixed total work, mesh "
            "size n swept, per-epoch (unfused) path so the sync phase is "
            "attributable (`train/measure.py measure_dp_scaling`). On "
            "shared host cores ideal wall-clock is FLAT in n, so "
            "`overhead vs n=1` isolates the parallelization + sync cost "
            "the reference pays 375 s -> 1642 s for (BASELINE.md "
            "Table 1); real n-chip wall-clock divides by n modulo this "
            "curve.",
            "",
            fmt_row(["mesh n", "train+sync s", "sync s", "sync %",
                     "overhead vs n=1"]),
            fmt_row(["---"] * 5),
        ]
        for c in r["points"]:
            out.append(fmt_row([
                c["n"], c["train_s"], c["sync_phase_s"],
                f"{100 * c['sync_frac']:.2f}%", c["overhead_vs_n1"],
            ]))
        out += ["", r.get("note", ""), ""]

    sp_rows = [r for r in rows if "_sp_scaling_" in r.get("id", "")
               and "points" in r]
    for r in sp_rows:
        impl = r.get("attn_impl", "ring")
        out += [
            f"## Sequence-parallel scaling shape - {impl} attention, "
            f"{r['devices']}-device {r['platform']} mesh, "
            f"{r['host_cores']} host core(s)",
            "",
            "Long-context evidence within a one-chip environment: fixed "
            f"global sequence ({r['seq_len']} tokens, "
            f"d{r['d_model']}/L{r['n_layers']} LM), sp swept - each "
            "device holds seq/sp tokens and "
            + ("ring attention rotates K/V blocks sp-1 times per layer"
               if impl in ("ring", "zigzag") else
               "Ulysses re-shards heads<->sequence with one all_to_all "
               "each way per attention")
            + " (`parallel/ring.py`; "
            "`train/measure.py measure_sp_scaling`). Total FLOPs are "
            "identical at every sp on the shared host core, so ideal "
            "wall is flat and `overhead vs sp=1` is the measured "
            "sequence-parallel cost; real sp-chip wall divides by sp "
            "modulo this curve.",
            "",
            fmt_row(["sp", "wall s", "tokens/s", "loss",
                     "overhead vs sp=1"]),
            fmt_row(["---"] * 5),
        ]
        for c in r["points"]:
            out.append(fmt_row([
                c["sp"], c["wall_s"], f"{c['tokens_per_s']:,}",
                c["final_loss"], c["overhead_vs_sp1"],
            ]))
        out += [
            "",
            "The identical loss column is the semantics check: every sp "
            "computes the same model step.",
            "",
        ]
        if any(c["overhead_vs_sp1"] < 1.0 for c in r["points"]):
            mech = (
                "the sharded path works the scores in (S/sp)-tile K/V "
                "blocks that fit cache"
                if impl in ("ring", "zigzag") else
                "the sharded path attends heads/sp heads per device at "
                "a time, shrinking the live working set"
            )
            out += [
                "Cells < 1 are real on this host: the sp=1 baseline "
                "materializes the full (S, S) score matrix for every "
                f"head at once, while {mech} - locality outweighing "
                "the collective cost on a shared core. On real chips "
                "the same locality shows up inside flash attention "
                "instead, and the collectives ride ICI.",
                "",
            ]
        if impl == "zigzag":
            # the comparative claim is DERIVED from the sibling rows at
            # render time, never hardcoded: host noise has swung these
            # curves before, and prose must not outlive its data
            def _ov(which):
                row = next((x for x in sp_rows
                            if x.get("attn_impl") == which), None)
                return ({p["sp"]: p["overhead_vs_sp1"]
                         for p in row["points"]} if row else {})

            zig, ring_o, uly = _ov("zigzag"), _ov("ring"), _ov("ulysses")
            comp_sps = [s for s in zig
                        if s >= 2 and s in ring_o and s in uly]
            beats = bool(comp_sps) and all(
                zig[s] < min(ring_o[s], uly[s]) for s in comp_sps)
            out += [
                "Zigzag is the load-balanced causal ring: each device "
                "holds a (front, back) slice pair (`parallel/ring.py "
                "zigzag_order`), so causal work is even across the ring "
                "instead of early shards sitting nearly idle."
                + (" In the rows above it sits below both plain ring "
                   "and Ulysses at every measured sp >= 2 - the "
                   "load-balance claim, measured." if beats else "")
                + " Tokens are fed "
                "in zigzag shard order (the caller permutes; the sweep "
                "does this per sp - without it each point trains a "
                "differently-permuted objective and the loss column "
                "drifts, which is exactly how a missing permute was "
                "caught in round 5).",
                "",
            ]
        if impl == "ulysses":
            out += [
                "History: the r4 measurement of this row showed a 2x "
                "cliff exactly at sp=8 (overhead 1.923 after 0.897 at "
                "sp=4) - the H == sp boundary where each device holds "
                "ONE head. A component ablation "
                "(`tools/diagnose_ulysses.py`, artifact "
                "`tools/ulysses_diag.json`) isolated it: the four "
                "all_to_alls stay flat (~14 -> ~27 ms from sp=2 to "
                "sp=8) while the LOCAL attention alone reproduced the "
                "blow-up, and the artifact's mesh-free contrast shows "
                "the size-1-head 4-D einsum running SLOWER than the "
                "2-head case despite HALF the FLOPs (494 vs 422 ms "
                "fwd+bwd), where proper FLOP scaling predicts ~2x "
                "faster - an XLA:CPU lowering pathology, not a Ulysses "
                "cost. Fix: `parallel/ring.py attention()` routes "
                "H == 1 through an equivalent squeezed 3-D contraction "
                "(189 ms on the same shape, 2.6x; numerics pinned by "
                "`tests/test_ring.py`); the re-measured sp=8 cell "
                "above now sits at the curve's minimum.",
                "",
            ]

    epr = [r for r in rows if "_ep_scaling_" in r.get("id", "")
           and "points" in r]
    if epr:
        r = epr[-1]
        out += [
            f"## Expert-parallel scaling shape - {r['n_experts']} "
            f"experts, top-{r['top_k']}, {r['devices']}-device "
            f"{r['platform']} mesh, {r['host_cores']} host core(s)",
            "",
            "The EP analog of the dp/sp rows: fixed global batch and "
            f"data (d{r['d_model']}/L{r['n_layers']} MoE LM, "
            f"seq {r['seq_len']}), expert axis swept - experts shard "
            "over the data axis (`train/lm.py`), each MoE layer paying "
            "one all_to_all each way at ep>1 and none at ep=1 "
            "(`parallel/moe.py`; `train/measure.py measure_ep_scaling`). "
            "No-drop capacity (factor = E/top_k) makes every ep compute "
            "the same step, so the loss column agrees to "
            "blockwise-reduction tolerance.",
            "",
            fmt_row(["ep", "experts/device", "wall s", "tokens/s",
                     "loss", "overhead vs ep=1"]),
            fmt_row(["---"] * 6),
        ]
        for c in r["points"]:
            out.append(fmt_row([
                c["ep"], c["experts_per_device"], c["wall_s"],
                f"{c['tokens_per_s']:,}", c["final_loss"],
                c["overhead_vs_ep1"],
            ]))
        out += [""]

    zm = [r for r in rows if r.get("id", "").startswith("zero1_")
          and "optimizers" in r]
    if zm:
        r = zm[-1]
        opts = r["optimizers"]

        def mb(b):
            return f"{b / 1e6:.2f} MB"

        out += [
            "## ZeRO-1 optimizer-state footprint - measured device "
            "buffers",
            "",
            f"Committed per-device buffer bytes (`addressable_shards`) "
            f"for a d{r['d_model']}/L{r['n_layers']} LM "
            f"({r['n_params']:,} params, {mb(r['param_bytes_per_device'])}"
            f" of parameters per device) on a {r['devices']}-device "
            f"{r['platform']} mesh - counted at init and again after one "
            "compiled train step, so the artifact proves the state stays "
            "sharded through the jitted update "
            "(`train/measure.py measure_zero_memory`). The reference's "
            "per-worker private optimizers multiply this memory with "
            "worker count (`data_parallelism_train.py:187`); ZeRO-1 "
            "divides it.",
            "",
            fmt_row(["optimizer", "state MB/device (init)",
                     "after 1 step", "loss after 1 step"]),
            fmt_row(["---"] * 4),
        ]
        for name, o in opts.items():
            out.append(fmt_row([
                name, mb(o["state_bytes_per_device"]),
                mb(o["state_bytes_per_device_post_step"]),
                o["final_loss"],
            ]))
        red = r.get("reduction_x")
        exp = r.get("expected_zero_bytes_per_device")
        zb = opts.get("zero-adam", {}).get("state_bytes_per_device")
        exact = (" - byte-exact vs the derived per-leaf shard layout"
                 if zb == exp else "")
        out += [
            "",
            f"Measured reduction: **{red}x** per device{exact}; the "
            "identical loss is the semantics check (ZeRO-1 partitions "
            "state, not math - `tests/test_zero.py`).",
            "",
        ]

    nb = [r for r in rows if r.get("id", "").startswith("native_batcher")
          and "kernels" in r]
    if nb:
        r = nb[-1]
        out += [
            "## Native host kernels - C++ batcher vs its numpy fallback",
            "",
            "The runtime around the XLA compute path is native where the "
            "host input pipeline is hot (`native/batcher.cpp`, "
            "build-on-import + ctypes). Best-of-"
            f"{r['reps']} wall per kernel against the SAME pure-numpy "
            "fallback the wrappers ship (`native.fallback_*` - one "
            "source of truth, parity pinned by `tests/test_native.py`), "
            f"on {r['host_cores']} host core(s); no jax, no chip "
            "(`train/measure.py measure_native_batcher`).",
            "",
            fmt_row(["kernel", "native ms", "numpy ms", "speedup",
                     "native images/s"]),
            fmt_row(["---"] * 5),
        ]
        if not r.get("native_available"):
            out += [
                "**NOTE: the native library was unavailable when this "
                "row measured** - both columns ran the numpy fallback, "
                "so the speedups below are ~1x and price nothing; "
                "re-measure on a host with a C++ toolchain.",
                "",
            ]
        for name, k in r["kernels"].items():
            out.append(fmt_row([
                name, k["native_ms"], k["fallback_ms"],
                f"{k['speedup_x']}x", f"{k['native_images_per_s']:,}",
            ]))
        out += [""]

    ft = [r for r in rows if r.get("id", "").startswith("cnn_fault")
          and "points" in r]
    if ft:
        r = ft[-1]
        out += [
            "## Fault injection under load - the experiment the "
            "reference never ran",
            "",
            f"`--failure-probability` sweep at a fixed seed "
            f"({r['epochs']} epochs, bs {r['batch_size']}, "
            f"{r['devices']}-device {r['platform']} mesh; "
            "`train/measure.py measure_fault_tolerance`). The reference "
            "implements fault injection but published no fault numbers "
            "(its report section 6.2), and its straggler-sleep design "
            "stalls the whole epoch behind a blocking recv "
            "(`data_parallelism_train.py:227`); here a dropped device "
            "is excluded from the epoch-edge average by the live-mask "
            "(`parallel/fault.py`) and nobody waits.",
            "",
            fmt_row(["failure p", "val acc %", "val loss",
                     "mean live frac", "epochs degraded",
                     "wall vs p=0"]),
            fmt_row(["---"] * 6),
        ]
        # a custom sweep without a p=0 control carries wall_vs_p0=None
        # (+ wall_vs_first); render the ratio that actually exists
        has_p0 = all(c["wall_vs_p0"] is not None for c in r["points"])
        for c in r["points"]:
            out.append(fmt_row([
                c["failure_probability"], c["val_acc"], c["val_loss"],
                c["mean_live_frac"], c["epochs_degraded"],
                c["wall_vs_p0"] if has_p0
                else f"{c.get('wall_vs_first', '-')} (vs first point; "
                     "sweep has no p=0 control)",
            ]))
        out += [
            "",
            "Wall-clock flat in p is the drop-and-continue claim; "
            "accuracy holding at the control's level while only "
            f"{min(c['mean_live_frac'] for c in r['points']):.0%} of "
            "epoch contributions survive is the convergence-robustness "
            "claim"
            + (" (same seed: p=0 is the exact control)." if has_p0 else
               " (custom sweep: no p=0 control; ratios are vs the "
               "sweep's first point)."),
            "",
        ]
        st = r.get("straggler")
        if st:
            out += [
                "The reference's straggler semantics, priced: with "
                f"`--failure-duration {st['duration_s']}` at "
                f"p={st['failure_probability']} (same seed, identical "
                "masks and compute, per-epoch path, duration 0 vs "
                f"{st['duration_s']}), {st['epochs_degraded']} degraded "
                f"epochs predict a {st['predicted_stall_s']} s stall and "
                f"measure {st['measured_stall_s']} s - wall-clock the "
                "fused drop-and-continue path never pays.",
                "",
            ]
    return out


def _flash_tune_sections() -> list[str]:
    """Per-pass flash-attention ablation from tools/flash_tune_*.json.

    The r3 MFU diagnosis located the end-to-end gap in the attention
    backward pass; this renders the hardware evidence (fwd-only and
    fwd+bwd wall-clock per implementation, with attention-TFLOP/s) so the
    ceiling argument is a table in the artifact, not a memory. Files are
    written by tools/tune_flash.py under honest value-fetch fencing."""
    import glob

    out = []
    paths = sorted(glob.glob(os.path.join(REPO, "tools", "flash_tune_*.json")))
    for path in paths:
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        abl = data.get("ablation")
        shape = data.get("shape", {})
        if not abl:
            continue
        if not out:
            out += [
                "## Flash-attention kernel ablation - per-pass, measured",
                "",
                "Hard-fenced kernel microbenchmarks (`tools/tune_flash.py`,"
                " 20-step mean after warm-up). `own` = this framework's"
                " vma-typed Pallas kernels (`ops/flash_pallas.py`) at their"
                " best swept blocks; `lib` = the kernel shipped with JAX at"
                " its best uniform blocks; `xla` = fused plain attention."
                " bwd is derived (fwd+bwd minus fwd at the same forward"
                " config). TFLOP/s uses causal attention FLOPs"
                " (2*B*H*S^2*D fwd; 2.5x that bwd).",
                "",
            ]
        b, h = shape.get("batch"), shape.get("heads")
        s, d = shape.get("seq"), shape.get("head_dim")
        out += [
            f"### B{b} x H{h} x S{s} x Dh{d} ({data.get('device')}, "
            "bf16)",
            "",
        ]
        def _unmeasured(a):
            # an impl whose ms timings all failed or never ran; shared
            # by the note and (implicitly) the all-dash table rows so
            # the two cannot disagree
            return not a or all(a.get(k) is None
                                for k in ("fwd_ms", "fwdbwd_ms"))

        if data.get("recovered_from_log"):
            missing = [n for n in ("own", "lib", "xla")
                       if _unmeasured(abl.get(n))]
            gap = (f" Implementations the sweep never reached: "
                   f"{', '.join(missing)}." if missing else "")
            out += [
                "Recovered from the measurement-session log: the sweep "
                "ended early, so rows past that point were never "
                "re-measured - missing cells are `-`, not zero. The ms "
                "timings are direct fenced measurements; bwd and TFLOP/s "
                f"are derived from them as the intro above states.{gap}",
                "",
            ]
        out += [
            fmt_row(["impl", "fwd ms", "bwd ms", "fwd+bwd ms",
                     "fwd TFLOP/s", "bwd TFLOP/s"]),
            fmt_row(["---"] * 6),
        ]

        def _cell(v):
            return "-" if v is None else v

        suspect = []
        for name in ("own", "lib", "xla"):
            a = abl.get(name)
            if not a:
                continue
            # an all-dash row (every config of this impl errored) stays
            # visible rather than silently vanishing from the sweep
            out.append(fmt_row([
                name,
                _cell(a.get("fwd_ms")), _cell(a.get("bwd_ms_derived")),
                _cell(a.get("fwdbwd_ms")),
                _cell(a.get("fwd_attn_tflops_per_s")),
                _cell(a.get("bwd_attn_tflops_per_s")),
            ]))
            # a derived-bwd rate at/above the chip's peak is arithmetic
            # proof that the paired fwd-only timing overstates the fwd
            # cost inside the fwd+bwd program (different fusion/layout,
            # or unsubtracted fence RTT in older tune files) - flag it
            # rather than publish an impossible number. Peak is looked
            # up for the file's recorded device (tune files write the
            # kind with underscores)
            from distributed_neural_network_tpu.train.measure import (
                peak_flops,
            )

            kind = str(data.get("device", "")).replace("_", " ")
            peak = peak_flops(kind, "bfloat16")
            peak_tf = peak / 1e12 if peak else None
            bwd_tf = a.get("bwd_attn_tflops_per_s")
            # the tune's TFLOP/s convention credits HALVED causal FLOPs
            # (tools/tune_flash.py: fwd = 2*B*H*S^2*D, the work a
            # causal-skipping kernel actually executes), so even a
            # perfect skipping kernel tops out at 1x the hardware peak
            # (a non-skipping kernel at <=0.5x) - at/above peak the
            # split is arithmetically impossible
            if (peak_tf is not None
                    and isinstance(bwd_tf, (int, float))
                    and bwd_tf >= peak_tf):
                suspect.append(name)
        if suspect:
            out += [
                "",
                f"NOTE: derived bwd TFLOP/s for {', '.join(suspect)} "
                "meets/exceeds this device's bf16 peak "
                f"({peak_tf:.0f}) - impossible even with causal "
                "skipping (the convention already credits only the "
                "halved causal FLOPs), so the fwd/bwd SPLIT for that "
                "impl is unreliable (the standalone fwd timing does not "
                "match the fwd embedded in the fwd+bwd program); the "
                "fwd+bwd column remains a direct measurement.",
            ]
        best = data.get("best_own")
        if best:
            out += [
                "",
                "best own blocks: "
                f"fwd ({best['bq']}, {best['bk']}), "
                f"dq ({best['bq_dq']}, {best['bk_dq']}), "
                f"dkv ({best['bq_dkv']}, {best['bk_dkv']}) - loaded "
                "automatically at matching shapes "
                "(`ops/flash.py tuned_blocks`).",
                "",
            ]
    return out


if __name__ == "__main__":
    sys.exit(main())
