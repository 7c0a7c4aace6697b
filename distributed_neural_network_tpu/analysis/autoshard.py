"""Autoshard: static cost-model search over mesh/spec/optimizer plans.

``--sharding auto`` as pure static analysis: enumerate the mesh
factorizations of a device count (dp x sp x tp for the LM family,
dp x pp for the pipeline family), derive each candidate's PartitionSpecs
from the declarative rule table (parallel/rules.py - a candidate with a
tensor axis activates the tp rules, one without deactivates them), build
the REAL step program for it (`train/lm.py lm_step_program` /
`parallel/pipeline.py pp_step_program` - the same builders training and
shardlint use), abstract-trace it with the shardlint tracer (trace.py),
and score it with the static cost model (cost.py). Nothing executes;
scoring a candidate costs one ``jax.make_jaxpr``.

Candidates whose builder or trace raises (non-divisible batch/seq/heads,
zero-with-tp, pipeline stages not dividing the layers) are pruned as
infeasible with the builder's own error as the reason; candidates over
the HBM budget are pruned by the cost model. The survivors are ranked by
score (ties broken by plan label, so ranking is deterministic) and the
winner is pinned as a checked-in PLAN manifest (analysis/plans/
<config>.json - same contract/diff idea as the collective manifests):
``tools/autoshard.py --check`` re-runs the search and fails if the top
plan drifted, exactly like shardlint's ``--check`` for collectives.

Plans record whether the winner matches the hand-written canonical mesh
(``matches_hand_config``); a blessed-better plan is a reviewed manifest
diff, not a silent change.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field

from .. import compat
from .configs import (
    BLUEPRINTS,
    TRACE_BATCH,
    TRACE_BUCKET_MB,
    TRACE_SEQ,
    _require_devices,
    _trace_cfg,
    searchable_config_names,
)
from .cost import CostWeights, score_program
from .trace import collect_trace

PLAN_SCHEMA = 1


def default_plan_dir() -> str:
    return os.path.join(os.path.dirname(__file__), "plans")


def plan_path(name: str, plan_dir: str | None = None) -> str:
    return os.path.join(plan_dir or default_plan_dir(), f"{name}.json")


# ------------------------------------------------- candidate enumeration


def lm_mesh_candidates(n_devices: int) -> list:
    """Every ordered (dp, sp, tp) with dp*sp*tp == n_devices."""
    out = []
    for dp in range(1, n_devices + 1):
        if n_devices % dp:
            continue
        rest = n_devices // dp
        for sp in range(1, rest + 1):
            if rest % sp:
                continue
            out.append({"dp": dp, "sp": sp, "tp": rest // sp})
    return out


def pp_mesh_candidates(n_devices: int) -> list:
    """Every (dp, pp) with dp*pp == n_devices and at least two stages
    (a one-stage pipeline is the plain mesh family's ground)."""
    return [
        {"dp": n_devices // pp, "pp": pp}
        for pp in range(2, n_devices + 1)
        if n_devices % pp == 0
    ]


def _plan_label(family: str, dims: dict, optimizer: str) -> str:
    axes = "x".join(f"{k}{v}" for k, v in dims.items())
    return f"{family}:{axes}:{optimizer}"


def build_candidate_program(
    family: str,
    dims: dict,
    *,
    cfg,
    batch: int,
    seq_len: int,
    optimizer: str,
    kwargs: dict | None = None,
    name: str = "candidate",
):
    """The real step program for one candidate plan (trace-only)."""
    kwargs = dict(kwargs or {})
    kwargs.setdefault("bucket_mb", TRACE_BUCKET_MB)
    if family == "lm":
        from ..train import lm as lmtrain

        _require_devices(dims["dp"] * dims["sp"] * dims["tp"])
        mesh = lmtrain.create_lm_mesh(dims["dp"], dims["sp"], dims["tp"])
        return lmtrain.lm_step_program(
            cfg, mesh, batch=batch, seq_len=seq_len, name=name,
            optimizer=optimizer, **kwargs,
        )
    if family == "pp":
        from ..parallel import pipeline as ppl

        _require_devices(dims["dp"] * dims["pp"])
        mesh = ppl.create_pp_mesh(dims["dp"], dims["pp"], 1)
        return ppl.pp_step_program(
            cfg, mesh, batch=batch, seq_len=seq_len, name=name,
            optimizer=optimizer, **kwargs,
        )
    raise ValueError(f"unknown plan family {family!r} (use 'lm' or 'pp')")


# ------------------------------------------------------------ the search


@dataclass
class RankedPlan:
    label: str
    family: str
    dims: dict
    optimizer: str
    breakdown: object = None  # CostBreakdown when traced
    infeasible_reason: str = ""

    @property
    def feasible(self) -> bool:
        return self.breakdown is not None and self.breakdown.feasible

    @property
    def score(self) -> float:
        return self.breakdown.score if self.feasible else float("inf")


@dataclass
class SearchResult:
    config: str
    family: str
    devices: int
    optimizer: str
    ranked: list = field(default_factory=list)  # feasible, best first
    infeasible: list = field(default_factory=list)  # RankedPlan, reasoned
    hand_dims: dict | None = None
    # param-footprint pricing the search scored under ("as-traced" or a
    # DTYPE_BYTES name): recorded in the plan manifest - a plan searched
    # at int8 pricing is not comparable to a bf16 one
    precision: str = "as-traced"

    @property
    def chosen(self) -> RankedPlan | None:
        return self.ranked[0] if self.ranked else None

    def matches_hand_config(self) -> bool | None:
        if self.chosen is None or self.hand_dims is None:
            return None
        return (
            self.chosen.dims == self.hand_dims
            and self.chosen.optimizer == self.optimizer
        )

    def explain(self, *, top_k: int | None = None) -> str:
        """The ranked table + per-term why breakdown for the winner."""
        lines = [
            f"{self.config}: searched {len(self.ranked) + len(self.infeasible)}"
            f" plan(s) over {self.devices} device(s), "
            f"{len(self.ranked)} feasible"
        ]
        show = self.ranked if top_k is None else self.ranked[:top_k]
        for i, p in enumerate(show):
            marker = " <- chosen" if i == 0 else ""
            hand = (
                " (hand-written mesh)"
                if self.hand_dims is not None and p.dims == self.hand_dims
                else ""
            )
            lines.append(
                f"  #{i + 1} {p.label:<26} score {p.score:>14,.1f}"
                f"{hand}{marker}"
            )
        for p in self.infeasible:
            lines.append(
                f"   - {p.label:<26} INFEASIBLE: {p.infeasible_reason}"
            )
        if self.chosen is not None:
            lines.append("why the winner:")
            lines.extend(
                "  " + ln for ln in self.chosen.breakdown.why().splitlines()
            )
        return "\n".join(lines)


def _first_line(exc: Exception) -> str:
    return f"{type(exc).__name__}: {exc}".splitlines()[0][:300]


def search_plans(
    family: str,
    *,
    cfg,
    devices: int,
    batch: int,
    seq_len: int,
    optimizer: str,
    kwargs: dict | None = None,
    optimizers: tuple | None = None,
    weights: CostWeights | None = None,
    config: str = "adhoc",
    hand_dims: dict | None = None,
) -> SearchResult:
    """Enumerate -> build -> trace -> score every candidate plan for one
    model scenario; returns the deterministic ranking (score, then label).

    ``optimizers`` widens the optimizer-layout dimension of the search
    (e.g. ("sgd", "zero") scores the ZeRO weight-update sharding of
    arXiv 2004.13336 against the replicated update); default is just the
    scenario's own optimizer, which keeps the checked-in plans stable.
    """
    result = SearchResult(
        config=config, family=family, devices=devices,
        optimizer=optimizer, hand_dims=hand_dims,
        precision=(weights.param_precision if weights is not None
                   and weights.param_precision else "as-traced"),
    )
    dims_list = (
        lm_mesh_candidates(devices) if family == "lm"
        else pp_mesh_candidates(devices)
    )
    for dims in dims_list:
        for opt in optimizers or (optimizer,):
            label = _plan_label(family, dims, opt)
            plan = RankedPlan(
                label=label, family=family, dims=dict(dims), optimizer=opt
            )
            try:
                program = build_candidate_program(
                    family, dims, cfg=cfg, batch=batch, seq_len=seq_len,
                    optimizer=opt, kwargs=kwargs, name=label,
                )
                facts = collect_trace(program.make_jaxpr())
                plan.breakdown = score_program(
                    program, facts, weights, plan=label
                )
            except Exception as e:  # pruned: divisibility, axis rules, ...
                plan.infeasible_reason = _first_line(e)
            if plan.feasible:
                result.ranked.append(plan)
            else:
                if plan.breakdown is not None:
                    plan.infeasible_reason = (
                        plan.breakdown.infeasible_reason
                    )
                result.infeasible.append(plan)
    result.ranked.sort(key=lambda p: (p.score, p.label))
    return result


def search_config(
    name: str,
    *,
    devices: int | None = None,
    weights: CostWeights | None = None,
    optimizers: tuple | None = None,
) -> SearchResult:
    """The canonical-config entry: search the scenario behind one
    shardlint config (same trace model, same step kwargs) over every
    mesh factorization of its device count."""
    try:
        bp = BLUEPRINTS[name]
    except KeyError:
        raise KeyError(
            f"unknown autoshard config {name!r}; searchable configs: "
            f"{', '.join(searchable_config_names())}"
        ) from None
    if bp["family"] not in ("lm", "pp"):
        raise ValueError(
            f"config {name!r} (family {bp['family']!r}) has no mesh "
            "factorization to search; searchable configs: "
            f"{', '.join(searchable_config_names())}"
        )
    if bp["family"] == "lm":
        hand = {"dp": bp["dp"], "sp": bp["sp"], "tp": bp["tp"]}
        n = bp["dp"] * bp["sp"] * bp["tp"]
    else:
        hand = {"dp": bp["dp"], "pp": bp["pp"]}
        n = bp["dp"] * bp["pp"]
    return search_plans(
        bp["family"], cfg=_trace_cfg(**bp.get("cfg_kwargs", {})),
        devices=devices or n,
        batch=TRACE_BATCH, seq_len=TRACE_SEQ, optimizer=bp["optimizer"],
        kwargs=bp["kwargs"], optimizers=optimizers, weights=weights,
        config=name, hand_dims=hand if devices in (None, n) else None,
    )


# --------------------------------------------------------- plan manifests


def build_plan_doc(result: SearchResult) -> dict:
    """The checked-in plan manifest for one search (analysis/plans/)."""
    import jax

    chosen = result.chosen
    if chosen is None:
        raise ValueError(
            f"{result.config}: no feasible plan to pin - "
            + "; ".join(
                f"{p.label}: {p.infeasible_reason}" for p in result.infeasible
            )
        )
    bd = chosen.breakdown
    return {
        "schema": PLAN_SCHEMA,
        "config": result.config,
        "jax_version": jax.__version__,
        "trace_mode": compat.trace_mode(),
        "family": result.family,
        "devices": result.devices,
        "hand_dims": result.hand_dims,
        "matches_hand_config": result.matches_hand_config(),
        "precision": result.precision,
        "chosen": {
            "plan": chosen.label,
            "dims": chosen.dims,
            "optimizer": chosen.optimizer,
            "score": round(float(bd.score), 3),
            "collective_bytes": int(bd.collective_bytes),
            "wire_bytes": round(float(bd.wire_bytes), 3),
            "untraced_grad_sync_bytes": round(
                float(bd.untraced_grad_sync_bytes), 3
            ),
            "peak_state_bytes": int(bd.peak_state_bytes),
        },
        "ranking": [
            {
                "plan": p.label,
                "score": round(float(p.score), 3),
                "collective_bytes": int(p.breakdown.collective_bytes),
            }
            for p in result.ranked[:5]
        ],
        "infeasible": {
            p.label: p.infeasible_reason for p in result.infeasible
        },
    }


def save_plan(doc: dict, name: str, plan_dir: str | None = None) -> str:
    path = plan_path(name, plan_dir)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True, allow_nan=False)
        f.write("\n")
    return path


def load_plan(name: str, plan_dir: str | None = None) -> dict:
    path = plan_path(name, plan_dir)
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"no plan manifest for config {name!r} at {path} - generate "
            f"one with: python tools/autoshard.py --model {name} "
            "--write-manifest"
        )
    with open(path) as f:
        return json.load(f)


def diff_plans(expected: dict, result: SearchResult) -> list:
    """Human-actionable drift between a checked-in plan and a fresh
    search (empty == conforming). Environment mismatches short-circuit
    with a regenerate instruction, like collective manifests."""
    import jax

    actual_env = {"jax_version": jax.__version__,
                  "trace_mode": compat.trace_mode()}
    for key in ("jax_version", "trace_mode"):
        if expected.get(key) != actual_env[key]:
            return [
                f"plan for {expected.get('config')!r} was written under "
                f"{key}={expected.get(key)!r} but this run has "
                f"{key}={actual_env[key]!r}: traced programs are not "
                "comparable across jax generations - regenerate with "
                "--write-manifest (docs/STATIC_ANALYSIS.md)"
            ]
    msgs = []
    if (expected.get("precision") or "as-traced") != result.precision:
        return [
            f"plan for {expected.get('config')!r} was searched under "
            f"precision={expected.get('precision') or 'as-traced'!r} but "
            f"this run priced {result.precision!r} - quantized and "
            "full-precision footprints rank differently; regenerate or "
            "drop --precision"
        ]
    if expected.get("devices") != result.devices:
        return [
            f"device count changed: plan searched {expected.get('devices')}"
            f", this run searched {result.devices} - regenerate or pass "
            "--devices"
        ]
    chosen = result.chosen
    exp = expected.get("chosen") or {}
    if chosen is None:
        return [
            "no feasible plan found, but the checked-in manifest chose "
            f"{exp.get('plan')!r}"
        ]
    if exp.get("dims") != chosen.dims or exp.get("optimizer") != chosen.optimizer:
        msgs.append(
            f"top-ranked plan changed: manifest chose {exp.get('plan')!r}, "
            f"the search now ranks {chosen.label!r} first - review and "
            "either fix the regression or bless the new plan with "
            "--write-manifest"
        )
    elif exp.get("collective_bytes") != chosen.breakdown.collective_bytes:
        msgs.append(
            f"chosen plan's collective bytes changed: "
            f"{exp.get('collective_bytes'):,} -> "
            f"{chosen.breakdown.collective_bytes:,} per step (the plan "
            "still wins, but its traced program drifted - shardlint "
            "--check should name the site; regenerate both manifests "
            "together)"
        )
    return msgs


# ------------------------------------------------------------ the driver


def run_autoshard(
    names=None,
    *,
    mode: str = "rank",
    plan_dir: str | None = None,
    devices: int | None = None,
    explain: bool = False,
    optimizers: tuple | None = None,
    weights: CostWeights | None = None,
    verbose: bool = True,
):
    """Search configs; mode: 'rank' (print the ranking), 'write' (pin the
    winner as a plan manifest), 'check' (diff the fresh winner against
    the checked-in plan). Returns (exit_code, report) - 0 conforming,
    1 drift/missing plan, 2 a search failed - mirroring run_shardlint."""
    if mode not in ("rank", "write", "check"):
        raise ValueError(f"mode must be rank/write/check, got {mode!r}")
    names = list(names) if names else searchable_config_names()
    lines = []
    worst = 0

    def fail(rc):
        nonlocal worst
        worst = max(worst, rc)

    for name in names:
        try:
            result = search_config(
                name, devices=devices, optimizers=optimizers,
                weights=weights,
            )
        except Exception as e:
            fail(2)
            lines.append(f"{name}: SEARCH FAILED - {_first_line(e)}")
            continue
        chosen = result.chosen
        if chosen is None:
            fail(2)
            lines.append(
                f"{name}: no feasible plan over {result.devices} device(s)"
            )
            for p in result.infeasible:
                lines.append(f"    {p.label}: {p.infeasible_reason}")
            continue
        hand = result.matches_hand_config()
        hand_note = (
            "matches the hand-written config" if hand
            else "DIFFERS from the hand-written config" if hand is False
            else "no hand-written baseline"
        )
        lines.append(
            f"{name}: chose {chosen.label} "
            f"(score {chosen.score:,.1f}; {len(result.ranked)} feasible / "
            f"{len(result.infeasible)} pruned; {hand_note})"
        )
        if explain or (verbose and mode == "rank"):
            lines.extend("    " + ln for ln in result.explain().splitlines())
        if mode == "write":
            path = save_plan(build_plan_doc(result), name, plan_dir)
            lines.append(f"    wrote {path}")
        elif mode == "check":
            try:
                expected = load_plan(name, plan_dir)
            except FileNotFoundError as e:
                fail(1)
                lines.append(f"    {e}")
                continue
            diffs = diff_plans(expected, result)
            if diffs:
                fail(1)
                lines.append(f"    {name}: PLAN MISMATCH:")
                lines.extend(f"      - {d}" for d in diffs)
            else:
                lines.append(f"    plan conforms ({name}.json)")
    status = {0: "OK", 1: "FAIL", 2: "SEARCH ERROR"}[worst]
    lines.append(f"autoshard: {len(names)} config(s), {status}")
    return worst, "\n".join(lines)


# ----------------------------------------- the CNN engine's trivial plan


def auto_nb_proc(batch_size: int, device_count: int) -> int:
    """The CNN engine's one free sharding choice: the batch-axis worker
    count. The largest divisor of the global batch that fits the device
    count - every worker gets an identical integer share (the engine's
    divisibility contract), on as many devices as possible."""
    if batch_size < 1 or device_count < 1:
        raise ValueError(
            f"batch_size and device_count must be >= 1, got "
            f"{batch_size}/{device_count}"
        )
    for n in range(min(batch_size, device_count), 0, -1):
        if batch_size % n == 0:
            return n
    return 1
