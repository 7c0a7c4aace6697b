"""The shardlint driver: build -> trace -> lint -> manifest write/check.

Library API behind tools/shardlint.py and tests/test_shardlint.py:

    result = analyze_program(program)        # one StepProgram
    rc, report = run_shardlint(["lm_zero_overlap"], mode="check")

``run_shardlint`` returns a process-style exit code (0 conforming,
1 findings/diffs, 2 config could not be built/traced) plus a printable
report, so the CLI is a thin argv wrapper.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

from .configs import build_program, config_names
from .lint import lint_program
from .manifest import (
    build_manifest,
    diff_manifests,
    load_manifest,
    save_manifest,
)
from .trace import collect_trace


@dataclass
class AnalysisResult:
    program: object
    facts: object
    manifest: dict
    findings: list = field(default_factory=list)

    @property
    def errors(self) -> list:
        return [f for f in self.findings if f.severity == "error"]


def analyze_program(program) -> AnalysisResult:
    """Trace one StepProgram and run every lint family over it."""
    facts = collect_trace(program.make_jaxpr())
    return AnalysisResult(
        program=program,
        facts=facts,
        manifest=build_manifest(program, facts),
        findings=lint_program(program, facts),
    )


def explain_sites(facts) -> list:
    """Per-collective-site provenance table lines: op, axes, bytes/call,
    static multiplicity, dynamic flag, and WHERE the site lives (the
    jaxpr nesting recorded by the tracer) - ``shardlint --explain``."""
    if not facts.sites:
        return ["    (no collective sites)"]
    lines = [
        f"    {'op':<16} {'axes':<12} {'B/call':>10} {'count':>6} "
        f"{'dyn':>4}  where"
    ]
    for c in facts.sites:
        lines.append(
            f"    {c.op:<16} {','.join(c.axes) or '-':<12} "
            f"{c.bytes_per_call:>10,} {c.count:>6} "
            f"{'yes' if c.dynamic else '-':>4}  {c.path or '(top level)'}"
        )
    dyn = facts.dynamic_collective_bytes_per_iter()
    if dyn:
        lines.append(
            f"    dynamic sites move {dyn:,} B per while-loop iteration "
            "(excluded from the per-step total)"
        )
    return lines


def _run_one_config(
    name: str,
    mode: str,
    manifest_dir: str | None,
    verbose: bool,
    explain: bool,
):
    """One config's full build -> trace -> lint -> manifest pass:
    (exit_code, report_lines). Self-contained so `run_shardlint` can
    fan configs out over worker threads (tracing is abstract and
    side-effect free; manifest writes land in per-config files)."""
    t0 = time.perf_counter()
    try:
        program = build_program(name)
        result = analyze_program(program)
    except Exception as e:
        return 2, [f"{name}: TRACE FAILED - {type(e).__name__}: {e}"]
    dt = time.perf_counter() - t0
    rc = 0
    lines = []
    facts = result.facts
    summary = (
        f"{name}: {sum(c.count for c in facts.collectives)} collective "
        f"call(s), {facts.total_collective_bytes():,} B/step, "
        f"{len(result.findings)} finding(s) [{dt:.1f}s]"
    )
    if explain:
        lines.append(summary)
        lines.extend(explain_sites(facts))
    elif verbose:
        lines.append(summary)
        for c in facts.collectives:
            dyn = " DYNAMIC" if c.dynamic else ""
            lines.append(
                f"    {c.op:<16} axes={','.join(c.axes) or '-'}  "
                f"x{c.count:<4} {c.bytes_per_call:>10,} B/call{dyn}"
            )
    for f in result.findings:
        lines.append(f"    {f}")
    if result.errors:
        rc = 1
    if mode == "write":
        if result.errors:
            lines.append(
                f"    {name}: NOT writing manifest while lint errors "
                "are outstanding"
            )
        else:
            path = save_manifest(result.manifest, name, manifest_dir)
            lines.append(f"    wrote {path}")
    elif mode == "check":
        try:
            expected = load_manifest(name, manifest_dir)
        except FileNotFoundError as e:
            return max(rc, 1), lines + [f"    {e}"]
        diffs = diff_manifests(expected, result.manifest)
        if diffs:
            rc = max(rc, 1)
            lines.append(f"    {name}: MANIFEST MISMATCH:")
            lines.extend(f"      - {d}" for d in diffs)
        else:
            lines.append(f"    manifest conforms ({name}.json)")
    return rc, lines


def run_shardlint(
    names=None,
    *,
    mode: str = "lint",
    manifest_dir: str | None = None,
    verbose: bool = True,
    explain: bool = False,
    jobs: int = 1,
):
    """Analyze configs; mode: 'lint' (no manifest I/O), 'write' (regenerate
    manifests), 'check' (diff against checked-in manifests). Returns
    (exit_code, report_str). ``explain=True`` prints the per-site
    provenance table (op, axes, bytes, multiplicity, enclosing jaxprs)
    instead of the merged per-collective summary.

    ``jobs > 1`` traces configs on a thread pool (abstract tracing
    holds the GIL only in bursts, so the serial full-sweep wall time -
    the CI static-check's dominant cost - drops with real parallelism
    on program-building numpy/XLA work). The report is rendered in
    input order regardless of completion order, so line order, verdicts,
    and the exit code match a serial run (only the per-config wall-time
    stamps differ)."""
    if mode not in ("lint", "write", "check"):
        raise ValueError(f"mode must be lint/write/check, got {mode!r}")
    names = list(names) if names else config_names()
    jobs = max(1, int(jobs))
    if jobs == 1 or len(names) <= 1:
        results = [
            _run_one_config(name, mode, manifest_dir, verbose, explain)
            for name in names
        ]
    else:
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(
            max_workers=min(jobs, len(names)),
            thread_name_prefix="shardlint",
        ) as pool:
            results = list(pool.map(
                lambda name: _run_one_config(
                    name, mode, manifest_dir, verbose, explain
                ),
                names,
            ))
    worst = max((rc for rc, _ in results), default=0)
    lines = [ln for _, chunk in results for ln in chunk]
    status = {0: "OK", 1: "FAIL", 2: "TRACE ERROR"}[worst]
    lines.append(f"shardlint: {len(names)} config(s), {status}")
    return worst, "\n".join(lines)
