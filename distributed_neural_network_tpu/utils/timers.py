"""Phase-time instrumentation with proper device fencing.

Parity with the reference's five module-global wall-clock accumulators
(`data_parallelism_train.py:33-37`): data_loading, training, evaluation, and
communication (parent/children merged - there is no parent process here).
The reference's methodology flaw (report section 6.1: comm time measured
around the pickle call, not the blocking wait) is fixed by fencing every
phase with `jax.block_until_ready` on the phase's outputs before reading the
clock - asynchronous dispatch otherwise attributes device time to whichever
phase happens to block first.
"""

from __future__ import annotations

import time
from collections import defaultdict
from contextlib import contextmanager

import jax

# canonical phase names (reference globals, data_parallelism_train.py:33-37)
DATA_LOADING = "data_loading"
TRAINING = "training"
EVALUATION = "evaluation"
COMMUNICATION = "communication"

# Default report ordering: the reference's five accumulators - data loading,
# training, evaluation, parent comm, children comm - with the two comm
# accumulators merged (one mesh, no parent process), hence four names here.
CANONICAL_PHASES = (DATA_LOADING, TRAINING, EVALUATION, COMMUNICATION)

# the reference's stdout phrasing per phase (data_parallelism_train.py
# prints; utils/logfiles.py keeps the byte-compatible *file* variants)
REPORT_LABELS = {
    DATA_LOADING: "Train data loading time",
    TRAINING: "Time spent on training",
    EVALUATION: "Time spent on evaluation",
    COMMUNICATION: "Time spent on parent communication and param sync",
}


def hard_block(tree) -> None:
    """Wait until every array in `tree` has been computed.

    `jax.block_until_ready` under the name the timed phases call. The
    *fence* phase of `chip_smoke.py` times it against a value fetch on ten
    chained 8192^3 bf16 matmuls: on the local chip the two agree, so the
    fetch this once added is gone.
    """
    jax.block_until_ready(tree)


class PhaseTimers:
    """Accumulating wall-clock timers keyed by phase name."""

    def __init__(self):
        self.totals: dict[str, float] = defaultdict(float)

    @contextmanager
    def phase(self, name: str, fence=None):
        """Time a block; `fence` (any pytree of arrays) is block_until_ready'd
        before the clock stops, so device work is charged to this phase."""
        start = time.perf_counter()
        holder = _FenceHolder()
        try:
            yield holder
        finally:
            target = holder.value if holder.value is not None else fence
            if target is not None:
                hard_block(target)
            self.totals[name] += time.perf_counter() - start

    def add(self, name: str, seconds: float) -> None:
        self.totals[name] += seconds

    def get(self, name: str) -> float:
        return self.totals.get(name, 0.0)

    def summary(self) -> dict[str, float]:
        return dict(self.totals)

    def merge(self, other: "PhaseTimers") -> "PhaseTimers":
        """Accumulate another timer set into this one (e.g. per-worker or
        per-stage timers folded into a run total); returns self."""
        for name, seconds in other.summary().items():
            self.totals[name] += seconds
        return self

    def report(self) -> str:
        """The canonical phase-summary block, one line per phase.

        Canonical phases print first in the reference's order and phrasing
        (always, so consumers can diff reports line-by-line even when a
        phase never ran); any extra phases follow alphabetically as
        ``<name>: <seconds>``. This is the ONE formatter behind the CLI /
        measure printouts - entry points must not hand-roll their own.
        """
        lines = [
            f"{REPORT_LABELS[name]}: {self.totals.get(name, 0.0)}"
            for name in CANONICAL_PHASES
        ]
        for name in sorted(set(self.totals) - set(CANONICAL_PHASES)):
            lines.append(f"{name}: {self.totals[name]}")
        return "\n".join(lines)


class _FenceHolder:
    """`with timers.phase(...) as t: t.value = outputs` registers the fence."""

    value = None
