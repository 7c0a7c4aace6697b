"""What the readers of the serving tick's untraced tail share: the growth of
the program's counters from the traced window's end (`obs["counters_traced"]`
[1], 4 s after the measured window opened) to the measured window's end
(`obs["counters_window"][1]`). That is about 26 s of the same run's ticks
that no profile records; the profile's export (`jax.profiler.stop_trace`)
runs at its start, 13-25 s of it on the chip, and stretches the host's
phases there: in the longdoc and reason-1k cells the tail reads up to 46 %
above a wholly untraced window, in docqa within 10 %, so `BENCHMARK.json`
lists the readers for docqa alone (PERF.md section 3, PR 38).

The program publishes a tick's seconds, host parts and found states together,
after `serve_engine_steps_total` has counted it (`serve/scheduler.py
_publish_tick`), so the growth of a family over the growth of that counter is
a mean over whole ticks. A commit without a family reads None.
"""

from __future__ import annotations

STEPS = "serve_engine_steps_total"


def growth(obs: dict, key: str):
    """By how much the counter `key` grew over the untraced tail; None where
    the registry does not have it or the run was not traced."""
    traced, window = obs.get("counters_traced"), obs.get("counters_window")
    if not traced or not window or key not in window[1]:
        return None
    return window[1][key] - traced[1].get(key, 0.0)


def ms_a_tick(obs: dict, key: str):
    """Mean milliseconds a tick of the untraced tail on the seconds counter
    `key`."""
    seconds, ticks = growth(obs, key), growth(obs, STEPS)
    if seconds is None or not ticks:
        return None
    return 1e3 * seconds / ticks
