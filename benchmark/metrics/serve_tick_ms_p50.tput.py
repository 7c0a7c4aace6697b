"""Median time from one tick's end to the next in the measured window, as
`serve_engine_steps_total` shows the ticks (polled every 2 ms): one
`ServeEngine.step` and the scheduler's books around it.

The reader of the longdoc cell (moves serve_tokens_per_s)."""
from lib import harness


def read(obs):
    ticks = obs.get("ticks")
    if not ticks:
        return None
    return harness.median([1e3 * (t[1] - t[0]) for t in ticks])
