"""What the readers of the serving tick share: the growth of the program's
counters over the traced window (`obs["counters_traced"]`, the registry as a
scrape shows it at the window's two edges), and the least time for the decode
attention, whose work the cell's family counts from live positions
(`families/<family>/arith.py`).

The program publishes a tick's seconds and positions together, after
`serve_engine_steps_total` has counted it (`serve/scheduler.py
_publish_tick`), so the growth of a family over the growth of that counter is
a mean over whole ticks. A commit without a family reads None.
"""

from __future__ import annotations

from . import arith

STEPS = "serve_engine_steps_total"
LOOP_SECONDS = 'serve_loop_seconds_total{phase="%s"}'
KV_ITEMSIZE = 2  # bfloat16: `lib/serve.py Server` builds the pool in cfg.dtype


def growth(obs: dict, key: str):
    """By how much the counter `key` grew over the traced window; None where
    the registry does not have it."""
    a, b = obs.get("counters_traced") or ({}, {})
    if key not in b:
        return None
    return b[key] - a.get(key, 0.0)


def phase_ms_a_tick(obs: dict, phase: str):
    """Mean milliseconds of the loop thread in `phase` a tick."""
    seconds, ticks = growth(obs, LOOP_SECONDS % phase), growth(obs, STEPS)
    if seconds is None or not ticks:
        return None
    return 1e3 * seconds / ticks


def pad_pct(obs: dict, family: str):
    """Share of the positions the bucket programs were shaped for that no
    query attended to: 100 x (1 - live / padded)."""
    live = growth(obs, family + '{kind="live"}')
    padded = growth(obs, family + '{kind="padded"}')
    if live is None or not padded:
        return None
    return 100.0 * (1.0 - live / padded)


def decode_attn_least_seconds(obs: dict):
    """(least seconds for the decode attention of the traced window, which
    peak bounds it), from live positions alone: the same whatever kernel,
    bucket or layout does the work. None without the counter."""
    live = growth(obs, 'serve_decode_positions_total{kind="live"}')
    if not live:
        return None
    work = obs["family"].arith
    return arith.roofline_seconds(
        work.decode_attn_flops(obs["model"], live),
        work.decode_attn_bytes(obs["model"], live, KV_ITEMSIZE),
        obs["device_kind"])
