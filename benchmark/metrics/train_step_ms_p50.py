"""Median time between step completions in the measured window (host clock,
one step in flight, so it is the device's step time)."""
from lib import harness


def read(obs):
    gaps = obs.get("gaps_ms")
    return harness.median(gaps) if gaps else None
