"""Median time to first token of the requests sent in the window (closed loop:
recorded, not judged)."""
from lib import harness


def read(obs):
    xs = obs["lat"]["ttft_ms"]
    return harness.median(xs) if xs else None
