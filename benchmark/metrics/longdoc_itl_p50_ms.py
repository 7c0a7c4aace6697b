"""Median gap between streamed tokens of the requests sent in the window
(closed loop: recorded, not judged)."""
from lib import harness


def read(obs):
    xs = obs["lat"]["itl_ms"]
    return harness.median(xs) if xs else None
