"""Steps of the measured window that took over 1.25 times the median."""
from lib import harness


def read(obs):
    gaps = obs.get("gaps_ms")
    if not gaps:
        return None
    mid = harness.median(gaps)
    return sum(1 for g in gaps if g > 1.25 * mid)
