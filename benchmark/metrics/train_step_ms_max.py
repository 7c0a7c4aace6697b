"""The slowest step of the measured window: where a stall shows."""


def read(obs):
    gaps = obs.get("gaps_ms")
    return max(gaps) if gaps else None
