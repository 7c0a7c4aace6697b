"""Share of the traced window in which no operation ran on the device.

The reader of the longdoc cell (moves serve_tokens_per_s)."""


def read(obs):
    t = obs.get("trace") or {}
    if not t.get("busy_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
