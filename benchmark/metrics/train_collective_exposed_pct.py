"""Share of the traced window in collectives with no compute on that chip."""


def read(obs):
    t = obs.get("trace") or {}
    if not t.get("collective_s") or not t.get("window_s"):
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]
