"""Share of the device's busy time in collective operations."""


def read(obs):
    t = obs.get("trace") or {}
    if not t.get("collective_s") or not t.get("busy_s"):
        return None
    return 100.0 * t["collective_s"] / t["busy_s"]
