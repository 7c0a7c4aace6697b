"""Share of the device's busy time spent in Mosaic custom calls: the flash
attention kernels (`ops/flash_pallas.py`), the step's only Pallas calls."""


def read(obs):
    t = obs.get("trace") or {}
    if not t.get("mosaic_s") or not t.get("busy_s"):
        return None
    return 100.0 * t["mosaic_s"] / t["busy_s"]
