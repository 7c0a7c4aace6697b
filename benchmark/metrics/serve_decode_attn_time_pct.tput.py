"""Share of the device's busy time in Mosaic custom calls: the paged decode
kernel (`ops/decode_pallas.py`), the engine's only Pallas call.

The reader of the longdoc cell (moves serve_tokens_per_s)."""


def read(obs):
    t = obs.get("trace") or {}
    if not t.get("mosaic_s") or not t.get("busy_s"):
        return None
    return 100.0 * t["mosaic_s"] / t["busy_s"]
