"""Share of the device's busy time in Mosaic custom calls, in a cell whose
engine serves a latent cache: the paged latent decode kernel
(`ops/decode_pallas.py mla_decode_attention`, Mosaic name `mla_decode_attn`),
and a prefill attention kernel where the program has one.

The reader of the docqa cell (moves serve_tokens_per_s)."""


def read(obs):
    t = obs.get("trace") or {}
    if not t.get("mosaic_s") or not t.get("busy_s"):
        return None
    return 100.0 * t["mosaic_s"] / t["busy_s"]
