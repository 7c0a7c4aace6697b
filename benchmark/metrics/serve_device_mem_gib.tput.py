"""Peak bytes in use on the chip (`peak_bytes_in_use`; the weights and the KV
pool are nearly all of it).

The reader of the longdoc cell (moves serve_tokens_per_s)."""


def read(obs):
    peak = obs.get("memory_peak_bytes")
    return peak / 2 ** 30 if peak else None
