"""Sequences preempted on KV pressure in the window
(`serve_preemptions_total` at its edges).

The reader of the longdoc cell (moves serve_tokens_per_s)."""


def read(obs):
    a, b = obs["counters_window"]
    key = "serve_preemptions_total"
    return b.get(key, 0.0) - a.get(key, 0.0)
