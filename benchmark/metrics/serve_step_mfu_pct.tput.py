"""The whole step as a share of the bf16 peak: the model's forward FLOPs as its
family counts them (`arith.forward_flops`: 2 per matmul parameter) for
every prompt and generated token the server processed in the traced window
(`serve_tokens_total{kind}` at its edges) over peak times the window. The
attention over the cache is left out (no per-tick positions yet), so it
reads a little low.

The reader of the longdoc cell (moves serve_tokens_per_s)."""
from lib import arith

KINDS = ('serve_tokens_total{kind="decode"}',
         'serve_tokens_total{kind="prefill"}')


def read(obs):
    t = obs.get("trace") or {}
    if not t.get("window_s"):
        return None
    a, b = obs["counters_traced"]
    n = sum(b.get(k, 0.0) - a.get(k, 0.0) for k in KINDS)
    if n <= 0:
        return None
    peak = arith.peaks(obs["device_kind"])["flops_bf16"]
    return 100.0 * obs["family"].arith.forward_flops(obs["model"], n, 0) / (
        peak * t["window_s"])
