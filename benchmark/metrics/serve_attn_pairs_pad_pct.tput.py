"""Share of the (query, key) pairs whose scores the prefill programs'
attention computed that its masks threw away: 100 x (1 - live / scored)
over both layer kinds of a model with full and window attention, from
`serve_attn_pairs_total{layers, kind}` (`models/mimo_v2.py attn_pairs`): a
full layer scores a chunk against the key blocks it walks and keeps the
causal part, a window layer scores it against its ring and itself and keeps
the band of the last `window` positions. A banded window prefill or a
causal block skip would lower it. A program without the counter reads None.

The reader of the mixed-32k cell (moves serve_tokens_per_s)."""
from lib import servetick

PAIRS = 'serve_attn_pairs_total{kind="%s",layers="%s"}'
LAYERS = ("full", "window")


def read(obs):
    live = [servetick.growth(obs, PAIRS % ("live", k)) for k in LAYERS]
    scored = [servetick.growth(obs, PAIRS % ("scored", k)) for k in LAYERS]
    if None in live + scored or not sum(scored):
        return None
    return 100.0 * (1.0 - sum(live) / sum(scored))
