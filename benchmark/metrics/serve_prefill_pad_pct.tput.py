"""Share of the (query, cache position) pairs the prefill programs of the
traced window were shaped for (chunk bucket x width bucket x block size) that
are not a prompt token attending to itself or one before it:
`serve_prefill_positions_total`, 100 x (1 - live / padded).

The reader of the longdoc cell (moves serve_tokens_per_s)."""
from lib import servetick


def read(obs):
    return servetick.pad_pct(obs, "serve_prefill_positions_total")
