"""Share of the cache positions the decode programs of the traced window were
shaped for (batch bucket x width bucket x block size) that no query attended
to: `serve_decode_positions_total`, 100 x (1 - live / padded).

The reader of the longdoc cell (moves serve_tokens_per_s)."""
from lib import servetick


def read(obs):
    return servetick.pad_pct(obs, "serve_decode_positions_total")
