"""Share of the cache positions the decode programs of the traced window read
from the KV pool that no query attended to: `serve_decode_positions_total`,
100 x (1 - live / read). `read` counts the positions of the pages the paged
decode kernel fetches (live rounded up to whole pages a row of the bucket),
or the whole padded span where the program gathers the bucket instead; a
program that does not publish `kind="read"` reads None.

The reader of the longdoc cell (moves serve_tokens_per_s)."""
from lib import servetick

POSITIONS = 'serve_decode_positions_total{kind="%s"}'


def read(obs):
    live = servetick.growth(obs, POSITIONS % "live")
    fetched = servetick.growth(obs, POSITIONS % "read")
    if live is None or not fetched:
        return None
    return 100.0 * (1.0 - live / fetched)
