"""Share of the rows the expert products of the traced window multiplied
that no routed pair owns: `serve_moe_rows_total{kind}`, 100 x (1 - owned /
multiplied). The serving layout multiplies whole tiles of rows, each
expert's pairs starting on a tile boundary (`parallel/moe.py
moe_held_gated_serve`), so a tile's spare rows are the padding. A program
without the counter reads None.

The reader of the docqa cell (moves serve_tokens_per_s)."""
from lib import servetick

ROWS = 'serve_moe_rows_total{kind="%s"}'


def read(obs):
    owned = servetick.growth(obs, ROWS % "owned")
    multiplied = servetick.growth(obs, ROWS % "multiplied")
    if owned is None or not multiplied:
        return None
    return 100.0 * (1.0 - owned / multiplied)
