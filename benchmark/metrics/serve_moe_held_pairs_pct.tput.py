"""Share of the routed (token, expert) pairs of the traced window whose
expert this chip holds: `serve_moe_pairs_total{where}`, 100 x held / (held +
absent). Under even routing it is held / routed experts (6.25 with 16 of
256); seeded weights do not route evenly. A program without the counter
reads None.

The reader of the docqa cell (moves serve_tokens_per_s)."""
from lib import servetick

PAIRS = 'serve_moe_pairs_total{where="%s"}'


def read(obs):
    held = servetick.growth(obs, PAIRS % "held")
    absent = servetick.growth(obs, PAIRS % "absent")
    if held is None or absent is None or not held + absent:
        return None
    return 100.0 * held / (held + absent)
