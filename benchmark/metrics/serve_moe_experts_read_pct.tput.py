"""Share of the held experts whose matrices the expert products of the traced
window read: `serve_moe_experts_total{kind}`, 100 x read / held, over every
expert layer of every program dispatched. The serving layout multiplies the
tiles that own a routed pair (`parallel/moe.py moe_held_gated_serve`), so an
expert no token of the program chose is not read; at 64 tokens a decode
program with 4 of 64 experts a token, 98 of 100 are. A program without the
counter reads None.

The reader of the reason-1k cell (moves serve_tokens_per_s)."""
from lib import servetick

EXPERTS = 'serve_moe_experts_total{kind="%s"}'


def read(obs):
    read_ = servetick.growth(obs, EXPERTS % "read")
    held = servetick.growth(obs, EXPERTS % "held")
    if read_ is None or not held:
        return None
    return 100.0 * read_ / held
