"""The decode attention's share of its roofline: the least time the chip
could take for the traced window's decode attention over the time its Mosaic
calls took. The work is counted from live positions
(`serve_decode_positions_total{kind="live"}`): K and V of each read once per
layer, 4 * d FLOPs each per layer (the family's `arith.decode_attn_flops`,
`_bytes`, through `lib/servetick.py`); memory bounds it, one FLOP a byte. So it reads the same whatever kernel, bucket or layout does the
work.

The reader of the longdoc cell (moves serve_tokens_per_s)."""
from lib import servetick


def read(obs):
    mosaic_s = (obs.get("trace") or {}).get("mosaic_s")
    least = servetick.decode_attn_least_seconds(obs)
    if not mosaic_s or least is None:
        return None
    return 100.0 * least[0] / mosaic_s
