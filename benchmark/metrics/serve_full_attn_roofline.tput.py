"""The full-attention kernels' share of their roofline, in a model whose full
layers run both decode and prefill attention on Mosaic kernels: the least
time the chip could take for the work the traced window handed them, over
the time all its Mosaic calls took. Decode: the live cached positions
(`lib/servetick.py decode_attn_least_seconds`: K and V read once a layer,
memory-bound). Prefill: the live query-key pairs handed to Mosaic prefill
calls (`serve_attn_kernel_pairs_total{path="prefill"}`), each scored and
weighed by every query head in every full layer (the family's
`arith.decode_attn_flops`, which counts `pair_flops` a full layer for a
live position, the same work a pair is), at the bf16 peak; its bytes are
counted as nought, since a fetched row serves hundreds of queries and
compute bounds the term, so the sum stays a lower bound. The trace's summary
has one Mosaic total, so the two kernels share the denominator. None
without the Mosaic time, or where no prefill pair went to a kernel (a
program without one).

The reader of the mixed-32k cell (moves serve_tokens_per_s)."""
from lib import arith, servetick

PREFILL = 'serve_attn_kernel_pairs_total{path="prefill"}'


def read(obs):
    mosaic_s = (obs.get("trace") or {}).get("mosaic_s")
    pairs = servetick.growth(obs, PREFILL)
    if not mosaic_s or not pairs:
        return None
    decode = servetick.decode_attn_least_seconds(obs)
    prefill = arith.roofline_seconds(
        obs["family"].arith.decode_attn_flops(obs["model"], pairs), 0.0,
        obs["device_kind"])[0]
    return 100.0 * (prefill + (decode[0] if decode else 0.0)) / mosaic_s
