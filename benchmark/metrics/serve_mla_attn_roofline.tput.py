"""The latent attention kernels' share of their roofline: the least time the
chip could take for the work the traced window's Mosaic calls were given,
over the time those calls took. The work is what the program counts as handed
to them: `serve_attn_kernel_positions_total{path="decode"}` (live cached
positions of Mosaic decode calls: each row read once a layer for all heads,
and scored and weighed by every head, the family's `arith.decode_attn_flops`
/ `_bytes`) and `serve_attn_kernel_pairs_total{path="prefill"}` (live
query-key pairs of Mosaic prefill calls, `arith.prefill_attn_flops` /
`_bytes`; it does not grow where prefill attention is no Mosaic call). Each
term is the larger of its compute and memory time. The trace's summary has
one Mosaic total, so both kernels share this reader. A program without the
counters reads None.

The reader of the docqa cell (moves serve_tokens_per_s)."""
from lib import arith, servetick

DECODE = 'serve_attn_kernel_positions_total{path="decode"}'
PREFILL = 'serve_attn_kernel_pairs_total{path="prefill"}'


def read(obs):
    mosaic_s = (obs.get("trace") or {}).get("mosaic_s")
    positions = servetick.growth(obs, DECODE)
    pairs = servetick.growth(obs, PREFILL)
    if not mosaic_s or (positions is None and pairs is None):
        return None
    work, model, kind = obs["family"].arith, obs["model"], obs["device_kind"]
    least = 0.0
    if positions:
        least += arith.roofline_seconds(
            work.decode_attn_flops(model, positions),
            work.decode_attn_bytes(model, positions, servetick.KV_ITEMSIZE),
            kind)[0]
    if pairs:
        chunk = obs["traffic"]["engine"]["prefill_chunk"]
        least += arith.roofline_seconds(
            work.prefill_attn_flops(model, pairs),
            work.prefill_attn_bytes(model, pairs, chunk,
                                    servetick.KV_ITEMSIZE), kind)[0]
    return 100.0 * least / mosaic_s
