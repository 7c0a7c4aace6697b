"""The whole step as a share of the HBM's peak: the least time the HBM needs
for what the traced window's programs could not avoid reading, over the time
the device was busy. The bytes are the family's `arith.program_read_bytes`
over the growth of the program's counters: every matrix outside the routed
experts once a decode and once a prefill program (`serve_decode_calls_total`,
`serve_prefill_calls_total`, every bucket), the three matrices of every held
expert that owned a row in an expert layer of a program
(`serve_moe_experts_total{kind="read"}`), and the cache rows the decode
programs fetched (`serve_decode_positions_total{kind="read"}`). A lower bound
of the bytes over the time the device took, so at or under 100. At 64
sequences a tick a decode program's time is its experts' matrices, and the
share of the FLOP peak (`serve_step_mfu_pct.tput`) says little. A program
without the counters, or a family whose `arith` does not count a program's
bytes, reads None.

The reader of the reason-1k cell (moves serve_tokens_per_s)."""
from lib import arith, servetick

CALLS = {"decode": "serve_decode_calls_total",
         "prefill": "serve_prefill_calls_total"}
EXPERTS = 'serve_moe_experts_total{kind="read"}'
POSITIONS = 'serve_decode_positions_total{kind="read"}'


def calls(obs, family):
    """The growth of a labelled counter over all its buckets; None where
    the registry has no such family."""
    a, b = obs.get("counters_traced") or ({}, {})
    keys = [k for k in b if k.startswith(family + "{")]
    if not keys:
        return None
    return sum(b[k] - a.get(k, 0.0) for k in keys)


def read(obs):
    busy_s = (obs.get("trace") or {}).get("busy_s")
    count = getattr(obs["family"].arith, "program_read_bytes", None)
    experts = servetick.growth(obs, EXPERTS)
    positions = servetick.growth(obs, POSITIONS)
    decode, prefill = calls(obs, CALLS["decode"]), calls(obs, CALLS["prefill"])
    if not busy_s or count is None or experts is None or positions is None \
            or decode is None:
        return None
    model = obs["model"]
    # the experts read are counted over both kinds of program: they go to
    # the decode term, the prefill term brings its programs' other matrices
    nbytes = count(model, "decode", experts, positions, decode,
                   servetick.KV_ITEMSIZE)
    if prefill:
        nbytes += count(model, "prefill", 0.0, 0.0, prefill,
                        servetick.KV_ITEMSIZE)
    peak = arith.peaks(obs["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * nbytes / peak / busy_s
