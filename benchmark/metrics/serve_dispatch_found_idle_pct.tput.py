"""Share of the untraced tail's bucket-program dispatches that found the
device idle: `serve_dispatch_found_total{program, device}`, 100 x idle /
(idle + busy) over both programs. Just before it calls a prefill or decode
program the engine asks, without blocking, whether the pool that program
takes (the output of the program dispatched before it) is ready
(`serve/engine.py _call`); ready means the device had finished all it was
handed and waited on the host for this one. A program that does not publish
the family reads None.

The reader of the three serving cells (moves serve_tokens_per_s)."""
from lib import untraced

FOUND = 'serve_dispatch_found_total{device="%s",program="%s"}'


def read(obs):
    counts = {d: [untraced.growth(obs, FOUND % (d, p))
                  for p in ("prefill", "decode")] for d in ("idle", "busy")}
    if None in counts["idle"] + counts["busy"]:
        return None
    idle, busy = sum(counts["idle"]), sum(counts["busy"])
    if not idle + busy:
        return None
    return 100.0 * idle / (idle + busy)
