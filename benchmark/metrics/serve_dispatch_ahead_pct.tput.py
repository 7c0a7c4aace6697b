"""Share of the traced window's ticks whose programs the engine dispatched
before it fetched the tokens of the tick before, so that the device had them
queued behind the ones it was running: `serve_dispatch_ahead_total{outcome}`,
100 x ahead / (ahead + drained). The others were dispatched with nothing in
flight (the first tick after idleness, a speculative engine's, the one after a
tick that had nothing to dispatch). A program that does not publish the family
reads None.

The reader of the two serving cells (moves serve_tokens_per_s)."""
from lib import servetick

OUTCOME = 'serve_dispatch_ahead_total{outcome="%s"}'


def read(obs):
    ahead = servetick.growth(obs, OUTCOME % "ahead")
    drained = servetick.growth(obs, OUTCOME % "drained")
    if ahead is None or drained is None or not ahead + drained:
        return None
    return 100.0 * ahead / (ahead + drained)
