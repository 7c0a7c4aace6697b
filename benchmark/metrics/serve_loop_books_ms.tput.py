"""Mean milliseconds a tick of the traced window that the serve loop thread
spends in the scheduler's books after a step: counters, per-request traces, the
ledger's adds and publishing, the heartbeat (`serve.books`):
`serve_loop_seconds_total{phase="books"}` over `serve_engine_steps_total`.

The reader of the longdoc cell (moves serve_tokens_per_s)."""
from lib import servetick


def read(obs):
    return servetick.phase_ms_a_tick(obs, "books")
