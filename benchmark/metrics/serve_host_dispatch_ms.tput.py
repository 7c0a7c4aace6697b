"""Mean milliseconds a tick of the untraced tail that the engine's host
phases (`prefill_host`, `decode_host`) spend in their part `dispatch` (the
`serve.dispatch` span): each prefill and decode bucket program's call up to
its return: `serve_host_seconds_total{part="dispatch"}` over
`serve_engine_steps_total`. A program that does not publish the family reads
None.

The reader of the three serving cells (moves serve_tokens_per_s)."""
from lib import untraced


def read(obs):
    return untraced.ms_a_tick(obs,
                              'serve_host_seconds_total{part="dispatch"}')
