"""Mean milliseconds a tick of the untraced tail that the engine's host
phases (`prefill_host`, `decode_host`) spend in their part `stage` (the
`serve.stage` span): the host arrays, the block table, the `jnp.asarray`
transfers, `_state_slots`, and the `_row_keys` and `_feed_tokens` programs'
dispatch: `serve_host_seconds_total{part="stage"}` over
`serve_engine_steps_total`. A program that does not publish the family reads
None.

The reader of the three serving cells (moves serve_tokens_per_s)."""
from lib import untraced


def read(obs):
    return untraced.ms_a_tick(obs, 'serve_host_seconds_total{part="stage"}')
