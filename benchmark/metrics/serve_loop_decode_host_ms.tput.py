"""Mean milliseconds a tick of the traced window that the serve loop thread
spends in batch selection, sampling keys, the table, the transfers and the
dispatch of the decode program up to its return (`serve.decode_host`):
`serve_loop_seconds_total{phase="decode_host"}` over
`serve_engine_steps_total`.

The reader of the longdoc cell (moves serve_tokens_per_s)."""
from lib import servetick


def read(obs):
    return servetick.phase_ms_a_tick(obs, "decode_host")
