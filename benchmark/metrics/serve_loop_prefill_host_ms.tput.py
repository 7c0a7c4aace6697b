"""Mean milliseconds a tick of the traced window that the serve loop thread
spends in the chunked-prefill loop on the host: blocks, token and table arrays,
transfers and the dispatch of the prefill program up to its return, without
waiting for the device (`serve.prefill_host`):
`serve_loop_seconds_total{phase="prefill_host"}` over
`serve_engine_steps_total`.

The reader of the longdoc cell (moves serve_tokens_per_s)."""
from lib import servetick


def read(obs):
    return servetick.phase_ms_a_tick(obs, "prefill_host")
