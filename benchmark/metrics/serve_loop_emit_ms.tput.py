"""Mean milliseconds a tick of the traced window that the serve loop thread
spends in the per-sequence loop after the fetch with the scheduler's token
callbacks (queue puts to the SSE threads), and retiring (`serve.emit`):
`serve_loop_seconds_total{phase="emit"}` over `serve_engine_steps_total`.

The reader of the longdoc cell (moves serve_tokens_per_s)."""
from lib import servetick


def read(obs):
    return servetick.phase_ms_a_tick(obs, "emit")
