"""Mean milliseconds a tick of the traced window that the serve loop thread
spends in `np.asarray(nxt)`, blocked until the tick's programs have finished:
the device's own time as the host sees it (`serve.fetch`):
`serve_loop_seconds_total{phase="fetch"}` over `serve_engine_steps_total`.

The reader of the longdoc cell (moves serve_tokens_per_s)."""
from lib import servetick


def read(obs):
    return servetick.phase_ms_a_tick(obs, "fetch")
