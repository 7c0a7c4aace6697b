"""Mean milliseconds a tick of the traced window that the serve loop thread
spends in enacting cancels, re-admitting preempted sequences and admitting
queued requests (`serve.admit`; the interval the goodput ledger reads as
`batch_formation_idle`): `serve_loop_seconds_total{phase="admit"}` over
`serve_engine_steps_total`.

The reader of the longdoc cell (moves serve_tokens_per_s)."""
from lib import servetick


def read(obs):
    return servetick.phase_ms_a_tick(obs, "admit")
