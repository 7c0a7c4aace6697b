"""Mean milliseconds a tick of the untraced tail that the serve loop thread
spends in `ServeEngine.step` from `_land`'s return to its own, where the
landed tick's last reference goes, and with it its arrays on the device
(`serve.release`): `serve_loop_seconds_total{phase="release"}` over
`serve_engine_steps_total`. A program that does not publish the phase reads
None.

The reader of the three serving cells (moves serve_tokens_per_s)."""
from lib import untraced


def read(obs):
    return untraced.ms_a_tick(obs, 'serve_loop_seconds_total{phase="release"}')
