"""Backend compilations inside the measured window; anything but 0 also makes
the run not correct.

The reader of the longdoc cell (moves serve_tokens_per_s)."""


def read(obs):
    return obs.get("compiles_in_window")
