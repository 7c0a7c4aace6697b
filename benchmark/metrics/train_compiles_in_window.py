"""Backend compilations (cache loads included) inside the measured and the
traced window; anything but 0 also makes the run not correct."""


def read(obs):
    return obs.get("compiles_in_window")
