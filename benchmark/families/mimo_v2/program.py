"""The one place that names the program: what the serving engine takes as a
model of this family (`models/mimo_v2.py MiMoV2Config`). The family is
served, not trained: no training cell can name it."""

from __future__ import annotations

from distributed_neural_network_tpu.models import mimo_v2


def config(model: dict, traffic: dict, dtype):
    """The program's configuration for this model: the published keys as the
    configuration's file holds them."""
    return mimo_v2.from_published(model, dtype=dtype)
