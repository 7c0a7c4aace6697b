"""The one place that names the program: what the serving engine takes as a
model of this family (`models/pangu_ultra_moe.py PanguUltraMoEConfig`). The
family is served, not trained: no training cell can name it."""

from __future__ import annotations

from distributed_neural_network_tpu.models import pangu_ultra_moe


def config(model: dict, traffic: dict, dtype):
    """The program's configuration for this model: the published keys as the
    configuration's file holds them."""
    return pangu_ultra_moe.from_published(model, dtype=dtype)
