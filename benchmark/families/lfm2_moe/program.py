"""The one place that names the program: what the serving engine takes as a
model of this family (`models/lfm2_moe.py Lfm2MoEConfig`). The family is
served, not trained: no training cell can name it."""

from __future__ import annotations

from distributed_neural_network_tpu.models import lfm2_moe


def config(model: dict, traffic: dict, dtype):
    """The program's configuration for this model: the published keys as the
    configuration's file holds them."""
    return lfm2_moe.from_published(model, dtype=dtype)
