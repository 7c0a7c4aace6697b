"""The one place that names the program: what the trainer takes as a model of
this family (`models/nemotron_h.py NemotronHConfig`). The serving engine
does not run the family yet, so no serving cell can name it."""

from __future__ import annotations

from distributed_neural_network_tpu.models import nemotron_h


def config(model: dict, traffic: dict, dtype):
    """The program's configuration for this model under this traffic: the
    published keys as the configuration's file holds them, and the
    recomputation the training traffic names."""
    return nemotron_h.from_published(
        model, dtype=dtype, remat=traffic.get("remat", False),
        remat_policy=traffic.get("remat_policy", ""))
