"""The one place that names the program: what the trainer and the engine take
as a model of this family (`models/transformer.py TransformerConfig`)."""

from __future__ import annotations

from distributed_neural_network_tpu.models import transformer as tfm


def config(model: dict, traffic: dict, dtype):
    """The program's configuration for this model under this traffic: a
    training cell's traffic names the recomputation, a serving cell's does
    not."""
    remat = {k: traffic[k] for k in ("remat", "remat_policy") if k in traffic}
    return tfm.TransformerConfig(
        vocab_size=model["vocab_size"], d_model=model["n_embd"],
        n_heads=model["n_head"], n_layers=model["n_layer"],
        d_ff=model["n_inner"], dtype=dtype, **remat)
