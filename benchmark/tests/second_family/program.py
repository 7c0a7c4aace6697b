"""The second family's program: the repo's transformer, as gpt2's is."""

from __future__ import annotations

from distributed_neural_network_tpu.models import transformer as tfm


def config(model: dict, traffic: dict, dtype):
    remat = {k: traffic[k] for k in ("remat", "remat_policy") if k in traffic}
    return tfm.TransformerConfig(
        vocab_size=model["vocab"], d_model=model["width"],
        n_heads=model["heads"], n_layers=model["depth"], d_ff=model["mlp"],
        dtype=dtype, **remat)
