"""GPT-2's tree: its shapes and each leaf's mean and deviation from the
configuration alone, in the layout the system under test takes (stacked
layers under "layers"); the plain reference reads the same tree. The tree is
drawn whole, leaf i of the flattened shapes from `fold_in(key, i)`.
"""

from __future__ import annotations

import math

import jax.numpy as jnp

from lib import weights as lib


def vocab(model: dict) -> int:
    """The ids the traffic may draw: every row of the embedding."""
    return model["vocab_size"]


def shapes(model: dict) -> dict:
    d, L, d_ff, v = (model["n_embd"], model["n_layer"], model["n_inner"],
                     model["vocab_size"])
    return {
        "embed": (v, d), "lnf_scale": (d,), "lnf_bias": (d,),
        "head": (d, v),
        "layers": {
            "ln1_scale": (L, d), "ln1_bias": (L, d),
            "wq": (L, d, d), "wk": (L, d, d), "wv": (L, d, d),
            "wo": (L, d, d),
            "ln2_scale": (L, d), "ln2_bias": (L, d),
            "w1": (L, d, d_ff), "b1": (L, d_ff),
            "w2": (L, d_ff, d), "b2": (L, d),
        },
    }


def moments(model: dict):
    """name -> (mean, std) of a leaf: GPT-2's scheme (residual projections
    scaled by 1/sqrt(2L)), biases and norm gains off their neutral values so
    that a path that drops one is seen."""
    d, d_ff = model["n_embd"], model["n_inner"]
    resid = 1.0 / math.sqrt(2 * model["n_layer"])

    def of(name: str) -> tuple:
        if name == "embed":
            return 0.0, 1.0
        if name in ("wq", "wk", "wv", "w1", "head"):
            return 0.0, 1.0 / math.sqrt(d)
        if name == "wo":
            return 0.0, resid / math.sqrt(d)
        if name == "w2":
            return 0.0, resid / math.sqrt(d_ff)
        if name.endswith("_scale"):
            return 1.0, 0.02
        return 0.0, 0.02  # biases

    return of


def make(seed: int, model: dict, dtype=jnp.float32, shardings=None):
    """The seeded tree, whole, in the type and layout asked for."""
    return lib.make_tree(seed, shapes(model), moments(model), dtype,
                         shardings)
