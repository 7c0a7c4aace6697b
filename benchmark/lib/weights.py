"""Seeded weights and token batches, made on the device in one jitted call.

The tree has the layout the system under test takes as its input (stacked
layers under "layers"); the plain reference reads the same tree. Nothing here
comes from the program: the shapes follow from the configuration file alone.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A key from any non-negative seed, also one past 32 signed bits."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 31), stream)


def param_shapes(d: int, n_layers: int, d_ff: int, vocab: int) -> dict:
    L = n_layers
    return {
        "embed": (vocab, d), "lnf_scale": (d,), "lnf_bias": (d,),
        "head": (d, vocab),
        "layers": {
            "ln1_scale": (L, d), "ln1_bias": (L, d),
            "wq": (L, d, d), "wk": (L, d, d), "wv": (L, d, d),
            "wo": (L, d, d),
            "ln2_scale": (L, d), "ln2_bias": (L, d),
            "w1": (L, d, d_ff), "b1": (L, d_ff),
            "w2": (L, d_ff, d), "b2": (L, d),
        },
    }


def _std(name: str, d: int, n_layers: int, d_ff: int) -> tuple:
    """(mean, std) of a leaf: GPT-2's scheme (residual projections scaled by
    1/sqrt(2L)), biases and norm gains off their neutral values so that a
    path that drops one is seen."""
    resid = 1.0 / math.sqrt(2 * n_layers)
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


def make_params(seed: int, *, d: int, n_layers: int, d_ff: int, vocab: int,
                dtype=jnp.float32, shardings=None):
    """The whole tree from one jitted call; `shardings` is a tree of
    shardings with the same structure (None = the default device)."""
    shapes = param_shapes(d, n_layers, d_ff, vocab)
    flat, treedef = jax.tree.flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))

    def build(key):
        leaves = []
        for i, (path, shape) in enumerate(flat):
            mean, std = _std(path[-1].key, d, n_layers, d_ff)
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            leaves.append((mean + std * x).astype(dtype))
        return jax.tree.unflatten(treedef, leaves)

    fn = jax.jit(build, out_shardings=shardings)
    return fn(seed_key(seed, 1))


def make_batch_fn(seed: int, *, batch: int, seq: int, vocab: int,
                  sharding=None):
    """step index -> (tokens, targets), each (batch, seq) int32: uniform ids
    below `vocab`, every row different, the target the next token."""
    key = seed_key(seed, 2)

    def build(i):
        ids = jax.random.randint(jax.random.fold_in(key, i),
                                 (batch, seq + 1), 0, vocab, jnp.int32)
        return ids[:, :-1], ids[:, 1:]

    return jax.jit(build, out_shardings=(
        None if sharding is None else (sharding, sharding)))
