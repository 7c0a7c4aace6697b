"""Seeded weights and token batches, made on the device in one jitted call.

What no family owns: the key a seed gives, the maker of a tree from its
shapes and a rule for each leaf's mean and deviation, and the token batches.
Which tree a model has, and how its leaves are drawn, is its family's
(`families/<family>/weights.py`). Nothing here comes from the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A key from any non-negative seed, also one past 32 signed bits."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 31), stream)


def is_shape(x) -> bool:
    return isinstance(x, tuple)


def draw_tree(key, shapes, moments, dtype=jnp.float32):
    """Inside a jit: a tree of normal leaves, leaf i of the flattened
    `shapes` from `fold_in(key, i)`, so the order of the leaves is part of a
    seed's meaning. `moments(name)` gives the (mean, deviation) of the leaf
    whose last path key is `name`."""
    flat, treedef = jax.tree.flatten_with_path(shapes, is_leaf=is_shape)
    leaves = []
    for i, (path, shape) in enumerate(flat):
        mean, std = moments(path[-1].key)
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        leaves.append((mean + std * x).astype(dtype))
    return jax.tree.unflatten(treedef, leaves)


def make_tree(seed: int, shapes, moments, dtype=jnp.float32, shardings=None):
    """The whole tree from one jitted call; `shardings` is a tree of
    shardings with the same structure (None = the default device)."""
    fn = jax.jit(lambda key: draw_tree(key, shapes, moments, dtype),
                 out_shardings=shardings)
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
