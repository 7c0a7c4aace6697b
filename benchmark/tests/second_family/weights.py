"""A second family for the test that adds an architecture from files alone:
GPT-2's tree under other names for its sizes, with weights of its own, drawn a
layer at a time (`fold_in` by layer, then by leaf). The program is handed the
whole tree stacked from those draws; the reference takes them one by one."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from lib import weights as lib


def vocab(model: dict) -> int:
    return model["vocab"]


def top_shapes(model: dict) -> dict:
    d, v = model["width"], model["vocab"]
    return {"embed": (v, d), "lnf_scale": (d,), "lnf_bias": (d,),
            "head": (d, v)}


def layer_shapes(model: dict) -> dict:
    d, f = model["width"], model["mlp"]
    return {"ln1_scale": (d,), "ln1_bias": (d,),
            "wq": (d, d), "wk": (d, d), "wv": (d, d), "wo": (d, d),
            "ln2_scale": (d,), "ln2_bias": (d,),
            "w1": (d, f), "b1": (f,), "w2": (f, d), "b2": (d,)}


def shapes(model: dict) -> dict:
    """The stacked tree's shapes, as the program takes it."""
    L = model["depth"]
    return dict(top_shapes(model), layers={
        k: (L,) + s for k, s in layer_shapes(model).items()})


def moments(model: dict):
    d, f = model["width"], model["mlp"]
    resid = 1.0 / math.sqrt(2 * model["depth"])
    std = {"embed": 1.0, "wo": resid / math.sqrt(d),
           "w2": resid / math.sqrt(f)}

    def of(name: str) -> tuple:
        if name.endswith("_scale"):
            return 1.0, 0.05
        if name.endswith("_bias") or name in ("b1", "b2"):
            return 0.0, 0.05
        return 0.0, std.get(name, 1.0 / math.sqrt(d))

    return of


@functools.lru_cache(maxsize=None)
def _drawer(sizes: tuple, which: str, dtype):
    model = dict(sizes)
    shp = top_shapes(model) if which == "top" else layer_shapes(model)
    return jax.jit(lambda key: lib.draw_tree(key, shp, moments(model), dtype))


def _sizes(model: dict) -> tuple:
    return tuple((k, model[k]) for k in ("width", "depth", "heads", "mlp",
                                         "vocab"))


def top(seed: int, model: dict, dtype=jnp.float32) -> dict:
    """Embedding, final norm and head: layer 0 of the seed's stream."""
    key = jax.random.fold_in(lib.seed_key(seed, 1), 0)
    return _drawer(_sizes(model), "top", dtype)(key)


def layer(seed: int, model: dict, i: int, dtype=jnp.float32) -> dict:
    """Block i's leaves alone, from `fold_in(key, 1 + i)`."""
    key = jax.random.fold_in(lib.seed_key(seed, 1), 1 + i)
    return _drawer(_sizes(model), "layer", dtype)(key)


def make(seed: int, model: dict, dtype=jnp.float32, shardings=None):
    """The whole tree, stacked from the same draws a layer at a time."""
    layers = [layer(seed, model, i, dtype) for i in range(model["depth"])]
    tree = dict(top(seed, model, dtype), layers={
        k: jnp.stack([lp[k] for lp in layers]) for k in layers[0]})
    return tree if shardings is None else jax.device_put(tree, shardings)
