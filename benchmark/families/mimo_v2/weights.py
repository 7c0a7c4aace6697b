"""The `mimo_v2` family's tree: its shapes from the configuration alone, in
the layout the system under test takes, and each leaf drawn from the seed;
the plain reference reads the same leaves.

The tree: `embed` and `head` (vocabulary rows, d; untied), `normf_scale`
(d,), and the sublayers stacked BY KIND. Operators: `full` and `window`
(`op_norm`, `w_qkv` (d, H qk + KV (qk + v)) giving `[q ; k ; v]` with the
kind's KV heads, `w_o` (H v, d); a window layer's `sink` (H,) too).
Feed-forwards: `dense` (`ff_norm`, `w1`, `w3`, `w2`: `(silu(u W_1) * (u
W_3)) W_2`) and `moe` (`ff_norm`, `router`, `bias`, the held experts
`e_gate`, `e_up`, `e_down` on the second axis). Published layer l is a window
layer where `hybrid_layer_pattern[l]` is 1, and an expert layer where
`moe_layer_freq[l]` is 1; its entries are the next of each stack
(`layers_of`).

How a leaf is drawn. Leaf i of the flattened shapes has the key `fold_in(key,
i)`; a stacked leaf draws ENTRY l of it from `fold_in(that, l)`, so that one
layer can be drawn without the others (`draw_layer`: the float32 tree, 13.7
GB at the cell's size, and the reference takes it a layer at a time).
Matrices normal(0, 0.02), the projections into the residual (`w_o`, `w2`,
`e_down`) divided by sqrt(2 layers); the selection bias normal(0, 0.02); the
sink logits normal(0, 1), so that a window layer's sink takes a share a
dropped sink would be seen by; norm gains 1 +- 0.1, off 1 so that a path
that drops a norm is seen. The router, its bias and the sinks stay float32
whatever type is asked for (`FLOAT32`).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from lib import weights as lib

OPERATORS = ("full", "window")
KINDS = ("full", "window", "dense", "moe")
INTO_RESIDUAL = ("w_o", "w2", "e_down")
FLOAT32 = ("router", "bias", "sink")


def vocab(model: dict) -> int:
    """The ids the traffic may draw: every row of the embedding's slice."""
    return model["vocab_size"]


def sizes(model: dict) -> dict:
    """The widths every part of the family reads, under short names."""
    layers = model["num_hidden_layers"]
    ops = ["window" if t else "full" for t in model["hybrid_layer_pattern"]]
    ffs = ["moe" if f else "dense" for f in model["moe_layer_freq"]]
    if not len(ops) == len(ffs) == layers:
        raise ValueError(f"{len(ops)} / {len(ffs)} pattern entries for "
                         f"{layers} layers")
    held = model["n_routed_experts"]
    qk = model["head_dim"]
    return {
        "d": model["hidden_size"], "h": model["num_attention_heads"],
        "kv_full": model["num_key_value_heads"],
        "kv_window": model["swa_num_key_value_heads"],
        "qk": qk, "v": model["v_head_dim"],
        "r": int(qk * model["partial_rotary_factor"]),
        "window_len": model["sliding_window"],
        "ff": model["intermediate_size"], "f": model["moe_intermediate_size"],
        "layers": layers, "ops": ops, "ffs": ffs,
        "full": ops.count("full"), "window": ops.count("window"),
        "dense": ffs.count("dense"), "moe": ffs.count("moe"), "held": held,
        "routed": model.get("published", {}).get("n_routed_experts", held),
        "first": model.get("experts_held_first", 0),
        "top_k": model["num_experts_per_tok"],
    }


def layers_of(model: dict) -> list:
    """(operator kind, its index in that stack, feed-forward kind, its index
    in that stack) of every layer, in the model's order."""
    z = sizes(model)
    seen, out = dict.fromkeys(KINDS, 0), []
    for op, ff in zip(z["ops"], z["ffs"]):
        out.append((op, seen[op], ff, seen[ff]))
        seen[op] += 1
        seen[ff] += 1
    return out


def layer_shapes(model: dict) -> dict:
    """kind -> name -> shape of ONE entry of that kind's stack."""
    z = sizes(model)
    d, h = z["d"], z["h"]

    def attn(kv):
        return {"op_norm": (d,), "w_qkv": (d, h * z["qk"] + kv * (
            z["qk"] + z["v"])), "w_o": (h * z["v"], d)}

    return {
        "full": attn(z["kv_full"]),
        "window": dict(attn(z["kv_window"]), sink=(h,)),
        "dense": {"ff_norm": (d,), "w1": (d, z["ff"]), "w3": (d, z["ff"]),
                  "w2": (z["ff"], d)},
        "moe": {"ff_norm": (d,), "router": (d, z["routed"]),
                "bias": (z["routed"],), "e_gate": (z["held"], d, z["f"]),
                "e_up": (z["held"], d, z["f"]),
                "e_down": (z["held"], z["f"], d)},
    }


def shapes(model: dict) -> dict:
    z, per = sizes(model), layer_shapes(model)
    out = {"embed": (model["vocab_size"], z["d"]),
           "head": (model["vocab_size"], z["d"]), "normf_scale": (z["d"],)}
    for kind in KINDS:
        if z[kind]:
            out[kind] = {k: (z[kind],) + s for k, s in per[kind].items()}
    return out


def draw(name: str, key, shape, model: dict):
    """One leaf (of one layer), float32."""
    x = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("norm") or name == "normf_scale":
        return 1.0 + 0.1 * x
    if name == "sink":
        return x
    std = 0.02
    if name in INTO_RESIDUAL:
        std /= math.sqrt(2 * model["num_hidden_layers"])
    return std * x


def _leaf_keys(key, model: dict) -> dict:
    """path (tuple of names) -> the key of that leaf of the flattened shapes."""
    flat, _ = jax.tree.flatten_with_path(shapes(model), is_leaf=lib.is_shape)
    return {tuple(p.key for p in path): jax.random.fold_in(key, i)
            for i, (path, _) in enumerate(flat)}


def draw_layer(key, model: dict, kind: str, entry: int) -> dict:
    """Inside a jit: entry `entry` of the stack `kind`, float32, as the
    whole tree holds it (`key` is `lib.seed_key(seed, 1)`)."""
    keys = _leaf_keys(key, model)
    return {name: draw(name, jax.random.fold_in(keys[(kind, name)], entry),
                       shape, model)
            for name, shape in layer_shapes(model)[kind].items()}


def draw_top(key, model: dict, name: str):
    """Inside a jit: `embed`, `head` or `normf_scale`, float32."""
    return draw(name, _leaf_keys(key, model)[(name,)], shapes(model)[name],
                model)


def make(seed: int, model: dict, dtype=jnp.float32, shardings=None):
    """The seeded tree, whole, in the type and layout asked for (the router,
    its bias and the sinks float32 always)."""
    z = sizes(model)

    def cast(name, x):
        return x if name in FLOAT32 else x.astype(dtype)

    def build(key):
        out = {name: draw_top(key, model, name).astype(dtype)
               for name in ("embed", "head", "normf_scale")}
        for kind in KINDS:
            if z[kind]:
                entries = [draw_layer(key, model, kind, i)
                           for i in range(z[kind])]
                out[kind] = {name: jnp.stack(
                    [cast(name, e[name]) for e in entries])
                    for name in entries[0]}
        return out

    return jax.jit(build, out_shardings=shardings)(lib.seed_key(seed, 1))
