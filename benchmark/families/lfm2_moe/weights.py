"""The `lfm2_moe` family's tree: its shapes from the configuration alone, in
the layout the system under test takes, and each leaf drawn from the seed;
the plain reference reads the same leaves.

The tree: `embed` (vocabulary, d; also the head: the family ties them),
`normf_scale` (d,), and the sublayers stacked BY KIND. Operators: `conv`
(`op_norm`, `w_in` (d, 3d) giving `[B ; C ; x]`, `taps` (d, conv_L_cache),
`w_out`) and `attn` (`op_norm`, `wq`, `wk`, `wv`, `wo`, `q_norm` and `k_norm`
over a head). Feed-forwards: `dense` (`ff_norm`, `w1`, `w3`, `w2`: `(silu(u
W_1) * (u W_3)) W_2`) and `moe` (`ff_norm`, `router`, `bias`, the held
experts `e_gate`, `e_up`, `e_down` on the second axis). Layer l of the model
is entry `operator_index` of its operator's stack and entry `l` or `l -
num_dense_layers` of its feed-forward's (`layers_of`).

How a leaf is drawn. Leaf i of the flattened shapes has the key `fold_in(key,
i)`; a stacked leaf draws ENTRY l of it from `fold_in(that, l)`, so that one
layer can be drawn without the others (`draw_layer`: the float32 tree, 20.7
GB at the cell's size, does not fit a chip, and the reference takes it a
layer at a time). Matrices and taps normal(0, 0.02), the projections into
the residual (`w_out`, `wo`, `w2`, `e_down`) divided by sqrt(2 layers); the
selection bias normal(0, 0.02); norm gains 1 +- 0.1, off 1 so that a path
that drops a norm is seen. THE EMBEDDING at a sixteenth of the matrices'
deviation (`EMBED_DIVISOR`): it is also the head, and the residual stream
still carries the embedding it started from, so at the matrices' own
deviation a random tied model's largest logit is its input token's own by a
wide margin: every request would repeat its prompt's last token and the
comparison of served logits would see that token and nothing else, at any
precision (at this size the token's own logit is then under a tenth of the
others' spread). The router and its bias stay float32 whatever type
is asked for (`FLOAT32`): 131,136 values a layer that decide which quarter
of a layer's output a token gets.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from lib import weights as lib

OPERATORS = {"conv": "conv", "full_attention": "attn"}
KINDS = ("conv", "attn", "dense", "moe")
INTO_RESIDUAL = ("w_out", "wo", "w2", "e_down")
FLOAT32 = ("router", "bias")
EMBED_DIVISOR = 16.0


def vocab(model: dict) -> int:
    """The ids the traffic may draw: every row of the embedding."""
    return model["vocab_size"]


def sizes(model: dict) -> dict:
    """The widths every part of the family reads, under short names."""
    ops = [OPERATORS[t] for t in model["layer_types"]]
    layers = model["num_hidden_layers"]
    if len(ops) != layers:
        raise ValueError(f"{len(ops)} layer_types for {layers} layers")
    n_dense = min(model["num_dense_layers"], layers)
    held = model["num_experts"]
    h = model["num_attention_heads"]
    return {
        "d": model["hidden_size"], "h": h, "kv": model["num_key_value_heads"],
        "hd": model.get("head_dim") or model["hidden_size"] // h,
        "taps": model["conv_L_cache"], "ff": model["intermediate_size"],
        "f": model["moe_intermediate_size"], "layers": layers, "ops": ops,
        "conv": ops.count("conv"), "attn": ops.count("attn"),
        "dense": n_dense, "moe": layers - n_dense, "held": held,
        "routed": model.get("published", {}).get("num_experts", held),
        "first": model.get("experts_held_first", 0),
        "top_k": model["num_experts_per_tok"],
    }


def layers_of(model: dict) -> list:
    """(operator kind, its index in that stack, feed-forward kind, its index
    in that stack) of every layer, in the model's order."""
    z = sizes(model)
    seen, out = {"conv": 0, "attn": 0}, []
    for l, op in enumerate(z["ops"]):
        ff = ("dense", l) if l < z["dense"] else ("moe", l - z["dense"])
        out.append((op, seen[op], *ff))
        seen[op] += 1
    return out


def layer_shapes(model: dict) -> dict:
    """kind -> name -> shape of ONE entry of that kind's stack."""
    z = sizes(model)
    d, hd = z["d"], z["hd"]
    return {
        "conv": {"op_norm": (d,), "w_in": (d, 3 * d), "taps": (d, z["taps"]),
                 "w_out": (d, d)},
        "attn": {"op_norm": (d,), "wq": (d, z["h"] * hd),
                 "wk": (d, z["kv"] * hd), "wv": (d, z["kv"] * hd),
                 "wo": (z["h"] * hd, d), "q_norm": (hd,), "k_norm": (hd,)},
        "dense": {"ff_norm": (d,), "w1": (d, z["ff"]), "w3": (d, z["ff"]),
                  "w2": (z["ff"], d)},
        "moe": {"ff_norm": (d,), "router": (d, z["routed"]),
                "bias": (z["routed"],), "e_gate": (z["held"], d, z["f"]),
                "e_up": (z["held"], d, z["f"]),
                "e_down": (z["held"], z["f"], d)},
    }


def shapes(model: dict) -> dict:
    z, per = sizes(model), layer_shapes(model)
    out = {"embed": (model["vocab_size"], z["d"]), "normf_scale": (z["d"],)}
    for kind in KINDS:
        if z[kind]:
            out[kind] = {k: (z[kind],) + s for k, s in per[kind].items()}
    return out


def draw(name: str, key, shape, model: dict):
    """One leaf (of one layer), float32."""
    x = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("norm") or name == "normf_scale":
        return 1.0 + 0.1 * x
    std = model.get("initializer_range", 0.02)
    if name in INTO_RESIDUAL:
        std /= math.sqrt(2 * model["num_hidden_layers"])
    elif name == "embed":
        std /= EMBED_DIVISOR
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
    """Inside a jit: `embed` or `normf_scale`, float32."""
    return draw(name, _leaf_keys(key, model)[(name,)], shapes(model)[name],
                model)


def make(seed: int, model: dict, dtype=jnp.float32, shardings=None):
    """The seeded tree, whole, in the type and layout asked for (the router
    and its bias float32 always)."""
    z = sizes(model)

    def cast(name, x):
        return x if name in FLOAT32 else x.astype(dtype)

    def build(key):
        out = {name: draw_top(key, model, name).astype(dtype)
               for name in ("embed", "normf_scale")}
        for kind in KINDS:
            if z[kind]:
                entries = [draw_layer(key, model, kind, i)
                           for i in range(z[kind])]
                out[kind] = {name: jnp.stack(
                    [cast(name, e[name]) for e in entries])
                    for name in entries[0]}
        return out

    return jax.jit(build, out_shardings=shardings)(lib.seed_key(seed, 1))
