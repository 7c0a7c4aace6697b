"""The `pangu_ultra_moe` family's tree: its shapes from the configuration
alone, in the layout the system under test takes, and each leaf drawn from
the seed; the plain reference reads the same leaves.

The tree: `embed` (rows held, d), `head` (d, rows held), `normf_scale` (d,),
and the layers stacked BY KIND: `dense` (the leading dense layers) and `moe`
(the expert layers, the routed experts held here on the second axis of
`e_gate`, `e_up`, `e_down`). Every layer has the attention's leaves (`in_norm`,
`q_a`, `q_norm`, `q_b`, `kv_a`, `kv_norm`, `kv_b`, `o`) and the three further
sandwich norms; a dense layer `w_gate`, `w_up`, `w_down`; an expert layer
`router`, the held experts and the shared expert (`s_gate`, `s_up`, `s_down`).

How a leaf is drawn. Leaf i of the flattened shapes has the key `fold_in(key,
i)`; a stacked leaf draws LAYER l of it from `fold_in(that, l)`, so that one
layer can be drawn without the others (`draw_layer`: the float32 tree does
not fit a chip whole, and the reference takes it a layer at a time).
Matrices normal(0, `initializer_range` = 0.02 where the file gives none), the
projections into the residual (`o`, `w_down`, `e_down`, `s_down`) divided by
sqrt(2 layers); norm gains 1 +- 0.1, off 1 so that a path that drops a norm
is seen (`N_post(y)` of a gain near 1 and no norm at all differ by the scale
of y, which the comparison of logits sees at once).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from lib import weights as lib

KINDS = ("dense", "moe")
INTO_RESIDUAL = ("o", "w_down", "e_down", "s_down")


def vocab(model: dict) -> int:
    """The ids the traffic may draw: the rows of the embedding held here."""
    return model["vocab_size"]


def sizes(model: dict) -> dict:
    """The widths every part of the family reads, under short names."""
    n_dense = min(model["first_k_dense_replace"], model["num_hidden_layers"])
    held = model["n_routed_experts"]
    return {
        "d": model["hidden_size"], "h": model["num_attention_heads"],
        "nope": model["qk_nope_head_dim"], "rope": model["qk_rope_head_dim"],
        "v": model["v_head_dim"], "q_rank": model["q_lora_rank"],
        "kv_rank": model["kv_lora_rank"],
        "row": model["kv_lora_rank"] + model["qk_rope_head_dim"],
        "ff": model["intermediate_size"], "f": model["moe_intermediate_size"],
        "fs": model["n_shared_experts"] * model["moe_intermediate_size"],
        "dense": n_dense, "moe": model["num_hidden_layers"] - n_dense,
        "layers": model["num_hidden_layers"], "held": held,
        "routed": model.get("published", {}).get("n_routed_experts", held),
        "first": model.get("experts_held_first", 0),
        "top_k": model["num_experts_per_tok"],
    }


def layer_shapes(model: dict) -> dict:
    """kind -> name -> shape of ONE layer of that kind."""
    z = sizes(model)
    d, h = z["d"], z["h"]
    attn = {
        "in_norm": (d,), "q_a": (d, z["q_rank"]), "q_norm": (z["q_rank"],),
        "q_b": (z["q_rank"], h * (z["nope"] + z["rope"])),
        "kv_a": (d, z["row"]), "kv_norm": (z["kv_rank"],),
        "kv_b": (z["kv_rank"], h * (z["nope"] + z["v"])),
        "o": (h * z["v"], d), "post_attn_norm": (d,), "pre_mlp_norm": (d,),
        "post_mlp_norm": (d,),
    }
    return {
        "dense": dict(attn, w_gate=(d, z["ff"]), w_up=(d, z["ff"]),
                      w_down=(z["ff"], d)),
        "moe": dict(attn, router=(d, z["routed"]),
                    e_gate=(z["held"], d, z["f"]), e_up=(z["held"], d, z["f"]),
                    e_down=(z["held"], z["f"], d), s_gate=(d, z["fs"]),
                    s_up=(d, z["fs"]), s_down=(z["fs"], d)),
    }


def shapes(model: dict) -> dict:
    z, per = sizes(model), layer_shapes(model)
    out = {"embed": (model["vocab_size"], z["d"]),
           "head": (z["d"], model["vocab_size"]), "normf_scale": (z["d"],)}
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
    return std * x


def _leaf_keys(key, model: dict) -> dict:
    """path (tuple of names) -> the key of that leaf of the flattened shapes."""
    flat, _ = jax.tree.flatten_with_path(shapes(model), is_leaf=lib.is_shape)
    return {tuple(p.key for p in path): jax.random.fold_in(key, i)
            for i, (path, _) in enumerate(flat)}


def draw_layer(key, model: dict, kind: str, layer: int) -> dict:
    """Inside a jit: layer `layer` of the stack `kind`, float32, as the
    whole tree holds it (`key` is `lib.seed_key(seed, 1)`)."""
    keys = _leaf_keys(key, model)
    return {name: draw(name, jax.random.fold_in(keys[(kind, name)], layer),
                       shape, model)
            for name, shape in layer_shapes(model)[kind].items()}


def draw_top(key, model: dict, name: str):
    """Inside a jit: `embed`, `head` or `normf_scale`, float32."""
    return draw(name, _leaf_keys(key, model)[(name,)], shapes(model)[name],
                model)


def make(seed: int, model: dict, dtype=jnp.float32, shardings=None):
    """The seeded tree, whole, in the type and layout asked for."""
    z = sizes(model)

    def build(key):
        out = {name: draw_top(key, model, name).astype(dtype)
               for name in ("embed", "head", "normf_scale")}
        for kind in KINDS:
            if z[kind]:
                layers = [draw_layer(key, model, kind, i)
                          for i in range(z[kind])]
                out[kind] = {name: jnp.stack(
                    [lp[name].astype(dtype) for lp in layers])
                    for name in layers[0]}
        return out

    return jax.jit(build, out_shardings=shardings)(lib.seed_key(seed, 1))
