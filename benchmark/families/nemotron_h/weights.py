"""The `nemotron_h` family's tree: its shapes from the configuration alone, in
the layout the system under test takes, and each leaf drawn as the family
initialises it; the plain reference reads the same tree. Leaf i of the
flattened shapes is drawn from `fold_in(key, i)`.

The tree: `embed` (rows held, d), `head` (d, rows held), `normf_scale` (d,),
and `layers`, stacked BY KIND in pattern order: `m_*` (Mamba-2 layers), `e_*`
(expert layers, the routed experts held here on the second axis of `e_up` and
`e_down`), `a_*` (attention layers).

How a leaf is drawn (`nemotron_h`'s own `_init_weights`, with what it leaves
to PyTorch's defaults): matrices normal(0, 0.02); the out-projections
(`m_out`, `a_wo`, `e_down`, `e_shared_down`) divided by sqrt(layers)
(`rescale_prenorm_residual`); `m_dt_bias` the inverse softplus of a step drawn
log-uniformly in [`time_step_min`, `time_step_max`] and clamped at
`time_step_floor`; `m_a_log` = log(1..heads); `m_d` = 1; the depthwise
convolution and its bias uniform in +-1/sqrt(kernel); norm gains 1 +- 0.02
and the router's selection bias 0 +- `init.selection_bias_std` (0.1 where the
file gives none), off their neutral values so that a path that drops one is
seen.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from lib import weights as lib

KINDS = {"M": "m", "E": "e", "*": "a"}
OUT_PROJECTIONS = ("m_out", "a_wo", "e_down", "e_shared_down")


def vocab(model: dict) -> int:
    """The ids the traffic may draw: the rows of the embedding held here."""
    return model["vocab_size"]


def pattern(model: dict) -> str:
    return model["hybrid_override_pattern"]


def sizes(model: dict) -> dict:
    """The widths every part of the family reads, under short names."""
    h, p = model["mamba_num_heads"], model["mamba_head_dim"]
    g, n = model["n_groups"], model["ssm_state_size"]
    return {
        "d": model["hidden_size"], "h": h, "p": p, "g": g, "n": n,
        "d_inner": h * p, "conv_dim": h * p + 2 * g * n,
        "conv_kernel": model["conv_kernel"],
        "heads": model["num_attention_heads"],
        "kv_heads": model["num_key_value_heads"],
        "head_dim": model["head_dim"],
        "held": model["n_routed_experts"],
        "routed": model.get("published", {}).get(
            "n_routed_experts", model["n_routed_experts"]),
        "first": model.get("experts_held_first", 0),
        "top_k": model["num_experts_per_tok"],
        "f": model["moe_intermediate_size"],
        "fs": model["moe_shared_expert_intermediate_size"],
    }


def shapes(model: dict) -> dict:
    z, pat = sizes(model), pattern(model)
    d, di, cd = z["d"], z["d_inner"], z["conv_dim"]
    hq, hkv = z["heads"] * z["head_dim"], z["kv_heads"] * z["head_dim"]
    kinds = {
        "M": {"m_norm": (d,), "m_in": (d, di + cd + z["h"]),
              "m_conv_w": (z["conv_kernel"], cd), "m_conv_b": (cd,),
              "m_dt_bias": (z["h"],), "m_a_log": (z["h"],), "m_d": (z["h"],),
              "m_gnorm": (di,), "m_out": (di, d)},
        "E": {"e_norm": (d,), "e_router": (d, z["routed"]),
              "e_bias": (z["routed"],), "e_up": (z["held"], d, z["f"]),
              "e_down": (z["held"], z["f"], d),
              "e_shared_up": (d, z["fs"]), "e_shared_down": (z["fs"], d)},
        "*": {"a_norm": (d,), "a_wq": (d, hq), "a_wk": (d, hkv),
              "a_wv": (d, hkv), "a_wo": (hq, d)},
    }
    layers = {name: (pat.count(letter),) + shape
              for letter, leaves in kinds.items() if pat.count(letter)
              for name, shape in leaves.items()}
    v = model["vocab_size"]
    return {"embed": (v, d), "head": (d, v), "normf_scale": (d,),
            "layers": layers}


def draw(name: str, key, shape, model: dict):
    """One leaf, float32."""
    f32 = jnp.float32
    if name == "m_dt_bias":
        lo, hi = math.log(model["time_step_min"]), math.log(
            model["time_step_max"])
        dt = jnp.exp(jax.random.uniform(key, shape, f32) * (hi - lo) + lo)
        dt = jnp.maximum(dt, model["time_step_floor"])
        return dt + jnp.log(-jnp.expm1(-dt))
    if name == "m_a_log":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[-1] + 1, dtype=f32)), shape)
    if name == "m_d":
        return jnp.ones(shape, f32)
    if name in ("m_conv_w", "m_conv_b"):
        bound = 1.0 / math.sqrt(model["conv_kernel"])
        return jax.random.uniform(key, shape, f32, -bound, bound)
    x = jax.random.normal(key, shape, f32)
    if name.endswith("norm") or name == "normf_scale":
        return 1.0 + 0.02 * x
    if name == "e_bias":
        return model.get("init", {}).get("selection_bias_std", 0.1) * x
    std = model.get("initializer_range", 0.02)
    if name in OUT_PROJECTIONS and model.get("rescale_prenorm_residual"):
        std /= math.sqrt(len(pattern(model)))
    return std * x


def make(seed: int, model: dict, dtype=jnp.float32, shardings=None):
    """The seeded tree, whole, in the type and layout asked for."""
    flat, treedef = jax.tree.flatten_with_path(shapes(model),
                                               is_leaf=lib.is_shape)

    def build(key):
        return jax.tree.unflatten(treedef, [
            draw(path[-1].key, jax.random.fold_in(key, i), shape,
                 model).astype(dtype)
            for i, (path, shape) in enumerate(flat)])

    return jax.jit(build, out_shardings=shardings)(lib.seed_key(seed, 1))
