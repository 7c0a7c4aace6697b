"""Hybrid decoder of the `nemotron_h` family: Mamba-2, expert and attention
layers in one published order.

Every block is `x + mixer(RMSNorm(x))` with ONE mixer and no second half;
the letter of `cfg.pattern` picks the mixer (`M` Mamba-2, `E` experts, `*`
attention). After the last block an RMSNorm and an untied head. There is no
positional signal anywhere: the attention layers apply no rotary embedding
and the embedding adds no table.

- `M`: `[z | xBC | dt] = u W_in`; a depthwise causal convolution and silu
  on xBC, split into x (H heads of P), B and C (G groups of N); `dt =
  softplus(dt + dt_bias)`, `A = -exp(A_log)`; the selective state-space
  recurrence in chunks (`ops/ssd.py`) plus `D x`; a gated group RMSNorm
  `RMSNorm_groups(y silu(z)) w`; `W_out`.
- `E`: a chip's share of a sigmoid-routed expert layer with a shared expert
  (`parallel/moe.py moe_held_ffn`): the config says which of the routed
  experts are held here (`experts_held = (first, count)`); the router
  scores all `n_routed`.
- `*`: grouped-query causal attention, `n_heads x head_dim` need not be
  `d_model`, no bias (`ops/flash.py grouped_query_flash_attention`).

The parameter tree has the transformer's outline (`embed`, `head`,
`normf_scale`, and `layers`), with the layers stacked BY KIND: every leaf
under `layers` is named `<kind>_<leaf>` (`m_`, `e_`, `a_`) and its leading
axis counts the layers of that kind in pattern order. The pattern is short
and mixed, so `apply_hidden` walks it in Python: no scan over layers.

Runs under data parallelism only; a sequence, tensor or expert axis is
refused by name, as are the pipeline and the serving engine.
"""

from __future__ import annotations

import sys
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops.flash import grouped_query_flash_attention
from ..ops.flash_pallas import block_remat_policy
from ..ops.ssd import ssd_scan
from ..parallel.moe import moe_held_ffn

# what `train/lm.py` asks of a model's module (`models/transformer.py`):
# `apply_hidden`'s second result is the expert layers' routing counts, no
# loss term
NAME = "nemotron_h"
AUX_IS_LOSS = False
KINDS = {"M": "m", "E": "e", "*": "a"}


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 256            # the rows of embedding and head held here
    d_model: int = 64
    pattern: str = "MEMEM*EME"
    # Mamba-2
    mamba_heads: int = 8
    mamba_head_dim: int = 8
    ssm_state: int = 16
    ssm_groups: int = 2
    conv_kernel: int = 4
    chunk: int = 16
    # attention
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    # experts: the router's width, and which of them this chip holds
    n_routed: int = 16
    experts_held: tuple = (0, 8)
    top_k: int = 2
    routed_scale: float = 2.5
    expert_ff: int = 32
    shared_ff: int = 64
    norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32
    # rematerialize each block in the backward pass (`jax.checkpoint`,
    # `remat_policy` a jax.checkpoint_policies name, "" = save nothing)
    remat: bool = False
    remat_policy: str = ""

    def __post_init__(self):
        bad = set(self.pattern) - set(KINDS)
        if bad or not self.pattern:
            raise ValueError(
                f"{NAME}: pattern {self.pattern!r} may hold only "
                f"{sorted(KINDS)} (M Mamba-2, E experts, * attention)")
        first, count = self.experts_held
        if not (0 <= first and count >= 1 and first + count <= self.n_routed):
            raise ValueError(
                f"{NAME}: experts_held {self.experts_held} is not a range of "
                f"the {self.n_routed} routed experts")

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def module(self):
        """The module that runs this configuration."""
        return sys.modules[__name__]

    def count(self, letter: str) -> int:
        return self.pattern.count(letter)


def layer_shapes(cfg: NemotronHConfig) -> dict:
    """name -> shape of every leaf under `layers`, the kinds the pattern
    lacks left out."""
    d, di, cd = cfg.d_model, cfg.d_inner, cfg.conv_dim
    hq, hkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    held = cfg.experts_held[1]
    kinds = {
        "M": {"m_norm": (d,), "m_in": (d, di + cd + cfg.mamba_heads),
              "m_conv_w": (cfg.conv_kernel, cd), "m_conv_b": (cd,),
              "m_dt_bias": (cfg.mamba_heads,), "m_a_log": (cfg.mamba_heads,),
              "m_d": (cfg.mamba_heads,), "m_gnorm": (di,), "m_out": (di, d)},
        "E": {"e_norm": (d,), "e_router": (d, cfg.n_routed),
              "e_bias": (cfg.n_routed,),
              "e_up": (held, d, cfg.expert_ff),
              "e_down": (held, cfg.expert_ff, d),
              "e_shared_up": (d, cfg.shared_ff),
              "e_shared_down": (cfg.shared_ff, d)},
        "*": {"a_norm": (d,), "a_wq": (d, hq), "a_wk": (d, hkv),
              "a_wv": (d, hkv), "a_wo": (hq, d)},
    }
    return {name: (cfg.count(letter),) + shape
            for letter, leaves in kinds.items() if cfg.count(letter)
            for name, shape in leaves.items()}


def param_skeleton(cfg: NemotronHConfig):
    """The tree's structure with placeholder leaves, for the rule matcher."""
    return {"embed": 0, "head": 0, "normf_scale": 0,
            "layers": dict.fromkeys(layer_shapes(cfg), 0)}


def init_params(key: jax.Array, cfg: NemotronHConfig):
    """A seeded float32 tree as the family initialises it: normal(0.02)
    matrices, out-projections scaled by 1 / sqrt(layers), dt_bias the
    inverse softplus of a log-uniform step in [0.001, 0.1], A_log =
    log(1..heads), D = 1, gains 1, the selection bias nought."""
    d, v = cfg.d_model, cfg.vocab_size
    shapes = layer_shapes(cfg)
    keys = iter(jax.random.split(key, len(shapes) + 2))
    out_scale = 1.0 / np.sqrt(len(cfg.pattern))

    def normal(shape, std=0.02):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    layers = {}
    for name, shape in shapes.items():
        if name.endswith("norm") or name == "m_d":
            layers[name] = jnp.ones(shape, jnp.float32)
        elif name in ("m_conv_b", "e_bias"):
            next(keys)
            layers[name] = jnp.zeros(shape, jnp.float32)
        elif name == "m_dt_bias":
            u = jax.random.uniform(next(keys), shape, jnp.float32)
            dt = jnp.maximum(jnp.exp(u * (np.log(0.1) - np.log(0.001))
                                     + np.log(0.001)), 1e-4)
            layers[name] = dt + jnp.log(-jnp.expm1(-dt))
        elif name == "m_a_log":
            next(keys)
            layers[name] = jnp.broadcast_to(jnp.log(jnp.arange(
                1, shape[-1] + 1, dtype=jnp.float32)), shape)
        elif name == "m_conv_w":
            layers[name] = normal(shape, 1.0 / np.sqrt(cfg.conv_kernel))
        elif name in ("m_out", "a_wo", "e_down", "e_shared_down"):
            layers[name] = normal(shape, 0.02 * out_scale)
        else:
            layers[name] = normal(shape)
    return {"embed": normal((v, d)), "head": normal((d, v)),
            "normf_scale": jnp.ones((d,), jnp.float32), "layers": layers}


def param_specs(cfg: NemotronHConfig, tp_axis: str | None = None,
                ep_axis: str | None = None, rules=None):
    """PartitionSpec tree: every leaf replicated, which is all that data
    parallelism asks (`rules`, a `--sharding rules:<file>` table, is matched
    against the leaves' names where one is given)."""
    refuse_axes(tp_axis=tp_axis, ep_axis=ep_axis)
    skeleton = param_skeleton(cfg)
    if rules is not None:
        from ..parallel.rules import match_partition_rules

        return match_partition_rules(rules, skeleton, skip_scalars=False)
    return jax.tree.map(lambda _: P(), skeleton)


def refuse_axes(*, seq_axis=None, tp_axis=None, ep_axis=None) -> None:
    axes = {"sequence parallelism": ("seq_axis", seq_axis),
            "tensor parallelism": ("tp_axis", tp_axis),
            "an expert axis": ("ep_axis", ep_axis)}
    for what, (arg, axis) in axes.items():
        if axis is not None:
            raise ValueError(
                f"{NAME}: {what} ({arg}={axis!r}) is not supported - "
                "this model runs under data parallelism only; its experts "
                "are the share `experts_held` names, with no exchange")


def rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return x32 * jax.lax.rsqrt(var + eps) * scale


def causal_conv(x, w, bias):
    """Depthwise: y_t = bias + sum_k w[k] x_{t - (K - 1) + k}, x (B, S, C),
    w (K, C); positions before the first read as nought."""
    k, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return bias + sum(xp[:, i:i + s] * w[i] for i in range(k))


def mamba_mixer(u, lp, cfg: NemotronHConfig):
    """u (B, S, d) normed input in cfg.dtype -> (B, S, d)."""
    dt_, f32 = cfg.dtype, jnp.float32
    b, s, _ = u.shape
    h, p, g, n = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_groups,
                  cfg.ssm_state)
    di, cd = cfg.d_inner, cfg.conv_dim
    with jax.named_scope("lm.mamba.proj"):
        # [z | xBC | dt] = u W_in, as three products over W_in's columns:
        # no (B, S, 2 d_inner + ...) buffer that is then cut up
        w_z, w_xbc, w_dt = jnp.split(lp["m_in"].astype(dt_), [di, di + cd],
                                     axis=-1)
        z, xbc, dt = u @ w_z, u @ w_xbc, u @ w_dt
    with jax.named_scope("lm.mamba.conv"):
        xbc = jax.nn.silu(causal_conv(
            xbc, lp["m_conv_w"].astype(dt_), lp["m_conv_b"].astype(dt_)))
        x, bm, cm = jnp.split(xbc, [di, di + g * n], axis=-1)
    with jax.named_scope("lm.mamba.scan"):
        x = x.reshape(b, s, h, p)
        dt = jax.nn.softplus(dt.astype(f32) + lp["m_dt_bias"])
        y = ssd_scan(x, dt, -jnp.exp(lp["m_a_log"].astype(f32)),
                     bm.reshape(b, s, g, n), cm.reshape(b, s, g, n),
                     chunk=cfg.chunk)
        y = y + x * lp["m_d"].astype(dt_)[:, None]
    with jax.named_scope("lm.mamba.proj"):
        y = (y.reshape(b, s, di) * jax.nn.silu(z)).reshape(b, s, g, di // g)
        y = rms_norm(y, 1.0, cfg.norm_eps).reshape(b, s, di) * lp["m_gnorm"]
        return y.astype(dt_) @ lp["m_out"].astype(dt_)


def attention_mixer(u, lp, cfg: NemotronHConfig):
    dt_ = cfg.dtype
    b, s, _ = u.shape
    with jax.named_scope("lm.attn"):
        q = (u @ lp["a_wq"].astype(dt_)).reshape(b, s, cfg.n_heads,
                                                 cfg.head_dim)
        k = (u @ lp["a_wk"].astype(dt_)).reshape(b, s, cfg.n_kv_heads,
                                                 cfg.head_dim)
        v = (u @ lp["a_wv"].astype(dt_)).reshape(b, s, cfg.n_kv_heads,
                                                 cfg.head_dim)
        o = grouped_query_flash_attention(q, k, v, causal=True)
        return o.reshape(b, s, -1) @ lp["a_wo"].astype(dt_)


def expert_mixer(u, lp, cfg: NemotronHConfig):
    b, s, d = u.shape
    y, stats = moe_held_ffn(
        u.reshape(b * s, d), lp["e_router"], lp["e_bias"], lp["e_up"],
        lp["e_down"], lp["e_shared_up"], lp["e_shared_down"],
        first=cfg.experts_held[0], top_k=cfg.top_k, scale=cfg.routed_scale)
    return y.reshape(b, s, d), stats


def apply_hidden(params, tokens, cfg: NemotronHConfig, *,
                 seq_axis: str | None = None, tp_axis: str | None = None,
                 ep_axis: str | None = None, attn_impl: str = "flash"):
    """tokens (B, S) int32 -> (final-norm hidden (B, S, d) in cfg.dtype,
    stats): the signature `train/lm.py` calls. `stats` counts each expert
    layer's routing, stacked over the expert layers in pattern order (the
    keys of `moe_held_ffn`'s; `load` is (layers, held)). Attention is the
    local flash kernels': any other `attn_impl` is a sequence axis's."""
    refuse_axes(seq_axis=seq_axis, tp_axis=tp_axis, ep_axis=ep_axis)
    if attn_impl != "flash":
        raise ValueError(
            f"{NAME}: attn impl {attn_impl!r} is not supported - this model's "
            "attention layers run the local flash kernels (attn_impl='flash')")
    dt_ = cfg.dtype
    x = params["embed"][tokens].astype(dt_)

    def block(x, lp, letter):
        u = rms_norm(x, lp[KINDS[letter] + "_norm"], cfg.norm_eps).astype(dt_)
        if letter == "M":
            return x + mamba_mixer(u, lp, cfg), None
        if letter == "*":
            return x + attention_mixer(u, lp, cfg), None
        y, stats = expert_mixer(u, lp, cfg)
        return x + y, stats

    if cfg.remat:
        block = jax.checkpoint(
            block, policy=block_remat_policy(cfg.remat_policy),
            static_argnums=(2,))
    seen = dict.fromkeys(KINDS, 0)
    routing = []
    for letter in cfg.pattern:
        prefix, i = KINDS[letter] + "_", seen[letter]
        lp = {k: v[i] for k, v in params["layers"].items()
              if k.startswith(prefix)}
        x, stats = block(x, lp, letter)
        seen[letter] = i + 1
        if stats is not None:
            routing.append(stats)
    x = rms_norm(x, params["normf_scale"], cfg.norm_eps).astype(dt_)
    return x, jax.tree.map(lambda *xs: jnp.stack(xs), *routing)


def apply(params, tokens, cfg: NemotronHConfig, **kw):
    """Logits (B, S, vocab held) in float32."""
    x, _ = apply_hidden(params, tokens, cfg, **kw)
    return (x @ params["head"].astype(cfg.dtype)).astype(jnp.float32)


def param_count(params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))


def from_published(model: dict, *, dtype=jnp.float32, remat: bool = False,
                   remat_policy: str = "") -> NemotronHConfig:
    """The program's configuration from a published `config.json`'s keys,
    as `benchmark/configs/<name>.json` holds them: `n_routed_experts` and
    `vocab_size` are what is held here, `published.n_routed_experts` (where
    given) the router's width, `experts_held_first` the first held expert."""
    held = model["n_routed_experts"]
    return NemotronHConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        pattern=model["hybrid_override_pattern"],
        mamba_heads=model["mamba_num_heads"],
        mamba_head_dim=model["mamba_head_dim"],
        ssm_state=model["ssm_state_size"], ssm_groups=model["n_groups"],
        conv_kernel=model["conv_kernel"], chunk=model["chunk_size"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        n_routed=model.get("published", {}).get("n_routed_experts", held),
        experts_held=(model.get("experts_held_first", 0), held),
        top_k=model["num_experts_per_tok"],
        routed_scale=model["routed_scaling_factor"],
        expert_ff=model["moe_intermediate_size"],
        shared_ff=model["moe_shared_expert_intermediate_size"],
        norm_eps=model["layer_norm_epsilon"], dtype=dtype, remat=remat,
        remat_policy=remat_policy)
