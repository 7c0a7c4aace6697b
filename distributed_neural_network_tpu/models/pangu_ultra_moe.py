"""Decoder of the `pangu_ultra_moe` family: latent attention (MLA) with a
rotary part, sandwich norms, a leading stack of dense SwiGLU layers and a
stack of sigmoid-routed expert layers of which this chip holds a share.

Every block is (`N` an RMSNorm with its own gain, `sandwich_norm`):

    a  = N_post_attn(Attn(N_in(h)));   h'  = h + a
    m  = N_post_mlp(MLP(N_pre_mlp(h'))); h'' = h' + m

**Latent attention** at position t: `c_q = N_q(x W_qa)`; `q = c_q W_qb`, a
head's `q_nope` (`qk_nope`) and `q_rope` (`qk_rope`); `[c_kv ; k_r] = x W_kva`,
`c = N_kv(c_kv)`; `k_rope = RoPE(k_r, t)`, one for all heads, `q_rope =
RoPE(q_rope, t)` (rotate-half, no scaling). **The cache row of t is `[c ;
k_rope]`** (`cache_row_width`): the one thing the serving engine keeps a
position. Two forms of the same function:

- expanded (`expand_rows`; the whole-sequence forward and chunked prefill):
  `[k_nope ; v]_h = c W_kvb`, scores `(q_nope . k_nope + q_rope . k_rope) /
  sqrt(qk_nope + qk_rope)`, causal softmax in float32, `o_h = sum p v_h`;
- absorbed (`absorb_q`, `unabsorb_o`; decode): with `W_kvb` split a head into
  `W_uk` and `W_uv`, `q_lat = [W_uk q_nope ; q_rope]` is scored against the
  cache rows as they lie and the probabilities weigh `c`, so K and V are the
  same bytes and are read once for all heads; `o_h = W_uv^T sum p c`.

**MLP**: the first `n_dense` layers `(silu(x W_g) * (x W_u)) W_d`; the other
`n_moe` a chip's share of `n_routed` experts of the same form, `top_k` a
token by sigmoid scores without groups or a selection bias, weights
`routed_scale * s / sum_chosen s`, plus one shared expert
(`parallel/moe.py moe_held_gated_serve`). After the last block an RMSNorm
and an untied head over the rows of the vocabulary held here.

The parameter tree: `embed`, `head`, `normf_scale`, and the layers stacked BY
KIND under `dense` and `moe` (two stacks the engine scans one after the
other; layer l of the model is `dense[l]` for `l < n_dense`, `moe[l -
n_dense]` after). This module is SERVED (`serve/engine.py`), not trained:
what the engine asks of it is `CACHE`, `REFUSED`, `cache_row_width`,
`layer_stacks`,
`block_in` / `block_out` around its cache step, `absorb_q` / `unabsorb_o`,
`prefill_attention`, `embed_tokens` and `final_logits`.
"""

from __future__ import annotations

import sys
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.moe import moe_held_gated_serve, swiglu

NAME = "pangu_ultra_moe"
# what the serving engine keeps a position: one row of the latent pool,
# not per-head K and V
CACHE = "latent"
# what of the engine's options this module does not run, with the reason
REFUSED = {
    "spec_decode": "the early-exit drafter and the verify step are written "
                   "for per-head K and V pools",
    "kv_dtype int8": "the per-(block, head) scales have no head to belong "
                     "to in a latent row shared by all heads",
    "weight_dtype int8": "the prequantized matmul knows the GPT-2 block's "
                         "six matrices",
}

NEG = -1e30


@dataclass(frozen=True)
class PanguUltraMoEConfig:
    vocab_size: int = 256            # the rows of embedding and head held here
    d_model: int = 64
    n_heads: int = 4
    qk_nope: int = 16
    qk_rope: int = 8
    v_head: int = 16
    q_rank: int = 24
    kv_rank: int = 32
    d_ff: int = 128                  # the dense layers' MLP
    n_dense: int = 1
    n_moe: int = 2
    # experts: the router's width, and which of them this chip holds
    n_routed: int = 16
    experts_held: tuple = (0, 4)
    top_k: int = 2
    routed_scale: float = 2.5
    expert_ff: int = 32
    shared_ff: int = 32
    norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    dtype: jnp.dtype = jnp.float32

    def __post_init__(self):
        first, count = self.experts_held
        if not (0 <= first and count >= 1 and first + count <= self.n_routed):
            raise ValueError(
                f"{NAME}: experts_held {self.experts_held} is not a range of "
                f"the {self.n_routed} routed experts")
        if self.qk_rope % 2:
            raise ValueError(f"{NAME}: qk_rope {self.qk_rope} must be even")

    @property
    def n_layers(self) -> int:
        return self.n_dense + self.n_moe

    @property
    def cache_row_width(self) -> int:
        return self.kv_rank + self.qk_rope

    @property
    def softmax_scale(self) -> float:
        return 1.0 / float(np.sqrt(self.qk_nope + self.qk_rope))

    @property
    def module(self):
        """The module that runs this configuration."""
        return sys.modules[__name__]


def layer_stacks(cfg: PanguUltraMoEConfig) -> tuple:
    """(stack's key in the tree, its layers, the model's first layer in it),
    in the model's order; a kind the model lacks is left out."""
    return tuple((kind, n, l0) for kind, n, l0 in (
        ("dense", cfg.n_dense, 0), ("moe", cfg.n_moe, cfg.n_dense)) if n)


def layer_shapes(cfg: PanguUltraMoEConfig) -> dict:
    """kind -> name -> shape of one layer of that kind."""
    d, h, held = cfg.d_model, cfg.n_heads, cfg.experts_held[1]
    attn = {
        "in_norm": (d,), "q_a": (d, cfg.q_rank), "q_norm": (cfg.q_rank,),
        "q_b": (cfg.q_rank, h * (cfg.qk_nope + cfg.qk_rope)),
        "kv_a": (d, cfg.cache_row_width), "kv_norm": (cfg.kv_rank,),
        "kv_b": (cfg.kv_rank, h * (cfg.qk_nope + cfg.v_head)),
        "o": (h * cfg.v_head, d), "post_attn_norm": (d,),
        "pre_mlp_norm": (d,), "post_mlp_norm": (d,),
    }
    return {
        "dense": dict(attn, w_gate=(d, cfg.d_ff), w_up=(d, cfg.d_ff),
                      w_down=(cfg.d_ff, d)),
        "moe": dict(attn, router=(d, cfg.n_routed),
                    e_gate=(held, d, cfg.expert_ff),
                    e_up=(held, d, cfg.expert_ff),
                    e_down=(held, cfg.expert_ff, d),
                    s_gate=(d, cfg.shared_ff), s_up=(d, cfg.shared_ff),
                    s_down=(cfg.shared_ff, d)),
    }


def param_shapes(cfg: PanguUltraMoEConfig) -> dict:
    out = {"embed": (cfg.vocab_size, cfg.d_model),
           "head": (cfg.d_model, cfg.vocab_size),
           "normf_scale": (cfg.d_model,)}
    shapes = layer_shapes(cfg)
    for kind, n, _ in layer_stacks(cfg):
        out[kind] = {k: (n,) + s for k, s in shapes[kind].items()}
    return out


def init_params(key: jax.Array, cfg: PanguUltraMoEConfig):
    """A seeded float32 tree: normal(0.02) matrices, the projections into the
    residual (`o`, `w_down`, `e_down`, `s_down`) divided by sqrt(2 layers),
    gains 1 +- 0.1 (off 1, so that a path that drops a norm is seen)."""
    flat, treedef = jax.tree.flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    resid = 1.0 / np.sqrt(2 * cfg.n_layers)
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name.endswith("norm") or name == "normf_scale":
            leaves.append(1.0 + 0.1 * x)
        elif name in ("o", "w_down", "e_down", "s_down"):
            leaves.append(0.02 * resid * x)
        else:
            leaves.append(0.02 * x)
    return jax.tree.unflatten(treedef, leaves)


def from_published(model: dict, *, dtype=jnp.float32) -> PanguUltraMoEConfig:
    """The program's configuration from a published `config.json`'s keys, as
    `benchmark/configs/<name>.json` holds them: `n_routed_experts`,
    `vocab_size`, `num_hidden_layers` and `first_k_dense_replace` are what is
    held here, `published.n_routed_experts` (where given) the router's width,
    `experts_held_first` the first held expert."""
    if model.get("num_nextn_predict_layers"):
        raise ValueError(
            f"{NAME}: num_nextn_predict_layers="
            f"{model['num_nextn_predict_layers']} - the multi-token "
            "prediction module is not run here (a step yields one token)")
    held = model["n_routed_experts"]
    n_dense = min(model["first_k_dense_replace"], model["num_hidden_layers"])
    return PanguUltraMoEConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_heads=model["num_attention_heads"],
        qk_nope=model["qk_nope_head_dim"], qk_rope=model["qk_rope_head_dim"],
        v_head=model["v_head_dim"], q_rank=model["q_lora_rank"],
        kv_rank=model["kv_lora_rank"], d_ff=model["intermediate_size"],
        n_dense=n_dense, n_moe=model["num_hidden_layers"] - n_dense,
        n_routed=model.get("published", {}).get("n_routed_experts", held),
        experts_held=(model.get("experts_held_first", 0), held),
        top_k=model["num_experts_per_tok"],
        routed_scale=model["routed_scaling_factor"],
        expert_ff=model["moe_intermediate_size"],
        shared_ff=model["n_shared_experts"] * model["moe_intermediate_size"],
        norm_eps=model["rms_norm_eps"], rope_theta=model["rope_theta"],
        dtype=dtype)


# ------------------------------------------------------------ the block

def rms_norm(x, scale, eps):
    """RMSNorm in float32; the caller casts."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rope(x, pos, theta: float):
    """Rotate-half rotary embedding of x (..., r) at positions `pos` (the
    leading axes of x, or those less a head axis before r), no scaling."""
    r = x.shape[-1]
    inv = jnp.exp(-np.log(theta) * jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = pos.astype(jnp.float32)[..., None] * inv           # (..., r / 2)
    ang = jnp.concatenate([ang, ang], axis=-1)
    if ang.ndim < x.ndim:                                    # a head axis
        ang = ang[..., None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    turned = jnp.concatenate([-x2, x1], axis=-1)
    return (x32 * jnp.cos(ang) + turned * jnp.sin(ang)).astype(x.dtype)


def block_in(x, lp, cfg: PanguUltraMoEConfig, pos):
    """The block's first half, up to what its caller does with a cache: x
    (..., d) at positions `pos` (...,) -> (q_nope (..., H, qk_nope), q_rope
    (..., H, qk_rope) rotated, row (..., cache_row_width)): the queries, and
    the position's cache row `[c ; k_rope]`."""
    dt, h = cfg.dtype, cfg.n_heads
    u = rms_norm(x, lp["in_norm"], cfg.norm_eps).astype(dt)
    with jax.named_scope("lm.mla.q"):
        c_q = rms_norm(u @ lp["q_a"].astype(dt), lp["q_norm"],
                       cfg.norm_eps).astype(dt)
        q = (c_q @ lp["q_b"].astype(dt)).reshape(
            *x.shape[:-1], h, cfg.qk_nope + cfg.qk_rope)
        q_nope, q_rope = q[..., :cfg.qk_nope], q[..., cfg.qk_nope:]
        q_rope = rope(q_rope, pos, cfg.rope_theta)
    with jax.named_scope("lm.mla.kv"):
        ckv = u @ lp["kv_a"].astype(dt)
        c = rms_norm(ckv[..., :cfg.kv_rank], lp["kv_norm"],
                     cfg.norm_eps).astype(dt)
        k_rope = rope(ckv[..., cfg.kv_rank:], pos, cfg.rope_theta)
        row = jnp.concatenate([c, k_rope], axis=-1)
    return q_nope, q_rope, row


def _kv_b(lp, cfg: PanguUltraMoEConfig):
    """`W_kvb` as (kv_rank, H, qk_nope + v_head): a head's `W_uk | W_uv`."""
    return lp["kv_b"].astype(cfg.dtype).reshape(
        cfg.kv_rank, cfg.n_heads, cfg.qk_nope + cfg.v_head)


def absorb_q(q_nope, q_rope, lp, cfg: PanguUltraMoEConfig):
    """The query in the cache row's own space: `[W_uk q_nope ; q_rope]`,
    (..., H, cache_row_width)."""
    with jax.named_scope("lm.mla.q"):
        w_uk = _kv_b(lp, cfg)[..., :cfg.qk_nope]
        q_c = jnp.einsum("...hn,chn->...hc", q_nope, w_uk)
        return jnp.concatenate([q_c, q_rope], axis=-1)


def unabsorb_o(o_lat, lp, cfg: PanguUltraMoEConfig):
    """The absorbed form's output (..., H, kv_rank) back to a head's values
    (..., H, v_head): `W_uv^T o_lat`."""
    with jax.named_scope("lm.mla.out"):
        w_uv = _kv_b(lp, cfg)[..., cfg.qk_nope:]
        return jnp.einsum("...hc,chv->...hv", o_lat.astype(cfg.dtype), w_uv)


def expand_rows(rows, lp, cfg: PanguUltraMoEConfig):
    """Cache rows (S, cache_row_width or wider: a pool pads its rows) ->
    (k_nope (S, H, qk_nope), v (S, H, v_head), k_rope (S, qk_rope)): the
    expanded form's keys and values."""
    with jax.named_scope("lm.mla.kv"):
        kv = jnp.einsum("sc,chn->shn", rows[:, :cfg.kv_rank], _kv_b(lp, cfg))
        return (kv[..., :cfg.qk_nope], kv[..., cfg.qk_nope:],
                rows[:, cfg.kv_rank:cfg.cache_row_width])


def _scores(q_nope, q_rope, k_nope, k_rope, cfg):
    s = jnp.einsum("qhn,shn->hqs", q_nope, k_nope,
                   preferred_element_type=jnp.float32)
    s = s + jnp.einsum("qhr,sr->hqs", q_rope, k_rope,
                       preferred_element_type=jnp.float32)
    return s * cfg.softmax_scale


def prefill_attention(q_nope, q_rope, qpos, read_rows, n_keys, lp,
                      cfg: PanguUltraMoEConfig, *, key_block: int):
    """Expanded-form attention of a chunk's queries (C, H, .) at absolute
    positions `qpos` (C,) over cache positions `0 .. n_keys - 1` (traced),
    BLOCKED over the keys: `read_rows(j)` hands the cache rows of positions
    `j * key_block ..` as (key_block, cache_row_width), they are expanded,
    scored, and folded into a float32 online softmax, so that no score
    matrix larger than (H, C, key_block) is made and the blocks past the
    last live key are not read. Query i sees key positions <= qpos[i].
    Returns o (C, H, v_head)."""
    dt, f32 = cfg.dtype, jnp.float32
    c, h = q_nope.shape[0], cfg.n_heads

    def one(j, carry):
        m, l, acc = carry
        k_nope, v, k_rope = expand_rows(read_rows(j), lp, cfg)
        with jax.named_scope("lm.mla.attn"):
            s = _scores(q_nope, q_rope, k_nope, k_rope, cfg)
            kpos = j * key_block + jnp.arange(key_block)
            s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, NEG)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + p.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "hqs,shv->hqv", p.astype(dt), v, preferred_element_type=f32)
        return m_new, l, acc

    n_blocks = (n_keys + key_block - 1) // key_block
    m, l, acc = jax.lax.fori_loop(0, n_blocks, one, (
        jnp.full((h, c), NEG, f32), jnp.zeros((h, c), f32),
        jnp.zeros((h, c, cfg.v_head), f32)))
    # a dead query (a chunk's spare row before any key) has l = 0
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    return o.transpose(1, 0, 2).astype(dt)


def absorbed_attention(q_lat, rows, live, cfg: PanguUltraMoEConfig):
    """The decode kernel's oracle in plain `jax.numpy`: q_lat (B, H, W) over
    gathered cache rows (B, S, W) under `live` (B, S) -> the latent-space
    output (B, H, kv_rank), scores and softmax in float32."""
    with jax.named_scope("lm.mla.attn"):
        s = jnp.einsum("bhw,bsw->bhs", q_lat, rows,
                       preferred_element_type=jnp.float32)
        s = jnp.where(live[:, None, :], s * cfg.softmax_scale, NEG)
        p = jax.nn.softmax(s, axis=-1).astype(cfg.dtype)
        return jnp.einsum("bhs,bsc->bhc", p, rows[..., :cfg.kv_rank],
                          preferred_element_type=jnp.float32)


EXPERT_LEAVES = ("e_gate", "e_up", "e_down")


def mlp(u, lp, cfg: PanguUltraMoEConfig, kind: str, *, tile: int,
        valid=None, experts=None):
    """The block's MLP on normed rows u (T, d): (y, stats), stats None in a
    dense layer, an expert layer's routing counts otherwise. `experts` =
    (the `EXPERT_LEAVES` stacked over the expert layers, this layer's index
    among them) where `lp` does not hold this layer's own (a layer scan
    keeps them out of its xs: `parallel/moe.py moe_held_gated_serve`)."""
    dt = cfg.dtype
    if kind == "dense":
        with jax.named_scope("lm.mlp"):
            return swiglu(u, lp["w_gate"].astype(dt), lp["w_up"].astype(dt),
                          lp["w_down"].astype(dt)), None
    held, layer = experts or ({k: lp[k] for k in EXPERT_LEAVES}, None)
    return moe_held_gated_serve(
        u, lp["router"], held["e_gate"], held["e_up"], held["e_down"],
        (lp["s_gate"], lp["s_up"], lp["s_down"]),
        first=cfg.experts_held[0], top_k=cfg.top_k, scale=cfg.routed_scale,
        tile=tile, valid=valid, layer=layer)


def block_out(x, o, lp, cfg: PanguUltraMoEConfig, kind: str, *, tile: int,
              valid=None, experts=None):
    """The block's second half: the attention output o (T, H, v_head)
    through `o` and its norm into the residual x (T, d), then the MLP
    between its two norms. `tile`: the rows of one tile of the expert
    products (`moe_held_gated_serve`); `valid` (T,) the rows that are
    tokens; `experts` as `mlp` takes it. Returns (x, stats)."""
    dt = cfg.dtype
    with jax.named_scope("lm.mla.out"):
        a = o.reshape(o.shape[0], -1).astype(dt) @ lp["o"].astype(dt)
        x = x + rms_norm(a, lp["post_attn_norm"], cfg.norm_eps).astype(dt)
    u = rms_norm(x, lp["pre_mlp_norm"], cfg.norm_eps).astype(dt)
    y, stats = mlp(u, lp, cfg, kind, tile=tile, valid=valid,
                   experts=experts)
    return x + rms_norm(y, lp["post_mlp_norm"], cfg.norm_eps).astype(dt), stats


def embed_tokens(params, tokens, cfg: PanguUltraMoEConfig):
    """No positional signal here: the rotary part carries the position."""
    return params["embed"][tokens].astype(cfg.dtype)


def final_logits(params, x, cfg: PanguUltraMoEConfig):
    """Final RMSNorm and head of x (..., d) -> (..., rows held) float32; the
    head multiplied as it is stored, accumulated in float32."""
    u = rms_norm(x, params["normf_scale"], cfg.norm_eps).astype(cfg.dtype)
    return jnp.matmul(u, params["head"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


def layer_params(params, kind: str, i: int) -> dict:
    return {k: v[i] for k, v in params[kind].items()}


def apply(params, tokens, cfg: PanguUltraMoEConfig, *, tile: int = 8,
          with_stats: bool = False):
    """The whole-sequence forward: tokens (S,) of one sequence -> logits (S,
    rows held) float32, the expanded form under a full causal mask. The
    oracle of the engine's tests and what a `generate()`-style caller runs;
    with `with_stats` also the expert layers' routing counts, stacked."""
    s = tokens.shape[0]
    pos = jnp.arange(s)
    x = embed_tokens(params, tokens, cfg)
    routing = []
    for kind, n, _ in layer_stacks(cfg):
        for i in range(n):
            lp = layer_params(params, kind, i)
            q_nope, q_rope, rows = block_in(x, lp, cfg, pos)
            o = prefill_attention(q_nope, q_rope, pos, lambda j: rows, s, lp,
                                  cfg, key_block=s)
            x, stats = block_out(x, o, lp, cfg, kind, tile=tile)
            if stats is not None:
                routing.append(stats)
    logits = final_logits(params, x, cfg)
    if with_stats:
        return logits, jax.tree.map(lambda *xs: jnp.stack(xs), *routing)
    return logits
