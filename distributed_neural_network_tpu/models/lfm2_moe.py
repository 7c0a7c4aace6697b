"""Decoder of the `lfm2_moe` family: gated short convolutions and
grouped-query attention in a published order, a leading dense SwiGLU layer
and sigmoid-routed SwiGLU expert layers of which this chip holds a range
(all of them, where nothing is cut), tied logits.

Every block is (`N` an RMSNorm with its own gain):

    h'  = h  + Op(N_op(h));   h'' = h' + FF(N_ff(h'))

`Op` follows `layer_types`, `FF` is dense in the first `n_dense` layers and
the expert layer after them (`layer_plan`).

**Gated short convolution** at position t, input u_t: `[B_t ; C_t ; x_t] =
u_t W_in`; `z_t = B_t * x_t`; `c_t = sum_j w_j * z_{t - (taps - 1) + j}`, a
causal depthwise convolution of `conv_taps` taps a channel with z = 0 before
position 0; `Op = (C_t * c_t) W_out`. No activation besides the two gates and
no positional signal. **The state a sequence carries is the last `taps - 1`
rows of z**, whatever t (`next_state`).

**Attention**: `q = u W_q` as H heads, `k = u W_k`, `v = u W_v` as KV heads;
every head of q through `N_q` and of k through `N_k` (an RMSNorm over the
head's values, one gain for all heads), then rotate-half RoPE over the whole
head at the token's position; query head h reads KV head `h // (H / KV)`;
scores over `sqrt(head_dim)`, causal softmax in float32. **The cache row of t
is `[k_0 ; v_0 ; k_1 ; v_1 ; ...]`**, k after norm and rotation: a KV head's
keys and values side by side, so that at a head of 64 each KV head is one
128-lane tile of the row (`kv_row`, `split_row`; `ops/decode_pallas.py
gqa_decode_attention` reads the tiles as they lie).

**Expert layer**: `parallel/moe.py moe_held_gated_serve` with the selection
bias and no shared expert: `s = sigmoid(u W_r)` in float32, the `top_k`
largest of `s + bias` chosen, weights `routed_scale * s / (sum_chosen s +
1e-6)`.

The parameter tree: `embed` (also the head), `normf_scale`, and the
sublayers stacked BY KIND: `conv` and `attn` (the operators, each with its
`op_norm`), `dense` and `moe` (the feed-forwards, each with its `ff_norm`).
Layer l of the model is one entry of one operator stack and one entry of one
feed-forward stack (`layer_plan`). This module is SERVED (`serve/engine.py`),
not trained. What the engine asks of it: `CACHE`, `POOLS` (which pool each
operator kind keeps), `cache_shapes`, `layer_plan`; for the kind whose rows
are paged, `attn_in` / `attn_out` around the engine's own cache step with
`decode_attention`, `prefill_attention` and `decode_kernel` / `kernel_gate`;
for the kind kept a sequence, its state step whole (`conv_decode`,
`conv_prefill`); `feed_forward`, `expert_tile`, `embed_tokens` and
`final_logits`; and `REFUSED`, what of the engine's options it does not run,
with the reason.
"""

from __future__ import annotations

import math
import sys
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.decode_pallas import gqa_decode_attention, gqa_decode_ok
from ..parallel.moe import moe_held_gated_serve, swiglu
from .pangu_ultra_moe import NEG, layer_params, rms_norm, rope

NAME = "lfm2_moe"
# what the serving engine keeps: cache rows a position in the attention
# layers (a paged pool), and a state of fixed size a sequence in the
# convolution layers (a slot of the state pool), `cache_shapes`
CACHE = "hybrid"
# which pool each operator kind keeps: rows a position (paged), or a state of
# fixed size a sequence (`conv_decode` / `conv_prefill` step it whole)
POOLS = {"attn": "rows", "conv": "state"}

REFUSED = {
    "spec_decode": "a rejected draft would have to take the convolution "
                   "layers' state back with it, and the state pool keeps no "
                   "snapshot (preemption replays from the tokens)",
    "kv_dtype int8": "the per-(block, head) scales are written for per-head "
                     "K and V pools, and the convolution state has no block "
                     "to scale",
    "weight_dtype int8": "the prequantized matmul knows the GPT-2 block's "
                         "six matrices",
}

EXPERT_LEAVES = ("e_gate", "e_up", "e_down")
# what the family adds to the chosen scores' sum before it divides by it
ROUTE_SUM_EPS = 1e-6
OPERATORS = ("conv", "attn")


@dataclass(frozen=True)
class Lfm2MoEConfig:
    vocab_size: int = 256
    d_model: int = 64
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    conv_taps: int = 3
    d_ff: int = 128                  # the dense layers' MLP
    n_dense: int = 1
    layer_types: tuple = ("conv", "attn", "conv")
    # experts: the router's width, and which of them this chip holds
    n_routed: int = 8
    experts_held: tuple = (0, 8)
    top_k: int = 2
    routed_scale: float = 1.0
    expert_ff: int = 32
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    dtype: jnp.dtype = jnp.float32

    def __post_init__(self):
        first, count = self.experts_held
        if not (0 <= first and count >= 1 and first + count <= self.n_routed):
            raise ValueError(
                f"{NAME}: experts_held {self.experts_held} is not a range of "
                f"the {self.n_routed} routed experts")
        unknown = set(self.layer_types) - set(OPERATORS)
        if unknown:
            raise ValueError(
                f"{NAME}: layer_types has {sorted(unknown)}; an operator is "
                f"one of {OPERATORS}")
        if self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError(
                f"{NAME}: {self.n_heads} query heads over {self.n_kv_heads} "
                f"KV heads of {self.head_dim} - the groups must be whole and "
                "the head even")
        if not 0 <= self.n_dense <= len(self.layer_types):
            raise ValueError(
                f"{NAME}: {self.n_dense} dense layers of "
                f"{len(self.layer_types)}")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_conv(self) -> int:
        return self.layer_types.count("conv")

    @property
    def n_attn(self) -> int:
        return self.layer_types.count("attn")

    @property
    def n_moe(self) -> int:
        return self.n_layers - self.n_dense

    @property
    def kv_row(self) -> int:
        """A position's cache row in an attention layer: K and V of every
        KV head."""
        return 2 * self.n_kv_heads * self.head_dim

    @property
    def module(self):
        """The module that runs this configuration."""
        return sys.modules[__name__]


def layer_plan(cfg: Lfm2MoEConfig) -> tuple:
    """(operator kind, its index in that stack, feed-forward kind, its index
    in that stack) of every layer, in the model's order."""
    seen = dict.fromkeys(OPERATORS, 0)
    plan = []
    for l, op in enumerate(cfg.layer_types):
        ff = ("dense", l) if l < cfg.n_dense else ("moe", l - cfg.n_dense)
        plan.append((op, seen[op], *ff))
        seen[op] += 1
    return tuple(plan)


def cache_shapes(cfg: Lfm2MoEConfig) -> dict:
    """What the serving engine keeps, by pool: `kv` (attention layers, the
    values of one position's row) a position, `state` (convolution layers,
    the rows and values of one sequence's state) a sequence."""
    return {"kv": (cfg.n_attn, cfg.kv_row),
            "state": (cfg.n_conv, cfg.conv_taps - 1, cfg.d_model)}


def stack_shapes(cfg: Lfm2MoEConfig) -> dict:
    """kind -> (layers of that kind, name -> shape of one of them)."""
    d, hd = cfg.d_model, cfg.head_dim
    held = cfg.experts_held[1]
    return {
        "conv": (cfg.n_conv, {
            "op_norm": (d,), "w_in": (d, 3 * d),
            "taps": (d, cfg.conv_taps), "w_out": (d, d)}),
        "attn": (cfg.n_attn, {
            "op_norm": (d,), "wq": (d, cfg.n_heads * hd),
            "wk": (d, cfg.n_kv_heads * hd), "wv": (d, cfg.n_kv_heads * hd),
            "wo": (cfg.n_heads * hd, d), "q_norm": (hd,), "k_norm": (hd,)}),
        "dense": (cfg.n_dense, {
            "ff_norm": (d,), "w1": (d, cfg.d_ff), "w3": (d, cfg.d_ff),
            "w2": (cfg.d_ff, d)}),
        "moe": (cfg.n_moe, {
            "ff_norm": (d,), "router": (d, cfg.n_routed),
            "bias": (cfg.n_routed,), "e_gate": (held, d, cfg.expert_ff),
            "e_up": (held, d, cfg.expert_ff),
            "e_down": (held, cfg.expert_ff, d)}),
    }


def param_shapes(cfg: Lfm2MoEConfig) -> dict:
    out = {"embed": (cfg.vocab_size, cfg.d_model),
           "normf_scale": (cfg.d_model,)}
    for kind, (n, shapes) in stack_shapes(cfg).items():
        if n:
            out[kind] = {k: (n,) + s for k, s in shapes.items()}
    return out


INTO_RESIDUAL = ("w_out", "wo", "w2", "e_down")


def init_params(key: jax.Array, cfg: Lfm2MoEConfig):
    """A seeded float32 tree: normal(0.02) matrices and taps, the projections
    into the residual divided by sqrt(2 layers), the selection bias
    normal(0.02), gains 1 +- 0.1 (off 1, so that a dropped norm is seen);
    the embedding at a sixteenth of that deviation: it is also the head, and
    at the matrices' own a random tied model answers every token with that
    token."""
    flat, treedef = jax.tree.flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    resid = 1.0 / np.sqrt(2 * cfg.n_layers)
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name.endswith("norm") or name == "normf_scale":
            leaves.append(1.0 + 0.1 * x)
        elif name in INTO_RESIDUAL:
            leaves.append(0.02 * resid * x)
        elif name == "embed":
            leaves.append(0.02 / 16 * x)
        else:
            leaves.append(0.02 * x)
    return jax.tree.unflatten(treedef, leaves)


def from_published(model: dict, *, dtype=jnp.float32) -> Lfm2MoEConfig:
    """The program's configuration from a published `config.json`'s keys, as
    `benchmark/configs/<name>.json` holds them: `num_experts`, `layer_types`
    and `num_dense_layers` are what is held here, `published.num_experts`
    (where given) the router's width, `experts_held_first` the first held
    expert; the head is `hidden_size / num_attention_heads` where the file
    gives no `head_dim`."""
    if len(model["layer_types"]) != model["num_hidden_layers"]:
        raise ValueError(
            f"{NAME}: {len(model['layer_types'])} layer_types for "
            f"num_hidden_layers={model['num_hidden_layers']}")
    if model.get("conv_bias"):
        raise ValueError(f"{NAME}: conv_bias is not run here")
    held = model["num_experts"]
    return Lfm2MoEConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        head_dim=model.get("head_dim") or (
            model["hidden_size"] // model["num_attention_heads"]),
        conv_taps=model["conv_L_cache"], d_ff=model["intermediate_size"],
        n_dense=min(model["num_dense_layers"], model["num_hidden_layers"]),
        layer_types=tuple("attn" if t == "full_attention" else t
                          for t in model["layer_types"]),
        n_routed=model.get("published", {}).get("num_experts", held),
        experts_held=(model.get("experts_held_first", 0), held),
        top_k=model["num_experts_per_tok"],
        routed_scale=model["routed_scaling_factor"],
        expert_ff=model["moe_intermediate_size"],
        norm_eps=model["norm_eps"],
        rope_theta=model["rope_parameters"]["rope_theta"], dtype=dtype)


# ------------------------------------------------- the short convolution

def conv_in(x, lp, cfg: Lfm2MoEConfig):
    """The operator's first half, up to what its caller does with the state:
    x (T, d) -> (gate C (T, d), z = B * x (T, d))."""
    dt, d = cfg.dtype, cfg.d_model
    u = rms_norm(x, lp["op_norm"], cfg.norm_eps).astype(dt)
    with jax.named_scope("lm.conv.in"):
        bcx = u @ lp["w_in"].astype(dt)
        return bcx[:, d:2 * d], bcx[:, :d] * bcx[:, 2 * d:]


def conv_mix(tail, z, lp, cfg: Lfm2MoEConfig):
    """The causal depthwise convolution of z (T, d) behind the state `tail`
    (taps - 1, d), the z of the positions just before: c (T, d), and the rows
    `[tail ; z]` (T + taps - 1, d) the next state is cut from. Float32
    products and sum."""
    with jax.named_scope("lm.conv.mix"):
        zz = jnp.concatenate([tail.astype(z.dtype), z], axis=0)
        w = lp["taps"].astype(jnp.float32)                   # (d, taps)
        t = z.shape[0]
        c = sum(zz[j:j + t].astype(jnp.float32) * w[:, j]
                for j in range(cfg.conv_taps))
        return c.astype(cfg.dtype), zz


def next_state(zz, n_valid, cfg: Lfm2MoEConfig):
    """The state behind the first `n_valid` (traced) of the positions
    `conv_mix` was given, from its rows `zz = [state ; z]`: the z of the last
    `taps - 1` positions up to there, rows `n_valid ..`. A chunk of one valid
    token keeps one old row; what lies behind the valid positions (a
    bucket's dead tail) does not reach the state."""
    return jax.lax.dynamic_slice_in_dim(zz, n_valid, cfg.conv_taps - 1)


def conv_decode(x, lp, i, cfg: Lfm2MoEConfig, pool, slots, pos):
    """A convolution layer's decode step over its state in the state pool,
    whole: x (B, d) at positions `pos` (B,), each row's state at slot
    `slots` (B,) of convolution layer `i` of `pool`; one step of the
    convolution (from noughts at position 0: a slot is handed on as its last
    owner left it) and the new state back, the last `taps - 1` rows of
    `[state ; z]` (`next_state` behind one position). Returns (x, pool)."""
    gate, z = conv_in(x, lp, cfg)
    tail = jnp.where((pos == 0)[:, None, None], 0, pool[i, slots])
    c, zz = jax.vmap(lambda t, z_: conv_mix(t, z_[None], lp, cfg))(tail, z)
    pool = pool.at[i, slots].set(zz[:, 1:])
    return conv_out(x, gate, c[:, 0], lp, cfg), pool


def conv_prefill(x, lp, i, cfg: Lfm2MoEConfig, pool, slot, pos0, n_valid):
    """A convolution layer's step for a prefill chunk x (C, d) from position
    `pos0`: it starts from the sequence's state at slot `slot` (noughts at
    position 0) and leaves the state behind its last VALID position
    (`next_state`). Returns (x, pool)."""
    gate, z = conv_in(x, lp, cfg)
    tail = jnp.where(pos0 == 0, 0, pool[i, slot])
    c, zz = conv_mix(tail, z, lp, cfg)
    pool = pool.at[i, slot].set(next_state(zz, n_valid, cfg))
    return conv_out(x, gate, c, lp, cfg), pool


def conv_out(x, gate, c, lp, cfg: Lfm2MoEConfig):
    """The operator's second half: (C * c) W_out into the residual."""
    dt = cfg.dtype
    with jax.named_scope("lm.conv.out"):
        return x + (gate * c).astype(dt) @ lp["w_out"].astype(dt)


def short_conv(x, lp, cfg: Lfm2MoEConfig):
    """The whole operator over one sequence from position 0: x (S, d)."""
    gate, z = conv_in(x, lp, cfg)
    tail = jnp.zeros((cfg.conv_taps - 1, cfg.d_model), z.dtype)
    c, _ = conv_mix(tail, z, lp, cfg)
    return conv_out(x, gate, c, lp, cfg)


# -------------------------------------------------------------- attention

def attn_in(x, lp, cfg: Lfm2MoEConfig, pos):
    """The operator's first half, up to what its caller does with a cache: x
    (T, d) at positions `pos` (T,) -> (q (T, H, head_dim) normed and rotated,
    row (T, kv_row)): the queries and the positions' cache rows."""
    dt, hd = cfg.dtype, cfg.head_dim
    u = rms_norm(x, lp["op_norm"], cfg.norm_eps).astype(dt)
    with jax.named_scope("lm.attn.qkv"):
        t = x.shape[0]
        q = (u @ lp["wq"].astype(dt)).reshape(t, cfg.n_heads, hd)
        k = (u @ lp["wk"].astype(dt)).reshape(t, cfg.n_kv_heads, hd)
        v = (u @ lp["wv"].astype(dt)).reshape(t, cfg.n_kv_heads, hd)
        q = rope(rms_norm(q, lp["q_norm"], cfg.norm_eps).astype(dt), pos,
                 cfg.rope_theta)
        k = rope(rms_norm(k, lp["k_norm"], cfg.norm_eps).astype(dt), pos,
                 cfg.rope_theta)
        return q, jnp.stack([k, v], axis=2).reshape(t, cfg.kv_row)


def split_row(rows, cfg: Lfm2MoEConfig):
    """Cache rows (..., kv_row) -> (k, v), each (..., KV, head_dim)."""
    kv = rows.reshape(*rows.shape[:-1], cfg.n_kv_heads, 2, cfg.head_dim)
    return kv[..., 0, :], kv[..., 1, :]


def attn_out(x, o, lp, cfg: Lfm2MoEConfig):
    """The operator's second half: the heads' outputs o (T, H, head_dim)
    through W_o into the residual."""
    dt = cfg.dtype
    with jax.named_scope("lm.attn.out"):
        return x + o.reshape(o.shape[0], -1).astype(dt) @ lp["wo"].astype(dt)


def _grouped(q, cfg: Lfm2MoEConfig):
    """q (..., H, head_dim) -> (..., KV, H / KV, head_dim): the query heads
    under the KV head they read."""
    return q.reshape(*q.shape[:-2], cfg.n_kv_heads,
                     cfg.n_heads // cfg.n_kv_heads, cfg.head_dim)


def decode_attention(q, rows, live, cfg: Lfm2MoEConfig):
    """The decode kernel's oracle in plain `jax.numpy`: one query a sequence
    q (B, H, head_dim) over gathered cache rows (B, S, kv_row) under `live`
    (B, S) -> o (B, H, head_dim), scores and softmax in float32."""
    with jax.named_scope("lm.attn.attn"):
        k, v = split_row(rows, cfg)
        s = jnp.einsum("bgqd,bsgd->bgqs", _grouped(q, cfg), k,
                       preferred_element_type=jnp.float32)
        s = jnp.where(live[:, None, None, :],
                      s / math.sqrt(cfg.head_dim), NEG)
        p = jax.nn.softmax(s, axis=-1).astype(cfg.dtype)
        o = jnp.einsum("bgqs,bsgd->bgqd", p, v,
                       preferred_element_type=jnp.float32)
        return o.reshape(q.shape).astype(cfg.dtype)


def decode_kernel(q, pool, layer, table, pos, cfg: Lfm2MoEConfig, *,
                  block_size: int, interpret: bool):
    """The decode attention on the Mosaic kernel, over the pool where it
    lies (`gqa_decode_attention`)."""
    with jax.named_scope("lm.attn.attn"):
        return gqa_decode_attention(
            q, pool, layer, table, pos, block_size=block_size,
            n_kv_heads=cfg.n_kv_heads, interpret=interpret)


def kernel_gate(cfg: Lfm2MoEConfig, block_size: int, dtype) -> tuple:
    """(whether the decode kernel compiles for this pool, what to say where
    it was asked for and does not)."""
    return (gqa_decode_ok(block_size, cfg.n_kv_heads,
                          cfg.n_heads // cfg.n_kv_heads, cfg.head_dim, dtype),
            f"the grouped-query decode kernel does not compile for pages of "
            f"{block_size} {jnp.dtype(dtype)} rows of {cfg.n_kv_heads} x 2 x "
            f"{cfg.head_dim} (ops/decode_pallas.py gqa_decode_ok)")


def prefill_attention(q, qpos, read_rows, n_keys, cfg: Lfm2MoEConfig, *,
                      key_block: int):
    """Attention of a chunk's queries q (C, H, head_dim) at absolute
    positions `qpos` (C,) over cache positions `0 .. n_keys - 1` (traced),
    BLOCKED over the keys: `read_rows(j)` hands the cache rows of positions
    `j * key_block ..` as (key_block, kv_row) and they are folded into a
    float32 online softmax, so that no score block larger than (H, C,
    key_block) is made and the blocks past the last live key are not read.
    Query i sees key positions <= qpos[i]. Returns o (C, H, head_dim)."""
    dt, f32 = cfg.dtype, jnp.float32
    c = q.shape[0]
    qg = _grouped(q, cfg)                                 # (C, KV, G, hd)
    lead = (cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, c)
    scale = 1.0 / math.sqrt(cfg.head_dim)

    def one(j, carry):
        m, l, acc = carry
        with jax.named_scope("lm.attn.attn"):
            k, v = split_row(read_rows(j), cfg)           # (S, KV, hd)
            s = jnp.einsum("cgqd,sgd->gqcs", qg, k,
                           preferred_element_type=f32) * scale
            kpos = j * key_block + jnp.arange(key_block)
            s = jnp.where(kpos[None, None, None, :] <= qpos[:, None], s, NEG)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + p.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "gqcs,sgd->gqcd", p.astype(dt), v,
                preferred_element_type=f32)
        return m_new, l, acc

    n_blocks = (n_keys + key_block - 1) // key_block
    m, l, acc = jax.lax.fori_loop(0, n_blocks, one, (
        jnp.full(lead, NEG, f32), jnp.zeros(lead, f32),
        jnp.zeros(lead + (cfg.head_dim,), f32)))
    # a dead query (a chunk's spare row before any key) has l = 0
    o = acc / jnp.maximum(l, 1e-30)[..., None]            # (KV, G, C, hd)
    return o.transpose(2, 0, 1, 3).reshape(q.shape).astype(dt)


# ----------------------------------------------------------- feed-forward

def expert_tile(cfg: Lfm2MoEConfig, rows: int) -> int:
    """The rows of one tile of the expert products for a program of `rows`
    tokens: twice the pairs an expert sees under even routing, in sixteens,
    so that an expert's pairs nearly always fit ONE tile (a second tile reads
    the expert's matrices a second time) and little of it is padding."""
    mean = rows * cfg.top_k / cfg.n_routed
    return int(min(128, max(16, 16 * math.ceil(2 * mean / 16))))


def feed_forward(x, lp, cfg: Lfm2MoEConfig, kind: str, *, tile: int,
                 valid=None, experts=None):
    """The block's second sublayer on the residual x (T, d): (x, stats),
    stats None in a dense layer, the expert layer's routing counts
    otherwise. `experts` = (the `EXPERT_LEAVES` stacked over the expert
    layers, this layer's index among them) where `lp` does not hold this
    layer's own (`parallel/moe.py moe_held_gated_serve`: a tile reads
    `w[layer, expert]` where it lies); `valid` (T,) the rows that are
    tokens."""
    dt = cfg.dtype
    u = rms_norm(x, lp["ff_norm"], cfg.norm_eps).astype(dt)
    if kind == "dense":
        with jax.named_scope("lm.mlp"):
            return x + swiglu(u, lp["w1"].astype(dt), lp["w3"].astype(dt),
                              lp["w2"].astype(dt)), None
    held, layer = experts or ({k: lp[k] for k in EXPERT_LEAVES}, None)
    y, stats = moe_held_gated_serve(
        u, lp["router"], held["e_gate"], held["e_up"], held["e_down"], None,
        bias=lp["bias"], first=cfg.experts_held[0], top_k=cfg.top_k,
        scale=cfg.routed_scale, tile=tile, valid=valid, layer=layer,
        sum_eps=ROUTE_SUM_EPS)
    return x + y, stats


def embed_tokens(params, tokens, cfg: Lfm2MoEConfig):
    """No positional signal here: the attention layers' rotation carries
    the position, the convolution layers the order."""
    return params["embed"][tokens].astype(cfg.dtype)


def final_logits(params, x, cfg: Lfm2MoEConfig):
    """Final RMSNorm and the tied head of x (..., d) -> (..., vocab) float32:
    the embedding's rows as they are stored, accumulated in float32."""
    u = rms_norm(x, params["normf_scale"], cfg.norm_eps).astype(cfg.dtype)
    return jnp.einsum("...d,vd->...v", u, params["embed"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


def apply(params, tokens, cfg: Lfm2MoEConfig, *, tile: int = 8,
          with_stats: bool = False):
    """The whole-sequence forward: tokens (S,) of one sequence -> logits (S,
    vocab) float32, the convolutions from a state of noughts, the attention
    under a full causal mask. The oracle of the engine's tests; with
    `with_stats` also the expert layers' routing counts, stacked."""
    s = tokens.shape[0]
    pos = jnp.arange(s)
    x = embed_tokens(params, tokens, cfg)
    routing = []
    for op, oi, ff, fi in layer_plan(cfg):
        lp = layer_params(params, op, oi)
        if op == "conv":
            x = short_conv(x, lp, cfg)
        else:
            q, rows = attn_in(x, lp, cfg, pos)
            o = prefill_attention(q, pos, lambda j: rows, s, cfg, key_block=s)
            x = attn_out(x, o, lp, cfg)
        x, stats = feed_forward(x, layer_params(params, ff, fi), cfg, ff,
                                tile=tile)
        if stats is not None:
            routing.append(stats)
    logits = final_logits(params, x, cfg)
    if with_stats:
        return logits, jax.tree.map(lambda *xs: jnp.stack(xs), *routing)
    return logits
