"""Decoder of the `mimo_v2` family: grouped-query attention of two kinds in a
published order - full causal attention, and attention over a window of the
last `window` positions with a learned sink logit a query head - whose query
and key heads are wider than their value heads and rotated over their first
`rope_dim` values alone, a leading dense SwiGLU layer and sigmoid-routed
SwiGLU expert layers of which this chip holds a range, an untied head.

Every block is (`N` an RMSNorm with its own gain):

    h'  = h  + Attn(N_a(h));   h'' = h' + FF(N_f(h'))

`Attn` follows `layer_types` (`full` or `window`), `FF` follows `ff_types`
(`dense` or `moe`): `layer_plan`.

**Attention of a kind**: `[q ; k ; v] = u W_qkv`, split as H x qk | KV x qk |
KV x v, KV the kind's KV heads; rotate-half RoPE over the FIRST `rope_dim` of
each q and k head at the token's position with the kind's theta, the other
values unrotated; `v <- value_scale * v`; query head h reads KV head
`h // (H / KV)`; scores `q . k / sqrt(qk)` in float32. A full layer attends
to keys j <= i; a window layer to `i - window < j <= i`, and its softmax has
one more term in the denominator, `exp(sink_h)`, that weighs no value.

**What the serving engine keeps** (`POOLS`): a full layer's rows a POSITION
in the paged pool, a window layer's last `window` rows a SEQUENCE in a ring
of the state pool, row `t mod window` for position t. A row is `to_row`: the
KV heads' unrotated key parts, then their rotated parts, then their values,
so that at the published widths each part is whole 128-lane tiles
(`ops/decode_pallas.py split_gqa_decode_attention` reads them as they lie).
Whether a ring row belongs to the sequence is decided from positions in the
program (`ring_positions`): a slot is handed on as its last owner left it.

**Expert layer**: `parallel/moe.py moe_held_gated_serve` with the selection
bias and no shared expert: `s = sigmoid(u W_r)` in float32, the `top_k`
largest of `s + bias` chosen, weights `routed_scale * s / sum_chosen s`.

The parameter tree: `embed`, `head` (untied), `normf_scale`, and the
sublayers stacked BY KIND: `full` and `window` (each with its `op_norm`; a
window layer's `sink` too), `dense` and `moe` (each with its `ff_norm`). This
module is SERVED (`serve/engine.py`), not trained. What the engine asks of
it: `CACHE`, `POOLS`, `cache_shapes`, `layer_plan`, for the kind whose rows
are paged `full_in` / `full_out` around the engine's own cache step with
`decode_attention` and `prefill_attention` (the oracles) and their kernels
`decode_kernel` / `kernel_gate` and `prefill_kernel` / `prefill_kernel_gate`
/ `prefill_kernel_scored`, for the kind kept a sequence `window_decode` /
`window_prefill` (its state step whole), `attn_pairs`, `feed_forward`,
`expert_tile`, `embed_tokens`, `final_logits`; and `REFUSED`, what of the
engine's options it does not run, with the reason.
"""

from __future__ import annotations

import math
import sys
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.decode_pallas import (
    split_gqa_decode_attention,
    split_gqa_decode_ok,
    split_gqa_prefill_attention,
    split_gqa_prefill_ok,
    split_gqa_prefill_pairs,
)
from ..parallel.moe import moe_held_gated_serve, swiglu
from .lfm2_moe import expert_tile
from .pangu_ultra_moe import NEG, layer_params, rms_norm, rope

NAME = "mimo_v2"
# what the serving engine keeps: cache rows a position in the full layers (a
# paged pool), and a ring of the last `window` rows a sequence in the window
# layers (a slot of the state pool), `cache_shapes`
CACHE = "hybrid"
POOLS = {"full": "rows", "window": "state"}

REFUSED = {
    "spec_decode": "a rejected draft would have to take the window layers' "
                   "ring rows back with it, and the state pool keeps no "
                   "snapshot (preemption replays from the tokens)",
    "kv_dtype int8": "the per-(block, head) scales are written for per-head "
                     "K and V pools, and the window layers' ring has no block "
                     "to scale",
    "weight_dtype int8": "the prequantized matmul knows the GPT-2 block's "
                         "six matrices",
}

EXPERT_LEAVES = ("e_gate", "e_up", "e_down")
OPERATORS = ("full", "window")
FEED_FORWARDS = ("dense", "moe")


@dataclass(frozen=True)
class MiMoV2Config:
    vocab_size: int = 256
    d_model: int = 64
    n_heads: int = 4
    qk_head: int = 24
    v_head: int = 16
    rope_dim: int = 8
    n_kv_full: int = 1
    n_kv_window: int = 2
    window: int = 8
    rope_theta: float = 1e7
    window_rope_theta: float = 1e4
    value_scale: float = 0.707
    d_ff: int = 128                  # the dense layers' MLP
    layer_types: tuple = ("full", "window", "window", "full")
    ff_types: tuple = ("dense", "moe", "moe", "moe")
    # experts: the router's width, and which of them this chip holds
    n_routed: int = 16
    experts_held: tuple = (0, 4)
    top_k: int = 4
    routed_scale: float = 1.0
    expert_ff: int = 32
    norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32

    def __post_init__(self):
        first, count = self.experts_held
        if not (0 <= first and count >= 1 and first + count <= self.n_routed):
            raise ValueError(
                f"{NAME}: experts_held {self.experts_held} is not a range of "
                f"the {self.n_routed} routed experts")
        if set(self.layer_types) - set(OPERATORS) or set(
                self.ff_types) - set(FEED_FORWARDS):
            raise ValueError(
                f"{NAME}: layer_types {self.layer_types} / ff_types "
                f"{self.ff_types}: an operator is one of {OPERATORS}, a "
                f"feed-forward one of {FEED_FORWARDS}")
        if len(self.layer_types) != len(self.ff_types):
            raise ValueError(
                f"{NAME}: {len(self.layer_types)} operators for "
                f"{len(self.ff_types)} feed-forwards")
        if self.n_heads % self.n_kv_full or self.n_heads % self.n_kv_window \
                or self.rope_dim % 2 or not 0 < self.rope_dim <= self.qk_head:
            raise ValueError(
                f"{NAME}: {self.n_heads} query heads over {self.n_kv_full} "
                f"and {self.n_kv_window} KV heads, {self.rope_dim} of "
                f"{self.qk_head} rotated - the groups must be whole and the "
                "rotated part even")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_full(self) -> int:
        return self.layer_types.count("full")

    @property
    def n_window(self) -> int:
        return self.layer_types.count("window")

    @property
    def n_dense(self) -> int:
        return self.ff_types.count("dense")

    @property
    def n_moe(self) -> int:
        return self.ff_types.count("moe")

    def kv_heads(self, kind: str) -> int:
        return self.n_kv_full if kind == "full" else self.n_kv_window

    def row(self, kind: str) -> int:
        """A position's cache row in a layer of `kind`: keys and values of
        every KV head."""
        return self.kv_heads(kind) * (self.qk_head + self.v_head)

    def theta(self, kind: str) -> float:
        return self.rope_theta if kind == "full" else self.window_rope_theta

    @property
    def module(self):
        """The module that runs this configuration."""
        return sys.modules[__name__]


def layer_plan(cfg: MiMoV2Config) -> tuple:
    """(operator kind, its index in that stack, feed-forward kind, its index
    in that stack) of every layer, in the model's order."""
    seen = dict.fromkeys(OPERATORS + FEED_FORWARDS, 0)
    plan = []
    for op, ff in zip(cfg.layer_types, cfg.ff_types):
        plan.append((op, seen[op], ff, seen[ff]))
        seen[op] += 1
        seen[ff] += 1
    return tuple(plan)


def cache_shapes(cfg: MiMoV2Config) -> dict:
    """What the serving engine keeps, by pool: `kv` (full layers, the values
    of one position's row) a position, `state` (window layers, the ring's
    rows and a row's values) a sequence."""
    return {"kv": (cfg.n_full, cfg.row("full")),
            "state": (cfg.n_window, cfg.window, cfg.row("window"))}


def stack_shapes(cfg: MiMoV2Config) -> dict:
    """kind -> (layers of that kind, name -> shape of one of them)."""
    d, h = cfg.d_model, cfg.n_heads
    held = cfg.experts_held[1]

    def attn(kind):
        return {"op_norm": (d,),
                "w_qkv": (d, h * cfg.qk_head + cfg.row(kind)),
                "w_o": (h * cfg.v_head, d)}

    return {
        "full": (cfg.n_full, attn("full")),
        "window": (cfg.n_window, dict(attn("window"), sink=(h,))),
        "dense": (cfg.n_dense, {
            "ff_norm": (d,), "w1": (d, cfg.d_ff), "w3": (d, cfg.d_ff),
            "w2": (cfg.d_ff, d)}),
        "moe": (cfg.n_moe, {
            "ff_norm": (d,), "router": (d, cfg.n_routed),
            "bias": (cfg.n_routed,), "e_gate": (held, d, cfg.expert_ff),
            "e_up": (held, d, cfg.expert_ff),
            "e_down": (held, cfg.expert_ff, d)}),
    }


def param_shapes(cfg: MiMoV2Config) -> dict:
    out = {"embed": (cfg.vocab_size, cfg.d_model),
           "head": (cfg.vocab_size, cfg.d_model),
           "normf_scale": (cfg.d_model,)}
    for kind, (n, shapes) in stack_shapes(cfg).items():
        if n:
            out[kind] = {k: (n,) + s for k, s in shapes.items()}
    return out


INTO_RESIDUAL = ("w_o", "w2", "e_down")


def init_params(key: jax.Array, cfg: MiMoV2Config):
    """A seeded float32 tree: normal(0.02) matrices, the projections into
    the residual divided by sqrt(2 layers), the selection bias normal(0.02),
    the sink logits normal(0, 1), gains 1 +- 0.1 (off 1, so that a dropped
    norm is seen)."""
    flat, treedef = jax.tree.flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    resid = 1.0 / np.sqrt(2 * cfg.n_layers)
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name.endswith("norm") or name == "normf_scale":
            leaves.append(1.0 + 0.1 * x)
        elif name == "sink":
            leaves.append(x)
        elif name in INTO_RESIDUAL:
            leaves.append(0.02 * resid * x)
        else:
            leaves.append(0.02 * x)
    return jax.tree.unflatten(treedef, leaves)


def from_published(model: dict, *, dtype=jnp.float32) -> MiMoV2Config:
    """The program's configuration from a published `config.json`'s keys, as
    `benchmark/configs/<name>.json` holds them: `n_routed_experts`,
    `hybrid_layer_pattern` and `moe_layer_freq` are what is held here,
    `published.n_routed_experts` (where given) the router's width,
    `experts_held_first` the first held expert. Refuses by name what is not
    built: a sink in the full layers, shared experts, expert groups, a
    pattern that is not `num_hidden_layers` long."""
    layers = model["num_hidden_layers"]
    for key in ("hybrid_layer_pattern", "moe_layer_freq"):
        if len(model[key]) != layers:
            raise ValueError(
                f"{NAME}: {len(model[key])} entries of {key} for "
                f"num_hidden_layers={layers}")
    if model.get("add_full_attention_sink_bias"):
        raise ValueError(
            f"{NAME}: add_full_attention_sink_bias is not run here: the full "
            "layers' decode kernel has no sink")
    if model.get("n_shared_experts"):
        raise ValueError(f"{NAME}: n_shared_experts is not run here")
    if model.get("n_group", 1) > 1:
        raise ValueError(
            f"{NAME}: n_group {model['n_group']} > 1: grouped routing is not "
            "run here")
    if not model.get("add_swa_attention_sink_bias", True):
        raise ValueError(
            f"{NAME}: a window layer without its sink is not run here")
    if (model.get("swa_head_dim", model["head_dim"]) != model["head_dim"]
            or model.get("swa_v_head_dim", model["v_head_dim"])
            != model["v_head_dim"]
            or model.get("swa_num_attention_heads",
                         model["num_attention_heads"])
            != model["num_attention_heads"]):
        raise ValueError(
            f"{NAME}: window layers whose heads differ from the full "
            "layers' in width or number are not run here")
    held = model["n_routed_experts"]
    qk = model["head_dim"]
    return MiMoV2Config(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_heads=model["num_attention_heads"], qk_head=qk,
        v_head=model["v_head_dim"],
        rope_dim=int(qk * model["partial_rotary_factor"]),
        n_kv_full=model["num_key_value_heads"],
        n_kv_window=model["swa_num_key_value_heads"],
        window=model["sliding_window"], rope_theta=model["rope_theta"],
        window_rope_theta=model["swa_rope_theta"],
        value_scale=model["attention_value_scale"],
        d_ff=model["intermediate_size"],
        layer_types=tuple("window" if t else "full"
                          for t in model["hybrid_layer_pattern"]),
        ff_types=tuple("moe" if f else "dense"
                       for f in model["moe_layer_freq"]),
        n_routed=model.get("published", {}).get("n_routed_experts", held),
        experts_held=(model.get("experts_held_first", 0), held),
        top_k=model["num_experts_per_tok"],
        routed_scale=model.get("routed_scaling_factor") or 1.0,
        expert_ff=model["moe_intermediate_size"],
        norm_eps=model["layernorm_epsilon"], dtype=dtype)


# -------------------------------------------------------------- attention

def rotate(x, pos, theta: float, cfg: MiMoV2Config):
    """Rotate-half RoPE over the first `rope_dim` values of each head of x
    (T, heads, qk) at positions `pos` (T,); the rest as it is."""
    r = cfg.rope_dim
    return jnp.concatenate([rope(x[..., :r], pos, theta), x[..., r:]], -1)


def qkv(x, lp, cfg: MiMoV2Config, kind: str, pos):
    """x (T, d) at positions `pos` (T,) -> q (T, H, qk) and k (T, KV, qk)
    rotated, v (T, KV, v) scaled, all in the compute type."""
    dt, t = cfg.dtype, x.shape[0]
    h, kv, qk = cfg.n_heads, cfg.kv_heads(kind), cfg.qk_head
    u = rms_norm(x, lp["op_norm"], cfg.norm_eps).astype(dt)
    with jax.named_scope("lm.attn.qkv"):
        y = u @ lp["w_qkv"].astype(dt)
        q = y[:, :h * qk].reshape(t, h, qk)
        k = y[:, h * qk:(h + kv) * qk].reshape(t, kv, qk)
        v = y[:, (h + kv) * qk:].reshape(t, kv, cfg.v_head)
        theta = cfg.theta(kind)
        v = (v.astype(jnp.float32) * cfg.value_scale).astype(dt)
        return rotate(q, pos, theta, cfg), rotate(k, pos, theta, cfg), v


def to_row(k, v, cfg: MiMoV2Config):
    """Keys (T, KV, qk) and values (T, KV, v) -> cache rows (T, row): every
    KV head's unrotated key part, then every head's rotated part, then every
    head's values."""
    r, t = cfg.rope_dim, k.shape[0]
    return jnp.concatenate([k[..., r:].reshape(t, -1),
                            k[..., :r].reshape(t, -1), v.reshape(t, -1)], -1)


def split_row(rows, cfg: MiMoV2Config):
    """Cache rows (..., row) -> keys (..., KV, qk), rotated part first, and
    values (..., KV, v)."""
    r, nope = cfg.rope_dim, cfg.qk_head - cfg.rope_dim
    kv = rows.shape[-1] // (cfg.qk_head + cfg.v_head)
    lead = rows.shape[:-1]
    k_nope = rows[..., :kv * nope].reshape(*lead, kv, nope)
    k_rope = rows[..., kv * nope:kv * cfg.qk_head].reshape(*lead, kv, r)
    v = rows[..., kv * cfg.qk_head:].reshape(*lead, kv, cfg.v_head)
    return jnp.concatenate([k_rope, k_nope], -1), v


def attend(q, k, v, keep, cfg: MiMoV2Config, sink=None):
    """Queries q (Q, H, qk) over keys k (S, KV, qk) and values v (S, KV, v)
    under `keep` (Q, S) -> o (Q, H, v): scores and softmax in float32, the
    probabilities in the compute type against the values. With `sink` (H,)
    each head's softmax has `exp(sink_h)` in its denominator as one more
    term that weighs no value."""
    f32, n_q = jnp.float32, q.shape[0]
    kv = k.shape[-2]
    qg = q.reshape(n_q, kv, cfg.n_heads // kv, cfg.qk_head)
    s = jnp.einsum("qgjd,sgd->gjqs", qg, k,
                   preferred_element_type=f32) / math.sqrt(cfg.qk_head)
    s = jnp.where(keep[None, None], s, NEG)
    if sink is not None:
        a = sink.astype(f32).reshape(kv, -1)[:, :, None, None]
        s = jnp.concatenate([s, jnp.broadcast_to(a, s.shape[:-1] + (1,))], -1)
    p = jax.nn.softmax(s, axis=-1)[..., :k.shape[0]].astype(cfg.dtype)
    o = jnp.einsum("gjqs,sgd->qgjd", p, v, preferred_element_type=f32)
    return o.reshape(n_q, cfg.n_heads, cfg.v_head).astype(cfg.dtype)


def attn_out(x, o, lp, cfg: MiMoV2Config):
    """The operator's second half: the heads' outputs o (T, H, v) through
    W_o into the residual."""
    dt = cfg.dtype
    with jax.named_scope("lm.attn.out"):
        return x + o.reshape(o.shape[0], -1).astype(dt) @ lp["w_o"].astype(dt)


def full_in(x, lp, cfg: MiMoV2Config, pos):
    """A full layer's first half, up to what its caller does with the paged
    pool: (q (T, H, qk), the positions' cache rows (T, row))."""
    q, k, v = qkv(x, lp, cfg, "full", pos)
    return q, to_row(k, v, cfg)


full_out = attn_out


def decode_attention(q, rows, live, cfg: MiMoV2Config):
    """The decode kernel's oracle in plain `jax.numpy`: one query a sequence
    q (B, H, qk) over gathered full-layer cache rows (B, S, row) under
    `live` (B, S) -> o (B, H, v)."""
    with jax.named_scope("lm.attn.full"):
        k, v = split_row(rows, cfg)
        return jax.vmap(lambda q_, k_, v_, keep: attend(
            q_[None], k_, v_, keep[None], cfg)[0])(q, k, v, live)


def decode_kernel(q, pool, layer, table, pos, cfg: MiMoV2Config, *,
                  block_size: int, interpret: bool):
    """The full layers' decode attention on the Mosaic kernel, over the
    pool where it lies (`split_gqa_decode_attention`)."""
    with jax.named_scope("lm.attn.full"):
        return split_gqa_decode_attention(
            q, pool, layer, table, pos, block_size=block_size,
            n_kv_heads=cfg.n_kv_full, rope=cfg.rope_dim, v_dim=cfg.v_head,
            interpret=interpret)


def kernel_gate(cfg: MiMoV2Config, block_size: int, dtype) -> tuple:
    """(whether the decode kernel compiles for this pool, what to say where
    it was asked for and does not)."""
    return (split_gqa_decode_ok(
        block_size, cfg.n_kv_full, cfg.n_heads // cfg.n_kv_full,
        cfg.qk_head, cfg.rope_dim, cfg.v_head, dtype),
        f"the split-row grouped-query decode kernel does not compile for "
        f"pages of {block_size} {jnp.dtype(dtype)} rows of {cfg.n_kv_full} x "
        f"({cfg.qk_head} + {cfg.v_head}) (ops/decode_pallas.py "
        "split_gqa_decode_ok)")


def prefill_attention(q, qpos, read_rows, n_keys, cfg: MiMoV2Config, *,
                      key_block: int):
    """A full layer's attention of a chunk's queries q (C, H, qk) at
    absolute positions `qpos` (C,) over cache positions `0 .. n_keys - 1`
    (traced), BLOCKED over the keys: `read_rows(j)` hands the cache rows of
    positions `j * key_block ..` as (key_block, row) and they are folded
    into a float32 online softmax, so that no score block larger than (H, C,
    key_block) is made and the blocks past the last live key are not read.
    Query i sees key positions <= qpos[i]. Returns o (C, H, v). The oracle
    of `prefill_kernel`, and the engine's prefill where that kernel is not
    taken."""
    dt, f32 = cfg.dtype, jnp.float32
    c, kv = q.shape[0], cfg.n_kv_full
    qg = q.reshape(c, kv, cfg.n_heads // kv, cfg.qk_head)
    lead = (kv, cfg.n_heads // kv, c)
    scale = 1.0 / math.sqrt(cfg.qk_head)

    def one(j, carry):
        m, l, acc = carry
        with jax.named_scope("lm.attn.full"):
            k, v = split_row(read_rows(j), cfg)
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
        jnp.zeros(lead + (cfg.v_head,), f32)))
    o = acc / jnp.maximum(l, 1e-30)[..., None]            # (KV, G, C, v)
    return o.transpose(2, 0, 1, 3).reshape(
        c, cfg.n_heads, cfg.v_head).astype(dt)


def prefill_kernel(q, pool, layer, table, pos0, n_keys, cfg: MiMoV2Config,
                   *, block_size: int, interpret: bool):
    """A full layer's prefill attention on the Mosaic kernel, over the pool
    where it lies (`split_gqa_prefill_attention`): the chunk's queries q (C,
    H, qk) at positions `pos0 ..` over cache positions `0 .. n_keys - 1`,
    the chunk's own rows among them -> o (C, H, v)."""
    with jax.named_scope("lm.attn.full"):
        return split_gqa_prefill_attention(
            q, pool, layer, table, pos0, n_keys, block_size=block_size,
            n_kv_heads=cfg.n_kv_full, rope=cfg.rope_dim, v_dim=cfg.v_head,
            interpret=interpret)


def prefill_kernel_gate(cfg: MiMoV2Config, block_size: int, dtype) -> bool:
    """Whether the prefill kernel compiles for this pool (where it does not,
    the engine's prefill takes `prefill_attention`)."""
    return split_gqa_prefill_ok(
        block_size, cfg.n_kv_full, cfg.n_heads // cfg.n_kv_full,
        cfg.qk_head, cfg.rope_dim, cfg.v_head, dtype)


def prefill_kernel_scored(cfg: MiMoV2Config, pos0: int, n: int, chunk: int,
                          *, block_size: int, width: int) -> int:
    """The (query, key) pairs one full layer's prefill kernel scores for `n`
    tokens from `pos0` in a chunk bucket of `chunk` over a table of `width`
    blocks (`split_gqa_prefill_pairs`)."""
    return split_gqa_prefill_pairs(pos0, n, chunk,
                                   cfg.n_heads // cfg.n_kv_full, width,
                                   block_size)


def ring_positions(last, cfg: MiMoV2Config):
    """The position each ring row holds once positions up to `last` (...,)
    have been written: row j holds the largest p <= last with p = j mod
    window (..., window). A row is the sequence's own where p >= 0: the
    writes before cover every position from `last - window + 1` on, and a
    slot handed on from another sequence holds nothing else that the mask
    keeps."""
    j = jnp.arange(cfg.window)
    last = jnp.asarray(last)[..., None]
    return last - (last - j) % cfg.window


def window_decode(x, lp, i, cfg: MiMoV2Config, pool, slots, pos):
    """A window layer's decode step over its ring in the state pool, whole:
    x (B, d) at positions `pos` (B,), each row's ring at slot `slots` (B,) of
    window layer `i` of `pool` (window layers, slots, window, row). The new
    row goes in ring row `pos mod window` (written to the pool, and put in
    place in the ring as read for this step), and the query attends over the
    ring's rows whose position is its own (`ring_positions`), with the sink.
    Returns (x, pool)."""
    q, k, v = qkv(x, lp, cfg, "window", pos)
    row = to_row(k, v, cfg)                                   # (B, row)
    at = pos % cfg.window
    with jax.named_scope("lm.attn.window"):
        ring = pool[i, slots]                                 # (B, w, row)
        here = jnp.arange(cfg.window)[None, :] == at[:, None]
        ring = jnp.where(here[..., None], row[:, None].astype(ring.dtype),
                         ring)
        keep = ring_positions(pos, cfg) >= 0
        rk, rv = split_row(ring.astype(cfg.dtype), cfg)
        o = jax.vmap(lambda q_, k_, v_, keep_: attend(
            q_[None], k_, v_, keep_[None], cfg, lp["sink"])[0])(
                q, rk, rv, keep)
        # the new row as the ring read holds it: the write then waits for
        # the read, and the pool is updated in place (written first, the
        # read would need a copy of the pool)
        pool = pool.at[i, slots, at].set(ring[jnp.arange(x.shape[0]), at])
    return attn_out(x, o, lp, cfg), pool


def window_prefill(x, lp, i, cfg: MiMoV2Config, pool, slot, pos0, n_valid):
    """A window layer's step for a prefill chunk x (C, d) at positions `pos0
    ..` of which the first `n_valid` (traced) are tokens, over the ring at
    slot `slot`: the keys are the ring's rows (positions `pos0 - window ..
    pos0 - 1`, kept where they are the sequence's own) followed by the
    chunk's, under the band `p - window < j <= p`, with the sink. Then the
    chunk's LAST min(n_valid, window) valid positions are written into the
    ring: a bucket's dead tail never reaches it, and a chunk shorter than the
    window keeps the older rows. Returns (x, pool)."""
    c, w = x.shape[0], cfg.window
    pv = pos0 + jnp.arange(c)
    q, k, v = qkv(x, lp, cfg, "window", pv)
    rows = to_row(k, v, cfg)                                  # (C, row)
    with jax.named_scope("lm.attn.window"):
        ring = pool[i, slot]                                  # (w, row)
        p_ring = ring_positions(pos0 - 1, cfg)
        kpos = jnp.concatenate([p_ring, pv])
        ok = jnp.concatenate([p_ring >= 0, jnp.arange(c) < n_valid])
        keep = ok[None, :] & (kpos[None, :] <= pv[:, None]) & (
            kpos[None, :] > pv[:, None] - w)
        keys = jnp.concatenate([ring, rows.astype(ring.dtype)])
        rk, rv = split_row(keys.astype(cfg.dtype), cfg)
        o = attend(q, rk, rv, keep, cfg, lp["sink"])
        # ring row r takes the chunk's row n_valid - w + r where that is
        # one, taken from the keys as read (so that the write waits for the
        # read, and the pool is updated in place)
        last = n_valid - w + jnp.arange(w)
        dest = jnp.where(last >= 0, (pos0 + last) % w, w)    # w: dropped
        pool = pool.at[i, slot, dest].set(
            keys[w + jnp.maximum(last, 0)], mode="drop")
    return attn_out(x, o, lp, cfg), pool


def attn_pairs(cfg: MiMoV2Config, pos0: int, n: int, chunk: int,
               scored: int) -> dict:
    """The (query, key) pairs of one prefill program's attention, summed
    over the layers of each kind: ("full" | "window", "live" | "scored").
    Scored: a full layer's `scored` pairs, what its attention walks (the
    blocked loop's chunk of `chunk` rows against its key blocks, or the
    kernel's blocks of query positions against their fetch steps,
    `prefill_kernel_scored`), a window layer's chunk against the ring and
    the chunk. Live: what the masks keep for the `n` tokens from `pos0` -
    every position up to its own in a full layer, the last `window` of them
    in a window layer."""
    w = cfg.window
    span = np.arange(pos0, pos0 + n)
    return {
        ("full", "live"): cfg.n_full * (n * pos0 + n * (n + 1) // 2),
        ("full", "scored"): cfg.n_full * scored,
        ("window", "live"): cfg.n_window * int(
            np.minimum(span + 1, w).sum()),
        ("window", "scored"): cfg.n_window * chunk * (w + chunk),
    }


# ----------------------------------------------------------- feed-forward

def feed_forward(x, lp, cfg: MiMoV2Config, kind: str, *, tile: int,
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
        scale=cfg.routed_scale, tile=tile, valid=valid, layer=layer)
    return x + y, stats


def embed_tokens(params, tokens, cfg: MiMoV2Config):
    """No positional signal here: each attention layer rotates its own."""
    return params["embed"][tokens].astype(cfg.dtype)


def final_logits(params, x, cfg: MiMoV2Config):
    """Final RMSNorm and the untied head of x (..., d) -> (..., vocab)
    float32, accumulated in float32."""
    u = rms_norm(x, params["normf_scale"], cfg.norm_eps).astype(cfg.dtype)
    return jnp.einsum("...d,vd->...v", u, params["head"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


def apply(params, tokens, cfg: MiMoV2Config, *, tile: int = 8,
          with_stats: bool = False):
    """The whole-sequence forward: tokens (S,) of one sequence -> logits (S,
    vocab) float32, the full layers under a causal mask and the window
    layers under the band with their sinks, no cache and no ring. The oracle
    of the engine's tests; with `with_stats` also the expert layers' routing
    counts, stacked."""
    s = tokens.shape[0]
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]
    band = causal & (pos[None, :] > pos[:, None] - cfg.window)
    x = embed_tokens(params, tokens, cfg)
    routing = []
    for op, oi, ff, fi in layer_plan(cfg):
        lp = layer_params(params, op, oi)
        q, k, v = qkv(x, lp, cfg, op, pos)
        o = attend(q, k, v, causal if op == "full" else band, cfg,
                   lp.get("sink"))
        x = attn_out(x, o, lp, cfg)
        x, stats = feed_forward(x, layer_params(params, ff, fi), cfg, ff,
                                tile=tile)
        if stats is not None:
            routing.append(stats)
    logits = final_logits(params, x, cfg)
    if with_stats:
        return logits, jax.tree.map(lambda *xs: jnp.stack(xs), *routing)
    return logits
