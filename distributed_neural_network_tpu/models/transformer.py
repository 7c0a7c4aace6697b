"""Decoder-only transformer LM with composable DP x SP x TP shardings.

The reference framework has exactly one model family - the LeNet CNN
(`/root/reference/models/model.py:9-27`) - and scales only the batch axis.
This module is the framework's second model family and its long-context /
multi-axis-parallel showcase: a GPT-style causal LM whose forward pass runs
unchanged on a single device or inside `jax.shard_map` over any combination
of

- a **data** axis (batch-sharded tokens),
- a **seq** axis (sequence/context parallelism: activations sharded along
  the sequence, attention via `parallel/ring.py`'s ring or Ulysses
  primitives, positions computed from the global offset),
- a **model** axis (Megatron-style tensor parallelism: attention heads and
  the MLP hidden dim column-sharded, row-sharded second projections
  followed by a single psum per block),
- an **expert** dimension (`cfg.n_experts > 0`): the dense FFN becomes a
  mixture-of-experts (`parallel/moe.py`), experts sharded over the data
  axis GShard-style with one all_to_all each way (`ep_axis`).

Design choices, TPU-first:
- Pure-JAX parameter pytree (no Module class): inside shard_map every leaf
  is the *local* shard, and the same `apply` code path serves all layouts -
  the sharding lives entirely in `param_specs()` + the mesh, XLA inserts
  the collectives.
- Matmul-heavy, static shapes, `lax` control-flow free: everything tiles
  onto the MXU; bf16-friendly (`cfg.dtype`).
- Sinusoidal positions computed on the fly from global offsets, so sequence
  shards need no position table and arbitrary context lengths cost nothing.
- Grad synchronization falls out of shard_map's autodiff typing: replicated
  (invariant) params get their gradient psum over data/seq automatically;
  tensor-sharded params keep local gradients. No hand-written allreduce.
"""

from __future__ import annotations

import os
import sys
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops.decode_pallas import decode_cache_attention, decode_kernel_ok
from ..ops.flash_pallas import block_remat_policy
from ..parallel.moe import expert_capacity, moe_ffn
from ..parallel.ring import (
    attention,
    ring_attention,
    ulysses_attention,
    zigzag_positions,
    zigzag_ring_attention,
)
from ..runtime import on_tpu

# "zigzag" = load-balanced causal ring attention; tokens must be fed in
# zigzag shard order (parallel/ring.py zigzag_order) - ~2x the causal
# throughput of "ring" at scale. "flash" = Pallas TPU flash kernel for the
# LOCAL (seq_axis=None) case - long contexts on one chip (ops/flash.py).
ATTN_IMPLS = ("full", "ring", "ulysses", "zigzag", "flash")

# What `train/lm.py` asks of a model's module (reached from a configuration
# as `cfg.module`): `NAME`, `init_params`, `param_specs`, `apply_hidden` ->
# (hidden, aux), `refuse_axes`, and what `aux` is. Here it is the expert
# layers' balancing loss: averaged over the mesh and added to the loss at
# `aux_weight`. (Where it is not a loss, it is counts: summed over the mesh
# and handed out as the step's last output.)
NAME = "transformer"
AUX_IS_LOSS = True


def refuse_axes(*, seq_axis=None, tp_axis=None, ep_axis=None) -> None:
    """This model runs under every axis `train/lm.py` lays out."""


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 256
    d_model: int = 128
    n_heads: int = 8
    n_layers: int = 2
    d_ff: int = 512
    dtype: jnp.dtype = jnp.float32
    # rematerialize each block in the backward pass (jax.checkpoint): trades
    # ~1/3 more FLOPs for O(layers * seq^2) less activation memory - the
    # standard long-context/deep-stack memory lever on TPU
    remat: bool = False
    # jax.checkpoint policy NAME (jax.checkpoint_policies.*) applied with
    # remat=True; "" = save nothing (full recompute). "dots_saveable"
    # stores every matmul output and recomputes only the elementwise ops
    # (LN/gelu/residual) in backward - a few percent FLOP tax instead of
    # full remat's ~1/3, while still dropping the non-dot intermediates
    # that OOM the 16 GB chip at d1024/b8 no-remat (measured r5:
    # AllocateBuffer on 512 MB stacked-scan temps). The canonical TPU
    # memory/FLOP trade between "none" and "full". A dots-saving policy
    # keeps the flash kernel's output too (`block_remat_policy`).
    remat_policy: str = ""
    # rematerialize ONLY the attention inner call (scores/softmax/values):
    # the (B, H, S, S) score tensor - the piece that actually OOMs at long
    # seq - is recomputed in backward while every matmul residual
    # ((B, S, d)-sized, cheap) stays stored. Costs ~4*S*d extra
    # FLOPs/token/layer (the attention einsums only) instead of block
    # remat's full ~1/3, and needs no Pallas kernel. Ignored when
    # remat=True (block remat already covers the scores).
    remat_attn: bool = False
    # Mixture-of-experts FFN (0 = dense). Experts replace the MLP in every
    # block; capacity_factor sizes the static per-expert slot count.
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 2.0
    # dispatch: "sort" (scatter/gather coordinates, O(T*k + E*C*d) memory,
    # real-scale default) or "dense" ((T, E, C) one-hot einsums, the
    # small-shape oracle) - identical numerics (parallel/moe.py)
    moe_dispatch: str = "sort"
    # low-precision attention forward ("" = off): "int8" / "fp8" run the
    # QK^T and PV matmuls in the quantized dtype with per-token scales
    # and wide accumulation (ops/quant.py; the Pallas quant kernel under
    # attn_impl='flash' on TPU, the XLA reference elsewhere). Training
    # backward stays full precision (straight-through); the bench parity
    # gate bounds the loss/logit effect (docs/MEASUREMENT.md). Local
    # attention only - a sequence axis (ring/ulysses/zigzag) rejects it.
    attn_quant: str = ""
    # router z-loss weight RELATIVE to the load-balance aux: the training
    # loss adds aux_weight * (switch_aux + moe_z_weight * mean(lse^2)), so
    # the default 0.1 with lm_loss's aux_weight=0.01 gives the standard
    # 1e-3 z-loss coefficient (ST-MoE)
    moe_z_weight: float = 0.1

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def module(self):
        """The module that runs this configuration."""
        return sys.modules[__name__]


def init_params(key: jax.Array, cfg: TransformerConfig):
    """Replicated-layout parameter pytree (shard with `param_specs`)."""
    d, f, v = cfg.d_model, cfg.d_ff, cfg.vocab_size
    k_embed, k_layers, k_out = jax.random.split(key, 3)
    scale = 1.0 / np.sqrt(d)

    def dense(k, shape, s):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(jnp.float32)

    e = cfg.n_experts
    layers = []
    for lk in jax.random.split(k_layers, cfg.n_layers):
        ks = jax.random.split(lk, 7)
        layer = {
            "ln1_scale": jnp.ones((d,), jnp.float32),
            "ln1_bias": jnp.zeros((d,), jnp.float32),
            "wq": dense(ks[0], (d, d), scale),
            "wk": dense(ks[1], (d, d), scale),
            "wv": dense(ks[2], (d, d), scale),
            "wo": dense(ks[3], (d, d), scale / np.sqrt(2 * cfg.n_layers)),
            "ln2_scale": jnp.ones((d,), jnp.float32),
            "ln2_bias": jnp.zeros((d,), jnp.float32),
        }
        w2_scale = 1.0 / np.sqrt(f) / np.sqrt(2 * cfg.n_layers)
        if e:
            layer.update(
                {
                    "wr": dense(ks[6], (d, e), scale),
                    "w1": dense(ks[4], (e, d, f), scale),
                    "b1": jnp.zeros((e, f), jnp.float32),
                    "w2": dense(ks[5], (e, f, d), w2_scale),
                    "b2": jnp.zeros((e, d), jnp.float32),
                }
            )
        else:
            layer.update(
                {
                    "w1": dense(ks[4], (d, f), scale),
                    "b1": jnp.zeros((f,), jnp.float32),
                    "w2": dense(ks[5], (f, d), w2_scale),
                    "b2": jnp.zeros((d,), jnp.float32),
                }
            )
        layers.append(layer)
    return {
        "embed": dense(k_embed, (v, d), 1.0),
        "lnf_scale": jnp.ones((d,), jnp.float32),
        "lnf_bias": jnp.zeros((d,), jnp.float32),
        "head": dense(k_out, (d, v), scale),
        "layers": _stack_layers(layers),
    }


def _stack_layers(layers):
    """Stack per-layer dicts on a leading layer axis (scanned in apply)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def param_skeleton(cfg: TransformerConfig):
    """The param tree's STRUCTURE (same keys as `init_params`, placeholder
    leaves) - what the partition-rule matcher walks when no real params
    exist yet. Kept next to `init_params` so the two can never drift."""
    layer_keys = [
        "ln1_scale", "ln1_bias", "wq", "wk", "wv", "wo",
        "ln2_scale", "ln2_bias",
    ]
    if cfg.n_experts:
        layer_keys += ["wr", "w1", "b1", "w2", "b2"]
    else:
        layer_keys += ["w1", "b1", "w2", "b2"]
    return {
        "embed": 0,
        "lnf_scale": 0,
        "lnf_bias": 0,
        "head": 0,
        "layers": {k: 0 for k in layer_keys},
    }


def param_specs(
    cfg: TransformerConfig,
    tp_axis: str | None = None,
    ep_axis: str | None = None,
    rules=None,
):
    """PartitionSpec pytree for the param tree, derived from the
    declarative rule table (`parallel/rules.py lm_partition_rules`).

    With `tp_axis`: wq/wk/wv and w1 column-sharded (heads / ff-hidden split),
    wo and w2 row-sharded (psum after), b1 sharded with its columns;
    everything else replicated. Without: fully replicated. With
    `cfg.n_experts > 0` and `ep_axis`: expert tensors additionally sharded
    over the expert dimension (router replicated).

    ``rules`` overrides the built-in table with a custom ordered
    ``(regex, PartitionSpec)`` list (the ``--sharding rules:<file>``
    path); every leaf must match or derivation fails with the path named.
    """
    from ..parallel.rules import lm_partition_rules, match_partition_rules

    if rules is None:
        rules = lm_partition_rules(
            tp_axis=tp_axis, ep_axis=ep_axis, n_experts=cfg.n_experts
        )
    return match_partition_rules(
        rules, param_skeleton(cfg), skip_scalars=False
    )


def _layer_norm(x, scale, bias, eps=1e-5):
    m = x.mean(-1, keepdims=True)
    v = ((x - m) ** 2).mean(-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + eps) * scale + bias


def _positions(s_local: int, seq_axis: str | None, attn_impl: str = "ring"):
    if seq_axis is None:
        return jnp.arange(s_local)
    if attn_impl == "zigzag":
        return zigzag_positions(s_local, seq_axis)
    return jax.lax.axis_index(seq_axis) * s_local + jnp.arange(s_local)


def _sinusoid_pe(pos, d_model, dtype):
    half = d_model // 2
    freqs = jnp.exp(-np.log(10000.0) * jnp.arange(half) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


def _attend(q, k, v, *, impl, seq_axis, s_local, quant: str = ""):
    if seq_axis is None:
        if impl == "flash":
            from ..ops.flash import flash_local_attention

            return flash_local_attention(q, k, v, causal=True,
                                         quant=quant or None)
        if quant:
            from ..ops.quant import quantized_attention

            return quantized_attention(q, k, v, causal=True, fmt=quant)
        return attention(q, k, v, causal=True)
    if quant:
        raise ValueError(
            f"attn_quant={quant!r} is the local quantized path; a "
            "sequence axis (ring/ulysses/zigzag) has no quantized "
            "attention - drop the seq axis or attn_quant"
        )
    if impl == "flash":
        raise ValueError(
            "attn impl 'flash' is the local kernel (no sequence axis); use "
            "'ring'/'ulysses'/'zigzag' for sequence parallelism"
        )
    if impl == "ring":
        return ring_attention(q, k, v, seq_axis, causal=True)
    if impl == "ulysses":
        return ulysses_attention(q, k, v, seq_axis, causal=True)
    if impl == "zigzag":
        return zigzag_ring_attention(q, k, v, seq_axis)
    raise ValueError(
        f"with a sequence axis, attn impl must be 'ring', 'ulysses' or "
        f"'zigzag', got {impl!r}"
    )


def plain_mm(dt):
    """How a caller multiplies by a weight unless it says otherwise: ``x @
    w`` at the model dtype. The serving engine hands the block halves its
    int8-weight product in this one's place (serve/engine.py `_make_mm`)."""
    def mm(x, w):
        return x @ w.astype(dt)
    return mm


def block_qkv(x, lp, cfg: TransformerConfig, mm=None):
    """The block's first half, up to what its caller does with a cache:
    LayerNorm and the three projections of x (..., d), each returned as
    (..., H_local, head_dim)."""
    mm = mm or plain_mm(cfg.dtype)
    h = _layer_norm(x, lp["ln1_scale"], lp["ln1_bias"]).astype(cfg.dtype)

    def heads(w):
        y = mm(h, w)
        return y.reshape(*y.shape[:-1], -1, cfg.head_dim)

    return heads(lp["wq"]), heads(lp["wk"]), heads(lp["wv"])


def block_out(x, o, lp, cfg: TransformerConfig, mm=None, *, tp_axis=None,
              ep_axis=None, capacity=None, moe_dispatch=None):
    """The block's second half: the attention output o (..., H_local,
    head_dim) through `wo` into the residual x (..., d), then LayerNorm
    and the GELU MLP - or, with `cfg.n_experts`, the expert layer at the
    `capacity` and `moe_dispatch` its caller names (the configuration's
    dispatch by default). Returns (x, aux), aux the expert layer's
    balancing loss (0.0 dense)."""
    dt = cfg.dtype
    mm = mm or plain_mm(dt)
    o = mm(o.reshape(*o.shape[:-2], -1), lp["wo"])
    if tp_axis is not None:
        o = jax.lax.psum(o, tp_axis)
    x = x + o

    h = _layer_norm(x, lp["ln2_scale"], lp["ln2_bias"]).astype(dt)
    if cfg.n_experts:
        y, aux = moe_ffn(
            h.reshape(-1, cfg.d_model),
            lp["wr"], lp["w1"], lp["b1"], lp["w2"], lp["b2"],
            top_k=cfg.moe_top_k,
            capacity=capacity,
            ep_axis=ep_axis,
            tp_axis=tp_axis,
            dispatch_impl=moe_dispatch or cfg.moe_dispatch,
            z_loss_weight=cfg.moe_z_weight,
        )
        x = x + y.reshape(x.shape)
    else:
        h = jax.nn.gelu(mm(h, lp["w1"]) + lp["b1"].astype(dt))
        h = mm(h, lp["w2"])
        if tp_axis is not None:
            h = jax.lax.psum(h, tp_axis)
        x = x + h + lp["b2"].astype(dt)
        aux = jnp.float32(0.0)
    return x, aux


def transformer_block(x, lp, cfg: TransformerConfig, *, attend, tp_axis=None,
                      ep_axis=None, capacity=None):
    """One pre-norm block on x (B, S_local, d) with layer params lp.

    `attend`: (q, k, v) -> output, each (B, S_local, H_local, head_dim) -
    the caller chooses full/ring/Ulysses and the causal offset convention.
    Returns (x, aux) where aux is the MoE load-balancing loss (0.0 dense).
    `apply_hidden` (flat or dp/sp/tp-sharded execution) and the pipeline
    schedule (`parallel/pipeline.py`) come through here; a caller whose
    middle is more than a function of (q, k, v) - `generate` and the
    serving engine's bucket programs, which update a cache there - calls
    the two halves itself.
    """
    q, k, v = block_qkv(x, lp, cfg)
    return block_out(x, attend(q, k, v), lp, cfg, tp_axis=tp_axis,
                     ep_axis=ep_axis, capacity=capacity)


def masked_attention(q, ks, vs, live, dt):
    """Attention of queries q (B, Q, H, Dh) over cached keys and values
    (B, H, S, Dh), where `live` (broadcast against (B, H, Q, S)) says
    which cache positions a query may see: scores in float32 over
    sqrt(Dh), softmax, probabilities back at `dt`. Returns (B, Q, H, Dh).
    What `generate` and the serving engine run where no kernel does."""
    scores = jnp.einsum("bqhd,bhsd->bhqs", q, ks).astype(jnp.float32)
    scores = scores / np.sqrt(q.shape[-1])
    neg = jnp.asarray(-1e30, jnp.float32)
    probs = jax.nn.softmax(jnp.where(live, scores, neg), axis=-1)
    return jnp.einsum("bhqs,bhsd->bqhd", probs.astype(dt), vs)


def final_norm(params, x, dt):
    """The final LayerNorm, at `dt`: the hidden a chunked loss takes."""
    return _layer_norm(x, params["lnf_scale"], params["lnf_bias"]).astype(dt)


def final_logits(params, x, dt):
    """Final LayerNorm and head of x (..., d) -> (..., vocab) float32, the
    head's product accumulated and kept in float32 (logits rounded to
    bfloat16 tie, and a greedy token is an argmax). A caller slices the
    positions it wants first."""
    head = params["head"].astype(dt).astype(jnp.float32)
    return final_norm(params, x, dt) @ head


def _blocks(params, tokens, cfg: TransformerConfig, *, seq_axis=None,
            tp_axis=None, ep_axis=None, attn_impl="ring"):
    """Embedding and the scanned blocks: tokens (B, S_local) -> (x (B,
    S_local, d_model) before the final norm, mean aux over layers)."""
    dt = cfg.dtype
    b, s_local = tokens.shape
    x = params["embed"][tokens].astype(dt)
    x = x + _sinusoid_pe(
        _positions(s_local, seq_axis, attn_impl), cfg.d_model, dt
    )[None]
    cap = expert_capacity(
        b * s_local, cfg.n_experts, cfg.moe_top_k, cfg.moe_capacity_factor
    ) if cfg.n_experts else None

    def attend(q, k, v):
        return _attend(
            q, k, v, impl=attn_impl, seq_axis=seq_axis, s_local=s_local,
            quant=cfg.attn_quant,
        )

    if cfg.remat_attn and not cfg.remat:
        attend = jax.checkpoint(attend)

    def block(x, lp):
        return transformer_block(x, lp, cfg, attend=attend, tp_axis=tp_axis,
                                 ep_axis=ep_axis, capacity=cap)

    if cfg.remat:
        block = jax.checkpoint(
            block, policy=block_remat_policy(cfg.remat_policy))
    x, aux = jax.lax.scan(block, x, params["layers"])
    return x, aux.mean()


def apply_hidden(params, tokens, cfg: TransformerConfig, *, seq_axis=None,
                 tp_axis=None, ep_axis=None, attn_impl="ring"):
    """tokens (B, S_local) int32 -> (hidden (B, S_local, d_model), aux).

    The pre-head forward: embedding + blocks + final layer norm, WITHOUT the
    vocab projection. Loss paths that chunk the cross-entropy (train/lm.py)
    consume this directly so the (B, S, vocab) logits tensor is never
    materialized whole - at vocab 32k/seq 2048 that tensor is GBs of HBM
    traffic and the single biggest single-chip LM cost.
    """
    x, aux = _blocks(params, tokens, cfg, seq_axis=seq_axis, tp_axis=tp_axis,
                     ep_axis=ep_axis, attn_impl=attn_impl)
    return final_norm(params, x, cfg.dtype), aux


def apply_with_aux(
    params,
    tokens,
    cfg: TransformerConfig,
    *,
    seq_axis: str | None = None,
    tp_axis: str | None = None,
    ep_axis: str | None = None,
    attn_impl: str = "ring",
):
    """tokens (B, S_local) int32 -> (logits (B, S_local, vocab) f32, aux).

    Call directly for single-device, or inside shard_map with tokens sharded
    (data/seq axes) and params placed per `param_specs`. With tp_axis, each
    device holds H/tp heads and d_ff/tp hidden columns; one psum per
    attention-out and MLP-out projection restores the full residual. With
    cfg.n_experts, the MLP is a mixture-of-experts (experts sharded over
    `ep_axis` when given) and `aux` is the mean Switch load-balancing loss
    over layers (0.0 for dense).
    """
    x, aux = _blocks(params, tokens, cfg, seq_axis=seq_axis, tp_axis=tp_axis,
                     ep_axis=ep_axis, attn_impl=attn_impl)
    return final_logits(params, x, cfg.dtype), aux


def apply(params, tokens, cfg: TransformerConfig, **kw):
    """Logits-only wrapper over `apply_with_aux` (same signature)."""
    return apply_with_aux(params, tokens, cfg, **kw)[0]


def early_exit_params(params, n_layers: int):
    """The same param tree truncated to its FIRST ``n_layers`` blocks
    (leading stacked-layer axis sliced; embed / final LN / head shared
    with the full model). This IS the serving drafter's model
    (docs/SERVING.md "Speculative decoding"): `ServeEngine` slices once
    at init and runs k cheap greedy steps through it per speculative
    round, so the draft distribution is pinned against
    ``apply(early_exit_params(p, E), ...)`` - no second set of weights,
    no train-time change."""
    total = next(iter(jax.tree.leaves(params["layers"]))).shape[0]
    if not 1 <= n_layers <= total:
        raise ValueError(
            f"early-exit depth must be in [1, {total}], got {n_layers}"
        )
    return {
        **params,
        "layers": jax.tree.map(lambda p: p[:n_layers], params["layers"]),
    }


def early_exit_logits(params, tokens, cfg: TransformerConfig,
                      n_layers: int):
    """Teacher-forced logits of the early-exit drafter: the first
    ``n_layers`` blocks + the shared final LN/head, (B, S) -> (B, S,
    vocab) f32. The offline oracle tests pin the engine's jitted
    drafter against (greedy argmax over these logits == the drafted
    tokens)."""
    return apply(early_exit_params(params, n_layers), tokens, cfg,
                 attn_impl="full")


def param_count(params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))


def generate_sharded(
    params,
    prompt,
    cfg: TransformerConfig,
    mesh,
    *,
    data_axis: str = "data",
    **kw,
):
    """`generate` with the batch sharded over `data_axis` of `mesh`.

    Fleet-style decode: params replicate, each device decodes its slice of
    the prompt batch - the KV caches and every per-token intermediate
    carry the batch dimension, so XLA's SPMD partitioner runs the whole
    scan with zero cross-device traffic after the initial placement
    (verified identical to single-device `generate` by
    tests/test_generate.py). Batch must divide the axis size.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    b = prompt.shape[0]
    n = mesh.shape[data_axis]
    if b % n:
        raise ValueError(
            f"prompt batch ({b}) must divide by the {data_axis!r} axis "
            f"size ({n})"
        )
    repl = NamedSharding(mesh, PartitionSpec())
    params = jax.tree.map(lambda p: jax.device_put(p, repl), params)
    prompt = jax.device_put(
        prompt, NamedSharding(mesh, PartitionSpec(data_axis))
    )
    if kw.get("prompt_lens") is not None:
        kw = dict(kw)
        kw["prompt_lens"] = jax.device_put(
            jnp.asarray(kw["prompt_lens"], jnp.int32),
            NamedSharding(mesh, PartitionSpec(data_axis)),
        )
    return generate(params, prompt, cfg, **kw)


# ------------------------------------------------------------- inference


def generate(
    params,
    prompt,
    cfg: TransformerConfig,
    *,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 0.0,
    key: jax.Array | None = None,
    prompt_lens=None,
):
    """Autoregressive decoding with per-layer KV caches.

    prompt: (B, S_p) int32. Returns (B, S_p + max_new_tokens) int32 - the
    prompt followed by generated tokens. temperature 0 = greedy argmax;
    > 0 samples from softmax(logits / temperature) (requires `key`);
    top_k > 0 restricts sampling to the k most likely tokens first;
    top_p in (0, 1) further restricts it to the nucleus - the smallest
    set of tokens whose cumulative probability (at this temperature,
    after any top-k cut) reaches top_p. Both filters always keep the
    most likely token, so sampling never degenerates.

    ``prompt_lens`` (B,) int32 makes the batch LEFT-PADDED mixed-length:
    sequence b's real tokens occupy the LAST ``prompt_lens[b]`` columns
    (columns 0..S_p-len-1 are pad and fully ignored - their cache
    entries are masked out of every attention and their position ids
    never exist). Left padding aligns every sequence's last prompt token
    at column S_p-1, so generation is the uniform region [S_p, total) -
    exactly the batch shape a continuous-batching server feeds
    (serve/engine.py). Per-sequence positions are 0..len-1 (position
    embeddings offset by the pad width), so each row decodes exactly as
    its unpadded single-sequence `generate` would (pinned by
    tests/test_generate.py against the per-sequence oracle). Not
    supported with the fused Pallas decode kernel (a scalar-pos kernel;
    per-sequence masks need the XLA path) - explicitly rejected.

    TPU-shaped: one `lax.scan` over time steps (static total length
    S_p + max_new_tokens), an inner scan over the stacked layers, KV
    caches updated in place with `dynamic_update_slice` - no growing
    shapes, one compile. The prompt is consumed through the same cached
    step as generation (its logits are discarded), so there is a single
    code path whose cache math is pinned against the teacher-forced
    forward by tests/test_generate.py. Training-side parallelism
    (`apply`'s seq/tp/ep axes) is out of scope here: decode is the
    single-device inference path; shard the batch outside for fleet
    serving. MoE models route through the dense dispatch with capacity
    sized so decode never drops a token; the training forward, by
    contrast, is capacity-limited (moe_capacity_factor) and can drop
    under router imbalance - parity with the teacher-forced forward
    therefore holds exactly in the no-drop regime and diverges on
    whatever tokens training would have dropped.
    """
    if temperature > 0.0 and key is None:
        raise ValueError("temperature > 0 sampling requires `key`")
    if not 0.0 <= top_p <= 1.0:
        raise ValueError(f"top_p must be in [0, 1], got {top_p}")
    dt = cfg.dtype
    b, s_p = prompt.shape
    offsets = None
    if prompt_lens is not None:
        prompt_lens = jnp.asarray(prompt_lens, jnp.int32)
        if prompt_lens.shape != (b,):
            raise ValueError(
                f"prompt_lens must be shape ({b},) to match the prompt "
                f"batch, got {prompt_lens.shape}"
            )
        lens = np.asarray(prompt_lens)
        if (lens < 1).any() or (lens > s_p).any():
            raise ValueError(
                f"prompt_lens must be in [1, {s_p}] (the padded prompt "
                f"width), got {lens.tolist()}"
            )
        offsets = s_p - prompt_lens  # pad width per sequence
    total = s_p + max_new_tokens
    L, H, Dh = cfg.n_layers, cfg.n_heads, cfg.head_dim
    prompt_pad = jnp.pad(prompt, ((0, 0), (0, max_new_tokens)))
    # caches are (L, B, H, total, Dh): collapsing (B, H) for the decode
    # kernel is then a free reshape. DNN_TPU_DECODE_IMPL selects the
    # per-step attention: "auto"/"xla" (`masked_attention`), "pallas" (the
    # dense-cache kernel, ops/decode_pallas.py `decode_cache_attention`),
    # "pallas-interpret" (the kernel on the CPU, for tests). No benchmark
    # cell runs `generate`: it is the tests' token-exact reference for the
    # serving engine, whose own "auto" takes the paged kernel of the same
    # module on a TPU (serve/engine.py `_attn_route`).
    impl = os.environ.get("DNN_TPU_DECODE_IMPL", "auto")
    if impl not in ("auto", "xla", "pallas", "pallas-interpret"):
        raise ValueError(f"unknown decode impl {impl!r} "
                         "(DNN_TPU_DECODE_IMPL)")
    if impl == "pallas-interpret" and on_tpu():
        raise ValueError(
            "decode impl 'pallas-interpret' is the CPU test vehicle; on a "
            "TPU use 'pallas' (DNN_TPU_DECODE_IMPL)"
        )
    use_kernel = impl in ("pallas", "pallas-interpret")
    if use_kernel and offsets is not None:
        raise ValueError(
            "decode impl {!r} does not support left-padded batches "
            "(prompt_lens): the fused kernel masks on a scalar position; "
            "use impl=auto/xla for mixed-length prompts".format(impl)
        )
    if use_kernel and not decode_kernel_ok(total):
        # an explicitly requested kernel must not silently measure XLA
        raise ValueError(
            f"decode impl {impl!r} requested but cache size {total} "
            "admits no sublane-legal k block (decode_kernel_ok: the "
            "largest divisor of the total at or under the k block size "
            "must be a multiple of 16) - choose prompt+max_new_tokens "
            "with such a divisor (any multiple of 128 works) or use "
            "impl=auto"
        )
    cache_k = jnp.zeros((L, b, H, total, Dh), dt)
    cache_v = jnp.zeros((L, b, H, total, Dh), dt)
    pe_all = _sinusoid_pe(jnp.arange(total), cfg.d_model, dt)
    # positions a query at `pos` may see: the static cache up to pos, and
    # in a left-padded batch not the pad columns before each row's offset
    # (they never existed)
    slots = jnp.arange(total)

    def layer_step(xp, lcaches):
        (x, pos) = xp
        lp, ck, cv = lcaches
        q, k, v = block_qkv(x, lp, cfg)            # each (b, 1, H, Dh)
        ck = jax.lax.dynamic_update_slice_in_dim(
            ck, k.transpose(0, 2, 1, 3), pos, axis=2)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cv, v.transpose(0, 2, 1, 3), pos, axis=2)
        if use_kernel:
            # fused single-query kernel: one pallas_call instead of the
            # einsum/softmax/einsum chain, dead cache blocks skipped
            # (ops/decode_pallas.py)
            o = decode_cache_attention(
                q.reshape(b, H, Dh), ck, cv, pos,
                interpret=impl == "pallas-interpret",
            )[:, None]
        else:
            live = (slots <= pos)[None, :]
            if offsets is not None:
                live = live & (slots[None, :] >= offsets[:, None])
            o = masked_attention(q, ck, cv, live[:, None, None, :], dt)
        # an expert layer: dense dispatch at capacity b (b tokens a step)
        # drops no token (the docstring's parity caveat)
        x, _ = block_out(x, o, lp, cfg, capacity=b, moe_dispatch="dense")
        return (x, pos), (ck, cv)

    def time_step(carry, pos):
        ck, cv, prev, k_rng = carry
        tok = jnp.where(
            pos < s_p,
            jax.lax.dynamic_index_in_dim(prompt_pad, pos, axis=1,
                                         keepdims=False),
            prev,
        )
        if offsets is None:
            pe = pe_all[pos][None, None]
        else:
            # per-sequence positions: global slot pos maps to local
            # position pos - offset (clipped: pad slots get position 0,
            # masked out of every attention anyway)
            pe = pe_all[jnp.clip(pos - offsets, 0)][:, None, :]
        x = params["embed"][tok].astype(dt)[:, None, :] + pe
        (x, _), (ck, cv) = jax.lax.scan(
            layer_step, (x, pos), (params["layers"], ck, cv),
            # unrolled in chunks of 8 so that XLA can overlap across
            # layers at small widths. At 1.3 B the serving engine found the
            # opposite and keeps its scan rolled (serve/engine.py
            # `_scan_layers`); no cell measures this one.
            unroll=min(L, 8),
        )
        logits = final_logits(params, x[:, 0], dt)
        if temperature > 0.0:
            if top_k > 0:
                kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
                logits = jnp.where(logits < kth, -jnp.inf, logits)
            if 0.0 < top_p < 1.0:
                # nucleus cut on the temperature-scaled distribution
                # (ordering is temperature-invariant; the cumulative
                # mass is not): keep tokens whose cumulative probability
                # of STRICTLY more likely tokens is < top_p - the top-1
                # always survives, and -inf (top-k-cut) entries sort
                # last with zero mass
                srt = jnp.sort(logits, axis=-1)[:, ::-1]
                p_srt = jax.nn.softmax(srt / temperature, axis=-1)
                keep = (jnp.cumsum(p_srt, axis=-1) - p_srt) < top_p
                cutoff = jnp.min(
                    jnp.where(keep, srt, jnp.inf), axis=-1, keepdims=True
                )
                logits = jnp.where(logits < cutoff, -jnp.inf, logits)
            k_rng, k_tok = jax.random.split(k_rng)
            nxt = jax.random.categorical(k_tok, logits / temperature, axis=-1)
        else:
            nxt = jnp.argmax(logits, axis=-1)
        nxt = nxt.astype(jnp.int32)
        return (ck, cv, nxt, k_rng), nxt

    k0 = key if key is not None else jax.random.key(0)
    (_, _, _, _), nexts = jax.lax.scan(
        time_step,
        (cache_k, cache_v, jnp.zeros((b,), jnp.int32), k0),
        jnp.arange(total),
    )
    # nexts[t] = token predicted AFTER consuming position t; generation
    # starts from the prediction at the last prompt position
    gen = nexts.swapaxes(0, 1)[:, s_p - 1: total - 1]
    return jnp.concatenate([prompt, gen], axis=1)
