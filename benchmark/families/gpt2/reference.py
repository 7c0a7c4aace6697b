"""The plain reference: GPT-2's block as the configuration files state it, in
straightforward `jax.numpy`, float32 at the highest matmul precision.

Pre-LayerNorm with bias, multi-head causal attention (no bias on its four
projections), a GELU (tanh form) MLP with biases, sinusoidal positions added
to the embedding (sin half, cos half), a final LayerNorm and an untied head.
It imports nothing of the program; its weights are this family's own seeded
tree (`weights.make`), made whole. Every matmul goes through the shared
`einsum(precision, ...)`, so the controls (`bf16`, `fp8`) are the same
equations at a lower precision.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from lib.reference import einsum, mean_loss_and_grads

from . import weights


def layer_norm(x, gain, bias, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * gain + bias


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def sinusoid(positions, d):
    half = d // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    ang = positions[:, None].astype(jnp.float32) * freqs[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def block(x, lp, n_heads: int, precision: str, fault: str = ""):
    """x (B, S, d) float32 -> (B, S, d). `fault="no_tp_exchange"` plants what
    a two-way tensor-parallel block gives when the exchange between the chips
    is left out: one chip's half of the heads and of the MLP's hidden units
    alone reach the residual."""
    b, s, d = x.shape
    dh = d // n_heads
    mm = partial(einsum, precision)
    h = layer_norm(x, lp["ln1_scale"], lp["ln1_bias"])
    q = mm("bsd,de->bse", h, lp["wq"]).reshape(b, s, n_heads, dh)
    k = mm("bsd,de->bse", h, lp["wk"]).reshape(b, s, n_heads, dh)
    v = mm("bsd,de->bse", h, lp["wv"]).reshape(b, s, n_heads, dh)
    scores = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = mm("bhqk,bkhd->bqhd", probs, v)
    if fault == "no_tp_exchange":
        o = o.at[:, :, n_heads // 2:].set(0.0)
    x = x + mm("bsd,de->bse", o.reshape(b, s, d), lp["wo"])
    h = layer_norm(x, lp["ln2_scale"], lp["ln2_bias"])
    h = gelu_tanh(mm("bsd,df->bsf", h, lp["w1"]) + lp["b1"])
    if fault == "no_tp_exchange":
        h = h.at[..., h.shape[-1] // 2:].set(0.0)
    return x + mm("bsf,fd->bsd", h, lp["w2"]) + lp["b2"]


def hidden(params, tokens, n_heads: int, precision: str = "f32",
           remat: bool = False, fault: str = ""):
    """tokens (B, S) -> final-norm hidden states (B, S, d), float32."""
    d = params["embed"].shape[1]
    x = params["embed"][tokens].astype(jnp.float32)
    x = x + sinusoid(jnp.arange(tokens.shape[1]), d)[None]

    def body(x, lp):
        return block(x, lp, n_heads, precision, fault), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    return layer_norm(x, params["lnf_scale"], params["lnf_bias"])


def loss_sum(params, tokens, targets, n_heads: int, precision: str,
             fault: str = ""):
    """Summed next-token cross-entropy of a block of rows."""
    x = hidden(params, tokens, n_heads, precision, remat=True, fault=fault)
    logits = einsum(precision, "bsd,dv->bsv", x, params["head"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -jnp.sum(picked)


def loss_and_grads(seed: int, model: dict, traffic: dict,
                   precision: str = "f32", fault: str = "", shardings=None):
    """(the seeded float32 tree, fn): fn(params, tokens, targets) -> (mean
    loss, gradients of it), the rows taken `reference_rows_per_block` at a
    time. The tree has the program's structure, so the optimizers' plain
    updates and the per-leaf norms line up with the program's state."""
    n_heads = model["n_head"]
    fn = mean_loss_and_grads(
        lambda p, t, y: loss_sum(p, t, y, n_heads, precision, fault),
        traffic["reference_rows_per_block"])
    return weights.make(seed, model, shardings=shardings), fn


def served_logits(seed: int, model: dict, tokens, rows,
                  precision: str = "f32") -> np.ndarray:
    """tokens (N, S), rows (N, R) -> logits (N, R, vocab) on the host: each
    sequence's own full teacher-forced forward, one at a time, at the given
    positions. The tree is made whole, once a call, and freed with it."""
    n_heads = model["n_head"]

    @jax.jit
    def one(params, tokens, rows):
        x = hidden(params, tokens, n_heads, precision)[0]
        return einsum(precision, "rd,dv->rv", x[rows], params["head"])

    params = weights.make(seed, model)
    return np.stack([np.asarray(jax.device_get(one(params, t[None], r)))
                     for t, r in zip(np.asarray(tokens), np.asarray(rows))])
