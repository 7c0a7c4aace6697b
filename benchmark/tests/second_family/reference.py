"""The second family's plain reference: GPT-2's equations under its own
names. Serving never holds more than one block's weights: each is drawn,
used and dropped. Training takes the stacked tree that the optimizers'
plain updates hold, and scans it a block at a time."""

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


def embed(top, tokens):
    d = top["embed"].shape[1]
    half = d // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    ang = jnp.arange(tokens.shape[1])[:, None].astype(jnp.float32) * freqs
    pos = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
    return top["embed"][tokens].astype(jnp.float32) + pos[None]


def block(x, lp, heads: int, precision: str):
    b, s, d = x.shape
    dh = d // heads
    mm = partial(einsum, precision)
    h = layer_norm(x, lp["ln1_scale"], lp["ln1_bias"])
    q, k, v = (mm("bsd,de->bse", h, lp[w]).reshape(b, s, heads, dh)
               for w in ("wq", "wk", "wv"))
    scores = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None, None], scores,
                       -jnp.inf)
    o = mm("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + mm("bsd,de->bse", o.reshape(b, s, d), lp["wo"])
    h = layer_norm(x, lp["ln2_scale"], lp["ln2_bias"])
    h = gelu_tanh(mm("bsd,df->bsf", h, lp["w1"]) + lp["b1"])
    return x + mm("bsf,fd->bsd", h, lp["w2"]) + lp["b2"]


def loss_and_grads(seed: int, model: dict, traffic: dict,
                   precision: str = "f32", fault: str = "", shardings=None):
    heads = model["heads"]

    def loss_sum(params, tokens, targets):
        body = jax.checkpoint(
            lambda x, lp: (block(x, lp, heads, precision), None))
        x, _ = jax.lax.scan(body, embed(params, tokens), params["layers"])
        x = layer_norm(x, params["lnf_scale"], params["lnf_bias"])
        logits = einsum(precision, "bsd,dv->bsv", x, params["head"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], -1))

    fn = mean_loss_and_grads(loss_sum, traffic["reference_rows_per_block"])
    return weights.make(seed, model, shardings=shardings), fn


def served_logits(seed: int, model: dict, tokens, rows,
                  precision: str = "f32") -> np.ndarray:
    heads = model["heads"]
    one_block = jax.jit(lambda x, lp: block(x, lp, heads, precision))

    @jax.jit
    def head(top, x, rows):
        x = layer_norm(x, top["lnf_scale"], top["lnf_bias"])[0]
        return einsum(precision, "rd,dv->rv", x[rows], top["head"])

    top = weights.top(seed, model)
    x = embed(top, jnp.asarray(tokens))
    for i in range(model["depth"]):
        x = one_block(x, weights.layer(seed, model, i))  # drawn, used, dropped
    return np.stack([np.asarray(head(top, x[n:n + 1], r))
                     for n, r in enumerate(np.asarray(rows))])
