"""The plain reference: GPT-2's block as the configuration files state it, in
straightforward `jax.numpy`, float32 at the highest matmul precision.

Pre-LayerNorm with bias, multi-head causal attention (no bias on its four
projections), a GELU (tanh form) MLP with biases, sinusoidal positions added
to the embedding (sin half, cos half), a final LayerNorm and an untied head.
It imports nothing of the program and is given only the seeded weights and
batches that the benchmark makes itself. `precision` puts the same mathematics
at a lower precision: that is the control which the comparison has to fail.

  f32   float32 operands, Precision.HIGHEST (the reference proper)
  bf16  matmul operands rounded to bfloat16, float32 accumulation
  fp8   matmul operands rounded to float8_e4m3 with a per-tensor scale
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _round_fp8(x):
    """x rounded to float8_e4m3 under a per-tensor scale. The gradient passes
    straight through the rounding: a cotangent cast to float8 would flush to
    nought and leave the leaves behind it unmoved, which is a fault and not a
    precision."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _operands(precision: str, *xs):
    if precision == "f32":
        return [x.astype(jnp.float32) for x in xs]
    if precision == "bf16":
        return [x.astype(jnp.bfloat16) for x in xs]
    if precision == "fp8":
        return [_round_fp8(x) for x in xs]
    raise ValueError(f"unknown precision {precision!r}")


def einsum(precision: str, eq: str, a, b):
    a, b = _operands(precision, a, b)
    return jnp.einsum(eq, a, b, precision=HI,
                      preferred_element_type=jnp.float32)


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


def make_loss_and_grads(n_heads: int, precision: str, rows_per_block: int,
                        fault: str = ""):
    """(params, tokens, targets) -> (mean loss, gradients of it): the rows
    are taken in blocks so that a 24-layer model fits beside its gradients."""
    vg = jax.value_and_grad(
        lambda p, t, y: loss_sum(p, t, y, n_heads, precision, fault))

    @jax.jit
    def fn(params, tokens, targets):
        b, s = tokens.shape
        nb = b // rows_per_block
        tb = tokens.reshape(nb, rows_per_block, s)
        yb = targets.reshape(nb, rows_per_block, s)
        zero = jax.tree.map(jnp.zeros_like, params)

        def body(carry, xy):
            tot, acc = carry
            l, g = vg(params, *xy)
            return (tot + l, jax.tree.map(jnp.add, acc, g)), None

        (tot, acc), _ = jax.lax.scan(body, (jnp.float32(0), zero), (tb, yb))
        n = b * s
        return tot / n, jax.tree.map(lambda g: g / n, acc)

    return fn


@partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps"),
         donate_argnums=(0, 2, 3))
def adam_update(params, grads, m, v, t, *, lr, b1, b2, eps):
    """Bias-corrected Adam (Kingma and Ba), no weight decay; t counts from 1."""
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t

    def leaf(p, g, m, v):
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        return p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps), m, v

    out = jax.tree.map(leaf, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


@partial(jax.jit, static_argnames=("lr", "momentum"), donate_argnums=(0, 2))
def sgd_update(params, grads, m, *, lr, momentum):
    """Momentum SGD as torch.optim.SGD has it: no dampening, no Nesterov."""
    m = jax.tree.map(lambda m, g: momentum * m + g, m, grads)
    return jax.tree.map(lambda p, m: p - lr * m, params, m), m


def spread_over(devices, shapes):
    """A sharding for each leaf that splits its largest divisible axis over
    the devices (or keeps it whole): how the reference of a cell on several
    chips fits, the partitioning left to the compiler."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.asarray(devices), ("all",))
    n = len(devices)

    def one(shape):
        axes = [i for i, k in enumerate(shape) if k % n == 0]
        if not axes or n == 1:
            return NamedSharding(mesh, PartitionSpec())
        ax = max(axes, key=lambda i: shape[i])
        return NamedSharding(mesh, PartitionSpec(
            *[("all" if i == ax else None) for i in range(len(shape))]))

    return jax.tree.map(one, shapes, is_leaf=lambda x: isinstance(x, tuple))


def make_served_logits(n_heads: int, precision: str):
    """(params, tokens (1, S), rows (R,)) -> logits (R, vocab) at the given
    positions of one full teacher-forced forward."""

    @jax.jit
    def fn(params, tokens, rows):
        x = hidden(params, tokens, n_heads, precision)[0]
        return einsum(precision, "rd,dv->rv", x[rows], params["head"])

    return fn


@jax.jit
def leaf_norms(tree):
    """Per-leaf L2 norms, the stacked layers each on their own: a tree with
    () leaves at the top and (L,) leaves under "layers"."""
    def norm(path, x):
        x = x.astype(jnp.float32)
        stacked = getattr(path[0], "key", None) == "layers"
        axes = tuple(range(1, x.ndim)) if stacked else None
        return jnp.sqrt(jnp.sum(jnp.square(x), axis=axes))
    return jax.tree.map_with_path(norm, tree)


@jax.jit
def diff_norms(a, b):
    """`leaf_norms(a - b)` without holding the difference."""
    def norm(path, x, y):
        z = x.astype(jnp.float32) - y.astype(jnp.float32)
        stacked = getattr(path[0], "key", None) == "layers"
        axes = tuple(range(1, z.ndim)) if stacked else None
        return jnp.sqrt(jnp.sum(jnp.square(z), axis=axes))
    return jax.tree.map_with_path(norm, a, b)
