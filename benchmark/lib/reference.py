"""What every family's plain reference shares: the precision-controlled
`einsum`, the optimizers' plain updates, the row-blocked mean of a summed loss
with its gradients, and the per-leaf norms that the comparison reads. The
model's own equations are its family's (`families/<family>/reference.py`).

`precision` puts the same mathematics at a lower precision: that is the
control which the comparison has to fail.

  f32   float32 operands, Precision.HIGHEST (the reference proper)
  bf16  matmul operands rounded to bfloat16, float32 accumulation
  fp8   matmul operands rounded to float8_e4m3 with a per-tensor scale
"""

from __future__ import annotations

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


def mean_loss_and_grads(loss_sum, rows_per_block: int):
    """`loss_sum(params, tokens, targets)` -> a jitted (params, tokens,
    targets) -> (mean loss, gradients of it): the rows are taken in blocks so
    that a deep model fits beside its gradients."""
    vg = jax.value_and_grad(loss_sum)

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
