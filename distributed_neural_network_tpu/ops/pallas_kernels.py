"""Pallas TPU kernels for the model's hot dense path.

The reference's compute is plain ATen ops under torch (`models/model.py:24-27`
runs fc1->fc2->fc3 as three separate GEMMs with separate ReLU kernels and a
round-trip through memory between each). XLA already fuses bias+ReLU into the
GEMM epilogue, but still materializes the (B,120) and (B,84) intermediates in
HBM between the three dots. This module fuses the whole classifier head -

    logits = (relu(relu(x @ W1 + b1) @ W2 + b2)) @ W3 + b3

- into ONE Pallas kernel: all three weight matrices (~59K floats, ~236 KB)
are pinned in VMEM for the kernel's lifetime, the batch streams through in
tiles, and the h1/h2 intermediates never leave VMEM. A custom VJP provides a
matching fused backward kernel (dx plus all six weight/bias grads in one
pass, with cross-tile accumulation in VMEM), so the op is trainable.

Design notes (per the Pallas TPU guide):
- Grid is 1-D over batch tiles; weight/bias blocks use a constant index_map
  so Mosaic keeps them resident in VMEM across grid steps.
- Batch is padded to the tile size on the host-facing wrapper; padded rows
  carry zeros, produce garbage logits that are sliced off, and contribute
  exactly zero to every gradient (their upstream cotangent is zero-padded).
- The backward kernel accumulates dW/db across batch tiles by revisiting the
  same output block each grid step (`@pl.when(i == 0)` zero-init, then `+=`)
  - TPU grid execution is sequential, so this is well-defined.
- All matmuls request `preferred_element_type=float32` so the MXU accumulates
  in f32 regardless of input dtype.
- Off-TPU execution: `interpret=True` runs the kernel code through the Pallas
  interpreter and is how the kernel unit tests exercise it on CPU - but the
  interpreter is not shard_map-compatible (vma typing), so *inside the
  sharded engine* the off-TPU path is the plain-jnp `mlp3_reference` math,
  not the kernel. Mosaic-compiled behavior is only truly covered on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..parallel.collectives import vma_union
from ..runtime import on_tpu

# batch tile: 8-row sublane alignment, big enough to keep the MXU busy
_TILE_B = 128


def _fwd_kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, w3_ref, b3_ref,
                out_ref, h1_ref=None, h2_ref=None):
    """Forward head; h1/h2 residual outputs only exist on the VJP-fwd
    variant - inference calls write logits alone, keeping the intermediates
    purely in VMEM."""
    h1 = jnp.maximum(
        jnp.dot(x_ref[:], w1_ref[:], preferred_element_type=jnp.float32)
        + b1_ref[:],
        0.0,
    )
    h2 = jnp.maximum(
        jnp.dot(h1, w2_ref[:], preferred_element_type=jnp.float32) + b2_ref[:],
        0.0,
    )
    out_ref[:] = (
        jnp.dot(h2, w3_ref[:], preferred_element_type=jnp.float32) + b3_ref[:]
    )
    if h1_ref is not None:
        h1_ref[:] = h1
        h2_ref[:] = h2


def _bwd_kernel(g_ref, x_ref, h1_ref, h2_ref, w1_ref, w2_ref, w3_ref,
                dx_ref, dw1_ref, db1_ref, dw2_ref, db2_ref, dw3_ref, db3_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        dw1_ref[:] = jnp.zeros_like(dw1_ref)
        db1_ref[:] = jnp.zeros_like(db1_ref)
        dw2_ref[:] = jnp.zeros_like(dw2_ref)
        db2_ref[:] = jnp.zeros_like(db2_ref)
        dw3_ref[:] = jnp.zeros_like(dw3_ref)
        db3_ref[:] = jnp.zeros_like(db3_ref)

    g = g_ref[:]
    h1 = h1_ref[:]
    h2 = h2_ref[:]
    x = x_ref[:]

    dmm = functools.partial(jax.lax.dot_general, preferred_element_type=jnp.float32)
    # dh2 = g @ W3^T, masked by ReLU
    dh2 = dmm(g, w3_ref[:], dimension_numbers=(((1,), (1,)), ((), ())))
    dh2 = jnp.where(h2 > 0, dh2, 0.0)
    dh1 = dmm(dh2, w2_ref[:], dimension_numbers=(((1,), (1,)), ((), ())))
    dh1 = jnp.where(h1 > 0, dh1, 0.0)
    dx_ref[:] = dmm(dh1, w1_ref[:], dimension_numbers=(((1,), (1,)), ((), ())))

    # weight grads: X^T @ dY contractions over the batch tile, accumulated
    # across grid steps
    tmm = functools.partial(
        jax.lax.dot_general,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dw3_ref[:] += tmm(h2, g)
    db3_ref[:] += jnp.sum(g, axis=0, keepdims=True)
    dw2_ref[:] += tmm(h1, dh2)
    db2_ref[:] += jnp.sum(dh2, axis=0, keepdims=True)
    dw1_ref[:] += tmm(x, dh1)
    db1_ref[:] += jnp.sum(dh1, axis=0, keepdims=True)


def _out_struct(shape, *vma_sources):
    """ShapeDtypeStruct stamped with the union of the inputs' varying-axes
    (vma) type, required for pallas_call outputs inside jax.shard_map
    (check_vma=True): per-device kernel outputs vary over whatever mesh axes
    the data inputs vary over."""
    vma = vma_union(*vma_sources)
    if vma is None:  # outside shard_map / older API
        return jax.ShapeDtypeStruct(shape, jnp.float32)
    return jax.ShapeDtypeStruct(shape, jnp.float32, vma=vma)


def _pad_batch(a: jax.Array, tile: int):
    b = a.shape[0]
    pad = (-b) % tile
    if pad:
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    return a, b


def _full_spec(shape):
    """Weight/bias block resident across all grid steps."""
    return pl.BlockSpec(shape, lambda i: (0, 0), memory_space=pltpu.VMEM)


def _tile_spec(cols, tile):
    return pl.BlockSpec((tile, cols), lambda i: (i, 0), memory_space=pltpu.VMEM)


def _fwd_call(x, w1, b1, w2, b2, w3, b3, *, tile, interpret, residuals=True):
    xp, b = _pad_batch(x, tile)
    bp = xp.shape[0]
    d_in, d1 = w1.shape
    d2 = w2.shape[1]
    d3 = w3.shape[1]
    out_specs = [_tile_spec(d3, tile)]
    out_shape = [_out_struct((bp, d3), xp, w1, w2, w3)]
    if residuals:
        out_specs += [_tile_spec(d1, tile), _tile_spec(d2, tile)]
        out_shape += [
            _out_struct((bp, d1), xp, w1, w2, w3),
            _out_struct((bp, d2), xp, w1, w2, w3),
        ]
    outs = pl.pallas_call(
        _fwd_kernel,
        grid=(bp // tile,),
        in_specs=[
            _tile_spec(d_in, tile),
            _full_spec(w1.shape),
            _full_spec((1, d1)),
            _full_spec(w2.shape),
            _full_spec((1, d2)),
            _full_spec(w3.shape),
            _full_spec((1, d3)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(xp, w1, b1.reshape(1, -1), w2, b2.reshape(1, -1), w3, b3.reshape(1, -1))
    if residuals:
        out, h1, h2 = outs
        return out[:b], h1, h2
    return outs[0][:b], None, None


def _bwd_call(g, x, h1, h2, w1, w2, w3, *, tile, interpret):
    gp, b = _pad_batch(g, tile)  # zero rows -> zero grad contributions
    xp, _ = _pad_batch(x, tile)
    bp = xp.shape[0]
    d_in, d1 = w1.shape
    d2 = w2.shape[1]
    d3 = w3.shape[1]
    dx, dw1, db1, dw2, db2, dw3, db3 = pl.pallas_call(
        _bwd_kernel,
        grid=(bp // tile,),
        in_specs=[
            _tile_spec(d3, tile),
            _tile_spec(d_in, tile),
            _tile_spec(d1, tile),
            _tile_spec(d2, tile),
            _full_spec(w1.shape),
            _full_spec(w2.shape),
            _full_spec(w3.shape),
        ],
        out_specs=[
            _tile_spec(d_in, tile),
            _full_spec(w1.shape),
            _full_spec((1, d1)),
            _full_spec(w2.shape),
            _full_spec((1, d2)),
            _full_spec(w3.shape),
            _full_spec((1, d3)),
        ],
        out_shape=[
            _out_struct((bp, d_in), gp, xp, w1, w2, w3),
            _out_struct(w1.shape, gp, xp, w1, w2, w3),
            _out_struct((1, d1), gp, xp, w1, w2, w3),
            _out_struct(w2.shape, gp, xp, w1, w2, w3),
            _out_struct((1, d2), gp, xp, w1, w2, w3),
            _out_struct(w3.shape, gp, xp, w1, w2, w3),
            _out_struct((1, d3), gp, xp, w1, w2, w3),
        ],
        interpret=interpret,
    )(gp, xp, h1, h2, w1, w2, w3)
    return dx[:b], dw1, db1[0], dw2, db2[0], dw3, db3[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _fused_mlp3(x, w1, b1, w2, b2, w3, b3, tile, interpret):
    out, _, _ = _fwd_call(
        x, w1, b1, w2, b2, w3, b3, tile=tile, interpret=interpret, residuals=False
    )
    return out


def _fused_mlp3_fwd(x, w1, b1, w2, b2, w3, b3, tile, interpret):
    out, h1, h2 = _fwd_call(x, w1, b1, w2, b2, w3, b3, tile=tile, interpret=interpret)
    return out, (x, h1, h2, w1, w2, w3)


def _fused_mlp3_bwd(tile, interpret, res, g):
    x, h1, h2, w1, w2, w3 = res
    dx, dw1, db1, dw2, db2, dw3, db3 = _bwd_call(
        g, x, h1, h2, w1, w2, w3, tile=tile, interpret=interpret
    )
    return dx, dw1, db1, dw2, db2, dw3, db3


_fused_mlp3.defvjp(_fused_mlp3_fwd, _fused_mlp3_bwd)


def mlp3_reference(x, w1, b1, w2, b2, w3, b3):
    """Plain-jnp math of the fused head - the off-TPU execution path.

    Same computation, natively differentiable; used automatically off-TPU
    because the Pallas HLO interpreter's internal primitives violate
    shard_map's varying-axes (vma) typing when kernel operands mix sharded
    activations with replicated weights. XLA:CPU fuses this fine; the Pallas
    kernel is for the MXU."""
    x = x.astype(jnp.float32)
    h1 = jnp.maximum(x @ w1 + b1, 0.0)
    h2 = jnp.maximum(h1 @ w2 + b2, 0.0)
    return h2 @ w3 + b3


def fused_mlp3(x, w1, b1, w2, b2, w3, b3, *, tile=_TILE_B, interpret=None):
    """relu(relu(x@W1+b1)@W2+b2)@W3+b3 as one Pallas kernel (trainable).

    x: (B, d_in) float32. Returns (B, d_out) float32 logits. All arrays are
    cast to float32 (the kernel's compute and accumulation type).

    `interpret`: None (default) = compiled Mosaic kernel on TPU, jnp
    reference math elsewhere; True = force the Pallas interpreter (kernel
    unit tests; not shard_map-compatible); False = force compilation.
    """
    args = [jnp.asarray(a, jnp.float32) for a in (x, w1, b1, w2, b2, w3, b3)]
    if interpret is None:
        if not on_tpu():
            return mlp3_reference(*args)
        interpret = False
    return _fused_mlp3(*args, tile, bool(interpret))
