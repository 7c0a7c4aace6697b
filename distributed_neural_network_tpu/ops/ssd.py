"""The selective state-space recurrence of a Mamba-2 layer, in chunks.

A head h with state S (P x N) follows, over the sequence,

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t

with x_t (P,), B_t and C_t (N,) shared by the heads of a group, dt_t > 0 and
A < 0 scalars of the head. `ssd_scan` computes it in the state-space dual
form (Dao and Gu, "Transformers are SSMs"): the sequence is cut into chunks
of `chunk` positions; inside a chunk the outputs are one masked product
(C B^T weighted by the decay between the two positions, times x), each
chunk's contribution to the state is one product, the states are passed
from chunk to chunk by a `lax.scan` over the chunks (the only sequential
part, S / chunk steps), and what a chunk inherits reaches its outputs
through one more product. The result equals the recurrence step by step up
to reassociation.

Plain `jax.numpy`: the log-decays, their cumulative sums, the decay
matrices and the carried state are float32; the four products take their
operands in x's dtype and accumulate in float32. Backward is autodiff (a
layer's `jax.checkpoint` bounds what it keeps: the chunk states, 4 bytes x
B x S/chunk x H x P x N). A Pallas kernel for this scan is a later PR's; the
named scope around the caller (`lm.mamba.scan`) is where its time shows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..parallel.collectives import vary_like


def ssd_scan(x, dt, a, b, c, *, chunk: int):
    """x (B, S, H, P), dt (B, S, H) positive, a (H,) negative, b and c
    (B, S, G, N) with H a multiple of G (head h reads group h // (H / G))
    -> y (B, S, H, P) in x's dtype, from a zero initial state.

    A length that is no multiple of `chunk` is padded at the end with
    positions of dt = 0, which neither decay nor feed the state, and the
    padded outputs are cut off."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    if h % g:
        raise ValueError(f"ssd_scan: {h} heads do not divide into {g} groups")
    pad = -s % chunk
    if pad:
        widen = lambda t: jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        x, dt, b, c = widen(x), widen(dt), widen(b), widen(c)
    nc, q, k = (s + pad) // chunk, chunk, h // g
    f32, dtype = jnp.float32, x.dtype
    dt = dt.astype(f32).reshape(bsz, nc, q, h)
    # log-decay of every step and its running sum inside the chunk
    cum = jnp.cumsum(dt * a.astype(f32), axis=2)
    cum_h = cum.transpose(0, 1, 3, 2)                       # (B, nc, H, Q)
    dt_h = dt.transpose(0, 1, 3, 2)
    xc = x.reshape(bsz, nc, q, g, k, p)
    bc = b.reshape(bsz, nc, q, g, n)
    cc = c.reshape(bsz, nc, q, g, n)

    # inside a chunk: y_i += sum_{j <= i} (C_i . B_j) exp(cum_i - cum_j) dt_j x_j
    # (the step dt_j rides with the decay matrix, which is float32 anyway,
    # so that no float32 copy of x is made)
    cb = jnp.einsum("bcign,bcjgn->bcgij", cc, bc, preferred_element_type=f32)
    causal = jnp.tril(jnp.ones((q, q), bool))
    seg = cum_h[..., :, None] - cum_h[..., None, :]         # (B, nc, H, Q, Q)
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf)) * dt_h[..., None, :]
    m = (cb[:, :, :, None] * decay.reshape(bsz, nc, g, k, q, q)).astype(dtype)
    y = jnp.einsum("bcgkij,bcjgkp->bcigkp", m, xc, preferred_element_type=f32)

    # what each chunk adds to the state by its end
    to_end = jnp.exp(cum_h[..., -1:] - cum_h) * dt_h        # (B, nc, H, Q)
    xw = xc * to_end.transpose(0, 1, 3, 2).reshape(
        bsz, nc, q, g, k, 1).astype(dtype)
    added = jnp.einsum("bcjgkp,bcjgn->bcgkpn", xw, bc,
                       preferred_element_type=f32)

    # the state each chunk starts from: the one sequential pass
    chunk_decay = jnp.exp(cum_h[..., -1]).reshape(bsz, nc, g, k, 1, 1)

    def carry_on(state, step):
        keep, add = step
        return state * keep + add, state

    zero = vary_like(jnp.zeros((bsz, g, k, p, n), f32), added, chunk_decay)
    _, start = jax.lax.scan(
        carry_on, zero,
        (chunk_decay.swapaxes(0, 1), added.swapaxes(0, 1)))
    start = start.swapaxes(0, 1)                            # (B, nc, G, K, P, N)

    # what the inherited state gives: y_i += exp(cum_i) C_i . S_start
    inherited = jnp.einsum("bcign,bcgkpn->bcigkp", cc, start.astype(dtype),
                           preferred_element_type=f32)
    y = y + inherited * jnp.exp(cum).reshape(bsz, nc, q, g, k, 1)
    return y.reshape(bsz, s + pad, h, p)[:, :s].astype(dtype)
