"""The plain reference: the `lfm2_moe` block as the configuration's keys
state it, in straightforward `jax.numpy`, float32 at the highest matmul
precision, every sequence whole from position 0: THE CONVOLUTION IS THE
WHOLE-SEQUENCE SUM, never a step from a carried state, and the attention is
one causal softmax over all the keys, never a cache. It imports nothing of
the program; its weights are this family's own seeded leaves
(`weights.draw_layer`), drawn, used and dropped a layer at a time, because
the float32 tree (20.7 GB at the cell's size) does not fit a chip.

The equations (`config.json` keys in backticks; `N(.)` an RMSNorm with its
own gain, eps `norm_eps`; no bias on any projection). Block:

    h'  = h  + Op(N_op(h));   h'' = h' + FF(N_ff(h'))

`Op` is the short convolution where `layer_types` says `conv`, attention
where it says `full_attention`; `FF` is dense in the first `num_dense_layers`
layers and the expert layer in the others.

Gated short convolution at position t, input u_t: `[B_t ; C_t ; x_t] = u_t
W_in`, three parts of `hidden_size` in this order; `z_t = B_t * x_t`; `c_t =
sum_{j < L} w_j * z_{t - (L - 1) + j}` with `L = conv_L_cache` taps a channel
and z = 0 before position 0; `Op = (C_t * c_t) W_out`.

Attention, H = `num_attention_heads`, KV = `num_key_value_heads`, heads of
`hidden_size / H`: `q = u W_q`, `k = u W_k`, `v = u W_v`; each head of q
through `N_q` and of k through `N_k` (over the head's values, one gain for
all heads); rotate-half RoPE over the whole head of q and k at the token's
position (`f_i = rope_theta^(-2i/r)`, `out = x cos(t f) + [-x_2 ; x_1] sin(t
f)`); query head h attends to KV head `h // (H / KV)`; `s = q . k /
sqrt(head)`; causal softmax; `Op = concat_h(sum_j p v) W_o`.

Feed-forward. Dense: `(silu(u W_1) * (u W_3)) W_2` at `intermediate_size`.
Experts: `s = sigmoid(float32(u) W_r)` over all `num_experts`; the
`num_experts_per_tok` largest of `s + b` are chosen (`b` the selection bias,
`use_expert_bias`: it selects only); weights `routed_scaling_factor * s /
(sum_chosen s + 1e-6)` (`norm_topk_prob`); each expert the same gated MLP
at `moe_intermediate_size`; no shared expert. EVERY HELD EXPERT IS COMPUTED
FOR EVERY TOKEN and masked by the routing (nothing is sorted). The router
runs in float32 at every `precision`, as the program's does.

Logits: `N_final(h) W_emb^T` over all `vocab_size` rows (tied).

Every matmul but the router's goes through the shared `einsum(precision,
...)`, so the controls (`bf16`, `fp8`) are the same equations at a lower
precision. Memory, noted: what is done a row at a time runs over blocks of
`ROW_BLOCK` rows, the attention over blocks of `QUERY_BLOCK` queries against
all the keys, the experts one held expert at a time (a scan); a sequence is
padded to a power of two of `ROW_BLOCK` (what follows its last row asked
for changes nothing before it). None of this changes a value.
"""

from __future__ import annotations

import gc
import json
import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from lib import weights as lib
from lib.reference import HI, einsum

from . import weights

ROW_BLOCK = 1024
QUERY_BLOCK = 256
FAULTS = ("", "no_head_norm", "no_rope", "rope_on_conv", "wrong_kv_head",
          "no_bias")


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * gain


def rope(x, pos, theta):
    """x (S, heads, r) at positions pos (S,): rotate halves."""
    r = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def gated_mlp(mm, x, w_gate, w_up, w_down):
    h = jax.nn.silu(mm("sd,df->sf", x, w_gate)) * mm("sd,df->sf", x, w_up)
    return mm("sf,fd->sd", h, w_down)


def over_rows(fn, *xs):
    """fn over blocks of ROW_BLOCK rows of xs (S, ...), S a multiple."""
    n = xs[0].shape[0] // ROW_BLOCK
    blocks = [x.reshape((n, ROW_BLOCK) + x.shape[1:]) for x in xs]
    out = jax.lax.map(lambda b: fn(*b), tuple(blocks))
    return jax.tree.map(lambda y: y.reshape((-1,) + y.shape[2:]), out)


def short_conv(x, lp, model: dict, precision: str, fault: str = ""):
    """x (S, d) -> Op(N_op(x)) (S, d), the whole sequence from position 0."""
    z, eps = weights.sizes(model), model["norm_eps"]
    mm = partial(einsum, precision)
    d, taps, s = z["d"], z["taps"], x.shape[0]
    theta = model["rope_parameters"]["rope_theta"]

    def gates(xb, pos):
        u = rms_norm(xb, lp["op_norm"], eps)
        if fault == "rope_on_conv":
            u = rope(u.reshape(-1, z["h"], z["hd"]), pos, theta).reshape(
                -1, d)
        bcx = mm("sd,de->se", u, lp["w_in"])
        return bcx[:, d:2 * d], bcx[:, :d] * bcx[:, 2 * d:]

    gate, zs = over_rows(gates, x, jnp.arange(s))
    padded = jnp.pad(zs, ((taps - 1, 0), (0, 0)))       # z = 0 before 0
    c = sum(padded[j:j + s] * lp["taps"][:, j] for j in range(taps))
    return over_rows(lambda g, cb: mm("sd,de->se", g * cb, lp["w_out"]),
                     gate, c)


def attention(x, lp, model: dict, precision: str, fault: str = ""):
    """x (S, d) -> Op(N_op(x)) (S, d), S a multiple of ROW_BLOCK."""
    z, eps = weights.sizes(model), model["norm_eps"]
    mm = partial(einsum, precision)
    s, h, kv, hd = x.shape[0], z["h"], z["kv"], z["hd"]
    theta = model["rope_parameters"]["rope_theta"]
    # the KV head each query head reads
    reads = (np.arange(h) % kv if fault == "wrong_kv_head"
             else np.arange(h) // (h // kv))

    def project(xb, pos):
        u = rms_norm(xb, lp["op_norm"], eps)
        q = mm("sd,de->se", u, lp["wq"]).reshape(-1, h, hd)
        k = mm("sd,de->se", u, lp["wk"]).reshape(-1, kv, hd)
        v = mm("sd,de->se", u, lp["wv"]).reshape(-1, kv, hd)
        if fault != "no_head_norm":
            q = rms_norm(q, lp["q_norm"], eps)
            k = rms_norm(k, lp["k_norm"], eps)
        if fault != "no_rope":
            q, k = rope(q, pos, theta), rope(k, pos, theta)
        return q, k[:, reads], v[:, reads]

    q, k, v = over_rows(project, x, jnp.arange(s))      # each (S, H, hd)
    kpos = jnp.arange(s)

    def attend(args):
        qb, qpos = args
        sc = mm("qhd,khd->hqk", qb, k) / math.sqrt(hd)
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        return mm("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v)

    nq = s // QUERY_BLOCK
    o = jax.lax.map(attend, (q.reshape(nq, QUERY_BLOCK, h, hd),
                             kpos.reshape(nq, QUERY_BLOCK)))
    return over_rows(lambda ob: mm("se,ed->sd", ob, lp["wo"]),
                     o.reshape(s, h * hd))


def expert_layer(x, lp, model: dict, precision: str, held=None,
                 fault: str = ""):
    """x (T, d) normed rows -> the held experts' weighted sum. `held` =
    (first, count) overrides the file's range (the test that adds the
    halves up)."""
    z = weights.sizes(model)
    first, count = held or (z["first"], z["held"])
    mm = partial(einsum, precision)
    scores = jax.nn.sigmoid(jnp.matmul(x, lp["router"], precision=HI))
    select = scores if fault == "no_bias" else scores + lp["bias"]
    _, experts = jax.lax.top_k(select, z["top_k"])
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    w = model["routed_scaling_factor"] * chosen / (
        chosen.sum(-1, keepdims=True) + 1e-6)

    def add_expert(y, held_expert):
        e, w_gate, w_up, w_down = held_expert
        w_e = jnp.sum(jnp.where(experts == first + e, w, 0.0), axis=-1)
        return y + w_e[:, None] * gated_mlp(mm, x, w_gate, w_up, w_down), None

    # (a scan and not a Python loop: one expert's program, compiled once)
    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (
        jnp.arange(count), lp["e_gate"][:count], lp["e_up"][:count],
        lp["e_down"][:count]))
    return y


def layer(x, op_lp, ff_lp, model: dict, op: str, ff: str, precision: str,
          fault: str = ""):
    """One block on x (S, d), S a multiple of ROW_BLOCK."""
    mm = partial(einsum, precision)
    mixer = short_conv if op == "conv" else attention
    x = x + mixer(x, op_lp, model, precision, fault)

    def feed_forward(xb):
        u = rms_norm(xb, ff_lp["ff_norm"], model["norm_eps"])
        if ff == "dense":
            return xb + gated_mlp(mm, u, ff_lp["w1"], ff_lp["w3"],
                                  ff_lp["w2"])
        return xb + expert_layer(u, ff_lp, model, precision, fault=fault)

    return over_rows(feed_forward, x)


def padded_length(n: int) -> int:
    """The rows a sequence of n tokens is computed at: a power of two of
    ROW_BLOCK (every length is a program of its own to compile, three a
    length: 1,024 to 8,192 for the cell's requests)."""
    padded = ROW_BLOCK
    while padded < n:
        padded *= 2
    return padded


def sequence_logits(seed: int, model: dict, tokens, rows, precision: str,
                    layer_fn) -> np.ndarray:
    """One sequence: tokens (S,) and rows (R,) on the host -> logits (R,
    vocabulary) at those positions of its full teacher-forced forward.
    `layer_fn(op, ff)`: the jitted `layer` of that pair of kinds (one
    program a pair and padded length, shared by the sequences of a call)."""
    key = lib.seed_key(seed, 1)
    n = int(np.max(rows)) + 1
    toks = np.zeros((padded_length(n),), np.int32)
    toks[:n] = np.asarray(tokens)[:n]

    @jax.jit
    def embed(key, toks):
        return weights.draw_top(key, model, "embed")[toks]

    x = embed(key, toks)
    for op, oi, ff, fi in weights.layers_of(model):
        op_lp, ff_lp = jax.jit(lambda k: (
            weights.draw_layer(k, model, op, oi),
            weights.draw_layer(k, model, ff, fi)))(key)
        x = layer_fn(op, ff)(x, op_lp, ff_lp)
        del op_lp, ff_lp

    @jax.jit
    def head(key, x, rows):
        u = rms_norm(x[rows], weights.draw_top(key, model, "normf_scale"),
                     model["norm_eps"])
        return einsum(precision, "rd,vd->rv", u,
                      weights.draw_top(key, model, "embed"))

    return np.asarray(jax.device_get(head(key, x, np.asarray(rows))))


@lru_cache(maxsize=None)
def _layer_fn(model_json: str, op: str, ff: str, precision: str, fault: str):
    """The jitted `layer` of a pair of kinds: one object a (model, pair,
    precision, fault), so that calls share its compiled programs."""
    return jax.jit(partial(layer, model=json.loads(model_json), op=op, ff=ff,
                           precision=precision, fault=fault),
                   donate_argnums=(0,))


def served_logits(seed: int, model: dict, tokens, rows,
                  precision: str = "f32", fault: str = "") -> np.ndarray:
    """tokens (N, S), rows (N, R) -> logits (N, R, vocabulary) on the host:
    each sequence's own full teacher-forced forward, one at a time, cut to
    the last row asked for. `fault` plants a departure from the equations
    (`FAULTS`) for the tests that show the comparison sees it."""
    if fault not in FAULTS:
        raise ValueError(f"fault {fault!r}: one of {FAULTS}")
    # a float32 expert layer and its temporaries are 3 GB: what the caller
    # has let go of but Python has not yet collected (a server's object
    # cycles keep its weights and cache on the device) has to go first
    gc.collect()
    layer_fn = partial(_layer_fn, json.dumps(model, sort_keys=True),
                       precision=precision, fault=fault)
    return np.stack([
        sequence_logits(seed, model, t, r, precision, layer_fn)
        for t, r in zip(np.asarray(tokens), np.asarray(rows))])
