"""The plain reference: the `mimo_v2` block as the configuration's keys state
it, in straightforward `jax.numpy`, float32 at the highest matmul precision,
every sequence whole from position 0: ATTENTION IS A SOFTMAX OVER THE
SEQUENCE'S OWN KEYS UNDER A MASK BY POSITION - causal in a full layer, the
band of the last `sliding_window` positions in a window layer - never a
cache and never a ring. It imports nothing of the program; its weights are
this family's own seeded leaves (`weights.draw_layer`), drawn, used and
dropped a layer at a time (an expert layer's are 1.6 GB in float32).

The equations (`config.json` keys in backticks; `N(.)` an RMSNorm with its
own gain, eps `layernorm_epsilon`; no bias on any projection). Block:

    h'  = h  + Attn(N_a(h));   h'' = h' + FF(N_f(h'))

Layer l is a window layer where `hybrid_layer_pattern[l]` is 1, a full
layer where it is 0; its FF is the expert layer where `moe_layer_freq[l]` is
1 and dense where it is 0.

Attention of a kind, H = `num_attention_heads`, KV = `num_key_value_heads`
(full) or `swa_num_key_value_heads` (window), qk = `head_dim`, v =
`v_head_dim`: `[q ; k ; v] = u W_qkv`, split as H x qk | KV x qk | KV x v;
rotate-half RoPE over the first `r = int(qk x partial_rotary_factor)` values
of each q and k head at the token's position (`f_i = theta^(-2i/r)`, `out = x
cos(t f) + [-x_2 ; x_1] sin(t f)`), theta `rope_theta` in a full layer and
`swa_rope_theta` in a window layer; `v <- attention_value_scale * v`; query
head h reads KV head `h // (H / KV)`; `s_j = q . k_j / sqrt(qk)`. A full
layer attends to keys j <= i with `o = sum_j softmax(s)_j v_j`; a window
layer to `i - sliding_window < j <= i` with `o = sum_j exp(s_j) v_j /
(exp(a_h) + sum_j exp(s_j))`, `a_h` head h's sink logit
(`add_swa_attention_sink_bias`). `Attn = concat_h(o) W_o`.

Feed-forward. Dense: `(silu(u W_1) * (u W_3)) W_2` at `intermediate_size`.
Experts: `s = sigmoid(float32(u) W_r)` over all routed experts (the
router's width is `published.n_routed_experts`); the `num_experts_per_tok`
largest of `s + b` are chosen (`b` the selection bias: it selects only);
weights `s / sum_chosen s` (`norm_topk_prob`, `routed_scaling_factor` null:
1); each expert the same gated MLP at `moe_intermediate_size`; no shared
expert. EVERY HELD EXPERT IS COMPUTED FOR EVERY TOKEN and masked by the
routing (nothing is sorted). The router runs in float32 at every
`precision`, as the program's does.

Logits: `N_final(h) W_head^T` over the head's rows (untied).

Every matmul but the router's goes through the shared `einsum(precision,
...)`, so the controls (`bf16`, `fp8`) are the same equations at a lower
precision. Memory, noted: what is done a row at a time runs over blocks of
`ROW_BLOCK` rows, the attention over blocks of `QUERY_BLOCK` queries against
all the keys (a full layer) or against the band's keys alone (a window
layer: the positions `sliding_window` before the block's first query up to
its last; the mask is the same), the experts one held expert at a time (a
scan); a sequence is padded to a power of two of `ROW_BLOCK`, at most the
requests' longest rounded up to `ROW_BLOCK` (what follows its last row asked
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
QUERY_BLOCK = 64
FAULTS = ("", "no_sink", "window_off_by_one", "no_value_scale",
          "thetas_swapped", "rope_whole_head")


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


def attention(x, lp, model: dict, kind: str, precision: str,
              fault: str = ""):
    """x (S, d) -> Attn(N_a(x)) (S, d) of a layer of `kind` ("full" or
    "window"), S a multiple of ROW_BLOCK."""
    z, eps = weights.sizes(model), model["layernorm_epsilon"]
    mm = partial(einsum, precision)
    s, h, qk, vd = x.shape[0], z["h"], z["qk"], z["v"]
    kv = z["kv_full"] if kind == "full" else z["kv_window"]
    window = kind == "window"
    thetas = (model["rope_theta"], model["swa_rope_theta"])
    if fault == "thetas_swapped":
        thetas = thetas[::-1]
    theta = thetas[window]
    r = qk if fault == "rope_whole_head" else z["r"]
    scale = 1.0 if fault == "no_value_scale" else model[
        "attention_value_scale"]
    g = h // kv       # query head h reads KV head h // g: (KV, g) heads

    def project(xb, pos):
        u = rms_norm(xb, lp["op_norm"], eps)
        y = mm("sd,de->se", u, lp["w_qkv"])
        q = y[:, :h * qk].reshape(-1, h, qk)
        k = y[:, h * qk:(h + kv) * qk].reshape(-1, kv, qk)
        v = y[:, (h + kv) * qk:].reshape(-1, kv, vd) * scale
        q = jnp.concatenate([rope(q[..., :r], pos, theta), q[..., r:]], -1)
        k = jnp.concatenate([rope(k[..., :r], pos, theta), k[..., r:]], -1)
        return q.reshape(-1, kv, g, qk), k, v

    q, k, v = over_rows(project, x, jnp.arange(s))  # (S, KV, g, qk), (S, KV, .)
    w = z["window_len"] + (1 if fault == "window_off_by_one" else 0)
    sink = (lp["sink"] if window and fault != "no_sink" else None)

    def softmax_sum(sc, keep, vb):
        # sc (KV, g, Q, K) -> o (Q, H, v)
        sc = jnp.where(keep[None, None], sc, -jnp.inf)
        if sink is not None:
            a = jnp.broadcast_to(sink.reshape(kv, g)[:, :, None, None],
                                 sc.shape[:3] + (1,))
            sc = jnp.concatenate([sc, a], -1)
        p = jax.nn.softmax(sc, axis=-1)[..., :vb.shape[0]]
        return mm("gjqk,kgd->qgjd", p, vb).reshape(-1, h, vd)

    def attend_all(args):
        qb, qpos = args
        kpos = jnp.arange(s)
        sc = mm("qgjd,kgd->gjqk", qb, k) / math.sqrt(qk)
        return softmax_sum(sc, kpos[None, :] <= qpos[:, None], v)

    def attend_band(args):
        # the keys from `pad` before the block's first query to its last:
        # every key the band keeps, and the same mask
        qb, qpos = args
        pad = w
        start = qpos[0]
        kb = jax.lax.dynamic_slice_in_dim(kp, start, QUERY_BLOCK + pad)
        vb = jax.lax.dynamic_slice_in_dim(vp, start, QUERY_BLOCK + pad)
        kpos = start - pad + jnp.arange(QUERY_BLOCK + pad)
        sc = mm("qgjd,kgd->gjqk", qb, kb) / math.sqrt(qk)
        keep = (kpos[None, :] >= 0) & (kpos[None, :] <= qpos[:, None]) & (
            kpos[None, :] > qpos[:, None] - w)
        return softmax_sum(sc, keep, vb)

    if window:
        kp = jnp.pad(k, ((w, 0), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((w, 0), (0, 0), (0, 0)))
    nq = s // QUERY_BLOCK
    o = jax.lax.map(attend_band if window else attend_all, (
        q.reshape(nq, QUERY_BLOCK, kv, g, qk),
        jnp.arange(s).reshape(nq, QUERY_BLOCK)))
    return over_rows(lambda ob: mm("se,ed->sd", ob, lp["w_o"]),
                     o.reshape(s, h * vd))


def expert_layer(x, lp, model: dict, precision: str, held=None):
    """x (T, d) normed rows -> the held experts' weighted sum. `held` =
    (first, count) overrides the file's range (the test that adds the
    shares up): the leaves hold the experts from the file's first on."""
    z = weights.sizes(model)
    first, count = held or (z["first"], z["held"])
    at = first - z["first"]
    mm = partial(einsum, precision)
    scores = jax.nn.sigmoid(jnp.matmul(x, lp["router"], precision=HI))
    _, experts = jax.lax.top_k(scores + lp["bias"], z["top_k"])
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    w = chosen / chosen.sum(-1, keepdims=True) * (
        model.get("routed_scaling_factor") or 1.0)

    def add_expert(y, held_expert):
        e, w_gate, w_up, w_down = held_expert
        w_e = jnp.sum(jnp.where(experts == first + e, w, 0.0), axis=-1)
        return y + w_e[:, None] * gated_mlp(mm, x, w_gate, w_up, w_down), None

    # (a scan and not a Python loop: one expert's program, compiled once)
    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (
        jnp.arange(count), lp["e_gate"][at:at + count],
        lp["e_up"][at:at + count], lp["e_down"][at:at + count]))
    return y


def layer(x, op_lp, ff_lp, model: dict, op: str, ff: str, precision: str,
          fault: str = ""):
    """One block on x (S, d), S a multiple of ROW_BLOCK."""
    mm = partial(einsum, precision)
    x = x + attention(x, op_lp, model, op, precision, fault)

    def feed_forward(xb):
        u = rms_norm(xb, ff_lp["ff_norm"], model["layernorm_epsilon"])
        if ff == "dense":
            return xb + gated_mlp(mm, u, ff_lp["w1"], ff_lp["w3"],
                                  ff_lp["w2"])
        return xb + expert_layer(u, ff_lp, model, precision)

    return over_rows(feed_forward, x)


def padded_length(n: int, cap: int) -> int:
    """The rows a sequence of n tokens is computed at: a power of two of
    ROW_BLOCK, at most `cap` (every length is a program of its own to
    compile, three a length)."""
    padded = ROW_BLOCK
    while padded < n:
        padded *= 2
    return min(padded, cap)


def sequence_logits(seed: int, model: dict, tokens, rows, precision: str,
                    layer_fn) -> np.ndarray:
    """One sequence: tokens (S,) and rows (R,) on the host -> logits (R,
    head rows) at those positions of its full teacher-forced forward.
    `layer_fn(op, ff)`: the jitted `layer` of that pair of kinds (one
    program a pair and padded length, shared by the sequences of a call)."""
    key = lib.seed_key(seed, 1)
    tokens = np.asarray(tokens)
    n = int(np.max(rows)) + 1
    cap = -(-tokens.shape[0] // ROW_BLOCK) * ROW_BLOCK
    toks = np.zeros((padded_length(n, cap),), np.int32)
    toks[:n] = tokens[:n]

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
                     model["layernorm_epsilon"])
        return einsum(precision, "rd,vd->rv", u,
                      weights.draw_top(key, model, "head"))

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
    """tokens (N, S), rows (N, R) -> logits (N, R, head rows) on the host:
    each sequence's own full teacher-forced forward, one at a time, cut to
    the last row asked for. `fault` plants a departure from the equations
    (`FAULTS`) for the tests that show the comparison sees it."""
    if fault not in FAULTS:
        raise ValueError(f"fault {fault!r}: one of {FAULTS}")
    # what the caller has let go of but Python has not yet collected (a
    # server's object cycles keep its weights and cache on the device) has
    # to go first
    gc.collect()
    layer_fn = partial(_layer_fn, json.dumps(model, sort_keys=True),
                       precision=precision, fault=fault)
    return np.stack([
        sequence_logits(seed, model, t, r, precision, layer_fn)
        for t, r in zip(np.asarray(tokens), np.asarray(rows))])
