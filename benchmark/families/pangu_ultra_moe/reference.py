"""The plain reference: the `pangu_ultra_moe` block as the configuration's
keys state it, in straightforward `jax.numpy`, float32 at the highest matmul
precision, THE EXPANDED FORM OF LATENT ATTENTION ONLY. It imports nothing of
the program; its weights are this family's own seeded leaves
(`weights.draw_layer`), drawn, used and dropped a layer at a time, because
the float32 tree (19.7 GB at the cell's size) does not fit a chip.

The equations (`config.json` keys in backticks; `N(.)` an RMSNorm with its
own gain, eps `rms_norm_eps`). Block (`sandwich_norm`):

    a = N_post_attn(Attn(N_in(h)));    h'  = h + a
    m = N_post_mlp(MLP(N_pre_mlp(h'))); h'' = h' + m

Latent attention at position t, H = `num_attention_heads`: `c_q = N_q(x
W_qa)` (`q_lora_rank`); `q = c_q W_qb`, a head's `q_nope` (`qk_nope_head_dim`)
then `q_rope` (`qk_rope_head_dim`); `[c_kv ; k_r] = x W_kva` (`kv_lora_rank` ;
`qk_rope_head_dim`); `c = N_kv(c_kv)`; `k_rope = RoPE(k_r, t)`, one for all
heads; `q_rope = RoPE(q_rope, t)`; `[k_nope ; v]_h = c W_kvb` (`qk_nope_head_dim`
; `v_head_dim` a head); `s_h(t, j) = (q_nope_h . k_nope_h(j) + q_rope_h .
k_rope(j)) / sqrt(qk_nope_head_dim + qk_rope_head_dim)`; causal softmax; `o_h =
sum_j p_h(t, j) v_h(j)`; `Attn = concat_h(o_h) W_o`. RoPE rotates halves:
with `f_i = rope_theta^(-2i/r)`, i < r/2, `out = x cos(t f) + [-x_2 ; x_1]
sin(t f)`, `x = [x_1 ; x_2]`, no scaling.

MLP. The first `first_k_dense_replace` layers: `(silu(x W_g) * (x W_u)) W_d`
at `intermediate_size`. The others, experts: `s = sigmoid(float32(x) W_r)`
over all the model's routed experts; the `num_experts_per_tok` largest are
chosen (no groups, no selection bias); their weights are `routed_scaling_factor
* s / sum_chosen s` (`norm_topk_prob`); every expert is the same gated MLP at
`moe_intermediate_size`; output: the weighted sum over the chosen plus the
shared expert (`n_shared_experts` x `moe_intermediate_size` wide). A CHIP'S
SHARE: of the routed experts only the ones held (`n_routed_experts` of the
file, from `experts_held_first`) are computed, EVERY ONE OF THEM FOR EVERY
TOKEN, masked by the routing (nothing is sorted); what the absent experts
would add is left out, as in the program. The router runs in float32 at
every `precision`, as the program's does: a choice that flips moves one
expert's whole term, and is no matter of a matmul's precision.

Logits: `N_final(h) W_head` over the rows held.

Every matmul but the router's goes through the shared `einsum(precision,
...)`, so the controls (`bf16`, `fp8`) are the same equations at a lower
precision. Memory, noted: what is done a row at a time (projections, MLPs)
runs over blocks of `ROW_BLOCK` rows, the attention over blocks of
`QUERY_BLOCK` queries against all the keys, the experts one held expert at a
time (a scan); a sequence is padded to one of a few lengths (`padded_length`:
what follows its last row asked for changes nothing before it). None of this
changes a value.
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
LENGTH_GRID = 4096


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * gain


def rope(x, pos, theta):
    """x (S, ..., r) at positions pos (S,): rotate halves."""
    r = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (r,))
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


def attention(x, lp, model: dict, precision: str, fault: str = ""):
    """x (S, d) -> Attn(N_in(x)) (S, d), S a multiple of ROW_BLOCK."""
    z, eps = weights.sizes(model), model["rms_norm_eps"]
    mm = partial(einsum, precision)
    s, h = x.shape[0], z["h"]
    scale = 1.0 / math.sqrt(z["nope"] + z["rope"])

    def project(xb, pos):
        u = rms_norm(xb, lp["in_norm"], eps)
        c_q = rms_norm(mm("sd,dr->sr", u, lp["q_a"]), lp["q_norm"], eps)
        q = mm("sr,re->se", c_q, lp["q_b"]).reshape(
            -1, h, z["nope"] + z["rope"])
        q_nope, q_rope = q[..., :z["nope"]], q[..., z["nope"]:]
        ckv = mm("sd,dr->sr", u, lp["kv_a"])
        c = rms_norm(ckv[:, :z["kv_rank"]], lp["kv_norm"], eps)
        k_rope = rope(ckv[:, z["kv_rank"]:], pos, model["rope_theta"])
        if fault == "no_k_rope":
            k_rope = jnp.zeros_like(k_rope)
        kv = mm("sc,ce->se", c, lp["kv_b"]).reshape(-1, h, z["nope"] + z["v"])
        return (q_nope, rope(q_rope, pos, model["rope_theta"]),
                kv[..., :z["nope"]], kv[..., z["nope"]:], k_rope)

    q_nope, q_rope, k_nope, v, k_rope = over_rows(project, x, jnp.arange(s))
    kpos = jnp.arange(s)

    def attend(args):
        qn, qr, qpos = args
        sc = (mm("qhn,khn->hqk", qn, k_nope)
              + mm("qhr,kr->hqk", qr, k_rope)) * scale
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        return mm("hqk,khv->qhv", jax.nn.softmax(sc, axis=-1), v)

    nq = s // QUERY_BLOCK
    o = jax.lax.map(attend, (
        q_nope.reshape(nq, QUERY_BLOCK, h, -1),
        q_rope.reshape(nq, QUERY_BLOCK, h, -1),
        kpos.reshape(nq, QUERY_BLOCK)))
    o = o.reshape(s, h * z["v"])
    return over_rows(lambda ob: mm("se,ed->sd", ob, lp["o"]), o)


def expert_layer(x, lp, model: dict, precision: str, held=None,
                 fault: str = ""):
    """x (T, d) normed rows -> the held experts' weighted sum plus the shared
    expert. `held` = (first, count) overrides the file's share (the test
    that adds the shares up)."""
    z = weights.sizes(model)
    first, count = held or (z["first"], z["held"])
    mm = partial(einsum, precision)
    scores = jax.nn.sigmoid(jnp.matmul(x, lp["router"], precision=HI))
    chosen, experts = jax.lax.top_k(scores, z["top_k"])
    factor = 1.0 if fault == "no_routed_scale" else model[
        "routed_scaling_factor"]
    w = factor * chosen / chosen.sum(-1, keepdims=True)
    y = gated_mlp(mm, x, lp["s_gate"], lp["s_up"], lp["s_down"])

    def add_expert(y, held_expert):
        e, w_gate, w_up, w_down = held_expert
        w_e = jnp.sum(jnp.where(experts == first + e, w, 0.0), axis=-1)
        return y + w_e[:, None] * gated_mlp(mm, x, w_gate, w_up, w_down), None

    # (a scan and not a Python loop: one expert's program, compiled once)
    y, _ = jax.lax.scan(add_expert, y, (
        jnp.arange(count), lp["e_gate"][:count], lp["e_up"][:count],
        lp["e_down"][:count]))
    return y


def layer(x, lp, model: dict, kind: str, precision: str, fault: str = ""):
    """One block on x (S, d), S a multiple of ROW_BLOCK."""
    eps = model["rms_norm_eps"]
    mm = partial(einsum, precision)
    a = attention(x, lp, model, precision, fault)
    if fault != "no_post_attn_norm":
        a = rms_norm(a, lp["post_attn_norm"], eps)
    x = x + a

    def mlp(xb):
        u = rms_norm(xb, lp["pre_mlp_norm"], eps)
        if kind == "dense":
            m = gated_mlp(mm, u, lp["w_gate"], lp["w_up"], lp["w_down"])
        else:
            m = expert_layer(u, lp, model, precision, fault=fault)
        return xb + rms_norm(m, lp["post_mlp_norm"], eps)

    return over_rows(mlp, x)


def layers_of(model: dict):
    """(kind, index in its stack) of every layer, in the model's order."""
    z = weights.sizes(model)
    return [(kind, i) for kind in weights.KINDS for i in range(z[kind])]


def padded_length(n: int) -> int:
    """The rows a sequence of n tokens is computed at: a power of two of
    ROW_BLOCK up to LENGTH_GRID, a multiple of LENGTH_GRID past it. Every
    length is a program of its own to compile (two a length, a quarter of
    a minute each at the cell's widths), so the lengths are few: 4,096,
    8,192, 12,288 and 16,384 for the cell's requests."""
    if n > LENGTH_GRID:
        return -(-n // LENGTH_GRID) * LENGTH_GRID
    padded = ROW_BLOCK
    while padded < n:
        padded *= 2
    return padded


def sequence_logits(seed: int, model: dict, tokens, rows, precision: str,
                    layer_fns: dict) -> np.ndarray:
    """One sequence: tokens (S,) and rows (R,) on the host -> logits (R,
    rows held) at those positions of its full teacher-forced forward.
    `layer_fns`: kind -> the jitted `layer` of that kind (one program a kind
    and padded length, shared by the sequences of a call)."""
    key = lib.seed_key(seed, 1)
    n = int(np.max(rows)) + 1
    toks = np.zeros((padded_length(n),), np.int32)
    toks[:n] = np.asarray(tokens)[:n]

    @jax.jit
    def embed(key, toks):
        return weights.draw_top(key, model, "embed")[toks]

    x = embed(key, toks)
    for kind, i in layers_of(model):
        lp = jax.jit(lambda k: weights.draw_layer(k, model, kind, i))(key)
        x = layer_fns[kind](x, lp)
        del lp

    @jax.jit
    def head(key, x, rows):
        u = rms_norm(x[rows], weights.draw_top(key, model, "normf_scale"),
                     model["rms_norm_eps"])
        return einsum(precision, "rd,dv->rv", u,
                      weights.draw_top(key, model, "head"))

    return np.asarray(jax.device_get(head(key, x, np.asarray(rows))))


@lru_cache(maxsize=None)
def _layer_fn(model_json: str, kind: str, precision: str, fault: str):
    """The jitted `layer` of a kind: one object a (model, kind, precision,
    fault), so that calls share its compiled programs."""
    return jax.jit(partial(layer, model=json.loads(model_json), kind=kind,
                           precision=precision, fault=fault),
                   donate_argnums=(0,))


def served_logits(seed: int, model: dict, tokens, rows,
                  precision: str = "f32", fault: str = "") -> np.ndarray:
    """tokens (N, S), rows (N, R) -> logits (N, R, rows held) on the host:
    each sequence's own full teacher-forced forward, one at a time, cut to
    the last row asked for. `fault` plants a departure from the equations
    (`no_post_attn_norm`, `no_k_rope`, `no_routed_scale`) for the tests that
    show the comparison sees it."""
    # a float32 layer and its temporaries are 10 GB: what the caller has
    # let go of but Python has not yet collected (a server's object cycles
    # keep its weights and cache on the device) has to go first
    gc.collect()
    layer_fns = {kind: _layer_fn(json.dumps(model, sort_keys=True), kind,
                                 precision, fault) for kind in weights.KINDS}
    return np.stack([
        sequence_logits(seed, model, t, r, precision, layer_fns)
        for t, r in zip(np.asarray(tokens), np.asarray(rows))])
