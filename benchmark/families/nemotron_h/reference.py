"""The plain reference: the `nemotron_h` block as the configuration's keys
state it, in straightforward `jax.numpy`, float32 at the highest matmul
precision. It imports nothing of the program; its weights are this family's
own seeded tree (`weights.make`), made whole.

The equations (`config.json` keys in backticks). Every block is
`x <- x + mixer(RMSNorm(x))`, eps `layer_norm_epsilon`; after the last block
an RMSNorm and an untied head. The letter of `hybrid_override_pattern` picks
the mixer.

`M`, Mamba-2. `d_inner = mamba_num_heads * mamba_head_dim` (H heads of P),
`G = n_groups`, `N = ssm_state_size`. `[z | xBC | dt] = u W_in` of widths
`d_inner | d_inner + 2 G N | H`, no bias. `xBC <- silu(conv(xBC))`: depthwise,
causal, width `conv_kernel`, with bias (`conv(x)_t = b + sum_k w_k
x_{t-K+1+k}`). xBC splits into x (H heads of P), B and C (G groups of N; head
h reads group h // (H / G)). `dt <- softplus(dt + dt_bias)`, `A = -exp(A_log)`
a head. The recurrence, a head, from S_0 = 0:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T   (P x N)
    y_t = S_t C_t + D x_t

Then `y <- RMSNorm_groups(y * silu(z)) * w` over G groups of d_inner / G, and
`out = y W_out`. HERE THE RECURRENCE IS COMPUTED STEP BY STEP, a `lax.scan`
over time, not in the chunked form the program uses.

`E`, experts. `s = sigmoid(float32(u) W_r)` over all `n_routed_experts` of
the model; the `num_experts_per_tok` largest of `s + e_score_correction_bias`
are chosen (`n_group` = `topk_group` = 1: no group limit); their weights are
the unbiased s at the chosen over their sum (`norm_topk_prob`), times
`routed_scaling_factor`. Expert e is `relu(u W_up,e)^2 W_down,e`, no gate, no
bias. Output: the weighted sum over the chosen plus the shared expert, the
same MLP at `moe_shared_expert_intermediate_size`. No auxiliary loss; the
selection bias only selects and gets no gradient. A CHIP'S SHARE: of the
routed experts only the ones held (`n_routed_experts` of the file, from
`experts_held_first`) are computed, EVERY ONE OF THEM FOR EVERY TOKEN, masked
by the routing (nothing is sorted); what the absent experts would add is left
out, as in the program. The router runs in float32 at every `precision`, as
the program's does: a choice that flips moves an expert's gradient in the
first order, and is no matter of a matmul's precision.

`*`, attention. `q = u W_q` (`num_attention_heads` of `head_dim`), k and v
(`num_key_value_heads`, each serving heads / kv_heads query heads), causal
softmax at scale head_dim^-1/2 as a full masked square, `out = o W_o`, no
bias, NO POSITIONAL SIGNAL (the family's attention layers apply no rotary
embedding).

Every matmul but the router's goes through the shared `einsum(precision,
...)`, so the controls (`bf16`, `fp8`) are the same equations at a lower
precision. Memory, noted: the gradient is taken a layer at a time and
`reference_rows_per_block` rows at a time (`loss_and_grads`), each layer's
backward pass recomputing its forward; the scan over time is cut
into blocks of up to 128 steps, each under `jax.checkpoint`, so that its
backward pass keeps one state a block and not one a step; what a Mamba-2
layer does before and after its recurrence is under `jax.checkpoint` too;
attention takes one query head and up to 2048 of its rows at a time and the
experts one held expert at a time, each under `jax.checkpoint`, so that one
slab of the (S, S) square and one expert's hidden units live at a time. None
of this changes a value.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from lib.reference import HI, einsum

from . import weights

KINDS = weights.KINDS


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * gain


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def causal_conv(x, w, b):
    """x (B, S, C), w (K, C), b (C,)."""
    k, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return b + sum(xp[:, i:i + s] * w[i] for i in range(k))


def recurrence(x, dt, a, bm, cm, precision: str):
    """x (B, S, H, P), dt (B, S, H), a (H,), bm and cm (B, S, G, N) -> the
    states' readout S_t C_t (B, S, H, P), one step at a time."""
    b, s, h, p = x.shape
    g, n = bm.shape[2:]

    def step(state, now):
        xt, dtt, bt, ct = now
        bt, ct = (jnp.repeat(t, h // g, axis=1) for t in (bt, ct))  # (B, H, N)
        state = (jnp.exp(dtt * a)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[..., None, :])
        return state, einsum(precision, "bhpn,bhn->bhp", state, ct)

    blk = math.gcd(s, 128)

    @jax.checkpoint
    def block(state, chunk):
        return jax.lax.scan(step, state, chunk)

    # time first, then cut into blocks: (S / blk, blk, B, ...)
    seq = tuple(t.swapaxes(0, 1).reshape((s // blk, blk) + t.shape[:1]
                                         + t.shape[2:])
                for t in (x, dt, bm, cm))
    _, y = jax.lax.scan(block, jnp.zeros((b, h, p, n), jnp.float32), seq)
    return y.reshape((s, b, h, p)).swapaxes(0, 1)


def mamba(u, lp, z: dict, eps: float, precision: str):
    mm = partial(einsum, precision)
    b, s, _ = u.shape
    h, p, g, n, di = z["h"], z["p"], z["g"], z["n"], z["d_inner"]

    @jax.checkpoint
    def before(u, lp):
        zxbcdt = mm("bsd,de->bse", u, lp["m_in"])
        gate, xbc, dt = jnp.split(zxbcdt, [di, di + z["conv_dim"]], axis=-1)
        xbc = jax.nn.silu(causal_conv(xbc, lp["m_conv_w"], lp["m_conv_b"]))
        x, bm, cm = jnp.split(xbc, [di, di + g * n], axis=-1)
        return (gate, x.reshape(b, s, h, p), bm.reshape(b, s, g, n),
                cm.reshape(b, s, g, n), jax.nn.softplus(dt + lp["m_dt_bias"]))

    @jax.checkpoint
    def after(y, x, gate, lp):
        y = y + lp["m_d"][:, None] * x
        y = (y.reshape(b, s, di) * jax.nn.silu(gate)).reshape(
            b, s, g, di // g)
        y = rms_norm(y, 1.0, eps).reshape(b, s, di) * lp["m_gnorm"]
        return mm("bse,ed->bsd", y, lp["m_out"])

    gate, x, bm, cm, dt = before(u, lp)
    y = recurrence(x, dt, -jnp.exp(lp["m_a_log"]), bm, cm, precision)
    return after(y, x, gate, lp)


def experts(u, lp, z: dict, scale: float, precision: str):
    mm = partial(einsum, precision)
    scores = jax.nn.sigmoid(jnp.matmul(u, lp["e_router"], precision=HI))
    _, chosen = jax.lax.top_k(
        scores + jax.lax.stop_gradient(lp["e_bias"]), z["top_k"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = scale * picked / jnp.sum(picked, -1, keepdims=True)
    y = mm("bsf,fd->bsd", relu2(mm("bsd,df->bsf", u, lp["e_shared_up"])),
           lp["e_shared_down"])

    def add_expert(y, expert):      # every held expert, for every token
        e, up, down = expert
        w_e = jnp.sum(jnp.where(chosen == z["first"] + e, weight, 0.0), -1)
        y_e = mm("bsf,fd->bsd", relu2(mm("bsd,df->bsf", u, up)), down)
        return y + w_e[..., None] * y_e, None

    y, _ = jax.lax.scan(jax.checkpoint(add_expert), y, (
        jnp.arange(z["held"]), lp["e_up"], lp["e_down"]))
    return y


def attention(u, lp, z: dict, precision: str):
    mm = partial(einsum, precision)
    b, s, _ = u.shape
    hq, hkv, dh = z["heads"], z["kv_heads"], z["head_dim"]
    q = mm("bsd,de->bse", u, lp["a_wq"]).reshape(b, s, hq, dh)
    k = mm("bsd,de->bse", u, lp["a_wk"]).reshape(b, s, hkv, dh)
    v = mm("bsd,de->bse", u, lp["a_wv"]).reshape(b, s, hkv, dh)
    # the full masked square, a query head and a block of its rows at a time
    rows = math.gcd(s, 2048)
    causal = jnp.tril(jnp.ones((s, s), bool)).reshape(s // rows, rows, s)

    @jax.checkpoint
    def some_rows(head, block, qb):
        kh, vh = k[:, :, head // (hq // hkv)], v[:, :, head // (hq // hkv)]
        scores = mm("bqd,bkd->bqk", qb, kh) / math.sqrt(dh)
        probs = jax.nn.softmax(jnp.where(causal[block], scores, -jnp.inf), -1)
        return mm("bqk,bkd->bqd", probs, vh)

    qs = jnp.moveaxis(q, 2, 0).reshape(hq, b, s // rows, rows, dh)
    qs = jnp.moveaxis(qs, 2, 1).reshape(hq * (s // rows), b, rows, dh)
    at = jnp.arange(hq * (s // rows))
    o = jax.lax.map(lambda t: some_rows(*t),
                    (at // (s // rows), at % (s // rows), qs))
    o = jnp.moveaxis(o.reshape(hq, s // rows, b, rows, dh), 2, 0)
    o = jnp.moveaxis(o.reshape(b, hq, s, dh), 1, 2).reshape(b, s, hq * dh)
    return mm("bse,ed->bsd", o, lp["a_wo"])


def layer(x, lp, letter: str, model: dict, precision: str):
    """One block on x (rows, S, d): x + mixer(RMSNorm(x))."""
    z, eps = weights.sizes(model), model["layer_norm_epsilon"]
    u = rms_norm(x, lp[KINDS[letter] + "_norm"], eps)
    if letter == "M":
        return x + mamba(u, lp, z, eps, precision)
    if letter == "E":
        return x + experts(u, lp, z, model["routed_scaling_factor"],
                           precision)
    return x + attention(u, lp, z, precision)


def head_loss(x, gain, head, targets, eps: float, precision: str):
    """Summed next-token cross-entropy of a block of rows over the rows of
    the vocabulary held here, from the last block's output."""
    logits = einsum(precision, "bsd,dv->bsv", rms_norm(x, gain, eps), head)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def loss_and_grads(seed: int, model: dict, traffic: dict,
                   precision: str = "f32", fault: str = "", shardings=None):
    """(the seeded float32 tree, fn): fn(params, tokens, targets) -> (mean
    loss, gradients of it). The tree has the program's structure, so the
    optimizers' plain updates and the per-leaf norms line up with the
    program's state. `fault` names nothing here: this family trains without
    an exchange between chips to leave out.

    `fn` walks the model a layer at a time, `reference_rows_per_block` rows
    at a time: forward through the layers keeping each layer's input, the
    (on the host), the head's loss and its gradient, then backward through
    the layers, each layer's `jax.vjp` recomputing its forward, every
    gradient added in place into one tree of the parameters' shape. That is the chain rule written
    out between layers and nothing else; it is written out because one
    program of the whole model (`lib.reference.mean_loss_and_grads`) keeps
    the blocks' summed gradients beside a block's own, and the layers'
    pieces beside the stacked tree, which beside the tree and Adam's two
    moments does not fit one chip at this size. One small program a kind of
    layer also compiles in a fraction of the time."""
    rows = traffic["reference_rows_per_block"]
    pat, eps = weights.pattern(model), model["layer_norm_epsilon"]
    first = {letter: pat.index(letter) for letter in set(pat)}

    def of_kind(layers, letter):
        return {k: v for k, v in layers.items()
                if k.startswith(KINDS[letter] + "_")}

    def pick(stacked, i):
        return {k: jax.lax.dynamic_index_in_dim(v, i, keepdims=False)
                for k, v in stacked.items()}

    forward, backward = {}, {}
    for letter in first:
        run = partial(layer, letter=letter, model=model, precision=precision)
        forward[letter] = jax.jit(
            lambda x, stacked, i, run=run: run(x, pick(stacked, i)))

        def back(x, stacked, i, dy, grads, run=run):
            """dx, and the layer's gradients added into row i of `grads`."""
            _, vjp = jax.vjp(run, x, pick(stacked, i))
            dx, dlp = vjp(dy)
            return dx, {k: g.at[i].add(dlp[k]) for k, g in grads.items()}

        backward[letter] = jax.jit(back, donate_argnums=(3, 4))

    @partial(jax.jit, donate_argnums=(4, 5))
    def head_step(x, gain, head, targets, d_gain, d_head):
        loss, (dx, dg, dh) = jax.value_and_grad(
            partial(head_loss, eps=eps, precision=precision),
            argnums=(0, 1, 2))(x, gain, head, targets)
        return loss, dx, d_gain + dg, d_head + dh

    embed = jax.jit(lambda table, tokens: table[tokens])
    embed_back = jax.jit(lambda d_table, tokens, dx: d_table.at[tokens].add(dx),
                         donate_argnums=(0,))
    zeros = jax.jit(lambda tree: jax.tree.map(jnp.zeros_like, tree))
    scale = jax.jit(lambda tree, n: jax.tree.map(lambda g: g / n, tree),
                    donate_argnums=(0,))

    def fn(params, tokens, targets):
        grads = zeros(params)
        d_layers = {letter: of_kind(grads["layers"], letter)
                    for letter in first}
        p_layers = {letter: of_kind(params["layers"], letter)
                    for letter in first}
        order = [(letter, pat[:at].count(letter))
                 for at, letter in enumerate(pat)]
        total = 0.0
        for r in range(0, tokens.shape[0], rows):
            tok, tgt = tokens[r:r + rows], targets[r:r + rows]
            x = embed(params["embed"], tok)
            xs = []                 # each layer's input, kept on the host
            for letter, i in order:
                xs.append(jax.device_get(x))
                x = forward[letter](x, p_layers[letter], i)
            loss, dx, grads["normf_scale"], grads["head"] = head_step(
                x, params["normf_scale"], params["head"], tgt,
                grads["normf_scale"], grads["head"])
            del x
            total = total + loss
            for letter, i in reversed(order):
                dx, d_layers[letter] = backward[letter](
                    xs.pop(), p_layers[letter], i, dx, d_layers[letter])
            grads["embed"] = embed_back(grads["embed"], tok, dx)
        grads["layers"] = {k: v for d in d_layers.values()
                           for k, v in d.items()}
        n = tokens.size
        return total / n, scale(grads, n)

    fn.programs = {"forward": forward, "backward": backward,
                   "head": head_step}  # for a look at what each compiles to
    return weights.make(seed, model, shardings=shardings), fn
