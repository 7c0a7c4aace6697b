"""Expert parallelism: mixture-of-experts dispatch/combine over a mesh axis.

The reference has no MoE or expert parallelism anywhere (SURVEY.md section 2:
expert parallelism explicitly absent; its only model is the 62K-param CNN at
`/root/reference/models/model.py:9-27`). This module is the framework's
expert-parallel capability, built TPU-first in the GShard/Switch style:

- **Static shapes everywhere.** Routing uses a fixed per-expert *capacity*;
  tokens that overflow an expert's capacity are dropped (their FFN
  contribution is zero, the residual stream passes them through). No
  data-dependent shapes, so the program never retraces.
- **Two dispatch implementations, one contract.** `dispatch_impl="dense"`
  materializes (T, E, C) one-hot dispatch/combine tensors and runs pure
  einsums - trivially correct, O(T*E*C) memory, the small-shape oracle.
  `dispatch_impl="sort"` (default; r2 VERDICT weak #4) computes each
  routed token's (expert, capacity-slot) coordinate with a one-hot cumsum
  in token order - the same priority order as the dense path, so numerics
  match - then scatter-adds tokens into the (E, C, d) slot tensor and
  gathers results back: O(T*k*E) routing work and O(T*k + E*C*d) memory,
  usable at real token/expert counts (tested at 64k tokens) where the
  dense tensors would be tens of GB.
- **Router z-loss** (ST-MoE): mean squared logsumexp of the router logits,
  weighted into the returned aux, keeps router logits from drifting to
  magnitudes where softmax saturates and bf16 rounds badly.
- **Expert parallelism = one all_to_all each way.** Experts are sharded over
  a mesh axis (conventionally the data axis, as in GShard); each device
  routes its local tokens, materializes per-expert capacity slots
  (E, C, d), and a single `jax.lax.all_to_all` re-shards slot tensors from
  token-major to expert-major: afterwards each device holds E/n experts'
  slots from *every* source device, runs its local expert FFNs as one
  batched einsum, and a second all_to_all sends results home.
- **Load balancing** via the Switch-Transformer auxiliary loss
  (E * sum_i fraction_routed_i * mean_router_prob_i), returned to the caller
  to be weighted into the training loss.

Pure functions designed for use inside `jax.shard_map`; with `ep_axis=None`
they run the identical math on one device (the parity oracle in
tests/test_moe.py).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .collectives import vary_like


def expert_capacity(n_tokens: int, n_experts: int, top_k: int, factor: float) -> int:
    """Per-source-device capacity slots per expert (static)."""
    return max(1, math.ceil(factor * top_k * n_tokens / n_experts))


def topk_dispatch(probs, top_k: int, capacity: int):
    """Greedy top-k routing with per-expert capacity.

    probs: (T, E) router probabilities. Returns (combine, dispatch, aux):
    combine (T, E, C) float weights, dispatch (T, E, C) 0/1 slot assignment,
    aux the Switch load-balancing loss. Position within each expert's
    capacity is assigned in token order (cumsum over the one-hot), the
    standard static-shape formulation. For top_k > 1 the k gates of each
    token are renormalized to sum to 1 over the *selected* experts.
    """
    t, e = probs.shape
    combine = jnp.zeros((t, e, capacity), probs.dtype)
    dispatch = jnp.zeros((t, e, capacity), probs.dtype)
    fill = jnp.zeros((e,), jnp.int32)
    masked = probs
    gate_sum = jnp.zeros((t,), probs.dtype)
    chosen = []  # per-round (onehot, gate, ok)
    for _ in range(top_k):
        idx = jnp.argmax(masked, axis=-1)
        onehot = jax.nn.one_hot(idx, e, dtype=probs.dtype)
        pos = jnp.cumsum(onehot, axis=0) - 1.0 + fill[None, :].astype(probs.dtype)
        pos_tok = (pos * onehot).sum(-1)
        ok = (pos_tok < capacity).astype(probs.dtype)
        gate = (probs * onehot).sum(-1)
        chosen.append((onehot, pos_tok, gate, ok))
        gate_sum = gate_sum + gate * ok
        fill = fill + (onehot * ok[:, None]).sum(0).astype(jnp.int32)
        masked = masked - 2.0 * onehot  # exclude chosen expert in later rounds
    denom = jnp.maximum(gate_sum, 1e-9)
    for onehot, pos_tok, gate, ok in chosen:
        slot = onehot[:, :, None] * jax.nn.one_hot(
            pos_tok.astype(jnp.int32), capacity, dtype=probs.dtype
        )[:, None, :] * ok[:, None, None]
        dispatch = dispatch + slot
        combine = combine + (gate / denom)[:, None, None] * slot

    # Switch aux loss from first-choice assignment: E * sum_i f_i * P_i
    first_onehot = chosen[0][0]
    frac = first_onehot.mean(0)
    mean_prob = probs.mean(0)
    aux = jnp.float32(e) * jnp.sum(frac * mean_prob)
    return combine, dispatch, aux


def sort_route(probs, top_k: int, capacity: int):
    """Coordinate-form top-k routing with per-expert capacity.

    probs: (T, E) router probabilities. Returns (expert_idx, slot_idx,
    weight, aux): each (k*T,) flat arrays in round-major order (all first
    choices in token order, then all second choices - the same priority
    the dense oracle uses), where `slot_idx` is the token's position in
    its expert's capacity buffer (== capacity when the token overflowed
    and must be dropped) and `weight` is the kept-gate renormalized
    combine weight (0 for dropped slots). O(T*k*E) work, no (T, E, C)
    tensor. aux is the Switch load-balancing loss.
    """
    t, e = probs.shape
    gates, experts = jax.lax.top_k(probs, top_k)  # (T, k), priority order
    flat_e = experts.T.reshape(-1)  # (k*T,) round-major
    flat_g = gates.T.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)  # (kT, E)
    # position among same-expert entries, in round-major (= dense) order
    pos = ((jnp.cumsum(onehot, axis=0) - 1) * onehot).sum(-1)  # (kT,)
    keep = pos < capacity
    slot = jnp.where(keep, pos, capacity)
    # renormalize each token's kept gates to sum to 1 (dense-path parity)
    kept_g = jnp.where(keep, flat_g, 0.0).reshape(top_k, t)
    denom = jnp.maximum(kept_g.sum(0), 1e-9)
    weight = (kept_g / denom[None, :]).reshape(-1)

    # Switch aux from first-choice assignment: E * sum_i f_i * P_i
    frac = onehot[:t].mean(0).astype(probs.dtype)
    aux = jnp.float32(e) * jnp.sum(frac * probs.mean(0))
    return flat_e, slot, weight, aux


def moe_ffn(
    x,
    wr,
    w1,
    b1,
    w2,
    b2,
    *,
    top_k: int = 2,
    capacity: int,
    ep_axis: str | None = None,
    tp_axis: str | None = None,
    dispatch_impl: str = "sort",
    z_loss_weight: float = 0.0,
):
    """Mixture-of-experts gelu FFN on a flat token batch.

    x: (T, d) local tokens. wr: (d, E) router (E = global expert count).
    w1 (E_local, d, F_local), b1 (E_local, F_local), w2 (E_local, F_local, d),
    b2 (E_local, d) - the local expert shard (E_local = E/|ep|, F_local =
    F/|tp|). Returns (y, aux) with y (T, d) in x.dtype; aux is the Switch
    load-balancing loss plus z_loss_weight * mean(logsumexp(logits)^2)
    (router z-loss; the caller's aux weight multiplies the whole thing).
    dispatch_impl: "sort" (scatter/gather, scalable) or "dense" (one-hot
    einsum oracle) - identical numerics, different memory scaling.
    """
    dt = x.dtype
    logits = x.astype(jnp.float32) @ wr.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    if dispatch_impl == "dense":
        combine, dispatch, aux = topk_dispatch(probs, top_k, capacity)
        xe = jnp.einsum("tec,td->ecd", dispatch.astype(dt), x)  # (E, C, d)
    elif dispatch_impl == "sort":
        k = top_k
        t, e = probs.shape
        flat_e, slot, weight, aux = sort_route(probs, top_k, capacity)
        x_rep = jnp.tile(x, (k, 1))  # (kT, d) round-major
        xe = jnp.zeros((e, capacity, x.shape[1]), dt)
        # slot == capacity for dropped tokens -> out of bounds -> 'drop';
        # slots are unique per expert, so add == set (combine applies the
        # gate weight, matching the 0/1 dense dispatch tensor)
        xe = xe.at[flat_e, slot].add(x_rep, mode="drop")
    else:
        raise ValueError(
            f"dispatch_impl must be 'sort' or 'dense', got {dispatch_impl!r}"
        )
    if ep_axis is not None:
        # token-major -> expert-major: device p gets slots for its E_local
        # experts from every source; (E, C, d) -> (E_local, n*C, d)
        xe = jax.lax.all_to_all(xe, ep_axis, split_axis=0, concat_axis=1, tiled=True)
    h = jnp.einsum("ecd,edf->ecf", xe, w1.astype(dt)) + b1.astype(dt)[:, None]
    h = jax.nn.gelu(h)
    y = jnp.einsum("ecf,efd->ecd", h, w2.astype(dt))
    if tp_axis is not None:
        y = jax.lax.psum(y, tp_axis)
    y = y + b2.astype(dt)[:, None]
    if ep_axis is not None:
        y = jax.lax.all_to_all(y, ep_axis, split_axis=1, concat_axis=0, tiled=True)
    if dispatch_impl == "dense":
        out = jnp.einsum("tec,ecd->td", combine.astype(dt), y)
    else:
        # dropped slots (slot == capacity) are out of bounds -> fill 0
        gathered = y.at[flat_e, slot].get(mode="fill", fill_value=0)
        out = (gathered * weight.astype(dt)[:, None]).reshape(
            top_k, t, x.shape[1]
        ).sum(0)
    if z_loss_weight:
        z = jax.scipy.special.logsumexp(logits, axis=-1)
        aux = aux + jnp.float32(z_loss_weight) * jnp.mean(z * z)
    return out, aux


# ------------------------------------------- a chip's share of the experts
#
# The layer below is the expert layer as expert parallelism leaves it on one
# chip: the router scores every routed expert of the model, the chip holds
# `w_up.shape[0]` of them (the experts `first`, `first + 1`, ...), and it
# computes the part of the layer's result that its own experts give, plus
# the shared expert, which every chip computes alike. What the absent
# experts would add is not computed and nothing stands in for it: across
# chips it arrives by the exchange, which a later PR adds.
#
# No token is ever dropped. The pairs (token, chosen expert) whose expert
# is held are sorted by expert into a buffer sized for the worst case (every
# pair of every token lands here) at a static shape, each expert's rows
# starting on a tile boundary, and the two products of the expert MLP are
# grouped products over that buffer: one tile of rows at a time against its
# expert's matrices, the tiles past the last held pair included. The buffer
# is an index map and is never filled: a tile's rows are gathered when its
# turn comes.


def sigmoid_topk_route(x, wr, bias, *, top_k: int, scale: float,
                       sum_eps: float = 0.0):
    """x (T, d), wr (d, E), bias (E,) or None -> (experts (T, k) int32, weights
    (T, k) float32). Scores are sigmoid(x wr) in float32 at the highest
    matmul precision (a choice that flips with the rounding of x moves an
    expert's gradient in the first order); the k largest of score + bias
    are chosen, and their weights are the unbiased scores over their sum
    (plus `sum_eps`, for a family that states one), times `scale`. The bias
    selects only: no gradient reaches it."""
    scores = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), wr.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    # (a router without a selection bias passes None: chosen in Python,
    # so a step that has one traces what it traced)
    _, experts = jax.lax.top_k(
        scores if bias is None
        else scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), top_k)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    scaled = scale * chosen
    total = chosen.sum(-1, keepdims=True)
    return experts, scaled / (total + sum_eps if sum_eps else total)


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def _tiles(src, valid, row_weight, tile_group):
    """What the tile loop scans: each tile's rows and its expert."""
    return tuple(v.reshape(tile_group.shape[0], -1)
                 for v in (src, valid, row_weight)) + (tile_group,)


@jax.custom_vjp
def grouped_expert_mlp(x, w_up, w_down, row_weight, src, valid, tile_group):
    """The held experts' MLPs over the sorted pairs, one tile of rows at a
    time: x (T, d), w_up (H, d, f), w_down (H, f, d); row m of the (never
    materialised) buffer is token `src[m]` where `valid[m]`, weighs
    `row_weight[m]` and belongs to expert `tile_group[m // tile]` with all
    of its tile (H: no expert owns the tile, and its rows are all nought).
    Each tile is gathered, taken through `relu(. W_up)^2 W_down` of its
    expert - the grouped product up and the grouped product down -
    weighted, and added to its tokens' rows of the float32 result (T, d).
    The backward pass walks the same tiles again and recomputes each;
    nothing of the size of the buffer is kept or made.

    EVERY tile of the worst-case buffer is multiplied, the unowned ones
    too (noughts, against the last expert): the step's time then does not
    depend on where the router sends the tokens, which under Adam from
    step 0 with no warm-up swings from nothing to everything within ten
    steps (`PERF.md` section 6, PR 27). Skipping the unowned tiles (a
    `lax.cond` on `g <= last`, measured: 581 ms a step for 1,015) is owed
    to this layer, not a gain for a later PR to claim: it comes back with
    the schedule that keeps the load even (`ROADMAP.md` Reach A3)."""
    last, f32 = w_up.shape[0] - 1, jnp.float32

    def one(y, tile):
        s, ok, wr, g = tile
        g = jnp.minimum(g, last)    # an unowned tile: all nought, any expert
        xb = jnp.where(ok[:, None], x[s], 0)
        yb = relu2(xb @ w_up[g]) @ w_down[g]
        return y.at[s].add(yb.astype(f32) * wr[:, None]), None

    y0 = vary_like(jnp.zeros(x.shape, f32), x, w_up, w_down, row_weight)
    y, _ = jax.lax.scan(one, y0, _tiles(src, valid, row_weight, tile_group))
    return y


def _grouped_expert_mlp_bwd(res, dy):
    x, w_up, w_down, row_weight, src, valid, tile_group = res
    last, f32, dt = w_up.shape[0] - 1, jnp.float32, x.dtype

    def one(carry, tiled):
        s, ok, wr, g = tiled
        g = jnp.minimum(g, last)
        dx, d_up, d_down = carry
        xb = jnp.where(ok[:, None], x[s], 0)
        r = jax.nn.relu(xb @ w_up[g])
        h = r * r
        gy = dy[s].astype(dt)
        # y_row = wr (h W_down): one product gy W_down^T gives both the
        # weight's gradient, <h, gy W_down^T>, and, times wr, h's
        d_h = gy @ w_down[g].T
        d_wr = jnp.sum(h.astype(f32) * d_h, axis=-1)
        d_down = d_down.at[g].add(jnp.matmul(
            h.T, (gy * wr[:, None]).astype(dt), preferred_element_type=f32))
        d_pre = (d_h * wr[:, None] * (2 * r)).astype(dt)
        d_up = d_up.at[g].add(
            jnp.matmul(xb.T, d_pre, preferred_element_type=f32))
        d_xb = jnp.where(ok[:, None], d_pre @ w_up[g].T, 0)
        return (dx.at[s].add(d_xb.astype(f32)), d_up, d_down), d_wr

    like = (x, w_up, w_down, row_weight, dy)
    carry = tuple(vary_like(jnp.zeros(t.shape, f32), *like)
                  for t in (x, w_up, w_down))
    (dx, d_up, d_down), d_wr = jax.lax.scan(
        one, carry, _tiles(src, valid, row_weight, tile_group))
    return (dx.astype(dt), d_up.astype(w_up.dtype),
            d_down.astype(w_down.dtype), d_wr.reshape(-1), None, None, None)


grouped_expert_mlp.defvjp(
    lambda *args: (grouped_expert_mlp(*args), args), _grouped_expert_mlp_bwd)


def held_pairs_layout(experts, *, first: int, n_held: int, tile: int):
    """experts (T, k), the chosen experts of every token over all routed
    experts -> where each pair whose expert is held (`first <= e < first +
    n_held`) lies in the buffer, at static shapes sized for every pair
    landing here: `src` (M,) the token of each row, `valid` (M,), `pos`
    (T, k) the row of each pair (M where its expert is absent), `tile_group`
    (M / tile,) the held expert that owns each tile (`n_held` for none),
    and the counts `load` (n_held,) and `absent` ()."""
    t, k = experts.shape
    n_pairs = t * k
    m = (-(-n_pairs // tile) + n_held) * tile
    local = experts.reshape(-1) - first
    group = jnp.where((local >= 0) & (local < n_held), local, n_held)
    # (a comparison and a sum, not a scatter-add of every pair into a few
    # bins: no collisions whose cost moves with the routing)
    counts = jnp.sum(group[:, None] == jnp.arange(n_held + 1), axis=0,
                     dtype=jnp.int32)
    load = counts[:n_held]
    padded = -(-load // tile) * tile
    ends = jnp.cumsum(padded)
    row0 = jnp.append(ends - padded, m)          # the absent pairs: no row
    order = jnp.argsort(group, stable=True)      # pairs by expert, absent last
    by_group = group[order]
    rank = jnp.arange(n_pairs, dtype=jnp.int32) - (
        jnp.cumsum(counts) - counts)[by_group]
    row = jnp.where(by_group < n_held, row0[by_group] + rank, m)
    pos = jnp.zeros((n_pairs,), jnp.int32).at[order].set(row).reshape(t, k)
    # a row no pair owns names a token of its own (and weighs nought): rows
    # that all named token 0 would collide in the scatter-adds, whose time
    # on the chip grows with collisions
    src = (jnp.arange(m, dtype=jnp.int32) % t).at[row].set(
        order // k, mode="drop")
    valid = jnp.zeros((m,), bool).at[row].set(True, mode="drop")
    tile_group = jnp.searchsorted(
        ends, jnp.arange(m // tile, dtype=jnp.int32) * tile,
        side="right").astype(jnp.int32)
    return src, valid, pos, tile_group, load, counts[n_held]


TILE = 512  # rows of one tile of the grouped product


def moe_held_ffn(x, wr, bias, w_up, w_down, shared_up, shared_down, *,
                 first: int, top_k: int, scale: float):
    """A chip's share of a sigmoid-routed expert layer on a flat batch.

    x (T, d) in the compute dtype; wr (d, E) and bias (E,) over all E
    routed experts; w_up (H, d, f), w_down (H, f, d) the H experts held
    here, which are experts `first .. first + H - 1`; the shared expert
    (d, fs), (fs, d). Every expert is relu(x W_up)^2 W_down with no gate
    and no bias. Returns (y (T, d), stats): y is the weighted sum over each
    token's chosen experts that are held, plus the shared expert; stats
    counts, as int32, the pairs routed to `held` and to `absent` experts,
    the pairs `dropped` (held pairs that found no row: 0 by construction),
    and each held expert's `load` (H,).
    """
    dt = x.dtype
    n_held = w_up.shape[0]
    # under shard_map the replicated matrices become device-varying before
    # the grouped product (a custom VJP), so that typed autodiff sums their
    # gradients over the mesh as it does for every other leaf
    w_up, w_down = (vary_like(w.astype(dt), x) for w in (w_up, w_down))
    with jax.named_scope("lm.moe.route"):
        experts, weights = sigmoid_topk_route(x, wr, bias, top_k=top_k,
                                              scale=scale)
        src, valid, pos, tile_group, load, absent = held_pairs_layout(
            experts, first=first, n_held=n_held, tile=TILE)
        # each row's weight, for the combine (differentiable in `weights`)
        row_weight = jnp.zeros(src.shape, jnp.float32).at[pos].set(
            weights, mode="drop")
    with jax.named_scope("lm.moe.experts"):
        y = grouped_expert_mlp(x, w_up, w_down, row_weight, src, valid,
                               tile_group).astype(dt)
    with jax.named_scope("lm.moe.shared"):
        y = y + relu2(x @ shared_up.astype(dt)) @ shared_down.astype(dt)
    held = load.sum()
    stats = {"held": held, "absent": absent,
             "dropped": held - valid.sum().astype(jnp.int32), "load": load}
    return y, stats


def swiglu(x, w_gate, w_up, w_down):
    """The gated MLP `(silu(x W_gate) * (x W_up)) W_down`."""
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def moe_held_gated_serve(x, wr, w_gate, w_up, w_down, shared, *, first: int,
                         top_k: int, scale: float, tile: int, valid=None,
                         layer=None, bias=None, sum_eps: float = 0.0):
    """A chip's share of a sigmoid-routed layer of GATED experts, forward
    only, in the layout serving wants: the work follows the pairs that
    arrived.

    x (T, d); wr (d, E) over all E routed experts; `bias` (E,) the selection
    bias of a router that has one (it selects only) and `sum_eps` what it
    adds to the chosen scores' sum (`sigmoid_topk_route`);
    w_gate / w_up (H, d, f) and w_down (H, f, d) the H experts held here
    (`first .. first + H - 1`), each `swiglu`; `shared` the shared expert's
    (gate, up, down), None for a layer without one (both chosen in Python: a
    program without them traces what it traced). `valid` (T,) bool marks the
    rows that are tokens (a bucket's spare rows route nowhere and are not
    counted). With `layer` (a
    scalar, traced under a layer scan) the held experts' matrices come
    stacked over layers, (L, H, d, f), and a tile reads `w[layer, expert]`
    where it lies: a layer's slice handed in through the scan is a COPY of
    all H experts' matrices a layer (1.5 GB at the served widths) before the
    loop reads six of them. Returns (y, stats) as `moe_held_ffn` does,
    `stats` with `multiplied` in `dropped`'s place.

    The pairs are sorted by held expert (`held_pairs_layout`) into rows of
    `tile`, each expert's rows starting on a tile boundary, so the tiles
    that own a pair are a prefix of the buffer: the loop runs over that
    prefix alone, on a traced bound. A decode tick's 32 tokens bring about
    16 held pairs to six or seven of 16 experts: with tiles of 16 rows it
    multiplies (and reads the matrices of) those experts and no others,
    where the training layout's 512-row tiles of the worst-case buffer
    would multiply 17 tiles for them. `multiplied` counts the rows of the
    tiles the loop ran, `held` the rows a pair owns."""
    dt, f32 = x.dtype, jnp.float32
    n_held = w_up.shape[-3]

    def of(w, g):
        return (w[g] if layer is None else w[layer, g]).astype(dt)

    with jax.named_scope("lm.moe.route"):
        experts, weights = sigmoid_topk_route(x, wr, bias, top_k=top_k,
                                              scale=scale, sum_eps=sum_eps)
        if valid is not None:   # a spare row's pairs: to no expert here
            experts = jnp.where(valid[:, None], experts, first - 1)
        src, ok, pos, tile_group, load, absent = held_pairs_layout(
            experts, first=first, n_held=n_held, tile=tile)
        if valid is not None:
            absent = absent - top_k * jnp.sum(~valid, dtype=jnp.int32)
        row_weight = jnp.zeros(src.shape, f32).at[pos].set(
            weights, mode="drop")
        n_tiles = jnp.sum(-(-load // tile))
    with jax.named_scope("lm.moe.experts"):
        def one(i, y):
            rows = jax.lax.dynamic_slice_in_dim(src, i * tile, tile)
            live = jax.lax.dynamic_slice_in_dim(ok, i * tile, tile)
            w = jax.lax.dynamic_slice_in_dim(row_weight, i * tile, tile)
            g = tile_group[i]
            xb = jnp.where(live[:, None], x[rows], 0)
            yb = swiglu(xb, of(w_gate, g), of(w_up, g), of(w_down, g))
            return y.at[rows].add(yb.astype(f32) * w[:, None])

        y = jax.lax.fori_loop(0, n_tiles, one, jnp.zeros(x.shape, f32))
    if shared is None:
        y = y.astype(dt)
    else:
        with jax.named_scope("lm.moe.shared"):
            y = y.astype(dt) + swiglu(x, *(w.astype(dt) for w in shared))
    return y, {"held": load.sum(), "absent": absent, "load": load,
               "multiplied": n_tiles * tile}
