"""Language-model training step over a DP x SP x TP device mesh.

The CNN engine (`train/engine.py`) covers the reference's batch-axis-only
scaling; this module is the multi-axis counterpart for the transformer
family (`models/transformer.py`): one compiled train step where

- tokens/targets are sharded (batch over `data`, sequence over `seq`),
- parameters are replicated over data/seq and tensor-sharded over `model`
  (per `transformer.param_specs`),
- attention runs ring or Ulysses sequence-parallel,
- gradient synchronization is *typed, not hand-written*: shard_map autodiff
  psums gradients of replicated params over data+seq automatically, while
  tensor-sharded params keep local gradients - the exact allreduce pattern
  Megatron implements by hand in NCCL.

The optimizer is the framework's SGD(momentum) (`ops/sgd.py`), applied
elementwise so it is layout-oblivious.
"""

from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import compat
from ..models import transformer as tfm
from ..ops.sgd import init_momentum, sgd_step
from ..parallel import zero
from ..parallel.collectives import vary_like

DATA_AXIS = "data"
SEQ_AXIS = "seq"
TP_AXIS = "model"


def create_lm_mesh(dp: int, sp: int, tp: int = 1) -> Mesh:
    """(dp, sp, tp) mesh over the first dp*sp*tp devices.

    Axis order puts `model` innermost: TP's psums per block are the
    highest-frequency collective, so they ride the fastest (most adjacent)
    ICI links; `data`'s once-per-step grad psum is outermost.
    """
    n = dp * sp * tp
    devices = jax.devices()
    if n > len(devices):
        raise ValueError(
            f"mesh {dp}x{sp}x{tp} needs {n} devices, have {len(devices)}"
        )
    arr = np.asarray(devices[:n]).reshape(dp, sp, tp)
    return Mesh(arr, (DATA_AXIS, SEQ_AXIS, TP_AXIS))


def _named_spec_leaves(specs):
    """[(path, spec)] over a spec pytree (rules-file diagnostics)."""
    from jax.sharding import PartitionSpec

    from ..parallel.rules import named_leaves

    return [
        (path, s)
        for path, s in named_leaves(
            specs, is_leaf=lambda s: isinstance(s, PartitionSpec)
        )
        if isinstance(s, PartitionSpec)
    ]


def _ep_axis(cfg, mesh: Mesh) -> str | None:
    """Experts shard over the data axis (GShard convention) when present."""
    dp = mesh.shape.get(DATA_AXIS, 1)
    if getattr(cfg, "n_experts", 0) and dp > 1:
        if cfg.n_experts % dp:
            raise ValueError(
                f"n_experts ({cfg.n_experts}) must be divisible by the data-"
                f"axis size ({dp}) for expert parallelism - use a multiple "
                f"of {dp} experts or a dp that divides {cfg.n_experts}"
            )
        return DATA_AXIS
    return None


def shard_params(params, cfg, mesh: Mesh, rules=None):
    """Place a replicated-layout param tree onto the mesh per param_specs
    (``rules`` overrides the built-in partition-rule table - the
    ``--sharding rules:<file>`` path, parallel/rules.py)."""
    tp = TP_AXIS if mesh.shape.get(TP_AXIS, 1) > 1 else None
    specs = cfg.module.param_specs(
        cfg, tp_axis=tp, ep_axis=_ep_axis(cfg, mesh), rules=rules
    )
    return jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)), params, specs
    ), specs


def _ce_sum_chunked(x, head, targets, n_chunks: int, axes=()):
    """Sum of next-token CE over all positions, computed in sequence chunks.

    x (B, S, d) pre-head hidden, head (d, V). Each chunk's logits
    ((B, S/n_chunks, V) f32) live only inside one checkpointed scan step: the
    forward never stores them (recomputed in backward), so peak HBM and
    residual traffic drop from O(B*S*V) to O(B*S*V/n_chunks). At vocab 32k,
    seq 2048, batch 16 that is the difference between 4.2 GB of stored f32
    logits (plus log_softmax residuals) and a ~260 MB working set - the
    single biggest single-chip LM throughput lever found in round 2.
    """
    b, s, d = x.shape
    cs = s // n_chunks
    xs = x.reshape(b, n_chunks, cs, d).swapaxes(0, 1)
    ts = targets.reshape(b, n_chunks, cs).swapaxes(0, 1)
    head = head.astype(x.dtype)

    @jax.checkpoint
    def chunk_ce(xc, tc):
        logits = (xc @ head).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, tc[..., None], axis=-1)[..., 0].sum()

    def body(acc, xt):
        return acc + chunk_ce(*xt), None

    # under shard_map the per-chunk CE is device-varying; the scan carry's
    # initial value must carry the same vma type
    init = vary_like(jnp.float32(0.0), extra=tuple(axes))
    total, _ = jax.lax.scan(body, init, (xs, ts))
    return total


def auto_loss_chunks(b: int, s: int, vocab: int) -> int:
    """Smallest chunk count dividing S that bounds one chunk's f32 logits
    ((b, s/c, vocab)) to ~64 MB; 1 when the single pass already fits."""
    budget = 64 * 2**20 // 4
    for c in range(1, s + 1):
        if s % c == 0 and b * (s // c) * vocab <= budget:
            return c
    return s


def lm_loss_and_aux(
    params,
    tokens,
    targets,
    cfg,
    *,
    seq_axis,
    tp_axis,
    attn_impl,
    axes,
    ep_axis=None,
    aux_weight: float = 0.01,
    loss_chunks: int = 0,
):
    """(loss, aux): mean next-token cross-entropy over the *global* token
    count, and what the model's `apply_hidden` hands back beside the hidden
    state, reduced over `axes` as the model's module declares it
    (`AUX_IS_LOSS`): a loss term (the MoE load-balancing aux) is averaged
    and, when cfg.n_experts, added to the loss at `aux_weight`; anything
    else (routing counts) is summed. `jax.value_and_grad(..., has_aux=True)`
    is how a step takes the pair.

    loss_chunks > 1 computes the CE in that many sequence chunks without
    ever materializing the full (B, S, vocab) logits tensor
    (`_ce_sum_chunked`); 0 auto-picks a chunking that bounds each chunk's
    logits to ~64 MB (1 = explicit single-pass)."""
    model = cfg.module
    x, aux = model.apply_hidden(
        params,
        tokens,
        cfg,
        seq_axis=seq_axis,
        tp_axis=tp_axis,
        ep_axis=ep_axis,
        attn_impl=attn_impl,
    )
    b, s_local = tokens.shape
    if loss_chunks == 0:
        loss_chunks = auto_loss_chunks(b, s_local, cfg.vocab_size)
    if loss_chunks > 1:
        local_sum = _ce_sum_chunked(
            x, params["head"], targets, loss_chunks, axes=axes
        )
    else:
        logits = (x @ params["head"].astype(cfg.dtype)).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        local_sum = -ll.sum()
    local_n = jnp.float32(b * s_local)
    if axes:
        total = jax.lax.psum(local_sum, axes)
        n = jax.lax.psum(local_n, axes)
        aux = (jax.lax.pmean if model.AUX_IS_LOSS else jax.lax.psum)(aux, axes)
    else:
        total, n = local_sum, local_n
    loss = total / n
    if model.AUX_IS_LOSS and cfg.n_experts:
        loss = loss + aux_weight * aux
    return loss, aux


def lm_loss(params, tokens, targets, cfg, **kw):
    """`lm_loss_and_aux`'s loss alone."""
    return lm_loss_and_aux(params, tokens, targets, cfg, **kw)[0]


OPTIMIZERS = ("sgd", "adam", "zero", "zero-adam")


def optimizer_state_specs(optimizer: str, specs):
    """PartitionSpec tree for the optimizer state matching
    `init_lm_momentum`'s structure: sgd mirrors the param specs; adam holds
    {"m", "v"} param-spec trees + a replicated counter; the zero variants
    shard every flat buffer over the data axis."""
    if optimizer == "sgd":
        return specs
    if optimizer == "adam":
        return {"m": specs, "v": specs, "t": P()}
    if optimizer == "zero":
        return jax.tree.map(lambda _: P(DATA_AXIS), specs)
    if optimizer == "zero-adam":
        shard = jax.tree.map(lambda _: P(DATA_AXIS), specs)
        return {"m": shard, "v": shard, "t": P()}
    raise ValueError(f"unknown optimizer {optimizer!r} (use one of {OPTIMIZERS})")


def init_lm_momentum(params, mesh: Mesh, optimizer: str = "sgd"):
    """Optimizer-state init matching `make_lm_train_step(optimizer=...)`:
    'sgd'/'adam' -> zero trees built with zeros_like, so each state leaf
    inherits its param's placement (replicated or tensor-sharded); adam
    adds the second moment and a step counter. 'zero'/'zero-adam' ->
    per-leaf flat ZeRO-1 buffers sharded over the data axis (each device
    holds 1/dp of every leaf; parallel/zero.py)."""
    from ..ops.adam import init_adam

    dp = mesh.shape.get(DATA_AXIS, 1)
    if optimizer == "sgd":
        return init_momentum(params)
    if optimizer == "adam":
        return init_adam(params)
    if optimizer == "zero":
        return jax.device_put(
            zero.init_zero_momentum_tree(params, dp),
            NamedSharding(mesh, P(DATA_AXIS)),
        )
    if optimizer == "zero-adam":
        state = zero.init_zero_adam_tree(params, dp)
        shard = jax.tree.map(
            lambda _: NamedSharding(mesh, P(DATA_AXIS)), state["m"]
        )
        return jax.device_put(
            state,
            {"m": shard, "v": shard, "t": NamedSharding(mesh, P())},
        )
    raise ValueError(f"unknown optimizer {optimizer!r} (use one of {OPTIMIZERS})")


def lm_wiring(cfg, mesh: Mesh, optimizer: str = "sgd", rules=None):
    """(sp, tp, ep, sync_axes, specs, mom_spec, data_spec) for a dp x sp x
    tp mesh - the single source of the axis/spec derivation shared by
    `make_lm_train_step`, `lm_step_program`, and the static analyzer
    (analysis/). Param specs derive from the declarative partition-rule
    table (parallel/rules.py `lm_partition_rules` via
    `transformer.param_specs`; ``rules`` substitutes a custom ordered
    rule list - the ``--sharding rules:<file>`` path). Validates every
    spec against the mesh's axes up front (parallel/partition.py), so a
    bad axis name fails here with the leaf and the available axes instead
    of deep inside pjit lowering."""
    sp = SEQ_AXIS if mesh.shape.get(SEQ_AXIS, 1) > 1 else None
    tp = TP_AXIS if mesh.shape.get(TP_AXIS, 1) > 1 else None
    ep = _ep_axis(cfg, mesh)
    sync_axes = tuple(a for a in (DATA_AXIS, SEQ_AXIS) if a in mesh.axis_names)
    cfg.module.refuse_axes(seq_axis=sp, tp_axis=tp, ep_axis=ep)
    specs = cfg.module.param_specs(cfg, tp_axis=tp, ep_axis=ep, rules=rules)
    data_spec = P(DATA_AXIS, SEQ_AXIS)
    if optimizer not in OPTIMIZERS:
        raise ValueError(
            f"unknown optimizer {optimizer!r} (use one of {OPTIMIZERS})"
        )
    if optimizer.startswith("zero") and (tp or ep):
        raise ValueError(
            f"optimizer={optimizer!r} shards the flat param vector over the "
            "data axis, which requires params replicated across the mesh - "
            f"not compatible with tp_axis={tp!r} / ep_axis={ep!r}; use "
            "'sgd'/'adam' for tensor/expert-sharded configs"
        )
    if rules is not None and optimizer.startswith("zero"):
        sharded = [
            (path, s) for path, s in _named_spec_leaves(specs)
            if any(e is not None for e in tuple(s))
        ]
        if sharded:
            raise ValueError(
                f"optimizer={optimizer!r} requires fully replicated param "
                "specs (the flat ZeRO buffers shard over the data axis), "
                f"but the rules file shards {sharded[0][0]!r} as "
                f"{sharded[0][1]} ({len(sharded)} sharded leaf/leaves "
                "total) - use 'sgd'/'adam' with sharded rules"
            )
    mom_spec = optimizer_state_specs(optimizer, specs)
    from ..parallel.partition import validate_spec_tree

    mesh_axes = dict(mesh.shape)
    validate_spec_tree(specs, mesh_axes, root="params")
    validate_spec_tree(mom_spec, mesh_axes, root="optimizer state")
    validate_spec_tree(data_spec, mesh_axes, root="tokens")
    return sp, tp, ep, sync_axes, specs, mom_spec, data_spec


def make_lm_shardings(cfg, mesh: Mesh, optimizer: str = "sgd", rules=None):
    """(specs, param_shardings, mom_shardings) for one mesh/optimizer -
    the placement triple the elastic driver (train/elastic.py) rebuilds
    whenever the mesh changes under a run (shrink/grow resume), derived
    from the same `lm_wiring` the compiled step uses so the restored
    leaves land exactly where the step expects them."""
    specs = lm_wiring(cfg, mesh, optimizer, rules=rules)[4]
    param_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs
    )
    mom_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        optimizer_state_specs(optimizer, specs),
    )
    return specs, param_shardings, mom_shardings


def make_lm_train_step(
    cfg,
    mesh: Mesh,
    *,
    lr: float = 0.1,
    momentum: float = 0.9,
    attn_impl: str = "ring",
    optimizer: str = "sgd",
    loss_chunks: int = 0,
    lr_schedule=None,
    clip_norm: float = 0.0,
    accum_steps: int = 1,
    weight_decay: float = 0.0,
    grad_sync: str = "end",
    bucket_mb: float = 4.0,
    with_health: bool = False,
    skip_nonfinite: bool = False,
    fault_plan=None,
    rules=None,
    dynamics: bool = False,
):
    """Compiled (params, mom, tokens, targets) -> (params, mom, loss).

    tokens/targets: (B, S) int32, B divisible by dp, S by sp. Loss returns
    replicated. The step is donate-safe on params/mom. optimizer='zero'
    shards the momentum buffer over the data axis (ZeRO-1,
    parallel/zero.py); init mom with `init_lm_momentum`. loss_chunks is
    passed through to `lm_loss` (0 = auto-chunk by the 64 MB logits budget).

    Loop transforms (ops/schedule.py):
    - lr_schedule: callable step -> lr (e.g. partial(warmup_cosine, ...)).
      When set, the compiled fn takes a fifth argument
      (params, mom, tokens, targets, step) with `step` a traced int32, so
      the schedule costs no recompile per step.
    - clip_norm > 0: clip gradients by sharding-aware global norm before
      the optimizer (identical scale factor on every device, including
      tensor-sharded leaves).
    - accum_steps = k > 1: each call scans k sequential fwd/bwd passes
      over B/k-row micro-batches and averages the gradients - k-times
      the effective batch in the same activation memory. B must be
      divisible by dp * k.
    - weight_decay > 0: decoupled (AdamW-style) decay for every
      optimizer - Adam applies it inside adam_leaf_update; SGD applies
      p -= lr_t * wd * p after the momentum update (never folded into
      the gradient, so momentum stays decay-free).
    - grad_sync: WHEN the cross-device gradient reduction happens under
      accumulation. "end" (default) is the existing schedule - typed
      autodiff's psums after each backward, the accumulator carrying the
      full gradient tree. "overlap" moves the collective INSIDE the
      accumulation scan (ops/schedule.py accumulate_fwd_bwd_overlap):
      gradients are taken w.r.t. device-varying params (local, no
      implicit psum) and each microbatch issues one explicit collective
      per size-capped leaf bucket (parallel/collectives.py, cap
      bucket_mb MiB, leaves grouped by PartitionSpec) so XLA's
      latency-hiding scheduler can run bucket j's collective under
      microbatch i+1's backward. For 'zero'/'zero-adam' the per-bucket
      collective is a reduce-scatter and the scan carry holds only this
      device's 1/dp shard - O(D/dp) accumulator instead of O(D) - with
      one invariant-typed bucket all-gather after the scan feeding the
      unchanged per-leaf optimizer. Matches "end" up to float
      reassociation; at accum_steps=1 there is nothing to overlap and
      the end schedule runs (bitwise identical). Not compatible with
      expert parallelism (expert leaves vary over exactly the data axis
      the overlap psum reduces over).

    Guard hooks (train/guard.py; all default-off, and the default-off
    program is the UNCHANGED one - bitwise identical step):
    - with_health: the step additionally returns a replicated health
      bundle {loss, grad_norm, all_finite} (ops/schedule.py
      health_bundle). The grad norm is the one clip_by_global_norm
      already computes when clip_norm > 0; otherwise one sharding-aware
      global_norm is added. The finite flag derives from the two scalars
      - no extra pass over the parameters.
    - skip_nonfinite: gate the whole update (params AND optimizer state,
      including Adam's t) on the finite flag inside the compiled step
      (ops/sgd.py guarded_sgd_step / ops/adam.py guarded_adam_step): a
      NaN'd gradient costs one wasted fwd/bwd, corrupts nothing, and
      never leaves the device. Implies the health output.
    - fault_plan (parallel/fault.py StepFaultPlan): compile chaos
      injection (NaN grads / loss spike at chosen steps) into the step
      for tests and the bench chaos row. Requires the step-index
      argument: the compiled fn takes (params, mom, tokens, targets,
      step) whenever a fault_plan is given, as with lr_schedule.
    - rules: a custom ordered (regex, PartitionSpec) partition-rule list
      replacing the built-in table (parallel/rules.py; the
      ``--sharding rules:<file>`` path). Every param leaf must match;
      zero optimizers additionally require the matched specs to be
      fully replicated.
    - dynamics: the step additionally returns a training-dynamics bundle
      as its LAST output (train/dynamics.py dynamics_bundle): per-leaf
      squared grad/param/update norms (mesh-reduced f32 scalars), the
      first-non-finite-leaf index for provenance, and - when
      grad_sync='end' with accum_steps >= 2 - the mean per-microbatch
      squared grad norm feeding the gradient-noise-scale estimator.
      Default-off leaves the compiled program unchanged.

    A model whose module declares `AUX_IS_LOSS = False`
    (`models/nemotron_h.py`) returns one output more, after every other:
    what its `apply_hidden` hands back beside the hidden state, summed over
    the mesh (there the expert layers' routing counts, int32: `held`,
    `absent`, `dropped` (layers,), `load` (layers, experts held)).
    Accumulation and the ZeRO optimizers, which would have to carry it
    through their own loops, are refused for such a model by name.
    """
    sp, tp, ep, sync_axes, specs, mom_spec, data_spec = lm_wiring(
        cfg, mesh, optimizer, rules=rules
    )
    aux_out = not cfg.module.AUX_IS_LOSS  # counts, handed out with the step
    if aux_out and (accum_steps > 1 or optimizer.startswith("zero")):
        raise ValueError(
            f"{cfg.module.NAME}: accum_steps={accum_steps} / optimizer="
            f"{optimizer!r} is not supported - the step hands the model's "
            "counts straight through; use accum_steps=1 with 'sgd' or 'adam'"
        )

    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    from ..ops.schedule import GRAD_SYNCS

    if grad_sync not in GRAD_SYNCS:
        raise ValueError(
            f"unknown grad_sync {grad_sync!r} (use one of {GRAD_SYNCS})"
        )
    if grad_sync == "overlap" and ep:
        raise ValueError(
            "grad_sync='overlap' psums every gradient bucket over the "
            "data axis, but expert-sharded leaves VARY over that axis "
            f"(ep_axis={ep!r}) - their gradients must stay local; use "
            "grad_sync='end' with expert parallelism"
        )

    def fwd_bwd_one(params, tokens, targets):
        (loss, aux), grads = jax.value_and_grad(lm_loss_and_aux, has_aux=True)(
            params,
            tokens,
            targets,
            cfg,
            seq_axis=sp,
            tp_axis=tp,
            ep_axis=ep,
            attn_impl=attn_impl,
            axes=sync_axes,
            loss_chunks=loss_chunks,
        )
        return ((loss, aux) if aux_out else loss), grads

    from ..ops.schedule import accumulate_fwd_bwd

    if grad_sync == "overlap" and accum_steps > 1:
        from ..ops.schedule import accumulate_fwd_bwd_overlap
        from ..parallel.collectives import (
            pack_buckets,
            plan_buckets,
            unpack_buckets,
        )

        bucket_bytes = max(int(bucket_mb * 2**20), 1)
        # leaves grouped by PartitionSpec: tensor-sharded leaves (whose
        # grads stay varying over 'model') never share a buffer with
        # replicated ones - each bucket has one vma type and one layout
        spec_keys = [
            str(s)
            for s in jax.tree.leaves(
                specs,
                is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec),
            )
        ]
        dp_size = mesh.shape.get(DATA_AXIS, 1)

        def fwd_bwd(params, tokens, targets):
            layout = plan_buckets(
                params, bucket_bytes=bucket_bytes, group_keys=spec_keys
            )
            # differentiate w.r.t. ALREADY-varied params: the implicit
            # typed-autodiff psum is suppressed and each microbatch's
            # grads are this device's local contribution - the explicit
            # per-bucket collective below is the only sync
            params_v = jax.tree.map(
                lambda p: vary_like(p, extra=sync_axes), params
            )
            if optimizer.startswith("zero"):
                reduce_fn, finalize_fn = zero.make_overlap_grad_reducers(
                    layout, DATA_AXIS, dp_size,
                    extra_axes=tuple(
                        a for a in sync_axes if a != DATA_AXIS
                    ),
                )
            else:
                def reduce_fn(grads):
                    return tuple(
                        jax.lax.psum(b, sync_axes)
                        for b in pack_buckets(layout, grads)
                    )

                def finalize_fn(bufs):
                    return unpack_buckets(layout, list(bufs))

            inner = accumulate_fwd_bwd_overlap(
                lambda _p, tok, tgt: fwd_bwd_one(params_v, tok, tgt),
                accum_steps, reduce_fn=reduce_fn, finalize_fn=finalize_fn,
            )
            return inner(params, tokens, targets)
    else:
        all_axes_early = tuple(mesh.axis_names)
        # GNS needs the per-microbatch grad norms the end-schedule scan
        # already synchronizes (typed autodiff psums after each backward);
        # the overlap schedule's in-scan grads are local pre-reduction
        # partials, so the estimator stays off there
        want_gns = dynamics and accum_steps >= 2

        sq_norm_fn = None
        if want_gns:
            from ..ops.schedule import per_leaf_sq_norms

            def sq_norm_fn(g):
                return sum(
                    jax.tree.leaves(
                        per_leaf_sq_norms(
                            g, specs=specs, axes=all_axes_early
                        )
                    )
                )

        fwd_bwd = accumulate_fwd_bwd(
            fwd_bwd_one, accum_steps, sq_norm_fn=sq_norm_fn
        )

    want_gns = (
        dynamics and grad_sync == "end" and accum_steps >= 2
    )
    if fault_plan is not None and not fault_plan:
        fault_plan = None  # empty plan compiles nothing
    want_health = with_health or skip_nonfinite
    all_axes = tuple(mesh.axis_names)

    def step(params, mom, tokens, targets, step_i=None):
        msq_small = None
        if want_gns:
            loss, grads, msq_small = fwd_bwd(params, tokens, targets)
        else:
            loss, grads = fwd_bwd(params, tokens, targets)
        if aux_out:
            loss, aux = loss
        if fault_plan is not None:
            from ..parallel.fault import inject_step_faults

            loss, grads = inject_step_faults(step_i, loss, grads, fault_plan)
        dyn = None
        if dynamics:
            # pre-clip gradients: the noise-scale estimator compares
            # against the (unclipped) per-microbatch norms, and the
            # provenance scalars must see the anomaly clipping rescales
            from .dynamics import dynamics_bundle

            dyn = dynamics_bundle(grads, params, specs=specs, axes=all_axes)
            if want_gns:
                dyn["msq_small"] = msq_small
            params_before = params
        norm = None
        if clip_norm > 0.0:
            from ..ops.schedule import clip_by_global_norm

            # pre-clip norm: the health signal must see the anomaly the
            # clip is about to rescale (clipping a NaN tree yields NaN
            # anyway - the flag still drops)
            grads, norm = clip_by_global_norm(
                grads, clip_norm, specs=specs, axes=all_axes,
            )
        elif want_health:
            from ..ops.schedule import global_norm

            norm = global_norm(grads, specs=specs, axes=all_axes)
        health = None
        if want_health:
            from ..ops.schedule import health_bundle

            health = health_bundle(loss, norm)
        lr_t = lr if lr_schedule is None else lr_schedule(step_i)
        if optimizer == "adam":
            # momentum doubles as Adam's b1 (its momentum analog), so the
            # CLI --momentum flag takes effect for every optimizer
            if skip_nonfinite:
                from ..ops.adam import guarded_adam_step

                params, mom = guarded_adam_step(
                    params, mom, grads, lr_t, ok=health["all_finite"],
                    b1=momentum, weight_decay=weight_decay,
                )
            else:
                from ..ops.adam import adam_step

                params, mom = adam_step(
                    params, mom, grads, lr_t, b1=momentum,
                    weight_decay=weight_decay,
                )
        elif skip_nonfinite:
            from ..ops.sgd import guarded_sgd_step

            params, mom = guarded_sgd_step(
                params, mom, grads, lr_t, momentum,
                ok=health["all_finite"], weight_decay=weight_decay,
            )
        else:
            params, mom = sgd_step(params, mom, grads, lr_t, momentum)
            from ..ops.schedule import apply_decoupled_weight_decay

            params = apply_decoupled_weight_decay(params, lr_t, weight_decay)
        if dynamics:
            from ..ops.schedule import per_leaf_sq_norms

            upd = jax.tree.map(
                lambda n, p: n.astype(jnp.float32) - p.astype(jnp.float32),
                params,
                params_before,
            )
            dyn["upd_sq"] = per_leaf_sq_norms(
                upd, specs=specs, axes=all_axes
            )
        out = (params, mom, loss)
        if want_health:
            out = out + (health,)
        if dynamics:
            out = out + (dyn,)
        if aux_out:
            out = out + (aux,)
        return out

    # attn='flash' composes with dp x tp meshes since round 4: the own
    # Pallas kernels (ops/flash_pallas.py) stamp vma-typed outputs, so the
    # shard_map checker accepts them and autodiff inserts the right psums
    # (attention is purely local when only batch/head axes are sharded).
    # A sequence axis still needs ring/ulysses/zigzag - flash is the
    # per-device kernel. The LIBRARY kernel (DNN_TPU_FLASH_IMPL=lib) is
    # not vma-typed and stays single-device-only.
    check_vma = True
    if attn_impl == "flash":
        if sp is not None:
            raise ValueError(
                "attn_impl 'flash' is the local (per-device) kernel; with "
                "a sequence axis use 'ring'/'ulysses'/'zigzag' (flash "
                "composes with dp/tp meshes, not sp)"
            )
        if os.environ.get("DNN_TPU_FLASH_IMPL") == "lib":
            if any(mesh.shape[a] > 1 for a in mesh.axis_names):
                raise ValueError(
                    "DNN_TPU_FLASH_IMPL=lib selects the library flash "
                    "kernel, which carries no vma typing and cannot run "
                    "on a non-trivial mesh; unset it (own kernel) or use "
                    "a single-device mesh"
                )
            # jax 0.9 rejects ANY untyped pallas_call output under
            # check_vma=True, even on an all-ones mesh - where disabling
            # the check is vacuous (no cross-device gradients exist)
            check_vma = False

    # fault injection fires on a step index, so a fault_plan forces the
    # step-taking signature even under a constant lr
    has_step = lr_schedule is not None or fault_plan is not None
    if optimizer.startswith("zero"):
        # Shared two-shard_map ZeRO-1 orchestration (parallel/zero.py
        # make_zero_split_step; the pipeline path uses the same helper).
        # zero forbids tp/ep, so every grad leaf here is the full
        # replicated gradient: the plain (no-psum) norm is global.
        clip_fn = None
        if clip_norm > 0.0:
            from ..ops.schedule import clip_by_global_norm

            def clip_fn(grads):
                return clip_by_global_norm(grads, clip_norm)[0]

        return zero.make_zero_split_step(
            mesh=mesh, fwd_bwd=fwd_bwd, specs=specs, mom_spec=mom_spec,
            data_spec=data_spec, optimizer=optimizer, lr=lr,
            momentum=momentum, weight_decay=weight_decay,
            lr_schedule=lr_schedule, clip_fn=clip_fn, axis_name=DATA_AXIS,
            check_vma=check_vma, with_health=with_health,
            skip_nonfinite=skip_nonfinite, fault_plan=fault_plan,
            dynamics=dynamics, gns=want_gns,
        )

    out_specs = (specs, mom_spec, P()) + ((P(),) if want_health else ())
    if dynamics:
        from .dynamics import dynamics_out_specs

        out_specs = out_specs + (
            dynamics_out_specs(specs, with_upd=True, with_gns=want_gns),
        )
    if aux_out:
        out_specs = out_specs + (P(),)
    if has_step:
        return jax.jit(
            compat.shard_map(
                step,
                mesh=mesh,
                in_specs=(specs, mom_spec, data_spec, data_spec, P()),
                out_specs=out_specs,
                check_vma=check_vma,
            ),
            donate_argnums=(0, 1),
        )
    return jax.jit(
        compat.shard_map(
            lambda p, m, a, b: step(p, m, a, b),
            mesh=mesh,
            in_specs=(specs, mom_spec, data_spec, data_spec),
            out_specs=out_specs,
            check_vma=check_vma,
        ),
        donate_argnums=(0, 1),
    )


def abstract_lm_state(cfg, mesh: Mesh, optimizer: str = "sgd"):
    """(params, mom) as ShapeDtypeStruct pytrees - the step's state
    signature without allocating anything (jax.eval_shape over the real
    init functions, so analysis can never drift from training)."""
    params = jax.eval_shape(
        lambda k: cfg.module.init_params(k, cfg),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    )
    dp = mesh.shape.get(DATA_AXIS, 1)
    if optimizer == "sgd":
        mom = params
    elif optimizer == "adam":
        mom = {
            "m": params, "v": params,
            "t": jax.ShapeDtypeStruct((), jnp.int32),
        }
    elif optimizer == "zero":
        mom = jax.eval_shape(
            lambda p: zero.init_zero_momentum_tree(p, dp), params
        )
    elif optimizer == "zero-adam":
        mom = jax.eval_shape(
            lambda p: zero.init_zero_adam_tree(p, dp), params
        )
    else:
        raise ValueError(
            f"unknown optimizer {optimizer!r} (use one of {OPTIMIZERS})"
        )
    return params, mom


def lm_step_program(
    cfg,
    mesh: Mesh,
    *,
    batch: int,
    seq_len: int,
    name: str = "lm",
    optimizer: str = "sgd",
    **step_kwargs,
):
    """`make_lm_train_step` packaged as a traceable `StepProgram`
    (train/program.py) for the static analyzer: the compiled step, its
    abstract (ShapeDtypeStruct) arguments, the spec trees, and the
    donation contract."""
    from .program import StepProgram

    step = make_lm_train_step(
        cfg, mesh, optimizer=optimizer, **step_kwargs
    )
    _, tp, ep, sync_axes, specs, mom_spec, data_spec = lm_wiring(
        cfg, mesh, optimizer, rules=step_kwargs.get("rules")
    )
    params, mom = abstract_lm_state(cfg, mesh, optimizer)
    tok = jax.ShapeDtypeStruct((batch, seq_len), jnp.int32)
    has_step = (
        step_kwargs.get("lr_schedule") is not None
        or step_kwargs.get("fault_plan") is not None
    )
    args = (params, mom, tok, tok) + (
        (jax.ShapeDtypeStruct((), jnp.int32),) if has_step else ()
    )
    return StepProgram(
        name=name,
        fn=step,
        mesh=mesh,
        abstract_args=args,
        specs={"params": specs, "opt": mom_spec, "data": data_spec},
        donate=(0, 1),
        donate_labels=("params", "optimizer state"),
        meta={
            "family": "lm",
            "optimizer": optimizer,
            "grad_sync": step_kwargs.get("grad_sync", "end"),
            "accum_steps": int(step_kwargs.get("accum_steps", 1)),
            "mesh": {k: int(v) for k, v in mesh.shape.items()},
            "dp": int(mesh.shape.get(DATA_AXIS, 1)),
            "tp_axis": tp,
            "ep_axis": ep,
            "sync_axes": list(sync_axes),
            "batch": batch,
            "seq_len": seq_len,
            # declares the low-precision contract to the shardlint
            # quantized-dtype lint: int8/fp8 values are legal in a trace
            # ONLY where this is set, and a declared-quantized step whose
            # trace shows none fails (the quantized path silently fell
            # back) - analysis/lint.py quantized_dtype_lint
            "quant": getattr(cfg, "attn_quant", "") or None,
        },
    )


def make_traced_step(
    step_fn,
    *,
    tracer,
    step_stats=None,
    items_per_step: float = 0.0,
    fence: bool = True,
    first_step: int = 0,
    compile_first: bool = True,
    registry=None,
    recompiles=None,
    ledger=None,
):
    """Wrap a compiled LM train step with span tracing + StepStats.

    Each call opens a ``train_step`` span (utils/tracing.py) and, when
    ``step_stats`` is given, records the step's wall time (first call =
    the compile step). ``fence=True`` hard-blocks the returned loss before
    the span closes so durations are device time, not dispatch time - the
    observer effect is that the host cannot run ahead of the device
    (`block_until_ready` per step; utils/timers.py hard_block). Pass
    ``fence=False`` to keep fully async dispatch; spans then measure
    dispatch only and carry ``fenced: false``.

    The wrapper is transparent: same signature and return as ``step_fn``
    (the trailing output - the loss, or the health bundle on guarded
    steps (with_health=True) - is what the fence blocks on; either way
    it data-depends on the whole step, matching every step builder in
    this module / parallel/pipeline.py).
    ``compile_first=False`` marks every record steady-state - for callers
    that already absorbed compilation in their own warm-up.

    ``registry`` (utils/obs.py MetricsRegistry; None = off) adds the live
    publishing layer: a liveness heartbeat + ``train_steps_total`` +
    ``train_step_seconds`` histogram + throughput gauge per step, with
    readiness flipped after the first completed (compiled) call.
    ``recompiles`` (train/monitor.py RecompileDetector) is observed once
    per call - one ``_cache_size()`` read - to count silent recompiles.
    ``ledger`` (utils/goodput.py GoodputLedger; None = the process
    ledger, a no-op while disarmed) receives each step's wall time as a
    compile/steady_step/rollback_recompute interval - the goodput
    accounting's compile-vs-steady feed.
    """
    import itertools

    from ..utils import goodput as _goodput
    from ..utils import tracing as _tracing
    from ..utils.obs import NULL_REGISTRY
    from ..utils.timers import hard_block

    counter = itertools.count(first_step)
    reg = registry if registry is not None else NULL_REGISTRY
    led = ledger if ledger is not None else _goodput.LEDGER
    m_steps = reg.counter(
        "train_steps_total", "Completed training steps"
    )
    m_wall = reg.histogram(
        "train_step_seconds", "Fenced wall time per training step"
    )
    m_thr = reg.gauge(
        "train_throughput_items_per_s",
        "Per-step training throughput (tokens/s for the LM paths)",
    )

    def traced_step(*args, **kwargs):
        i = next(counter)
        # begin-mark BEFORE the dispatch: the begin/beat pair is what
        # lets the fleet federation attribute a host-side wedge to the
        # rank that never STARTED the next step, even though every
        # rank's completion is held back equally by the collectives
        # (utils/obs.py begin_step; train/supervisor.py FleetFederation)
        reg.begin_step(i)
        t0 = time.perf_counter()
        with tracer.span(
            _tracing.TRAIN_STEP, track="train", step=i, fenced=fence
        ):
            out = step_fn(*args, **kwargs)
            if fence:
                hard_block(out[-1] if isinstance(out, tuple) else out)
        dt = time.perf_counter() - t0
        if step_stats is not None:
            step_stats.record(
                i, dt, items=items_per_step,
                is_compile=None if compile_first else False,
            )
        led.step_span(
            i, dt, tokens=items_per_step,
            is_compile=None if compile_first else False,
        )
        reg.beat(i)
        m_steps.inc()
        m_wall.observe(dt)
        reg.mark_ready()
        if items_per_step and dt > 0 and reg.ready and i != first_step:
            m_thr.set(items_per_step / dt)
        if recompiles is not None:
            recompiles.observe(i)
        return out

    return traced_step


class RoutingCounters:
    """Publishes the expert layers' routing counts that a `NemotronHConfig`
    step returns as its last output, one step behind the dispatch (reading
    a step's counts waits for that step, so the one just dispatched is kept
    and the one before it read): `lm_moe_pairs_total{where=held|absent}`,
    `lm_moe_dropped_total` (always 0: no pair is ever dropped) and, a
    layer, `lm_moe_expert_load_max_over_mean{layer}`, the fullest held
    expert's tokens over the mean held expert's in the last step read."""

    def __init__(self, registry):
        pairs = registry.counter(
            "lm_moe_pairs_total",
            "(token, chosen expert) pairs routed to experts this chip "
            "holds (held) and to experts of other chips (absent)")
        self._held = pairs.labels(where="held")
        self._absent = pairs.labels(where="absent")
        self._dropped = registry.counter(
            "lm_moe_dropped_total",
            "Held pairs that found no row in the pair buffer (always 0)")
        self._skew = registry.gauge(
            "lm_moe_expert_load_max_over_mean",
            "Tokens of the fullest held expert over the mean held "
            "expert's, per expert layer, in the last step read")
        self._pending = None

    def push(self, routing) -> None:
        pending, self._pending = self._pending, routing
        if pending is not None:
            self._publish(pending)

    def flush(self) -> None:
        self.push(None)

    def _publish(self, routing) -> None:
        r = jax.device_get(routing)
        self._held.inc(float(r["held"].sum()))
        self._absent.inc(float(r["absent"].sum()))
        self._dropped.inc(float(r["dropped"].sum()))
        for layer, load in enumerate(r["load"]):
            mean = float(load.mean())
            self._skew.labels(layer=str(layer)).set(
                float(load.max()) / mean if mean else 0.0)


def make_copy_task(key, *, batch, seq_len, vocab):
    """Tiny synthetic LM task: the second half of each sequence repeats the
    first half, so a causal model can learn it quickly - used for
    convergence tests without any dataset. Targets are the wrap-shifted
    sequence (full seq_len, so any mesh factorization divides evenly); the
    final position's wrapped target is consistent noise."""
    half = (seq_len + 1) // 2
    first = jax.random.randint(key, (batch, half), 2, vocab)
    seq = jnp.concatenate([first, first], axis=1)[:, :seq_len]
    targets = jnp.roll(seq, -1, axis=1)
    return seq.astype(jnp.int32), targets.astype(jnp.int32)
