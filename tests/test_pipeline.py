"""Pipeline parallelism (parallel/pipeline.py) on the 8-device CPU mesh.

Correctness bars:
- the GPipe microbatch schedule over a 4-stage pipe axis computes exactly
  the single-device LM loss (same params, same tokens) - bubbles, rotation,
  and masking are invisible in the result;
- gradients through the schedule match single-device gradients (embed/head
  via cross-stage psum, stage-local layer grads compared per shard);
- a dp2 x pp2 x tp2 mesh (all three axes non-trivial) trains the copy task.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_neural_network_tpu.models import transformer as tfm
from distributed_neural_network_tpu.ops.flash_pallas import block_remat_policy
from distributed_neural_network_tpu.parallel import pipeline as pp
from distributed_neural_network_tpu.train import lm as lmtrain

CFG = tfm.TransformerConfig(
    vocab_size=32, d_model=32, n_heads=4, n_layers=4, d_ff=64
)
# interleaved-schedule tests need pp * v = 8 | n_layers
CFG8 = tfm.TransformerConfig(
    vocab_size=32, d_model=32, n_heads=4, n_layers=8, d_ff=64
)


def _data(batch=8, seq=16, seed=0):
    k = jax.random.key(seed)
    return lmtrain.make_copy_task(k, batch=batch, seq_len=seq, vocab=CFG.vocab_size)


def _single_device_loss(params, tokens, targets):
    return lmtrain.lm_loss(
        params, tokens, targets, CFG,
        seq_axis=None, tp_axis=None, attn_impl="full", axes=(),
    )


def _pp_loss_fn(mesh, n_microbatches):
    tp = pp.TP_AXIS if mesh.shape.get(pp.TP_AXIS, 1) > 1 else None
    sync = tuple(a for a in (pp.DATA_AXIS,) if a in mesh.axis_names)
    specs = pp.pp_param_specs(CFG, tp_axis=tp)
    return jax.jit(
        jax.shard_map(
            lambda p, tok, tgt: pp.pipeline_lm_loss(
                p, tok, tgt, CFG,
                n_microbatches=n_microbatches, tp_axis=tp, sync_axes=sync,
            ),
            mesh=mesh,
            in_specs=(specs, P(pp.DATA_AXIS), P(pp.DATA_AXIS)),
            out_specs=P(),
        )
    )


@pytest.mark.parametrize("n_microbatches", [1, 2, 4])
def test_pipeline_loss_matches_single_device(n_devices, n_microbatches):
    mesh = pp.create_pp_mesh(1, 4, 1)
    params = tfm.init_params(jax.random.key(0), CFG)
    tokens, targets = _data()
    want = float(_single_device_loss(params, tokens, targets))
    sharded, _ = pp.shard_pp_params(params, CFG, mesh)
    got = float(_pp_loss_fn(mesh, n_microbatches)(sharded, tokens, targets))
    assert np.isclose(got, want, rtol=2e-5), (got, want)


@pytest.mark.slow
def test_pipeline_grads_match_single_device(n_devices):
    mesh = pp.create_pp_mesh(1, 4, 1)
    params = tfm.init_params(jax.random.key(1), CFG)
    tokens, targets = _data(seed=2)
    g_ref = jax.grad(_single_device_loss)(params, tokens, targets)

    tp = None
    specs = pp.pp_param_specs(CFG, tp_axis=tp)
    g_pp = jax.jit(
        jax.shard_map(
            lambda p, tok, tgt: jax.grad(pp.pipeline_lm_loss)(
                p, tok, tgt, CFG,
                n_microbatches=2, tp_axis=tp, sync_axes=(pp.DATA_AXIS,),
            ),
            mesh=mesh,
            in_specs=(specs, P(pp.DATA_AXIS), P(pp.DATA_AXIS)),
            out_specs=specs,
        )
    )(*pp.shard_pp_params(params, CFG, mesh)[0:1], tokens, targets)

    for path, want in [
        (("embed",), g_ref["embed"]),
        (("head",), g_ref["head"]),
        (("layers", "wq"), g_ref["layers"]["wq"]),
        (("layers", "b1"), g_ref["layers"]["b1"]),
    ]:
        got = g_pp
        for k in path:
            got = got[k]
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=5e-4, atol=1e-5
        )


@pytest.mark.slow
def test_pp_train_step_learns_dp_pp_tp(n_devices):
    """dp2 x pp2 x tp2: all three parallelism axes at once; loss falls."""
    mesh = pp.create_pp_mesh(2, 2, 2)
    params = tfm.init_params(jax.random.key(0), CFG)
    params, _ = pp.shard_pp_params(params, CFG, mesh)
    mom = jax.tree.map(jnp.zeros_like, params)
    step = pp.make_pp_train_step(CFG, mesh, n_microbatches=2, lr=0.3, momentum=0.9)
    tokens, targets = _data(batch=16, seq=16, seed=3)
    losses = []
    for _ in range(30):
        params, mom, loss = step(params, mom, tokens, targets)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] - 0.5, losses[:: len(losses) - 1]


@pytest.mark.parametrize("interleave,n_microbatches", [(2, 4), (2, 8), (1, 4)])
def test_interleaved_loss_matches_single_device(
    n_devices, interleave, n_microbatches
):
    """The circular (virtual-stage) schedule computes exactly the
    single-device loss: round-robin chunk placement, lap indexing, and
    group-strided exits are invisible in the result."""
    cfg = CFG8
    mesh = pp.create_pp_mesh(1, 4, 1)
    params = tfm.init_params(jax.random.key(3), cfg)
    tokens, targets = _data(batch=8, seed=4)
    want = float(lmtrain.lm_loss(
        params, tokens, targets, cfg,
        seq_axis=None, tp_axis=None, attn_impl="full", axes=(),
    ))
    sharded, specs = pp.shard_pp_params(params, cfg, mesh, interleave=interleave)
    got = float(
        jax.jit(
            jax.shard_map(
                lambda p, tok, tgt: pp.pipeline_lm_loss(
                    p, tok, tgt, cfg,
                    n_microbatches=n_microbatches, tp_axis=None,
                    sync_axes=(pp.DATA_AXIS,), interleave=interleave,
                ),
                mesh=mesh,
                in_specs=(specs, P(pp.DATA_AXIS), P(pp.DATA_AXIS)),
                out_specs=P(),
            )
        )(sharded, tokens, targets)
    )
    assert np.isclose(got, want, rtol=2e-5), (got, want)


@pytest.mark.slow
def test_interleaved_train_step_learns(n_devices):
    """pp4 x v2 end-to-end: the interleaved train step trains the copy
    task (gradients flow through lap indexing + permuted layout)."""
    mesh = pp.create_pp_mesh(1, 4, 1)
    params = tfm.init_params(jax.random.key(0), CFG8)
    params, _ = pp.shard_pp_params(params, CFG8, mesh, interleave=2)
    mom = jax.tree.map(jnp.zeros_like, params)
    step = pp.make_pp_train_step(
        CFG8, mesh, n_microbatches=4, lr=0.3, momentum=0.9, interleave=2
    )
    tokens, targets = _data(batch=16, seq=16, seed=3)
    losses = []
    for _ in range(30):
        params, mom, loss = step(params, mom, tokens, targets)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] - 0.5, losses[:: len(losses) - 1]


def test_interleave_layer_order_roundtrip():
    order = pp.interleave_layer_order(16, 4, 2)
    inv = pp.interleave_layer_order(16, 4, 2, inverse=True)
    assert (order[inv] == np.arange(16)).all()
    # device q's local rows are its laps in order: q=1, v=2, cl=2 ->
    # global chunks 1 (layers 2,3) then 5 (layers 10,11)
    assert order[4:8].tolist() == [2, 3, 10, 11]


def test_interleave_validation(n_devices):
    mesh = pp.create_pp_mesh(1, 4, 1)
    with pytest.raises(ValueError, match="multiple of"):
        pp.make_pp_train_step(CFG8, mesh, n_microbatches=2, interleave=2)
    cfg6 = tfm.TransformerConfig(
        vocab_size=32, d_model=32, n_heads=4, n_layers=6, d_ff=64
    )
    with pytest.raises(ValueError, match="divisible by pipeline size"):
        pp.make_pp_train_step(cfg6, mesh, n_microbatches=4, interleave=2)


def test_indivisible_layers_rejected(n_devices):
    mesh = pp.create_pp_mesh(1, 3, 1)
    with pytest.raises(ValueError, match="divisible by pipeline size"):
        pp.make_pp_train_step(CFG, mesh)


@pytest.mark.slow
def test_interior_ticks_do_no_vocab_work(n_devices):
    """The head must run once per microbatch (sharded over stages), not
    per tick per stage (r2 VERDICT weak #3). Measured on the compiled
    program: growing the vocab by dV adds head+embed FLOPs; with the
    boundary-only schedule the increase stays near the analytic
    once-per-microbatch cost, far below the per-tick-per-stage cost
    6 * P * (M+P-1) * mb * S * d * dV the old schedule paid."""
    P_, M, mb, seq, d = 4, 2, 2, 16, CFG.d_model
    mesh = pp.create_pp_mesh(1, P_, 1)
    tokens, targets = _data(batch=M * mb, seq=seq)

    def flops(vocab):
        cfg = tfm.TransformerConfig(
            vocab_size=vocab, d_model=d, n_heads=CFG.n_heads,
            n_layers=CFG.n_layers, d_ff=CFG.d_ff,
        )
        specs = pp.pp_param_specs(cfg, tp_axis=None)
        params, _ = pp.shard_pp_params(
            tfm.init_params(jax.random.key(0), cfg), cfg, mesh
        )
        fn = jax.jit(
            jax.shard_map(
                lambda p, tok, tgt: jax.grad(pp.pipeline_lm_loss)(
                    p, tok, tgt, cfg,
                    n_microbatches=M, tp_axis=None, sync_axes=(),
                ),
                mesh=mesh,
                in_specs=(specs, P(None), P(None)),
                out_specs=specs,
            )
        )
        cost = fn.lower(params, tokens, targets).compile().cost_analysis()
        return cost["flops"]

    dv = 480 - 32
    measured = flops(480) - flops(32)
    # fwd+bwd head matmuls ~ 6*d*V FLOPs/token; exits padded M -> mp
    mp = -(-M // P_) * P_
    tokens_total = M * mb * seq
    once_per_microbatch = 6 * d * dv * tokens_total * (mp / M)
    per_tick_per_stage = 6 * d * dv * mb * seq * P_ * (M + P_ - 1)
    assert measured < 3 * once_per_microbatch, (
        measured, once_per_microbatch
    )
    assert measured < 0.5 * per_tick_per_stage, (
        measured, per_tick_per_stage
    )


@pytest.mark.slow
def test_interleaved_grads_match_single_device(n_devices):
    """v=2 gradient parity: reverse-mode AD through lap-indexed chunk
    selection (dynamic_index_in_dim scatter-add), group-strided exits and
    the permuted layer layout must reproduce single-device gradients.
    Layer-stack grads come back in the interleaved layout; un-permute via
    interleave_layer_order(inverse=True) before comparing."""
    cfg = CFG8
    mesh = pp.create_pp_mesh(1, 4, 1)
    params = tfm.init_params(jax.random.key(5), cfg)
    tokens, targets = _data(batch=8, seed=6)
    g_ref = jax.grad(
        lambda p: lmtrain.lm_loss(
            p, tokens, targets, cfg,
            seq_axis=None, tp_axis=None, attn_impl="full", axes=(),
        )
    )(params)

    sharded, specs = pp.shard_pp_params(params, cfg, mesh, interleave=2)
    g_pp = jax.jit(
        jax.shard_map(
            lambda p, tok, tgt: jax.grad(pp.pipeline_lm_loss)(
                p, tok, tgt, cfg,
                n_microbatches=4, tp_axis=None,
                sync_axes=(pp.DATA_AXIS,), interleave=2,
            ),
            mesh=mesh,
            in_specs=(specs, P(pp.DATA_AXIS), P(pp.DATA_AXIS)),
            out_specs=specs,
        )
    )(sharded, tokens, targets)

    inv = pp.interleave_layer_order(cfg.n_layers, 4, 2, inverse=True)
    for path, want in [
        (("embed",), g_ref["embed"]),
        (("head",), g_ref["head"]),
        (("layers", "wq"), g_ref["layers"]["wq"]),
        (("layers", "b1"), g_ref["layers"]["b1"]),
    ]:
        got = g_pp
        for k in path:
            got = got[k]
        got = np.asarray(got)
        if path[0] == "layers":
            got = got[inv]
        np.testing.assert_allclose(
            got, np.asarray(want), rtol=5e-4, atol=1e-5
        )


@pytest.mark.slow
def test_interleaved_composes_with_dp_tp(n_devices):
    """dp2 x pp2 x tp2 with v=2: the circular schedule must compose with
    batch sharding (grad pmean over data) and tensor parallelism
    (per-block psums) - all three axes plus lap indexing in one step."""
    mesh = pp.create_pp_mesh(2, 2, 2)
    params = tfm.init_params(jax.random.key(0), CFG8)
    params, _ = pp.shard_pp_params(params, CFG8, mesh, interleave=2)
    mom = jax.tree.map(jnp.zeros_like, params)
    step = pp.make_pp_train_step(
        CFG8, mesh, n_microbatches=2, lr=0.3, momentum=0.9, interleave=2
    )
    tokens, targets = _data(batch=16, seq=16, seed=7)
    losses = []
    for _ in range(30):
        params, mom, loss = step(params, mom, tokens, targets)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] - 0.5, losses[:: len(losses) - 1]


@pytest.mark.parametrize("v,m", [(4, 2), (4, 4)])
def test_deep_interleave_pp2(n_devices, v, m):
    """pp=2 with v=4 virtual stages: four laps around a 2-ring - the lap
    indexing and group chaining at v > 2 match the single-device loss."""
    cfg = CFG8  # 8 layers = pp2 * v4 chunks of 1
    mesh = pp.create_pp_mesh(1, 2, 1)
    params = tfm.init_params(jax.random.key(8), cfg)
    tokens, targets = _data(batch=8, seed=9)
    want = float(lmtrain.lm_loss(
        params, tokens, targets, cfg,
        seq_axis=None, tp_axis=None, attn_impl="full", axes=(),
    ))
    sharded, specs = pp.shard_pp_params(params, cfg, mesh, interleave=v)
    got = float(
        jax.jit(
            jax.shard_map(
                lambda p, tok, tgt: pp.pipeline_lm_loss(
                    p, tok, tgt, cfg,
                    n_microbatches=m, tp_axis=None,
                    sync_axes=(pp.DATA_AXIS,), interleave=v,
                ),
                mesh=mesh,
                in_specs=(specs, P(pp.DATA_AXIS), P(pp.DATA_AXIS)),
                out_specs=P(),
            )
        )(sharded, tokens, targets)
    )
    assert np.isclose(got, want, rtol=2e-5), (got, want)


@pytest.mark.parametrize("remat_policy", ["", "dots_saveable"])
def test_interleave_with_remat_matches(n_devices, monkeypatch, remat_policy):
    """Block remat inside the lap-indexed chunk scan: same loss. The
    dots_saveable parametrization pins that remat_policy reaches the
    pipeline path too (r5 review: it was silently dropped there), through
    the one helper, whose policy gives the loss and the gradients that
    jax's own policy of that name gives."""
    import dataclasses

    cfg = dataclasses.replace(CFG8, remat=True, remat_policy=remat_policy)
    mesh = pp.create_pp_mesh(1, 4, 1)
    params = tfm.init_params(jax.random.key(3), cfg)
    tokens, targets = _data(batch=8, seed=4)
    want = float(lmtrain.lm_loss(
        params, tokens, targets, cfg,
        seq_axis=None, tp_axis=None, attn_impl="full", axes=(),
    ))
    sharded, specs = pp.shard_pp_params(params, cfg, mesh, interleave=2)

    def loss_and_grads(policy_of):
        asked = []
        monkeypatch.setattr(
            pp, "block_remat_policy",
            lambda name: asked.append(name) or policy_of(name))
        loss, grads = jax.jit(
            jax.value_and_grad(jax.shard_map(
                lambda p, tok, tgt: pp.pipeline_lm_loss(
                    p, tok, tgt, cfg,
                    n_microbatches=4, tp_axis=None,
                    sync_axes=(pp.DATA_AXIS,), interleave=2,
                ),
                mesh=mesh,
                in_specs=(specs, P(pp.DATA_AXIS), P(pp.DATA_AXIS)),
                out_specs=P(),
            ))
        )(sharded, tokens, targets)
        assert set(asked) == {remat_policy}
        return float(loss), grads

    got, grads = loss_and_grads(block_remat_policy)
    assert np.isclose(got, want, rtol=2e-5), (got, want)
    if not remat_policy:
        return  # no policy either way: the same program
    # what the blocks' policy was before the helper: jax's own of that name
    parent, parent_grads = loss_and_grads(
        lambda name: getattr(jax.checkpoint_policies, name))
    assert np.isclose(got, parent, rtol=2e-5), (got, parent)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(parent_grads)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6)


def test_pp_adam_learns(n_devices):
    """Adam under the interleaved pipeline: {m,v,t} state follows the
    pipe-sharded layer layout; loss falls on the copy task."""
    from distributed_neural_network_tpu.ops.adam import init_adam

    mesh = pp.create_pp_mesh(1, 4, 1)
    params = tfm.init_params(jax.random.key(0), CFG8)
    params, _ = pp.shard_pp_params(params, CFG8, mesh, interleave=2)
    mom = init_adam(params)
    step = pp.make_pp_train_step(
        CFG8, mesh, n_microbatches=4, lr=0.01, interleave=2,
        optimizer="adam", clip_norm=1.0,
    )
    tokens, targets = _data(batch=16, seq=16, seed=11)
    losses = []
    for _ in range(25):
        params, mom, loss = step(params, mom, tokens, targets)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] - 1.0, losses[:: len(losses) - 1]
    with pytest.raises(ValueError, match="one of sgd/adam/zero"):
        pp.make_pp_train_step(CFG8, mesh, optimizer="rmsprop")


@pytest.mark.parametrize(
    "zero_opt,base_opt", [("zero-adam", "adam"), ("zero", "sgd")]
)
def test_pp_zero_parity_vs_unsharded(n_devices, zero_opt, base_opt):
    """ZeRO-1 under dp2 x pp2 is numerically the unsharded optimizer.

    The per-leaf ZeRO step (parallel/zero.py) updates a partition of each
    stage-local leaf's elements with the same elementwise rule, so the
    trajectory must match the replicated-state optimizer to float
    round-off - including clipping and decoupled weight decay (VERDICT r3
    item 6: the DeepSpeed ZeRO-1 + PP layout)."""
    mesh = pp.create_pp_mesh(2, 2, 1)
    tokens, targets = _data(batch=16, seq=16, seed=13)
    kw = dict(n_microbatches=2, lr=0.02, momentum=0.9,
              clip_norm=1.0, weight_decay=0.01)

    def run(optimizer, steps=5):
        params = tfm.init_params(jax.random.key(5), CFG)
        params, specs = pp.shard_pp_params(params, CFG, mesh)
        if optimizer == "adam":
            from distributed_neural_network_tpu.ops.adam import init_adam

            mom = init_adam(params)
        elif optimizer == "sgd":
            mom = jax.tree.map(jnp.zeros_like, params)
        else:
            mom = pp.init_pp_zero_state(params, specs, mesh, optimizer)
        step = pp.make_pp_train_step(CFG, mesh, optimizer=optimizer, **kw)
        losses = []
        for _ in range(steps):
            params, mom, loss = step(params, mom, tokens, targets)
            losses.append(float(loss))
        return params, losses

    p_ref, l_ref = run(base_opt)
    p_z, l_z = run(zero_opt)
    np.testing.assert_allclose(l_z, l_ref, rtol=1e-5)
    for (path, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(p_z)[0],
        jax.tree_util.tree_flatten_with_path(p_ref)[0],
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6,
            err_msg=str(path),
        )


@pytest.mark.parametrize("optimizer", ["sgd", "zero-adam"])
def test_pp_accumulation_matches_full_batch(n_devices, optimizer):
    """accum_steps=2 under dp2 x pp2 equals one full-batch pass: the loss
    is a global token mean either way, so two averaged half-batch
    schedule passes reproduce the single-pass trajectory up to float
    reassociation (VERDICT r3 item 7: --accum-steps works under --pp)."""
    mesh = pp.create_pp_mesh(2, 2, 1)
    tokens, targets = _data(batch=16, seq=16, seed=17)
    kw = dict(lr=0.05, momentum=0.9, clip_norm=1.0, optimizer=optimizer)

    def run(accum, steps=3):
        params = tfm.init_params(jax.random.key(7), CFG)
        params, specs = pp.shard_pp_params(params, CFG, mesh)
        if optimizer == "sgd":
            mom = jax.tree.map(jnp.zeros_like, params)
        else:
            mom = pp.init_pp_zero_state(params, specs, mesh, optimizer)
        step = pp.make_pp_train_step(
            CFG, mesh, n_microbatches=2, accum_steps=accum, **kw
        )
        losses = []
        for _ in range(steps):
            params, mom, loss = step(params, mom, tokens, targets)
            losses.append(float(loss))
        return params, losses

    p1, l1 = run(1)
    p2, l2 = run(2)
    np.testing.assert_allclose(l2, l1, rtol=2e-5)
    for (path, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(p2)[0],
        jax.tree_util.tree_flatten_with_path(p1)[0],
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-6,
            err_msg=str(path),
        )


MOE_CFG = tfm.TransformerConfig(
    vocab_size=32, d_model=32, n_heads=4, n_layers=4, d_ff=64,
    n_experts=4, moe_top_k=2,
)


def test_pp_moe_loss_matches_single_device(n_devices):
    """MoE through the pipeline schedule at 1 microbatch equals the
    single-device MoE loss (same capacity: one microbatch IS the whole
    batch, so routing, drops, and the Switch aux all coincide)."""
    mesh = pp.create_pp_mesh(1, 4, 1)
    params = tfm.init_params(jax.random.key(4), MOE_CFG)
    tokens, targets = _data(batch=8, seq=16, seed=21)
    want = float(lmtrain.lm_loss(
        params, tokens, targets, MOE_CFG,
        seq_axis=None, tp_axis=None, attn_impl="full", axes=(),
    ))
    sharded, specs = pp.shard_pp_params(params, MOE_CFG, mesh)
    got = float(jax.jit(
        jax.shard_map(
            lambda p, tok, tgt: pp.pipeline_lm_loss(
                p, tok, tgt, MOE_CFG, n_microbatches=1,
                sync_axes=(pp.DATA_AXIS,),
            ),
            mesh=mesh,
            in_specs=(specs, P(pp.DATA_AXIS), P(pp.DATA_AXIS)),
            out_specs=P(),
        )
    )(sharded, tokens, targets))
    assert np.isclose(got, want, rtol=5e-5), (got, want)


def test_pp_moe_train_step_learns_dp_pp_ep(n_devices):
    """MoE under dp2 x pp2 with experts sharded over dp (GShard) trains:
    aux is bubble-masked, expert leaves carry the (pipe, data) composite
    sharding, and the copy-task loss falls."""
    mesh = pp.create_pp_mesh(2, 2, 1)
    params = tfm.init_params(jax.random.key(0), MOE_CFG)
    params, _ = pp.shard_pp_params(params, MOE_CFG, mesh)
    from distributed_neural_network_tpu.ops.adam import init_adam

    mom = init_adam(params)
    step = pp.make_pp_train_step(
        MOE_CFG, mesh, n_microbatches=2, lr=0.01,
        optimizer="adam", clip_norm=1.0,
    )
    tokens, targets = _data(batch=16, seq=16, seed=23)
    losses = []
    for _ in range(25):
        params, mom, loss = step(params, mom, tokens, targets)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] - 0.8, losses[:: len(losses) - 1]


def test_pp_moe_rejects_zero(n_devices):
    mesh = pp.create_pp_mesh(2, 2, 1)
    with pytest.raises(ValueError, match="expert parallelism"):
        pp.make_pp_train_step(MOE_CFG, mesh, optimizer="zero-adam")


def test_pp_zero_rejects_tp(n_devices):
    mesh = pp.create_pp_mesh(2, 2, 2)
    with pytest.raises(ValueError, match="stage-local leaf"):
        pp.make_pp_train_step(CFG, mesh, optimizer="zero-adam")


def test_pp_zero_interleaved_learns(n_devices):
    """zero-adam composes with the interleaved schedule + lr schedule."""
    import functools

    from distributed_neural_network_tpu.ops import schedule as sched

    mesh = pp.create_pp_mesh(2, 2, 1)
    params = tfm.init_params(jax.random.key(0), CFG8)
    params, specs = pp.shard_pp_params(params, CFG8, mesh, interleave=2)
    mom = pp.init_pp_zero_state(params, specs, mesh, "zero-adam")
    step = pp.make_pp_train_step(
        CFG8, mesh, n_microbatches=4, lr=0.01, interleave=2,
        optimizer="zero-adam", clip_norm=1.0,
        lr_schedule=functools.partial(
            sched.warmup_cosine, base_lr=0.01, total_steps=25,
            warmup_steps=2, min_lr_frac=0.1,
        ),
    )
    tokens, targets = _data(batch=16, seq=16, seed=11)
    losses = []
    for i in range(25):
        params, mom, loss = step(params, mom, tokens, targets, jnp.int32(i))
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] - 1.0, losses[:: len(losses) - 1]


# ------------------------- tick-model fit (pure, no measurement) ------


def _tick_configs(c, o, *, n_layers=8, mb_rows=2, seq_len=128, steps=6,
                  pp_n=4):
    """Synthesize measured configs from exact tick-model parameters."""
    out = []
    for m, v in ((2, 1), (4, 1), (8, 1), (16, 1), (4, 2), (8, 2), (16, 2)):
        ticks = v * m + pp_n - 1
        w = n_layers / (v * pp_n)
        t = ticks * (w * c + o)
        out.append({
            "microbatches": m, "interleave": v,
            "tokens_per_s": m * mb_rows * seq_len * steps / t,
            "bubble_analytic": round((pp_n - 1) / (v * m + pp_n - 1), 4),
        })
    return out


def test_fit_tick_model_recovers_exact_parameters():
    """Noiseless data: the fit recovers (c, o) and the overhead-adjusted
    bubble collapses to the analytic bubble exactly (useful/total =
    vM/ticks when the model is exact)."""
    from distributed_neural_network_tpu.train.measure import fit_tick_model

    results = _tick_configs(2.0, 0.1)
    tm = fit_tick_model(results, n_layers=8, mb_rows=2, seq_len=128,
                        steps=6)
    assert abs(tm["per_layer_s"] - 2.0) < 1e-6
    assert abs(tm["per_tick_overhead_s"] - 0.1) < 1e-6
    assert tm["rel_fit_err"] < 1e-6
    assert tm["n_configs"] == 7
    assert "boundary_solution" not in tm
    for r in results:
        assert abs(r["bubble_overhead_adjusted"] - r["bubble_analytic"]) \
            < 1e-3


def test_fit_tick_model_negative_overhead_hits_o_boundary():
    """Warm-cache-shaped data (unconstrained o < 0): the constrained fit
    sits at o=0 with the unconstrained optimum reported."""
    from distributed_neural_network_tpu.train.measure import fit_tick_model

    results = _tick_configs(2.0, -0.15)
    tm = fit_tick_model(results, n_layers=8, mb_rows=2, seq_len=128,
                        steps=6)
    assert tm["per_tick_overhead_s"] == 0.0
    assert tm["per_layer_s"] > 0
    bnd = tm["boundary_solution"]
    assert bnd["per_tick_overhead_s_unconstrained"] < 0


def test_fit_tick_model_negative_layer_cost_hits_c_boundary():
    """Degenerate data where the per-layer component fits negative: the
    constrained optimum must land on the c=0 boundary (o-only fit), not
    the c-only fit (the review-caught wrong-boundary bug)."""
    from distributed_neural_network_tpu.train.measure import fit_tick_model

    results = _tick_configs(-0.05, 1.0)
    tm = fit_tick_model(results, n_layers=8, mb_rows=2, seq_len=128,
                        steps=6)
    assert tm["per_layer_s"] == 0.0
    assert tm["per_tick_overhead_s"] > 0
    assert tm["boundary_solution"]["per_layer_s_unconstrained"] < 0

