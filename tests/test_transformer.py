"""Transformer LM family + DP x SP x TP train step on the 8-device CPU mesh.

Bar: sharded forward (any mesh decomposition, ring or Ulysses attention)
matches the single-device forward on the same params; the multi-axis train
step optimizes a copy task; tensor-parallel gradients stay shard-local while
replicated params sync over data+seq automatically.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_neural_network_tpu.models import transformer as tfm
from distributed_neural_network_tpu.ops.flash_pallas import block_remat_policy
from distributed_neural_network_tpu.ops.sgd import init_momentum
from distributed_neural_network_tpu.train import lm

CFG = tfm.TransformerConfig(vocab_size=64, d_model=64, n_heads=8, n_layers=2, d_ff=128)


def _data(batch=8, seq=32, seed=0):
    return lm.make_copy_task(
        jax.random.key(seed), batch=batch, seq_len=seq, vocab=CFG.vocab_size
    )


def _single_device_logits(params, tokens):
    return tfm.apply(params, tokens, CFG, seq_axis=None, tp_axis=None)


@pytest.mark.parametrize(
    "dp,sp,tp,attn",
    [
        (2, 4, 1, "ring"),
        (2, 4, 1, "ulysses"),
        (1, 8, 1, "ring"),
        (2, 2, 2, "ring"),
        (1, 1, 8, "ring"),  # pure TP: seq axis trivial
        (8, 1, 1, "ring"),  # pure DP
    ],
)
def test_sharded_forward_matches_single_device(n_devices, dp, sp, tp, attn):
    mesh = lm.create_lm_mesh(dp, sp, tp)
    params = tfm.init_params(jax.random.key(0), CFG)
    tokens, _ = _data()
    want = _single_device_logits(params, tokens)

    sharded, specs = lm.shard_params(params, CFG, mesh)
    sp_axis = lm.SEQ_AXIS if sp > 1 else None
    tp_axis = lm.TP_AXIS if tp > 1 else None

    from jax.sharding import PartitionSpec as P

    fwd = jax.jit(
        jax.shard_map(
            lambda p, t: tfm.apply(
                p, t, CFG, seq_axis=sp_axis, tp_axis=tp_axis, attn_impl=attn
            ),
            mesh=mesh,
            in_specs=(specs, P(lm.DATA_AXIS, lm.SEQ_AXIS)),
            out_specs=P(lm.DATA_AXIS, lm.SEQ_AXIS),
        )
    )
    got = fwd(sharded, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_lm_train_step_learns_copy_task(n_devices):
    mesh = lm.create_lm_mesh(2, 2, 2)
    params = tfm.init_params(jax.random.key(0), CFG)
    params, _ = lm.shard_params(params, CFG, mesh)
    mom = init_momentum(params)
    step = lm.make_lm_train_step(CFG, mesh, lr=0.05, momentum=0.9)
    tokens, targets = _data(batch=8, seq=32)
    losses = []
    for _ in range(30):
        params, mom, loss = step(params, mom, tokens, targets)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.6, losses[::10]


def test_tp_param_shapes_are_sharded(n_devices):
    """Tensor-parallel leaves are physically split over the model axis."""
    mesh = lm.create_lm_mesh(1, 1, 8)
    params = tfm.init_params(jax.random.key(0), CFG)
    sharded, _ = lm.shard_params(params, CFG, mesh)
    wq = sharded["layers"]["wq"]  # (L, d, d) column-sharded over 8 devices
    shard_shapes = {s.data.shape for s in wq.addressable_shards}
    assert shard_shapes == {(CFG.n_layers, CFG.d_model, CFG.d_model // 8)}


def test_apply_rejects_full_attn_with_seq_axis(n_devices):
    mesh = lm.create_lm_mesh(1, 8, 1)
    params = tfm.init_params(jax.random.key(0), CFG)
    sharded, specs = lm.shard_params(params, CFG, mesh)
    tokens, _ = _data()
    from jax.sharding import PartitionSpec as P

    with pytest.raises(ValueError, match="ring"):
        jax.jit(
            jax.shard_map(
                lambda p, t: tfm.apply(
                    p, t, CFG, seq_axis=lm.SEQ_AXIS, attn_impl="full"
                ),
                mesh=mesh,
                in_specs=(specs, P(None, lm.SEQ_AXIS)),
                out_specs=P(None, lm.SEQ_AXIS),
            )
        )(sharded, tokens)


def test_lm_loss_zigzag_matches_ring(n_devices):
    """Same tokens: zigzag-layout LM loss == ring-layout LM loss (the
    next-token objective is permutation-invariant when tokens/targets are
    permuted consistently and positions follow the layout)."""
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from distributed_neural_network_tpu.parallel.ring import zigzag_order
    from distributed_neural_network_tpu.train import lm as lmtrain

    cfg = tfm.TransformerConfig(
        vocab_size=32, d_model=32, n_heads=4, n_layers=2, d_ff=64
    )
    mesh = lmtrain.create_lm_mesh(2, 4, 1)
    params = tfm.init_params(jax.random.key(0), cfg)
    tokens, targets = lmtrain.make_copy_task(
        jax.random.key(1), batch=8, seq_len=32, vocab=32
    )

    def loss_fn(attn, tok, tgt):
        fn = jax.jit(
            jax.shard_map(
                lambda p, a, b: lmtrain.lm_loss(
                    p, a, b, cfg, seq_axis="seq", tp_axis=None,
                    attn_impl=attn, axes=("data", "seq"),
                ),
                mesh=mesh,
                in_specs=(P(), P("data", "seq"), P("data", "seq")),
                out_specs=P(),
            )
        )
        return float(fn(params, tok, tgt))

    want = loss_fn("ring", tokens, targets)
    perm = zigzag_order(32, 4)
    got = loss_fn("zigzag", tokens[:, perm], targets[:, perm])
    assert np.isclose(got, want, rtol=2e-5), (got, want)


@pytest.mark.slow
def test_remat_matches_no_remat(n_devices):
    """jax.checkpoint remat changes memory, not math: identical loss+grads."""
    import numpy as np

    from distributed_neural_network_tpu.train import lm as lmtrain

    base = dict(vocab_size=32, d_model=32, n_heads=4, n_layers=2, d_ff=64)
    tokens, targets = lmtrain.make_copy_task(
        jax.random.key(1), batch=4, seq_len=16, vocab=32
    )

    def loss_and_grad(remat, policy=""):
        cfg = tfm.TransformerConfig(**base, remat=remat, remat_policy=policy)
        params = tfm.init_params(jax.random.key(0), cfg)
        fn = lambda p: lm.lm_loss(
            p, tokens, targets, cfg,
            seq_axis=None, tp_axis=None, attn_impl="full", axes=(),
        )
        loss, grads = jax.value_and_grad(fn)(params)
        return float(loss), grads

    l0, g0 = loss_and_grad(False)
    l1, g1 = loss_and_grad(True)
    # a checkpoint POLICY (dots_saveable: matmul outputs stored, only
    # elementwise recomputed - the cheap-remat option measured r5) also
    # changes memory/FLOPs only, never math
    l2, g2 = loss_and_grad(True, policy="dots_saveable")
    assert np.isclose(l0, l1, rtol=1e-6)
    assert np.isclose(l0, l2, rtol=1e-6)
    for g in (g1, g2):
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            )


@pytest.mark.parametrize(
    "policy", ["dots_saveable", "dots_with_no_batch_dims_saveable",
               "nothing_saveable"])
def test_remat_policy_comes_from_the_helper(n_devices, monkeypatch, policy):
    """The blocks' checkpoint policy is `block_remat_policy` of the
    configuration's name, and it changes what a block keeps, never the
    numbers: loss and gradients are what jax's own policy of that name gave
    (the step before the helper), at test_remat_matches_no_remat's
    tolerances."""
    cfg = tfm.TransformerConfig(vocab_size=32, d_model=32, n_heads=4,
                                n_layers=2, d_ff=64, remat=True,
                                remat_policy=policy)
    tokens, targets = lm.make_copy_task(
        jax.random.key(1), batch=4, seq_len=16, vocab=32)
    params = tfm.init_params(jax.random.key(0), cfg)

    def loss_and_grads(policy_of):
        asked = []
        monkeypatch.setattr(
            tfm, "block_remat_policy",
            lambda name: asked.append(name) or policy_of(name))
        loss, grads = jax.value_and_grad(lambda p: lm.lm_loss(
            p, tokens, targets, cfg,
            seq_axis=None, tp_axis=None, attn_impl="flash", axes=(),
        ))(params)
        assert asked == [policy]
        return float(loss), grads

    got, grads = loss_and_grads(block_remat_policy)
    parent, parent_grads = loss_and_grads(
        lambda name: getattr(jax.checkpoint_policies, name))
    assert np.isclose(got, parent, rtol=1e-6), (got, parent)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(parent_grads)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


@pytest.mark.slow
@pytest.mark.parametrize("mesh_shape", [(1, 1, 1), (4, 1, 1), (2, 1, 2)])
def test_flash_attn_option_runs_and_matches(n_devices, mesh_shape):
    """attn_impl='flash' matches 'full' - including on dp and dp x tp
    meshes (round 4: the own Pallas kernels are vma-typed, so flash
    composes with the meshes under check_vma=True; off-TPU the dispatch
    falls back to the plain kernel, exercising the typed wiring)."""
    import numpy as np

    from distributed_neural_network_tpu.train import lm as lmtrain

    cfg = tfm.TransformerConfig(
        vocab_size=32, d_model=32, n_heads=4, n_layers=2, d_ff=64
    )
    mesh = lmtrain.create_lm_mesh(*mesh_shape)
    params0 = tfm.init_params(jax.random.key(0), cfg)
    tokens, targets = lmtrain.make_copy_task(
        jax.random.key(1), batch=8, seq_len=16, vocab=32
    )
    losses = {}
    for impl in ("full", "flash"):
        params, _ = lmtrain.shard_params(
            jax.tree.map(jnp.array, params0), cfg, mesh
        )
        mom = lmtrain.init_lm_momentum(params, mesh)
        step = lmtrain.make_lm_train_step(cfg, mesh, lr=0.3, attn_impl=impl)
        for _ in range(5):
            params, mom, loss = step(params, mom, tokens, targets)
        losses[impl] = float(loss)
    assert np.isclose(losses["full"], losses["flash"], rtol=1e-5), losses
    import pytest as _pytest

    # a sequence axis still needs ring/ulysses/zigzag
    with _pytest.raises(ValueError, match="sequence axis"):
        lmtrain.make_lm_train_step(
            cfg, lmtrain.create_lm_mesh(1, 4, 1), attn_impl="flash"
        )


def test_flash_rejects_sequence_axis(n_devices):
    import pytest as _pytest

    with _pytest.raises(ValueError, match="local kernel"):
        tfm._attend(
            jnp.zeros((1, 4, 2, 8)), jnp.zeros((1, 4, 2, 8)),
            jnp.zeros((1, 4, 2, 8)), impl="flash", seq_axis="seq", s_local=4,
        )


class TestChunkedCE:
    """train/lm.py chunked-CE path (ADVICE r2: the production throughput
    lever auto-activates only above ~16.7M logits elements, so CI never
    executed it): force loss_chunks>1 at test shapes and assert exact
    parity with the single-pass loss, values and gradients, standalone and
    under shard_map on the mesh."""

    CFG = dict(vocab_size=32, d_model=32, n_heads=4, n_layers=2, d_ff=64)

    def test_matches_single_pass_loss_and_grads(self, n_devices):
        import numpy as np

        from distributed_neural_network_tpu.train import lm as lmtrain

        cfg = tfm.TransformerConfig(**self.CFG)
        params = tfm.init_params(jax.random.key(0), cfg)
        tokens, targets = lmtrain.make_copy_task(
            jax.random.key(1), batch=4, seq_len=32, vocab=32
        )

        def loss_and_grad(chunks):
            fn = lambda p: lm.lm_loss(
                p, tokens, targets, cfg, seq_axis=None, tp_axis=None,
                attn_impl="full", axes=(), loss_chunks=chunks,
            )
            loss, grads = jax.value_and_grad(fn)(params)
            return float(loss), grads

        l1, g1 = loss_and_grad(1)
        l4, g4 = loss_and_grad(4)
        assert np.isclose(l1, l4, rtol=1e-6), (l1, l4)
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g4)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            )

    @pytest.mark.slow
    def test_matches_on_mesh_train_step(self, n_devices):
        import numpy as np

        from distributed_neural_network_tpu.train import lm as lmtrain

        cfg = tfm.TransformerConfig(**self.CFG)
        params0 = tfm.init_params(jax.random.key(0), cfg)
        tokens, targets = lmtrain.make_copy_task(
            jax.random.key(1), batch=8, seq_len=32, vocab=32
        )
        mesh = lmtrain.create_lm_mesh(2, 2, 2)
        losses = {}
        for chunks in (1, 4):
            params, _ = lmtrain.shard_params(
                jax.tree.map(jnp.array, params0), cfg, mesh
            )
            mom = lmtrain.init_lm_momentum(params, mesh)
            step = lmtrain.make_lm_train_step(
                cfg, mesh, lr=0.3, attn_impl="ring", loss_chunks=chunks
            )
            for _ in range(3):
                params, mom, loss = step(params, mom, tokens, targets)
            losses[chunks] = float(loss)
        assert np.isclose(losses[1], losses[4], rtol=1e-5), losses

    def test_auto_chunk_chooser(self):
        from distributed_neural_network_tpu.train.lm import auto_loss_chunks

        # tiny shapes: single pass fits the 64 MB budget
        assert auto_loss_chunks(8, 32, 32) == 1
        # production LM shapes: bs16 x seq2048 x vocab 32768 f32 logits are
        # 4 GB; the chooser must split into 64-position chunks
        assert auto_loss_chunks(16, 2048, 32768) == 64
        # chosen chunk count always divides S
        for b, s, v in [(16, 2048, 32768), (8, 384, 50000), (3, 96, 10**6)]:
            c = auto_loss_chunks(b, s, v)
            assert s % c == 0 and b * (s // c) * v <= 64 * 2**20 // 4


def test_remat_attn_matches_no_remat(n_devices):
    """remat_attn recomputes the attention inner call in backward; loss and
    gradients must be bit-comparable to the stored-scores path (same math,
    different schedule)."""
    base = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64)
    tokens, targets = lm.make_copy_task(
        jax.random.key(9), batch=4, seq_len=16, vocab=64
    )

    def loss_and_grads(**kw):
        cfg = tfm.TransformerConfig(**base, **kw)
        params = tfm.init_params(jax.random.key(0), cfg)
        return jax.value_and_grad(
            lambda p: lm.lm_loss(
                p, tokens, targets, cfg,
                seq_axis=None, tp_axis=None, attn_impl="full", axes=(),
            )
        )(params)

    l0, g0 = loss_and_grads()
    l1, g1 = loss_and_grads(remat_attn=True)
    assert np.isclose(float(l0), float(l1), rtol=1e-6), (l0, l1)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
        ),
        g0, g1,
    )
