"""End-to-end engine tests: three regimes on the 8-device CPU mesh.

The TPU-native analog of the reference's empirical verification (SURVEY.md
sec. 4): convergence on a small class-structured dataset, cross-regime
equivalences, fault-mask semantics, and the local-SGD vs per-step sync modes.
"""

import jax
import numpy as np
import pytest

from distributed_neural_network_tpu.data.cifar10 import Split, make_synthetic, normalize
from distributed_neural_network_tpu.train.engine import Engine, TrainConfig


def _splits(n_train=512, n_test=256, seed=3):
    xt, yt = make_synthetic(n_train, seed=seed, train=True)
    xv, yv = make_synthetic(n_test, seed=seed, train=False)
    return (
        Split(normalize(xt), yt, "synthetic"),
        Split(normalize(xv), yv, "synthetic"),
    )


TRAIN, TEST = _splits()


def _cfg(**kw):
    base = dict(lr=0.01, momentum=0.9, batch_size=32, epochs=2, seed=0)
    base.update(kw)
    return TrainConfig(**base)


@pytest.mark.slow
def test_single_regime_trains_and_converges(n_devices):
    eng = Engine(_cfg(regime="single", epochs=6), TRAIN, TEST)
    hist = eng.run(log=lambda *_: None)
    assert len(hist) == 6
    assert hist[-1].train_loss < hist[0].train_loss
    assert hist[-1].val_acc > 45.0  # way above 10% chance on class-structured data


@pytest.mark.slow
def test_data_parallel_regime_8dev(n_devices):
    eng = Engine(
        _cfg(regime="data_parallel", nb_proc=8, epochs=6, batch_size=8, lr=0.05),
        TRAIN,
        TEST,
    )
    hist = eng.run(log=lambda *_: None)
    assert hist[-1].train_loss < hist[0].train_loss
    assert hist[-1].val_acc > 60.0
    # shard math: 512 rows / 8 devices = 64 local rows
    assert eng.local_train_rows == 64


@pytest.mark.slow
def test_replication_regime_8dev(n_devices):
    eng = Engine(
        _cfg(regime="replication", nb_proc=8, epochs=4, batch_size=16), TRAIN, TEST
    )
    hist = eng.run(log=lambda *_: None)
    assert eng.local_train_rows == 512  # full data on every device
    assert hist[-1].val_acc > 60.0


def test_reference_compat_uses_n_minus_1_workers(n_devices):
    eng = Engine(
        _cfg(regime="data_parallel", nb_proc=8, reference_compat=True), TRAIN, TEST
    )
    assert eng.n_workers == 7
    assert eng.local_train_rows == 512 // 7


@pytest.mark.slow
def test_nb_proc_1_data_parallel_equals_single_regime(n_devices):
    """With one device, sharded local SGD == the single-process baseline."""
    e1 = Engine(_cfg(regime="single", epochs=2), TRAIN, TEST)
    h1 = e1.run(log=lambda *_: None)
    e2 = Engine(_cfg(regime="data_parallel", nb_proc=1, epochs=2), TRAIN, TEST)
    h2 = e2.run(log=lambda *_: None)
    assert h1[-1].train_loss == pytest.approx(h2[-1].train_loss, rel=1e-5)
    assert h1[-1].val_acc == pytest.approx(h2[-1].val_acc, abs=1e-6)


def test_param_averaging_equals_hand_computed_mean(n_devices):
    """One epoch of DP: synced params == numpy mean of per-device params."""
    eng = Engine(_cfg(regime="data_parallel", nb_proc=8, epochs=1), TRAIN, TEST)
    params_stacked, mom, loss_sums, n_batches = eng._train_fn(
        eng.params, eng.mom, eng.train_images, eng.train_labels, np.uint32(0)
    )
    stacked = jax.tree.map(np.asarray, params_stacked)
    live = jax.device_put(np.ones(8, np.float32), eng._shard)
    synced, _ = eng._sync_fn(params_stacked, live, loss_sums, n_batches)
    hand = jax.tree.map(lambda x: x.mean(axis=0), stacked)
    got = jax.tree.map(np.asarray, synced)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6),
        hand,
        got,
    )


def test_fault_mask_excludes_dead_device(n_devices):
    """With p=1 failure on every device the avg falls back to plain mean; with
    a hand-injected mask the dead device's params are excluded."""
    eng = Engine(_cfg(regime="data_parallel", nb_proc=8, epochs=1), TRAIN, TEST)
    params_stacked, mom, loss_sums, n_batches = eng._train_fn(
        eng.params, eng.mom, eng.train_images, eng.train_labels, np.uint32(0)
    )
    stacked = jax.tree.map(np.asarray, params_stacked)
    mask = np.ones(8, np.float32)
    mask[2] = 0.0
    live = jax.device_put(mask, eng._shard)
    synced, _ = eng._sync_fn(params_stacked, live, loss_sums, n_batches)
    hand = jax.tree.map(
        lambda x: x[mask.astype(bool)].mean(axis=0), stacked
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, np.asarray(b), rtol=2e-5, atol=1e-6),
        hand,
        jax.tree.map(np.asarray, synced),
    )


@pytest.mark.slow
def test_fault_run_survives_failures(n_devices):
    eng = Engine(
        _cfg(
            regime="data_parallel",
            nb_proc=8,
            epochs=4,
            failure_probability=0.4,
            seed=5,
        ),
        TRAIN,
        TEST,
    )
    hist = eng.run(log=lambda *_: None)
    assert all(np.isfinite(m.train_loss) for m in hist)
    assert any(m.n_live < 8 for m in hist)  # failures actually happened
    assert all(m.val_acc is not None for m in hist)


@pytest.mark.slow
def test_step_sync_mode(n_devices):
    eng = Engine(
        _cfg(
            regime="data_parallel",
            nb_proc=8,
            sync_mode="step",
            epochs=5,
            batch_size=8,
            lr=0.05,
        ),
        TRAIN,
        TEST,
    )
    hist = eng.run(log=lambda *_: None)
    assert hist[-1].train_loss < hist[0].train_loss
    assert hist[-1].val_acc > 60.0


def test_eval_handles_uneven_test_split(n_devices):
    """255 test rows over 8 devices: padded rows must not distort accuracy."""
    train, _ = _splits()
    xv, yv = make_synthetic(255, seed=3, train=False)
    test = Split(normalize(xv), yv, "synthetic")
    eng = Engine(_cfg(regime="data_parallel", nb_proc=8, epochs=1), train, test)
    hist = eng.run(log=lambda *_: None)
    assert 0.0 <= hist[0].val_acc <= 100.0


@pytest.mark.slow
def test_determinism_same_seed_same_result(n_devices):
    h1 = Engine(_cfg(regime="data_parallel", nb_proc=8, epochs=2), TRAIN, TEST).run(
        log=lambda *_: None
    )
    h2 = Engine(_cfg(regime="data_parallel", nb_proc=8, epochs=2), TRAIN, TEST).run(
        log=lambda *_: None
    )
    assert h1[-1].train_loss == h2[-1].train_loss
    assert h1[-1].val_acc == h2[-1].val_acc


@pytest.mark.slow
def test_momentum_reset_vs_persistent(n_devices):
    """reset_momentum=True (reference dynamics) differs from persistent."""
    hr = Engine(_cfg(regime="single", epochs=3, reset_momentum=True), TRAIN, TEST).run(
        log=lambda *_: None
    )
    hp = Engine(_cfg(regime="single", epochs=3, reset_momentum=False), TRAIN, TEST).run(
        log=lambda *_: None
    )
    assert hr[-1].train_loss != hp[-1].train_loss


@pytest.mark.slow
def test_fused_span_matches_per_epoch_path(n_devices):
    """run_span (one compiled multi-epoch dispatch) must reproduce the
    per-epoch path exactly: same losses, same eval, same fault masks, and
    numerically-identical final parameters."""
    cfg = _cfg(
        regime="data_parallel", nb_proc=8, epochs=3, failure_probability=0.3, seed=5
    )
    e1 = Engine(cfg, TRAIN, TEST)
    for ep in range(3):
        e1.run_epoch(ep)
    e2 = Engine(cfg, TRAIN, TEST)
    e2.run_span(0, 3, eval_inside=True)
    for m1, m2 in zip(e1.history, e2.history):
        assert m1.train_loss == pytest.approx(m2.train_loss, rel=1e-5)
        assert m1.val_loss == pytest.approx(m2.val_loss, rel=1e-5)
        assert m1.val_acc == pytest.approx(m2.val_acc, abs=1e-3)
        assert m1.n_live == m2.n_live
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
        ),
        e1.params,
        e2.params,
    )


@pytest.mark.slow
def test_fused_run_chunks_at_eval_boundaries(n_devices):
    """run(fused=True) with eval_every=2: spans split so eval lands exactly
    on the reference's eval cadence; history covers every epoch."""
    eng = Engine(_cfg(regime="data_parallel", nb_proc=8, epochs=4), TRAIN, TEST)
    hist = eng.run(log=lambda *_: None, fused=True, eval_every=2)
    assert [m.epoch for m in hist] == [0, 1, 2, 3]
    assert [m.val_acc is not None for m in hist] == [False, True, False, True]


def test_fused_span_without_eval(n_devices):
    eng = Engine(_cfg(regime="single", epochs=2), TRAIN, TEST)
    metrics = eng.run_span(0, 2, eval_inside=False)
    assert len(metrics) == 2
    assert all(m.val_acc is None for m in metrics)
    assert all(np.isfinite(m.train_loss) for m in metrics)


@pytest.mark.slow
def test_reset_state_reproduces_run(n_devices):
    """Warm-up + reset_state (bench.py pattern) must not change the measured
    training trajectory."""
    eng = Engine(_cfg(regime="data_parallel", nb_proc=8, epochs=2), TRAIN, TEST)
    h1 = [eng.run_epoch(e) for e in range(2)]
    eng.reset_state()
    eng.history = []
    h2 = [eng.run_epoch(e) for e in range(2)]
    assert h1[-1].train_loss == h2[-1].train_loss
    assert h1[-1].val_acc == h2[-1].val_acc


def test_fused_downgrades_with_straggler_sleep_and_warns(n_devices):
    """--fused + --failure-duration: straggler sleeps can only interleave
    between per-epoch dispatches, so run(fused=True) must fall back to the
    per-epoch path and say so (VERDICT r2 item 8)."""
    eng = Engine(
        _cfg(nb_proc=4, epochs=1, failure_duration=0.01,
             failure_probability=0.0),
        TRAIN, TEST,
    )
    messages = []
    hist = eng.run(fused=True, log=lambda *a: messages.append(" ".join(map(str, a))))
    assert len(hist) == 1
    assert any("failure-duration" in m and "per-epoch" in m for m in messages), messages
    # the fused span machinery must not have been engaged
    assert not eng._span_compiled


@pytest.mark.slow
def test_measure_fault_tolerance_flat_wall_and_survival(n_devices):
    """`measure_fault_tolerance` (the cnn_fault_sweep_cpu8 bench row):
    drop-and-continue keeps wall-clock flat in p and the run converges
    despite most epoch contributions being dropped at p=0.6."""
    from distributed_neural_network_tpu.train.measure import (
        measure_fault_tolerance,
    )

    # straggler_duration 1.0: the stall signal (epochs_degraded * 1 s)
    # must dominate host-timing noise on the two ~15 s per-epoch loops -
    # at the 0.25 s default the predicted 1 s stall sat inside +/-1.5 s
    # loop noise and the bound below flaked (observed measured=-1.45)
    r = measure_fault_tolerance(probs=(0.0, 0.6), epochs=4,
                                synthetic_size=800,
                                straggler_duration=1.0)
    p0, p6 = r["points"]
    assert p0["mean_live_frac"] == 1.0 and p0["epochs_degraded"] == 0
    assert p6["mean_live_frac"] < 0.8  # the sweep really dropped devices
    # nobody waits for dead devices: wall within noise of the control
    assert 0.7 <= p6["wall_vs_p0"] <= 1.3
    # convergence survives: both far above the 10% chance floor at this
    # short, seed-noisy length (the bench row's 8-epoch runs reach ~100%
    # at every p; this guard only pins "learns despite drops")
    assert p0["val_acc"] > 55.0
    assert p6["val_acc"] > 30.0
    # the straggler price exists and scales with degraded epochs (loose:
    # host timing noise; the claim is 'stall is real and bounded')
    st = r["straggler"]
    assert st["epochs_degraded"] > 0
    assert st["predicted_stall_s"] == pytest.approx(
        st["epochs_degraded"] * st["duration_s"])
    assert st["measured_stall_s"] > 0.3 * st["predicted_stall_s"]


# ------------------------------------------- gradient-sync granularity


def test_train_config_validates_grad_sync():
    cfg = _cfg(grad_sync="overlap", sync_mode="step", bucket_mb=2.0)
    assert cfg.grad_sync == "overlap"
    with pytest.raises(ValueError, match="grad_sync"):
        _cfg(grad_sync="sometimes")
    with pytest.raises(ValueError, match="bucket_mb"):
        _cfg(bucket_mb=0.0)


def test_cli_passes_grad_sync_and_compilation_cache(tmp_path, monkeypatch):
    """The shared CLI surface plumbs --grad-sync/--bucket-mb into
    TrainConfig and --compilation-cache-dir into jax's persistent-cache
    config (restored after the check) - the flag applies only while
    JAX_COMPILATION_CACHE_DIR is unset, so the variable is unset here."""
    import argparse

    from distributed_neural_network_tpu import runtime
    from distributed_neural_network_tpu.train import cli

    monkeypatch.delenv(runtime.CACHE_ENV, raising=False)

    p = argparse.ArgumentParser()
    cli.add_common_flags(p, epochs=2, batch_size=16)
    args = p.parse_args(
        ["--sync-mode", "step", "--grad-sync", "overlap",
         "--bucket-mb", "2.5",
         "--compilation-cache-dir", str(tmp_path / "cache")]
    )
    cfg = cli.config_from_args(args, "data_parallel")
    assert cfg.grad_sync == "overlap"
    assert cfg.bucket_mb == 2.5
    assert args.compilation_cache_dir == str(tmp_path / "cache")

    prev = jax.config.jax_compilation_cache_dir
    try:
        assert runtime.enable_compile_cache(
            args.compilation_cache_dir
        ) == str(tmp_path / "cache")
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


@pytest.mark.skipif(
    not hasattr(jax, "shard_map"),
    reason="needs jax.shard_map with vma-typed autodiff",
)
def test_step_sync_overlap_matches_end(n_devices):
    """sync_mode='step' with bucketed (overlap) grad pmean reproduces the
    per-leaf pmean trajectory - bucketing repartitions the identical
    elementwise mean."""

    def run(grad_sync):
        eng = Engine(
            _cfg(
                regime="data_parallel", nb_proc=4, sync_mode="step",
                epochs=1, batch_size=16, grad_sync=grad_sync,
                bucket_mb=0.001,
            ),
            TRAIN,
            TEST,
        )
        m = eng.run_epoch(0)
        return m.train_loss, eng.params

    loss_end, p_end = run("end")
    loss_ov, p_ov = run("overlap")
    assert np.isclose(loss_end, loss_ov, rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
        ),
        p_end, p_ov,
    )
