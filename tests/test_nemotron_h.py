"""The `nemotron_h` model on the trainer's normal path (`models/nemotron_h.py`,
`ops/ssd.py`, `parallel/moe.py moe_held_ffn`, `train/lm.py`), held to the
benchmark family's plain reference (`benchmark/families/nemotron_h/`, loaded
through `harness.load_family`) at tiny sizes on the CPU, with seeded weights.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
sys.path.insert(0, BENCH)  # while `lib` is imported, and no longer
try:
    from lib import harness, train as bench_train, weights as bench_weights
finally:
    sys.path.remove(BENCH)

from distributed_neural_network_tpu.models import nemotron_h as nh  # noqa: E402
from distributed_neural_network_tpu.ops.flash_pallas import (  # noqa: E402
    block_remat_policy,
)
from distributed_neural_network_tpu.ops.ssd import ssd_scan  # noqa: E402
from distributed_neural_network_tpu.parallel import moe  # noqa: E402
from distributed_neural_network_tpu.train import lm  # noqa: E402
from distributed_neural_network_tpu.utils.obs import MetricsRegistry  # noqa: E402

TINY = os.path.join(BENCH, "families", "nemotron_h", "tiny.json")
TRAFFIC = {"batch": 4, "seq": 32, "optimizer": "adam", "lr": 3e-3, "b1": 0.9,
           "check_steps": 3, "reference_rows_per_block": 2, "remat": True}
SCOPES = ("lm.mamba.conv", "lm.mamba.scan", "lm.moe.route", "lm.moe.experts",
          "lm.moe.shared", "lm.attn")


@pytest.fixture(scope="module")
def family():
    return harness.load_family("nemotron_h", "train")


@pytest.fixture(scope="module")
def model():
    with open(TINY) as f:
        return json.load(f)


def batch_fn(seed=5):
    return bench_weights.make_batch_fn(seed, batch=TRAFFIC["batch"],
                                       seq=TRAFFIC["seq"], vocab=128)


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


# ------------------------------------------------------------ the scan


def ssd_recurrence(x, dt, a, b, c):
    """`ssd_scan`'s map step by step, in float32: S_t = exp(dt_t A) S_{t-1} +
    dt_t x_t B_t^T, y_t = S_t C_t, head h reading group h // (H / G)."""
    bsz, s, h, p = x.shape
    n, k = b.shape[3], h // b.shape[2]
    bh, ch = jnp.repeat(b, k, axis=2), jnp.repeat(c, k, axis=2)

    def one(state, step):
        xt, dtt, bt, ct = step
        keep = jnp.exp(dtt * a)[..., None, None]
        state = state * keep + (dtt[..., None] * xt)[..., None] * bt[..., None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct)

    steps = tuple(t.swapaxes(0, 1) for t in (x, dt, bh, ch))
    _, y = jax.lax.scan(one, jnp.zeros((bsz, h, p, n), jnp.float32), steps)
    return y.swapaxes(0, 1)


def scan_inputs(s, b=2, h=8, p=4, g=2, n=8):
    k = jax.random.split(jax.random.key(s), 5)
    return (jax.random.normal(k[0], (b, s, h, p)),
            jax.nn.softplus(jax.random.normal(k[1], (b, s, h))),
            -jnp.exp(jax.random.normal(k[2], (h,))),
            jax.random.normal(k[3], (b, s, g, n)),
            jax.random.normal(k[4], (b, s, g, n)))


@pytest.mark.parametrize("s", [16, 32, 80, 37, 5],
                         ids=["one_chunk", "two_chunks", "five_chunks",
                              "padded_37", "shorter_than_a_chunk"])
def test_chunked_scan_equals_the_recurrence_step_by_step(s):
    """Values and gradients, at chunk 16: one, two and several chunks, and
    lengths that are no multiple of the chunk (padded with steps of dt = 0
    that neither decay nor feed the state)."""
    args = scan_inputs(s)
    want = jax.jit(ssd_recurrence)(*args)
    got = jax.jit(lambda *a: ssd_scan(*a, chunk=16))(*args)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-4)
    probe = jnp.cos(jnp.arange(want.size, dtype=jnp.float32)).reshape(
        want.shape)
    grads = lambda f: jax.jit(jax.grad(lambda *a: jnp.sum(f(*a) * probe),
                                       argnums=(0, 1, 2, 3, 4)))(*args)
    for g_scan, g_step in zip(grads(lambda *a: ssd_scan(*a, chunk=16)),
                              grads(ssd_recurrence)):
        assert rel(g_scan, g_step) < 2e-5


def test_scan_refuses_heads_that_do_not_divide_into_groups():
    x, dt, a, b, c = scan_inputs(16)
    with pytest.raises(ValueError, match="ssd_scan: 8 heads .* 3 groups"):
        ssd_scan(x, dt, a, b[:, :, :1].repeat(3, 2), c[:, :, :1].repeat(3, 2),
                 chunk=16)


# ------------------------------------- the model against the reference


def program_loss_and_grads(params, tok, tgt, cfg):
    def loss(p):
        return lm.lm_loss(p, tok, tgt, cfg, seq_axis=None, tp_axis=None,
                          attn_impl="flash", axes=())
    return jax.value_and_grad(loss)(params)


@pytest.mark.parametrize("dtype,loss_tol,grad_tol,remat_policy", [
    (jnp.float32, 2e-6, 2e-5, ""),
    # bfloat16 compute against the float32 reference: rounding of every
    # activation to 2^-9; at these tiny widths the worst leaf's gradient
    # differs by 2-3 % of its norm (the cell's own limit is set on the chip)
    (jnp.bfloat16, 2e-3, 6e-2, ""),
    # a named policy changes what a block keeps, never the numbers
    (jnp.float32, 2e-6, 2e-5, "dots_saveable"),
], ids=["float32", "bfloat16", "float32-dots_saveable"])
def test_loss_and_gradients_equal_the_familys_reference(
        family, model, monkeypatch, dtype, loss_tol, grad_tol, remat_policy):
    traffic = dict(TRAFFIC, remat_policy=remat_policy)
    params, ref = family.reference.loss_and_grads(5, model, traffic)
    tok, tgt = batch_fn()(0)
    want_loss, want = ref(params, tok, tgt)
    cfg = family.program.config(model, traffic, dtype)
    # the blocks' policy comes from the one helper, by the traffic's name
    asked = []
    monkeypatch.setattr(
        nh, "block_remat_policy",
        lambda name: asked.append(name) or block_remat_policy(name))
    got_loss, got = program_loss_and_grads(params, tok, tgt, cfg)
    assert asked == [remat_policy]
    assert abs(float(got_loss) - float(want_loss)) <= loss_tol * float(
        want_loss)
    flat_want = dict(jax.tree.leaves_with_path(want))
    for path, g in jax.tree.leaves_with_path(got):
        if path[-1].key == "e_bias":
            assert not np.any(np.asarray(g))  # selects only: no gradient
            continue
        assert rel(g.astype(jnp.float32), flat_want[path]) < grad_tol, path


def test_three_steps_of_the_train_step_follow_the_references_three(
        family, model):
    cfg = family.program.config(model, TRAFFIC, jnp.float32)
    mesh = lm.create_lm_mesh(1, 1, 1)
    _, p_shard, _ = lm.make_lm_shardings(cfg, mesh, "adam")
    params = family.weights.make(5, model, shardings=p_shard)
    mom = lm.init_lm_momentum(params, mesh, "adam")
    step = lm.make_lm_train_step(cfg, mesh, lr=TRAFFIC["lr"],
                                 momentum=TRAFFIC["b1"], attn_impl="flash",
                                 optimizer="adam")
    bf = batch_fn()
    losses = []
    for i in range(3):
        params, mom, loss, routing = step(params, mom, *bf(i))
        losses.append(float(loss))
        assert not np.any(np.asarray(routing["dropped"]))
    ref = bench_train.reference_steps(5, family, model, TRAFFIC, bf)
    np.testing.assert_allclose(losses, ref["losses"], rtol=5e-6)
    from lib import compare, reference

    change = compare.flat_norms(jax.device_get(reference.diff_norms(
        params, family.weights.make(5, model))))
    moving = compare.moving_leaves(ref["grad"])
    gap, leaf = compare.worst_norm_gap(change, ref["change"], moving)
    assert gap < 1e-3, (gap, leaf)


def test_data_parallel_step_equals_the_single_chip_step(family, model):
    cfg = family.program.config(model, TRAFFIC, jnp.float32)
    bf = batch_fn()
    out = {}
    for dp in (1, 2):
        mesh = lm.create_lm_mesh(dp, 1, 1)
        _, p_shard, _ = lm.make_lm_shardings(cfg, mesh, "sgd")
        params = family.weights.make(5, model, shardings=p_shard)
        mom = lm.init_lm_momentum(params, mesh, "sgd")
        step = lm.make_lm_train_step(cfg, mesh, lr=0.01, attn_impl="flash")
        out[dp] = step(params, mom, *bf(0))
    assert float(out[1][2]) == pytest.approx(float(out[2][2]), rel=1e-6)
    for a, b in zip(jax.tree.leaves(out[1][0]), jax.tree.leaves(out[2][0])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)
    np.testing.assert_array_equal(out[1][3]["load"], out[2][3]["load"])


# ------------------------------------------------------------- routing


def expert_layer(key, t=96, d=16, routed=32, held=2, f=12, fs=20):
    k = jax.random.split(key, 7)
    return {"x": jax.random.normal(k[0], (t, d)),
            "e_router": jax.random.normal(k[1], (d, routed)),
            "e_bias": 0.1 * jax.random.normal(k[2], (routed,)),
            "e_up": jax.random.normal(k[3], (routed, d, f)) / 4,
            "e_down": jax.random.normal(k[4], (routed, f, d)) / 4,
            "e_shared_up": jax.random.normal(k[5], (d, fs)) / 4,
            "e_shared_down": jax.random.normal(k[6], (fs, d)) / 4}


@pytest.fixture
def tiles_of_8(monkeypatch):
    """Tiles small enough that 96 tokens fill several of them an expert."""
    monkeypatch.setattr(moe, "TILE", 8)


def share(lp, first, held, top_k=3):
    return moe.moe_held_ffn(
        lp["x"], lp["e_router"], lp["e_bias"],
        lp["e_up"][first:first + held], lp["e_down"][first:first + held],
        lp["e_shared_up"], lp["e_shared_down"], first=first, top_k=top_k,
        scale=2.5)


def test_all_sixteen_shares_add_up_to_the_uncut_layer_of_the_reference(
        family, tiles_of_8):
    """The share tied to the model: 16 chips of 2 experts each route over
    all 32 and compute their own experts' part; those parts, with the
    shared expert (which every chip computes alike) counted once, are the
    reference's expert layer holding all 32."""
    lp = expert_layer(jax.random.key(3))
    shared = moe.relu2(lp["x"] @ lp["e_shared_up"]) @ lp["e_shared_down"]
    total, held_pairs, absent_pairs = shared, 0, 0
    for chip in range(16):
        y, stats = share(lp, 2 * chip, 2)
        total = total + (y - shared)
        held_pairs += int(stats["held"])
        absent_pairs += int(stats["absent"])
        assert int(stats["dropped"]) == 0
    z = {"held": 32, "first": 0, "top_k": 3}
    want = family.reference.experts(lp["x"][None], lp, z, 2.5, "f32")[0]
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)
    # every pair is held by exactly one of the sixteen
    assert held_pairs == 96 * 3 and absent_pairs == 15 * 96 * 3


def test_no_pair_is_dropped_when_every_token_picks_the_same_held_experts(
        family, tiles_of_8):
    """Routing skewed to the worst case: the selection bias sends every
    token's three choices to held experts 0, 1 and 2, so every pair of every
    token lands here and expert 0 alone gets a pair of every token. The
    buffer is sized for exactly this; the counter stays 0 and the result is
    the reference's."""
    lp = expert_layer(jax.random.key(4))
    lp["e_bias"] = lp["e_bias"].at[:3].add(100.0)
    y, stats = jax.jit(lambda lp: share(lp, 0, 8))(lp)
    assert int(stats["held"]) == 96 * 3 and int(stats["absent"]) == 0
    assert int(stats["dropped"]) == 0
    assert stats["load"].tolist() == [96, 96, 96, 0, 0, 0, 0, 0]
    z = {"held": 8, "first": 0, "top_k": 3}
    held = dict(lp, e_up=lp["e_up"][:8], e_down=lp["e_down"][:8])
    want = family.reference.experts(lp["x"][None], held, z, 2.5, "f32")[0]
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    registry = MetricsRegistry()
    counters = lm.RoutingCounters(registry)
    counters.push(jax.tree.map(lambda a: a[None], stats))
    counters.flush()
    text = registry.render()
    assert 'lm_moe_pairs_total{where="held"} 288' in text
    assert 'lm_moe_pairs_total{where="absent"} 0' in text
    assert "lm_moe_dropped_total 0" in text
    assert 'lm_moe_expert_load_max_over_mean{layer="0"} 2.6666' in text


def test_gradients_reach_the_held_experts_through_the_tiles(tiles_of_8):
    lp = expert_layer(jax.random.key(6))
    names = ("x", "e_router", "e_up", "e_down")

    def dense(lp):
        s = jax.nn.sigmoid(lp["x"] @ lp["e_router"])
        _, idx = jax.lax.top_k(s + lp["e_bias"], 3)
        w = jnp.take_along_axis(s, idx, -1)
        w = 2.5 * w / w.sum(-1, keepdims=True)
        y = moe.relu2(lp["x"] @ lp["e_shared_up"]) @ lp["e_shared_down"]
        for e in range(8, 16):
            we = jnp.where(idx == e, w, 0).sum(-1)
            y = y + we[:, None] * (
                moe.relu2(lp["x"] @ lp["e_up"][e]) @ lp["e_down"][e])
        return jnp.sum(jnp.sin(y))

    got = jax.grad(lambda lp: jnp.sum(jnp.sin(share(lp, 8, 8)[0])))(lp)
    want = jax.grad(dense)(lp)
    for name in names:
        assert rel(got[name], want[name]) < 1e-5, name
    assert not np.any(np.asarray(got["e_up"][:8]))  # absent: untouched


# ------------------------------------------------------------ refusals


def tiny_cfg():
    with open(TINY) as f:
        return nh.from_published(json.load(f))


def refused_tp():
    lm.make_lm_train_step(tiny_cfg(), lm.create_lm_mesh(1, 1, 2))


def refused_sp():
    lm.make_lm_train_step(tiny_cfg(), lm.create_lm_mesh(1, 2, 1))


def refused_pp():
    from distributed_neural_network_tpu.parallel import pipeline as ppl

    ppl.make_pp_train_step(tiny_cfg(), ppl.create_pp_mesh(1, 2, 1))


def refused_expert_axis():
    cfg = tiny_cfg()
    params = nh.init_params(jax.random.key(0), cfg)
    nh.apply_hidden(params, jnp.zeros((1, 16), jnp.int32), cfg,
                    ep_axis="data")


def refused_serving():
    from distributed_neural_network_tpu.serve.engine import (
        EngineConfig,
        ServeEngine,
    )

    cfg = tiny_cfg()
    ServeEngine(nh.init_params(jax.random.key(0), cfg), cfg, EngineConfig())


@pytest.mark.parametrize("call,what", [
    (refused_tp, "tensor parallelism"), (refused_sp, "sequence parallelism"),
    (refused_pp, "pipeline"), (refused_expert_axis, "expert axis"),
    (refused_serving, "serving engine"),
], ids=["tp", "sp", "pp", "expert_axis", "ServeEngine"])
def test_refusals_name_the_model_and_the_axis(call, what):
    with pytest.raises(ValueError, match=f"nemotron_h: .*{what}"):
        call()


@pytest.mark.parametrize("kw", [dict(accum_steps=2), dict(optimizer="zero")],
                         ids=["accumulation", "zero"])
def test_steps_that_would_carry_the_counts_through_a_loop_are_refused(kw):
    with pytest.raises(ValueError, match="nemotron_h: .*not supported"):
        lm.make_lm_train_step(tiny_cfg(), lm.create_lm_mesh(1, 1, 1), **kw)


# ------------------------------------------------- scopes and the CLI


def test_the_compiled_steps_operations_carry_the_six_scopes(family, model):
    cfg = family.program.config(model, TRAFFIC, jnp.float32)
    mesh = lm.create_lm_mesh(1, 1, 1)
    params, mom = lm.abstract_lm_state(cfg, mesh, "adam")
    tok = jax.ShapeDtypeStruct((4, 32), jnp.int32)
    step = lm.make_lm_train_step(cfg, mesh, optimizer="adam",
                                 attn_impl="flash")
    text = step.lower(params, mom, tok, tok).as_text(debug_info=True)
    for scope in SCOPES:
        assert scope in text, scope


def test_partition_rules_name_every_leaf_of_the_tree():
    cfg = tiny_cfg()
    specs = nh.param_specs(cfg)
    shapes = jax.eval_shape(lambda k: nh.init_params(k, cfg),
                            jax.random.key(0))
    assert jax.tree.structure(specs) == jax.tree.structure(shapes)
    assert set(shapes["layers"]) == set(nh.layer_shapes(cfg))
    assert shapes["layers"]["e_up"].shape == (4, 8, 32, 24)


def test_lm_train_names_the_model_by_its_configuration_file():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "lm_train.py"),
         "--model-config", TINY, "--steps", "8", "--batch-size", "4",
         "--seq-len", "32", "--dp", "2", "--optimizer", "adam", "--lr",
         "0.003", "--remat", "--compilation-cache-dir", ""],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    summary = json.loads(next(
        line for line in proc.stdout.splitlines()
        if line.startswith("SUMMARY "))[len("SUMMARY "):])
    assert summary["mesh"] == "data2"
    assert summary["final_loss"] < summary["first_loss"] - 0.5, summary
