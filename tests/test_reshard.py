"""Mesh-portable checkpoint resharding (parallel/reshard.py,
train/elastic.py; docs/ROBUSTNESS.md "Elastic resume").

Three layers, mirroring the subsystem:

- host-level transforms - spec/topology (de)serialization, ZeRO buffer
  re-padding, optimizer-layout conversion, accumulation rescale - all
  version-portable pure functions, bitwise-pinned;
- placement + checkpoint round trips on the 8-device CPU mesh: a state
  saved under one mesh shape restores onto another (dp8 -> dp4,
  dp8 -> dp2 x tp2, zero -> non-zero and back) through the real
  TreeCheckpointer, leaf values bitwise equal, shardings correct. None
  of this needs `jax.shard_map`, which is exactly what makes the
  reshard path testable on the pinned CI container;
- the CLI e2e (kill -> resume on a smaller mesh, in-process
  --chaos-shrink-at-step) - subprocess runs, slow-marked, requiring a
  modern jax like the other mesh-execution suites.
"""

import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_neural_network_tpu.models import transformer as tfm
from distributed_neural_network_tpu.parallel import reshard as R
from distributed_neural_network_tpu.train import elastic as E, lm as lmtrain
from distributed_neural_network_tpu.train.guard import resume_cursor
from distributed_neural_network_tpu.utils.checkpoint import (
    CheckpointCorruptError,
    TreeCheckpointer,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

requires_shard_map = pytest.mark.skipif(
    not hasattr(jax, "shard_map"),
    reason="needs jax.shard_map with vma-typed autodiff",
)


def _cfg(**kw):
    base = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64)
    base.update(kw)
    return tfm.TransformerConfig(**base)


def _host(tree):
    return jax.tree.map(lambda x: np.asarray(x), tree)


def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------ spec / topology (de)serde


def test_spec_json_roundtrip():
    for spec in (P(), P("data"), P(None, "model"), P(("pipe", "data")),
                 P(None, None, "model")):
        doc = R.spec_to_json(spec)
        json.dumps(doc)  # JSON-serializable
        assert R.spec_from_json(doc) == spec


def test_spec_tree_json_roundtrip():
    specs = tfm.param_specs(_cfg(), tp_axis="model")
    doc = R.spec_tree_to_json(specs)
    json.dumps(doc)
    back = R.spec_tree_from_json(doc)
    flat_a = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))
    flat_b = jax.tree.leaves(back, is_leaf=lambda s: isinstance(s, P))
    assert flat_a == flat_b


def test_mesh_topology_records_layout(n_devices):
    mesh = lmtrain.create_lm_mesh(4, 1, 2)
    specs = lmtrain.lm_wiring(_cfg(), mesh, "sgd")[4]
    topo = R.mesh_topology(mesh, specs=specs, optimizer="sgd", global_batch=32)
    json.dumps(topo)
    assert topo["axes"] == {"data": 4, "seq": 1, "model": 2}
    assert topo["devices"] == 8 and topo["process_count"] == 1
    assert topo["optimizer"] == "sgd" and topo["global_batch"] == 32
    back = R.spec_tree_from_json(topo["specs"])
    assert back["layers"]["wq"] == P(None, None, "model")


def test_topology_mismatch_names_differences(n_devices):
    m8 = lmtrain.create_lm_mesh(8, 1, 1)
    m22 = lmtrain.create_lm_mesh(2, 1, 2)
    a = R.mesh_topology(m8, optimizer="zero")
    assert R.topology_mismatch(a, R.mesh_topology(m8, optimizer="zero")) == []
    diffs = R.topology_mismatch(a, R.mesh_topology(m22, optimizer="sgd"))
    text = " / ".join(diffs)
    assert "'data': saved 8, target 2" in text
    assert "'model': saved 1, target 2" in text
    assert "device count: saved 8, target 4" in text
    assert "optimizer layout: saved 'zero', target 'sgd'" in text
    # interleave is layout-bearing (the layer axis is permuted on device)
    assert R.topology_mismatch({**a, "pp_interleave": 2}, a) == [
        "pp_interleave: saved 2, target 1"
    ]


# -------------------------------------------------- ZeRO layout transforms


def test_reshard_zero_leaf_repads_bitwise():
    # d=10: pad(10, 8) = 16, pad(10, 4) = 12, pad(10, 2) = 10
    buf8 = np.zeros(16, np.float32)
    buf8[:10] = np.arange(10, dtype=np.float32) + 1
    buf4 = R.reshard_zero_leaf(buf8, 10, 4)
    assert buf4.shape == (12,)
    np.testing.assert_array_equal(buf4[:10], buf8[:10])
    np.testing.assert_array_equal(buf4[10:], 0.0)
    back = R.reshard_zero_leaf(buf4, 10, 8)
    np.testing.assert_array_equal(back, buf8)
    with pytest.raises(ValueError, match="cannot hold"):
        R.reshard_zero_leaf(np.zeros(4, np.float32), 10, 2)


def test_zero_tree_momentum_roundtrip_bitwise():
    from distributed_neural_network_tpu.parallel.zero import (
        init_zero_momentum_tree,
    )

    params = _host(tfm.init_params(jax.random.key(0), _cfg()))
    flat = init_zero_momentum_tree(params, 8)
    rng = np.random.default_rng(0)
    flat = jax.tree.map(
        lambda b: rng.standard_normal(b.shape).astype(np.float32), flat
    )
    # zero the per-leaf padding: those slots carry no logical value and
    # are (correctly) not preserved by the round trip
    flat = jax.tree.map(
        lambda b, p: np.concatenate(
            [b[: p.size], np.zeros(b.size - p.size, np.float32)]
        ),
        flat, params,
    )
    mom = R.zero_tree_to_momentum(flat, params)
    for m, p in zip(jax.tree.leaves(mom), jax.tree.leaves(params)):
        assert m.shape == p.shape
    back = R.momentum_to_zero_tree(mom, 8)
    _assert_trees_equal(back, flat)


def test_convert_same_optimizer_repads_for_new_dp():
    from distributed_neural_network_tpu.parallel.zero import (
        init_zero_adam_tree,
    )

    params = _host(tfm.init_params(jax.random.key(0), _cfg()))
    st = init_zero_adam_tree(params, 8)
    st = {
        "m": jax.tree.map(lambda b: b + 1.0, st["m"]),
        "v": jax.tree.map(lambda b: b + 2.0, st["v"]),
        "t": st["t"],
    }
    out = R.convert_optimizer_state(
        st, src="zero-adam", dst="zero-adam", params_template=params,
        src_dp=8, dst_dp=4,
    )
    from distributed_neural_network_tpu.parallel.zero import leaf_shard_size

    for buf, p in zip(jax.tree.leaves(out["m"]), jax.tree.leaves(params)):
        assert buf.shape == (leaf_shard_size(p.size, 4) * 4,)
    # non-elastic identity: no dp change, state passes through untouched
    same = R.convert_optimizer_state(
        st, src="zero-adam", dst="zero-adam", params_template=params,
        src_dp=8, dst_dp=8,
    )
    assert same is st


def test_convert_cross_family_rejected():
    params = _host(tfm.init_params(jax.random.key(0), _cfg()))
    with pytest.raises(ValueError, match="sgd<->zero"):
        R.convert_optimizer_state(
            params, src="sgd", dst="adam", params_template=params,
            src_dp=1, dst_dp=1,
        )
    with pytest.raises(ValueError, match="unknown saved optimizer"):
        R.convert_optimizer_state(
            params, src="lion", dst="sgd", params_template=params,
            src_dp=1, dst_dp=1,
        )


def test_zero_to_sgd_and_back_bitwise():
    from distributed_neural_network_tpu.parallel.zero import (
        init_zero_momentum_tree,
    )

    params = _host(tfm.init_params(jax.random.key(0), _cfg()))
    flat = init_zero_momentum_tree(params, 8)
    rng = np.random.default_rng(1)
    flat = jax.tree.map(
        lambda b, p: np.concatenate([
            rng.standard_normal(p.size).astype(np.float32),
            np.zeros(b.size - p.size, np.float32),
        ]),
        flat, params,
    )
    sgd = R.convert_optimizer_state(
        flat, src="zero", dst="sgd", params_template=params,
        src_dp=8, dst_dp=4,
    )
    back = R.convert_optimizer_state(
        sgd, src="sgd", dst="zero", params_template=params,
        src_dp=4, dst_dp=8,
    )
    _assert_trees_equal(back, flat)


# --------------------------------------------------- batch / accum rescale


def test_rescale_accum_keeps_global_batch():
    # shrink: accum scales up so per-device microbatch rows stay constant
    assert R.rescale_accum(32, 8, 4, 1) == 2
    assert R.rescale_accum(32, 8, 2, 2) == 8
    # grow: accum scales down
    assert R.rescale_accum(32, 4, 8, 2) == 1
    # non-integral scale falls back to a slicing that still divides
    assert R.rescale_accum(24, 8, 3, 1) in (1, 2, 4, 8)
    assert 24 % (3 * R.rescale_accum(24, 8, 3, 1)) == 0
    with pytest.raises(ValueError, match="does not divide"):
        R.rescale_accum(32, 8, 5, 1)
    with pytest.raises(ValueError, match="new_dp"):
        R.rescale_accum(32, 8, 0, 1)


def test_rescaled_accum_steps_reads_saved_meta(n_devices):
    mesh = lmtrain.create_lm_mesh(8, 1, 1)
    saved = R.mesh_topology(mesh, global_batch=32, accum_steps=1)
    assert E.rescaled_accum_steps(saved, batch=32, new_dp=4,
                                  accum_steps=1) == 2
    # a deliberately changed global batch keeps the requested slicing
    assert E.rescaled_accum_steps(saved, batch=64, new_dp=4,
                                  accum_steps=3) == 3
    # checkpoints without the batch facts keep the requested value
    assert E.rescaled_accum_steps({}, batch=32, new_dp=4,
                                  accum_steps=5) == 5


# ------------------------------------------------ engine momentum stack


def test_reshard_momentum_stack_shrink_and_grow():
    stack = {"w": np.arange(8 * 3, dtype=np.float32).reshape(8, 3)}
    out = R.reshard_momentum_stack(stack, 4)
    np.testing.assert_array_equal(out["w"], stack["w"][:4])
    grown = R.reshard_momentum_stack(stack, 12)
    np.testing.assert_array_equal(grown["w"][:8], stack["w"])
    np.testing.assert_array_equal(grown["w"][8:], 0.0)
    with pytest.raises(ValueError, match="n_new"):
        R.reshard_momentum_stack(stack, 0)


# ------------------------------------------- placement across mesh shapes


def test_place_tree_cross_mesh_values_and_shardings(n_devices):
    cfg = _cfg()
    mesh8 = lmtrain.create_lm_mesh(8, 1, 1)
    params = tfm.init_params(jax.random.key(0), cfg)
    params8, _ = lmtrain.shard_params(params, cfg, mesh8)
    mesh22 = lmtrain.create_lm_mesh(2, 1, 2)
    specs22 = lmtrain.lm_wiring(cfg, mesh22, "sgd")[4]
    shardings = jax.tree.map(lambda s: NamedSharding(mesh22, s), specs22)
    placed = R.place_tree(params8, shardings)  # device -> device transfer
    assert placed["layers"]["wq"].sharding.spec == P(None, None, "model")
    assert placed["embed"].sharding.mesh.shape == {"data": 2, "seq": 1,
                                                   "model": 2}
    _assert_trees_equal(placed, params)
    # host numpy -> mesh placement takes the same path
    placed2 = R.place_tree(_host(params), shardings)
    _assert_trees_equal(placed2, params)


# --------------------------------------- checkpoint round trips (elastic)


def _save_checkpoint(tmp_path, cfg, *, dp, optimizer, step=7, seed=0,
                     batch=32, accum=1, mom_perturb=0.5):
    """A real TreeCheckpointer save under (dp, optimizer) with the
    elastic mesh_meta block lm_train.py writes; returns (ck, params, mom)
    with `mom` perturbed away from zero so value mapping is observable."""
    mesh = lmtrain.create_lm_mesh(dp, 1, 1)
    params = tfm.init_params(jax.random.key(seed), cfg)
    params, specs = lmtrain.shard_params(params, cfg, mesh)
    mom = lmtrain.init_lm_momentum(params, mesh, optimizer)
    if mom_perturb:
        if optimizer in ("adam", "zero-adam"):
            mom = {
                "m": jax.tree.map(lambda b: b + mom_perturb, mom["m"]),
                "v": jax.tree.map(lambda b: b + 2 * mom_perturb, mom["v"]),
                "t": mom["t"],
            }
        else:
            mom = jax.tree.map(lambda b: b + mom_perturb, mom)
    ck = TreeCheckpointer(str(tmp_path / "ck"), backend="npz")
    meta = {
        "optimizer": optimizer,
        "mesh_meta": E.lm_mesh_meta(
            mesh, specs, optimizer, batch=batch, accum_steps=accum
        ),
        **resume_cursor(step=step, seed=seed),
    }
    ck.save(step, {"params": params, "mom": mom}, meta)
    return ck, params, mom


def _target(cfg, *, dp, tp=1, optimizer):
    mesh = lmtrain.create_lm_mesh(dp, 1, tp)
    specs, ps, ms = lmtrain.make_lm_shardings(cfg, mesh, optimizer)
    return mesh, specs, ps, ms


def test_saved_state_template_matches_all_optimizers(n_devices):
    cfg = _cfg()
    for optimizer in ("sgd", "adam", "zero", "zero-adam"):
        mesh = lmtrain.create_lm_mesh(8, 1, 1)
        params = tfm.init_params(jax.random.key(0), cfg)
        params, _ = lmtrain.shard_params(params, cfg, mesh)
        mom = lmtrain.init_lm_momentum(params, mesh, optimizer)
        tpl = E.saved_state_template(
            cfg, {"optimizer": optimizer, "axes": {"data": 8}}
        )
        want = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), tpl)
        got = jax.tree.map(
            lambda x: (tuple(x.shape), str(np.asarray(x).dtype)),
            {"params": params, "mom": mom},
        )
        assert jax.tree.structure(want) == jax.tree.structure(got)
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            assert a == b, (optimizer, a, b)


def test_saved_state_template_pp_zero_matches_init(n_devices):
    """The ZeRO-under-pp template rebuilds init_pp_zero_state's per-stage
    split (pp segments of dp-padded stage-local buffers) exactly - shapes,
    dtypes, and tree structure - for both zero and zero-adam."""
    from distributed_neural_network_tpu.parallel.pipeline import (
        create_pp_mesh,
        init_pp_zero_state,
        pp_param_specs,
    )

    cfg = _cfg()
    mesh = create_pp_mesh(2, 2, 1)
    params = jax.eval_shape(
        lambda k: tfm.init_params(k, cfg),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    )
    specs = pp_param_specs(cfg)
    for optimizer in ("zero", "zero-adam"):
        want = jax.eval_shape(
            lambda p: init_pp_zero_state(p, specs, mesh, optimizer), params
        )
        tpl = E.saved_state_template(
            cfg, {"optimizer": optimizer, "axes": {"data": 2, "pipe": 2}}
        )
        assert jax.tree.structure(tpl["mom"]) == jax.tree.structure(want)
        for a, b in zip(jax.tree.leaves(tpl["mom"]), jax.tree.leaves(want)):
            assert (tuple(a.shape), a.dtype) == (tuple(b.shape), b.dtype)


def test_pp_zero_tree_momentum_roundtrip_bitwise(n_devices):
    """momentum -> ZeRO-under-pp flat buffers -> momentum is bitwise, and
    the stage-major segment layout holds each stage's contiguous layer
    chunk (the DeepSpeed ZeRO-1 + PP convention)."""
    from distributed_neural_network_tpu.parallel.pipeline import (
        pp_param_specs,
    )
    from distributed_neural_network_tpu.parallel.zero import leaf_shard_size

    cfg = _cfg()
    params = _host(tfm.init_params(jax.random.key(0), _cfg()))
    specs = pp_param_specs(cfg)
    rng = np.random.default_rng(0)
    mom = jax.tree.map(
        lambda p: rng.standard_normal(p.shape).astype(np.float32), params
    )
    flat = R.momentum_to_pp_zero_tree(mom, specs, 2, 2)
    # layer leaves carry the per-stage split: pp * dp * S elements
    wq, wq_m = flat["layers"]["wq"], mom["layers"]["wq"]
    local = wq_m.size // 2
    seg = 2 * leaf_shard_size(local, 2)
    assert wq.shape == (2 * seg,)
    np.testing.assert_array_equal(
        wq[:local], wq_m.reshape(-1)[:local]  # stage 0 = first layers
    )
    np.testing.assert_array_equal(
        wq[seg:seg + local], wq_m.reshape(-1)[local:]  # stage 1
    )
    # replicated leaves use the plain dp-padded layout
    assert flat["embed"].shape == (
        2 * leaf_shard_size(mom["embed"].size, 2),
    )
    back = R.pp_zero_tree_to_momentum(flat, params, specs, 2)
    _assert_trees_equal(back, mom)


def test_convert_pp_zero_roundtrips_bitwise(n_devices):
    """pp2/zero -> sgd -> pp2/zero and pp2/zero-adam -> adam -> back:
    the per-stage split survives two layout conversions bitwise, and the
    converter demands pp_specs when a stage split is involved."""
    from distributed_neural_network_tpu.parallel.pipeline import (
        pp_param_specs,
    )

    cfg = _cfg()
    params = _host(tfm.init_params(jax.random.key(0), cfg))
    specs = pp_param_specs(cfg)
    rng = np.random.default_rng(1)
    mom = jax.tree.map(
        lambda p: rng.standard_normal(p.shape).astype(np.float32), params
    )
    flat = R.momentum_to_pp_zero_tree(mom, specs, 2, 2)
    sgd = R.convert_optimizer_state(
        flat, src="zero", dst="sgd", params_template=params,
        src_dp=2, dst_dp=1, src_pp=2, pp_specs=specs,
    )
    _assert_trees_equal(sgd, mom)
    back = R.convert_optimizer_state(
        sgd, src="sgd", dst="zero", params_template=params,
        src_dp=1, dst_dp=2, dst_pp=2, pp_specs=specs,
    )
    _assert_trees_equal(back, flat)
    za = {"m": flat, "v": jax.tree.map(lambda x: x + 1.0, flat),
          "t": np.int32(5)}
    adam = R.convert_optimizer_state(
        za, src="zero-adam", dst="adam", params_template=params,
        src_dp=2, dst_dp=1, src_pp=2, pp_specs=specs,
    )
    _assert_trees_equal(adam["m"], mom)
    za2 = R.convert_optimizer_state(
        adam, src="adam", dst="zero-adam", params_template=params,
        src_dp=1, dst_dp=2, dst_pp=2, pp_specs=specs,
    )
    _assert_trees_equal(za2["m"], za["m"])
    _assert_trees_equal(za2["v"], za["v"])
    assert int(za2["t"]) == 5
    with pytest.raises(ValueError, match="pp_specs"):
        R.convert_optimizer_state(
            flat, src="zero", dst="sgd", params_template=params,
            src_dp=2, dst_dp=1, src_pp=2,
        )


def test_elastic_restore_matching_topology_is_plain(tmp_path, n_devices):
    cfg = _cfg()
    ck, params, mom = _save_checkpoint(tmp_path, cfg, dp=4, optimizer="sgd")
    mesh, specs, ps, ms = _target(cfg, dp=4, optimizer="sgd")
    out = E.elastic_restore(
        ck, cfg=cfg, mesh=mesh, specs=specs, optimizer="sgd",
        param_shardings=ps, mom_shardings=ms,
        current_meta=E.lm_mesh_meta(mesh, specs, "sgd", batch=32,
                                    accum_steps=1),
        log=lambda *_: None,
    )
    state, meta, step, resharded = out
    assert step == 7 and resharded is False
    _assert_trees_equal(state["params"], params)
    _assert_trees_equal(state["mom"], mom)
    ck.close()


@pytest.mark.parametrize("dp,tp", [(4, 1), (2, 2)])
def test_elastic_restore_dp8_onto_smaller_mesh(tmp_path, n_devices, dp, tp):
    """The acceptance shapes: a dp=8 checkpoint restores onto dp=4 and
    onto dp=2 x tp=2 with bitwise-equal values and correct shardings."""
    cfg = _cfg()
    ck, params, mom = _save_checkpoint(tmp_path, cfg, dp=8, optimizer="sgd")
    mesh, specs, ps, ms = _target(cfg, dp=dp, tp=tp, optimizer="sgd")
    out = E.elastic_restore(
        ck, cfg=cfg, mesh=mesh, specs=specs, optimizer="sgd",
        param_shardings=ps, mom_shardings=ms,
        current_meta=E.lm_mesh_meta(mesh, specs, "sgd", batch=32,
                                    accum_steps=1),
        log=lambda *_: None,
    )
    state, meta, step, resharded = out
    assert resharded is True and step == 7
    _assert_trees_equal(state["params"], params)
    _assert_trees_equal(state["mom"], mom)
    assert state["params"]["embed"].sharding.mesh.shape["data"] == dp
    if tp > 1:
        assert state["params"]["layers"]["wq"].sharding.spec == P(
            None, None, "model"
        )
    ck.close()


def test_elastic_restore_zero_to_sgd_and_back_bitwise(tmp_path, n_devices):
    """zero(dp8) -> sgd(dp4) -> zero(dp8): the momentum survives two
    layout conversions and a shard-count round trip bitwise."""
    cfg = _cfg()
    ck, params, mom = _save_checkpoint(tmp_path, cfg, dp=8, optimizer="zero")
    mesh4, specs4, ps4, ms4 = _target(cfg, dp=4, optimizer="sgd")
    state, meta, step, resharded = E.elastic_restore(
        ck, cfg=cfg, mesh=mesh4, specs=specs4, optimizer="sgd",
        param_shardings=ps4, mom_shardings=ms4,
        current_meta=E.lm_mesh_meta(mesh4, specs4, "sgd", batch=32,
                                    accum_steps=1),
        log=lambda *_: None,
    )
    assert resharded
    # save the sgd layout, restore back into zero(dp8)
    meta2 = {
        "mesh_meta": E.lm_mesh_meta(mesh4, specs4, "sgd", batch=32,
                                    accum_steps=2),
        **resume_cursor(step=9, seed=0),
    }
    ck.save(9, state, meta2)
    mesh8, specs8, ps8, ms8 = _target(cfg, dp=8, optimizer="zero")
    state2, _, step2, resharded2 = E.elastic_restore(
        ck, cfg=cfg, mesh=mesh8, specs=specs8, optimizer="zero",
        param_shardings=ps8, mom_shardings=ms8,
        current_meta=E.lm_mesh_meta(mesh8, specs8, "zero", batch=32,
                                    accum_steps=1),
        log=lambda *_: None,
    )
    assert resharded2 and step2 == 9
    _assert_trees_equal(state2["params"], params)
    _assert_trees_equal(state2["mom"], mom)
    ck.close()


def test_elastic_restore_zero_adam_to_adam(tmp_path, n_devices):
    cfg = _cfg()
    ck, params, mom = _save_checkpoint(
        tmp_path, cfg, dp=8, optimizer="zero-adam"
    )
    mesh4, specs4, ps4, ms4 = _target(cfg, dp=4, optimizer="adam")
    state, _, _, resharded = E.elastic_restore(
        ck, cfg=cfg, mesh=mesh4, specs=specs4, optimizer="adam",
        param_shardings=ps4, mom_shardings=ms4,
        current_meta=E.lm_mesh_meta(mesh4, specs4, "adam", batch=32,
                                    accum_steps=1),
        log=lambda *_: None,
    )
    assert resharded
    # every m leaf carries the 0.5 perturbation, v the 1.0, t untouched
    np.testing.assert_array_equal(
        np.asarray(state["mom"]["m"]["embed"]),
        np.full((64, 32), 0.5, np.float32),
    )
    np.testing.assert_array_equal(
        np.asarray(state["mom"]["v"]["embed"]),
        np.full((64, 32), 1.0, np.float32),
    )
    assert int(state["mom"]["t"]) == int(mom["t"])
    ck.close()


def test_elastic_restore_interleaved_pipe_to_mesh(tmp_path, n_devices):
    """A checkpoint written under the interleaved pipeline layout (layer
    axis permuted on device) restores onto the plain mesh in canonical
    layer order."""
    from distributed_neural_network_tpu.parallel.pipeline import (
        create_pp_mesh,
        interleave_layer_order,
    )

    cfg = _cfg(n_layers=4)
    mesh_pp = create_pp_mesh(1, 2, 1)
    params = _host(tfm.init_params(jax.random.key(0), cfg))
    order = interleave_layer_order(4, 2, 2)
    permuted = {
        **params,
        "layers": jax.tree.map(lambda x: x[np.asarray(order)],
                               params["layers"]),
    }
    mom = jax.tree.map(np.zeros_like, permuted)
    ck = TreeCheckpointer(str(tmp_path / "ck"), backend="npz")
    ck.save(3, {"params": permuted, "mom": mom}, {
        "mesh_meta": R.mesh_topology(
            mesh_pp, optimizer="sgd", global_batch=32, accum_steps=1,
            pp_interleave=2,
        ),
        **resume_cursor(step=3, seed=0),
    })
    mesh, specs, ps, ms = _target(cfg, dp=2, optimizer="sgd")
    state, _, _, resharded = E.elastic_restore(
        ck, cfg=cfg, mesh=mesh, specs=specs, optimizer="sgd",
        param_shardings=ps, mom_shardings=ms,
        current_meta=E.lm_mesh_meta(mesh, specs, "sgd", batch=32,
                                    accum_steps=1),
        log=lambda *_: None,
    )
    assert resharded
    _assert_trees_equal(state["params"], params)  # canonical order again
    ck.close()


def _save_pp_zero_checkpoint(tmp_path, cfg, *, dp=2, pp=2, step=7,
                             interleave=1, seed=0):
    """A real checkpoint saved under a dp x pp mesh with ZeRO state whose
    buffers derive from a known momentum tree; returns (ck, host params
    in CANONICAL layer order, canonical momentum values, flat buffers as
    saved)."""
    from distributed_neural_network_tpu.parallel.pipeline import (
        create_pp_mesh,
        init_pp_zero_state,
        interleave_layer_order,
        pp_param_specs,
        shard_pp_params,
    )

    mesh = create_pp_mesh(dp, pp, 1)
    params_c = _host(tfm.init_params(jax.random.key(seed), cfg))
    rng = np.random.default_rng(seed + 1)
    mom_c = jax.tree.map(
        lambda p: rng.standard_normal(p.shape).astype(np.float32), params_c
    )
    params_p, mom_p = params_c, mom_c
    if interleave > 1:
        order = np.asarray(
            interleave_layer_order(cfg.n_layers, pp, interleave)
        )
        perm = lambda t: {
            **t, "layers": jax.tree.map(lambda x: x[order], t["layers"]),
        }
        params_p, mom_p = perm(params_c), perm(mom_c)
    specs = pp_param_specs(cfg)
    flat = R.momentum_to_pp_zero_tree(mom_p, specs, pp, dp)
    placed, pspecs = shard_pp_params(
        jax.tree.map(jnp.asarray, params_c), cfg, mesh,
        interleave=interleave,
    )
    state_abs = init_pp_zero_state(placed, pspecs, mesh, "zero")
    mom_dev = jax.tree.map(
        lambda h, m: jax.device_put(h, m.sharding), flat, state_abs
    )
    ck = TreeCheckpointer(str(tmp_path / "ck"), backend="npz")
    ck.save(step, {"params": placed, "mom": mom_dev}, {
        "optimizer": "zero",
        "mesh_meta": E.lm_mesh_meta(
            mesh, pspecs, "zero", batch=16, accum_steps=1,
            pp_interleave=interleave,
        ),
        **resume_cursor(step=step, seed=seed),
    })
    return ck, params_c, mom_c, flat


def test_elastic_restore_pp_zero_roundtrip_bitwise(tmp_path, n_devices):
    """The acceptance shape: pp2 x dp2 / zero -> dp4 / zero -> back to
    pp2 x dp2 / zero through real checkpoints; optimizer state bitwise at
    every hop (the combination saved_state_template used to reject)."""
    from distributed_neural_network_tpu.parallel.pipeline import (
        create_pp_mesh,
        pp_optimizer_state_specs,
        pp_wiring,
    )

    cfg = _cfg()
    ck, params_c, mom_c, flat = _save_pp_zero_checkpoint(tmp_path, cfg)
    mesh4, specs4, ps4, ms4 = _target(cfg, dp=4, optimizer="zero")
    state, meta, step, resharded = E.elastic_restore(
        ck, cfg=cfg, mesh=mesh4, specs=specs4, optimizer="zero",
        param_shardings=ps4, mom_shardings=ms4,
        current_meta=E.lm_mesh_meta(mesh4, specs4, "zero", batch=16,
                                    accum_steps=1),
        log=lambda *_: None,
    )
    assert resharded and step == 7
    _assert_trees_equal(state["params"], params_c)
    _assert_trees_equal(state["mom"], R.momentum_to_zero_tree(mom_c, 4))
    # save the dp4 layout and restore BACK into the per-stage split
    ck.save(9, state, {
        "optimizer": "zero",
        "mesh_meta": E.lm_mesh_meta(mesh4, specs4, "zero", batch=16,
                                    accum_steps=1),
        **resume_cursor(step=9, seed=0),
    })
    mesh_pp = create_pp_mesh(2, 2, 1)
    pspecs = pp_wiring(cfg, mesh_pp)[3]
    ps = jax.tree.map(lambda s: NamedSharding(mesh_pp, s), pspecs)
    ms = jax.tree.map(
        lambda s: NamedSharding(mesh_pp, s),
        pp_optimizer_state_specs("zero", pspecs),
    )
    state2, _, step2, resharded2 = E.elastic_restore(
        ck, cfg=cfg, mesh=mesh_pp, specs=pspecs, optimizer="zero",
        param_shardings=ps, mom_shardings=ms,
        current_meta=E.lm_mesh_meta(mesh_pp, pspecs, "zero", batch=16,
                                    accum_steps=1),
        log=lambda *_: None,
    )
    assert resharded2 and step2 == 9
    _assert_trees_equal(state2["params"], params_c)
    _assert_trees_equal(state2["mom"], flat)
    ck.close()


def test_elastic_restore_interleaved_pp_zero_to_mesh(tmp_path, n_devices):
    """ZeRO saved under the INTERLEAVED pipeline layout: the flat buffers
    follow the placed (permuted) layer order, so the restore first
    reassembles them into the replicated family layout, applies the same
    layer-order mapping as the params, and lands in canonical order."""
    cfg = _cfg(n_layers=4)
    ck, params_c, mom_c, _ = _save_pp_zero_checkpoint(
        tmp_path, cfg, interleave=2
    )
    mesh, specs, ps, ms = _target(cfg, dp=2, optimizer="sgd")
    state, _, _, resharded = E.elastic_restore(
        ck, cfg=cfg, mesh=mesh, specs=specs, optimizer="sgd",
        param_shardings=ps, mom_shardings=ms,
        current_meta=E.lm_mesh_meta(mesh, specs, "sgd", batch=16,
                                    accum_steps=1),
        log=lambda *_: None,
    )
    assert resharded
    _assert_trees_equal(state["params"], params_c)
    _assert_trees_equal(state["mom"], mom_c)
    ck.close()


def test_elastic_restore_empty_dir_returns_none(tmp_path, n_devices):
    cfg = _cfg()
    ck = TreeCheckpointer(str(tmp_path / "ck"), backend="npz")
    mesh, specs, ps, ms = _target(cfg, dp=4, optimizer="sgd")
    assert E.elastic_restore(
        ck, cfg=cfg, mesh=mesh, specs=specs, optimizer="sgd",
        param_shardings=ps, mom_shardings=ms, log=lambda *_: None,
    ) is None
    ck.close()


# ------------------------------- npz backend: per-leaf sharded restore


def test_npz_restore_places_each_leaf_on_its_sharding(tmp_path, n_devices):
    """restore_latest(shardings=...) applies the target NamedSharding at
    restore time, per leaf - the restored leaves come back as committed
    device arrays on the right mesh, not host arrays re-placed later."""
    import jax.numpy as jnp

    mesh = lmtrain.create_lm_mesh(8, 1, 1)
    tree = {"a": jnp.arange(16.0).reshape(8, 2), "b": jnp.ones((3,))}
    shardings = {
        "a": NamedSharding(mesh, P("data")),
        "b": NamedSharding(mesh, P()),
    }
    ck = TreeCheckpointer(str(tmp_path / "ck"), backend="npz")
    ck.save(1, tree, {})
    state, meta, step = ck.restore_latest(tree, shardings)
    assert step == 1
    assert state["a"].sharding.spec == P("data")
    assert next(iter(state["a"].addressable_shards)).data.shape == (1, 2)
    _assert_trees_equal(state, tree)
    ck.close()


def test_corrupt_error_names_leaf_path(tmp_path):
    import jax.numpy as jnp

    tree = {"params": {"wq": jnp.zeros((4, 2))}, "mom": jnp.ones((3,))}
    ck = TreeCheckpointer(str(tmp_path / "ck"), backend="npz")
    ck.save(1, tree, {})
    with pytest.raises(CheckpointCorruptError, match=r"\['params'\]\['wq'\]"):
        ck._b.restore(
            1, {"params": {"wq": jnp.zeros((4, 3))}, "mom": jnp.ones((3,))}
        )
    with pytest.raises(CheckpointCorruptError, match=r"\['mom'\] dtype"):
        ck._b.restore(
            1,
            {"params": {"wq": jnp.zeros((4, 2))},
             "mom": jnp.ones((3,), jnp.int32)},
        )
    ck.close()


def test_latest_meta_skips_corrupt_newest(tmp_path):
    import jax.numpy as jnp

    tree = {"a": jnp.zeros((2,))}
    ck = TreeCheckpointer(str(tmp_path / "ck"), backend="npz", keep=0)
    ck.save(1, tree, {"note": "one"})
    ck.save(2, tree, {"note": "two"})
    (tmp_path / "ck" / "step_2" / "meta.json").write_text("{not json")
    step, meta = ck.latest_meta(log=lambda *_: None)
    assert step == 1 and meta["note"] == "one"
    ck.close()


# --------------------------------------------- device transfer program


def test_reshard_step_program_traces_with_gather(n_devices):
    """The shardlint config: one tiled all_gather over 'data' per state
    leaf, at the padded buffer size (the same contract the checked-in
    manifest pins)."""
    from distributed_neural_network_tpu.analysis.trace import collect_trace

    cfg = _cfg()
    mesh = lmtrain.create_lm_mesh(4, 1, 1)
    prog = R.reshard_step_program(cfg, mesh)
    facts = collect_trace(prog.make_jaxpr())
    n_leaves = len(jax.tree.leaves(prog.abstract_args[0]))
    gathers = [c for c in facts.collectives if c.op == "all_gather"]
    assert sum(c.count for c in gathers) == n_leaves
    assert all(c.axes == ("data",) for c in gathers)
    total = facts.total_collective_bytes()
    buf_bytes = sum(
        int(np.prod(leaf.shape, dtype=np.int64)) * 4
        for leaf in jax.tree.leaves(prog.abstract_args[0])
    )
    assert total == buf_bytes


@requires_shard_map
def test_zero_gather_fn_matches_host_transform(n_devices):
    """Executed parity (modern jax): the collective reassembly equals the
    host-level zero_tree_to_momentum bitwise."""
    from distributed_neural_network_tpu.parallel.zero import (
        init_zero_momentum_tree,
    )

    cfg = _cfg()
    mesh = lmtrain.create_lm_mesh(4, 1, 1)
    params = _host(tfm.init_params(jax.random.key(0), cfg))
    flat = init_zero_momentum_tree(params, 4)
    rng = np.random.default_rng(2)
    flat = jax.tree.map(
        lambda b: rng.standard_normal(b.shape).astype(np.float32), flat
    )
    placed = jax.tree.map(
        lambda b: jax.device_put(b, NamedSharding(mesh, P("data"))), flat
    )
    fn = R.make_zero_gather_fn(params, mesh)
    out = fn(placed)
    want = R.zero_tree_to_momentum(flat, params)
    _assert_trees_equal(out, want)


def test_reshard_pp_step_program_traces_with_gather_pair(n_devices):
    """The pp_reshard_zero_gather shardlint config: every pipe-sharded
    (layers) leaf gathers twice - data-axis segment gather + pipe-axis
    stage concat - while replicated leaves take one data gather (the
    contract the checked-in manifest pins)."""
    from distributed_neural_network_tpu.analysis.trace import collect_trace
    from distributed_neural_network_tpu.parallel.pipeline import (
        create_pp_mesh,
    )

    cfg = _cfg()
    mesh = create_pp_mesh(2, 2, 1)
    prog = R.reshard_pp_step_program(cfg, mesh)
    facts = collect_trace(prog.make_jaxpr())
    flat = prog.abstract_args[0]
    n_leaves = len(jax.tree.leaves(flat))
    n_layer_leaves = len(jax.tree.leaves(flat["layers"]))
    gathers = [c for c in facts.collectives if c.op == "all_gather"]
    assert sum(
        c.count for c in gathers if c.axes == ("data",)
    ) == n_leaves
    assert sum(
        c.count for c in gathers if c.axes == ("pipe",)
    ) == n_layer_leaves
    assert sum(c.count for c in gathers) == n_leaves + n_layer_leaves


@requires_shard_map
def test_pp_zero_gather_fn_matches_host_transform(n_devices):
    """Executed parity (modern jax): the two-gather collective reassembly
    of the ZeRO-under-pp buffers equals pp_zero_tree_to_momentum bitwise."""
    from distributed_neural_network_tpu.parallel.pipeline import (
        create_pp_mesh,
        pp_optimizer_state_specs,
        pp_param_specs,
    )

    cfg = _cfg()
    mesh = create_pp_mesh(2, 2, 1)
    params = _host(tfm.init_params(jax.random.key(0), cfg))
    specs = pp_param_specs(cfg)
    rng = np.random.default_rng(3)
    mom = jax.tree.map(
        lambda p: rng.standard_normal(p.shape).astype(np.float32), params
    )
    flat = R.momentum_to_pp_zero_tree(mom, specs, 2, 2)
    state_specs = pp_optimizer_state_specs("zero", specs)
    placed = jax.tree.map(
        lambda b, s: jax.device_put(b, NamedSharding(mesh, s)),
        flat, state_specs,
    )
    fn = R.make_pp_zero_gather_fn(params, mesh)
    out = fn(placed)
    _assert_trees_equal(out, mom)


# ------------------------------------------------ CLI e2e (slow, gated)


def _run_lm(tmp_path, *extra, steps=16, check=True, name="m.jsonl"):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    args = [
        sys.executable, os.path.join(REPO, "lm_train.py"),
        "--dp", "4", "--steps", str(steps), "--batch-size", "16",
        "--seq-len", "32", "--d-model", "32", "--n-heads", "4",
        "--n-layers", "2", "--d-ff", "64", "--vocab", "64",
        "--log-every", "1",
        "--metrics-jsonl", str(tmp_path / name),
        *extra,
    ]
    proc = subprocess.run(
        args, capture_output=True, text=True, cwd=REPO, env=env, timeout=600
    )
    if check:
        assert proc.returncode == 0, proc.stderr[-3000:]
    return proc


def _loss_series(path):
    out = []
    with open(path) as f:
        for line in f:
            ev = json.loads(line)
            if isinstance(ev, dict) and ev.get("series") == "train/loss":
                out.append(ev["value"])
    return out


def _losses_close(a, b, rtol=1e-3):
    assert len(a) == len(b), (len(a), len(b))
    for i, (x, y) in enumerate(zip(a, b)):
        assert math.isfinite(x) and math.isfinite(y)
        assert abs(x - y) <= rtol * max(abs(x), abs(y), 1e-3), (i, x, y)


@requires_shard_map
@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.parametrize("target", [("--dp", "2"), ("--dp", "2", "--tp", "2")])
def test_cli_kill_and_resume_on_smaller_mesh(tmp_path, target):
    """SIGTERM mid-run on dp=4 -> emergency checkpoint -> --elastic resume
    on dp=2 (and dp=2 x tp=2): the continued loss trajectory matches the
    uninterrupted dp=4 run. The loss psum reassociates across dp, so the
    gate is a tight tolerance rather than bitwise (the data stream itself
    IS exact - same global batch, same cursor)."""
    _run_lm(tmp_path, steps=24, name="a.jsonl")
    a = _loss_series(tmp_path / "a.jsonl")
    assert len(a) == 24

    ck = str(tmp_path / "ck")
    killed = _run_lm(
        tmp_path, "--checkpoint-dir", ck, "--checkpoint-every", "100",
        "--chaos-sigterm-after", "9", steps=24, name="b.jsonl",
    )
    assert "emergency checkpoint at step 9" in killed.stdout
    resumed = _run_lm(
        tmp_path, "--checkpoint-dir", ck, "--resume", "--elastic", *target,
        steps=14, name="c.jsonl",
    )
    assert "Resumed from step 9" in resumed.stdout
    assert "(elastic:" in resumed.stdout
    c = _loss_series(tmp_path / "c.jsonl")
    _losses_close(c, a[10:])


@requires_shard_map
@pytest.mark.slow
@pytest.mark.chaos
def test_cli_chaos_shrink_inprocess(tmp_path):
    """--chaos-shrink-at-step drives the FULL preempt -> checkpoint ->
    reshard -> resume path in one process: the run survives the shrink,
    completes every step, and the post-shrink trajectory matches the
    uninterrupted run within the dp-reassociation tolerance."""
    _run_lm(tmp_path, steps=24, name="a.jsonl")
    a = _loss_series(tmp_path / "a.jsonl")

    ck = str(tmp_path / "ck")
    proc = _run_lm(
        tmp_path, "--checkpoint-dir", ck, "--checkpoint-every", "100",
        "--chaos-shrink-at-step", "9", "--chaos-shrink-to", "2",
        steps=24, name="b.jsonl",
    )
    assert "SHRINK" in proc.stdout
    assert "(elastic: resharded checkpoint step 9" in proc.stdout
    assert "(elastic: continuing at step 10 on mesh data2" in proc.stdout
    b = _loss_series(tmp_path / "b.jsonl")
    summ = json.loads(next(
        ln for ln in proc.stdout.splitlines() if ln.startswith("SUMMARY ")
    )[len("SUMMARY "):])
    assert summ["preempted"] is False and summ["last_step"] == 23
    assert summ["mesh"] == "data2"
    assert math.isfinite(summ["final_loss"])
    assert b[:10] == a[:10]  # pre-shrink: bitwise, same compiled program
    _losses_close(b[10:], a[10:])


@requires_shard_map
@pytest.mark.slow
@pytest.mark.chaos
def test_cli_elastic_resume_zero_checkpoint_as_sgd(tmp_path):
    """Optimizer-layout elasticity from the CLI: a zero(dp=4) checkpoint
    resumes as sgd(dp=2) - the ZeRO shards reassemble into the replicated
    momentum and training continues on the matching trajectory."""
    _run_lm(tmp_path, "--optimizer", "zero", steps=24, name="a.jsonl")
    a = _loss_series(tmp_path / "a.jsonl")

    ck = str(tmp_path / "ck")
    _run_lm(
        tmp_path, "--optimizer", "zero", "--checkpoint-dir", ck,
        "--chaos-sigterm-after", "9", steps=24, name="b.jsonl",
    )
    resumed = _run_lm(
        tmp_path, "--checkpoint-dir", ck, "--resume", "--elastic",
        "--dp", "2", "--optimizer", "sgd", steps=14, name="c.jsonl",
    )
    assert "optimizer layout: saved 'zero', target 'sgd'" in resumed.stdout
    c = _loss_series(tmp_path / "c.jsonl")
    _losses_close(c, a[10:])
