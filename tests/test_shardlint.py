"""shardlint: static sharding/collective/donation analysis (analysis/).

Everything here runs on the 8-virtual-CPU-device mesh with NO step
execution - the analyzer traces via jax.make_jaxpr (the canonical-config
traces differ across jax generations, which is why manifests are
version-stamped; the checked-in-manifest conformance test skips on a
version mismatch).
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from distributed_neural_network_tpu import analysis, compat
from distributed_neural_network_tpu.analysis import lint as AL
from distributed_neural_network_tpu.parallel import partition as PT
from distributed_neural_network_tpu.train import lm as lmtrain
from distributed_neural_network_tpu.train.program import StepProgram

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------- spec validators (edge)


def test_validate_spec_unknown_axis_names_axis_and_available():
    with pytest.raises(ValueError) as e:
        PT.validate_partition_spec(
            P("nope"), {"data": 4, "model": 2}, shape=(8,), name="wq"
        )
    msg = str(e.value)
    assert "'nope'" in msg and "wq" in msg
    assert "data" in msg and "model" in msg  # the available axes


def test_validate_spec_duplicate_axis_in_one_spec():
    with pytest.raises(ValueError, match="twice"):
        PT.validate_partition_spec(
            P("data", "data"), {"data": 4}, shape=(8, 8)
        )
    # duplicate inside one tuple entry counts too
    with pytest.raises(ValueError, match="twice"):
        PT.validate_partition_spec(
            P(("data", "data")), {"data": 4}, shape=(16,)
        )


def test_validate_spec_non_divisible_dim():
    with pytest.raises(ValueError, match="does not divide"):
        PT.validate_partition_spec(P("data"), {"data": 4}, shape=(6,))
    # tuple entries multiply their shard counts
    with pytest.raises(ValueError, match="does not divide"):
        PT.validate_partition_spec(
            P(("data", "model")), {"data": 4, "model": 2}, shape=(12,)
        )


def test_validate_spec_none_padded_shorter_than_rank_ok():
    # specs SHORTER than the rank are jax-legal (trailing dims unsharded)
    PT.validate_partition_spec(P("data"), {"data": 4}, shape=(8, 3, 5))
    PT.validate_partition_spec(P(None, "data"), {"data": 4}, shape=(3, 8, 5))
    PT.validate_partition_spec(P(), {"data": 4}, shape=(7,))


def test_validate_spec_longer_than_rank_rejected():
    with pytest.raises(ValueError, match="rank"):
        PT.validate_partition_spec(
            P(None, None, "data"), {"data": 4}, shape=(8, 8)
        )


def test_validate_spec_tree_names_leaf_path():
    specs = {"layers": {"wq": P("ghost")}}
    with pytest.raises(ValueError) as e:
        PT.validate_spec_tree(specs, {"data": 4}, root="params")
    assert "wq" in str(e.value) and "'ghost'" in str(e.value)


def test_validate_spec_tree_broadcast_spec_over_subtree():
    # one spec for a whole pytree (shard_map prefix rule): every leaf
    # underneath is checked
    shapes = {"a": np.zeros((8, 2)), "b": np.zeros((6,))}
    with pytest.raises(ValueError, match="does not divide"):
        PT.validate_spec_tree(
            P("data"), {"data": 4}, shapes=shapes, root="mom"
        )


def test_lm_wiring_validates_specs_against_mesh():
    # a mesh missing the axes the LM wiring shards over fails EARLY with
    # the axis named, not deep inside pjit
    from distributed_neural_network_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64
    )
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(4), ("data",))
    with pytest.raises(ValueError) as e:
        lmtrain.lm_wiring(cfg, mesh)
    assert "'seq'" in str(e.value) and "data" in str(e.value)


# ------------------------------------------------------ the jaxpr walker


def _toy_mesh(n=4):
    return Mesh(np.asarray(jax.devices()[:n]).reshape(n), ("data",))


def _toy_program(fn, *abstract_args, donate=(), mesh=None, name="toy",
                 specs=None, meta=None):
    return StepProgram(
        name=name, fn=fn, mesh=mesh or _toy_mesh(),
        abstract_args=tuple(abstract_args), specs=specs or {},
        donate=tuple(donate),
        donate_labels=tuple(f"arg{i}" for i in donate), meta=meta or {},
    )


def test_collect_trace_counts_collectives_and_scan_multiplicity():
    mesh = _toy_mesh()

    def body(x):
        def step(c, _):
            return c + jax.lax.psum(x, "data").sum(), None

        c, _ = jax.lax.scan(step, 0.0, None, length=5)
        g = jax.lax.all_gather(x, "data", tiled=True)
        return c + g.sum()

    fn = jax.jit(
        compat.shard_map(
            body, mesh=mesh, in_specs=(P("data"),), out_specs=P(),
            check_vma=False,
        )
    )
    prog = _toy_program(fn, jax.ShapeDtypeStruct((8, 4), jnp.float32))
    facts = analysis.collect_trace(prog.make_jaxpr())
    by_op = {c.op: c for c in facts.collectives}
    # psum: (2, 4) f32 local shard = 32 B/call, x5 from the scan
    assert by_op["psum"].count == 5
    assert by_op["psum"].bytes_per_call == 2 * 4 * 4
    # all_gather counts its OUTPUT (the gathered (8, 4) buffer)
    assert by_op["all_gather"].count == 1
    assert by_op["all_gather"].bytes_per_call == 8 * 4 * 4
    assert facts.total_collective_bytes() == 5 * 32 + 128
    assert not facts.has_dynamic_loop


def test_collect_trace_upcasts_counted():
    def f(x):
        return (x.astype(jnp.float32) @ x.astype(jnp.float32).T).sum()

    prog = _toy_program(
        jax.jit(f), jax.ShapeDtypeStruct((4, 4), jnp.bfloat16)
    )
    facts = analysis.collect_trace(prog.make_jaxpr())
    assert "bfloat16->float32" in facts.upcasts
    assert facts.upcasts["bfloat16->float32"]["count"] >= 1
    assert facts.f64_sites == 0


def test_collect_trace_donation_and_alias():
    fn = jax.jit(lambda x, y: (x + 1.0, y.sum()), donate_argnums=(0,))
    prog = _toy_program(
        fn,
        jax.ShapeDtypeStruct((8,), jnp.float32),
        jax.ShapeDtypeStruct((3,), jnp.float32),
        donate=(0,),
    )
    facts = analysis.collect_trace(prog.make_jaxpr())
    assert facts.donated_invars == (True, False)
    assert AL.donation_audit(prog, facts) == []

    # donating an arg with no shape/dtype-matching output is flagged
    fn2 = jax.jit(lambda x: x.sum(), donate_argnums=(0,))
    prog2 = _toy_program(
        fn2, jax.ShapeDtypeStruct((8,), jnp.float32), donate=(0,)
    )
    facts2 = analysis.collect_trace(prog2.make_jaxpr())
    findings = AL.donation_audit(prog2, facts2)
    assert any(f.code == "donation-alias" for f in findings)


def test_dropped_donation_is_an_error():
    fn = jax.jit(lambda x, y: (x + 1.0, y))  # no donate_argnums
    prog = _toy_program(
        fn,
        jax.ShapeDtypeStruct((8,), jnp.float32),
        jax.ShapeDtypeStruct((3,), jnp.float32),
        donate=(0, 1),
    )
    facts = analysis.collect_trace(prog.make_jaxpr())
    findings = AL.donation_audit(prog, facts)
    assert sum(f.severity == "error" for f in findings) == 2
    assert "donate_argnums" in findings[0].message


# --------------------------------------------------- canonical configs


@pytest.mark.parametrize("name", analysis.config_names())
def test_canonical_config_traces_clean(name, n_devices):
    result = analysis.analyze_program(analysis.build_program(name))
    assert result.errors == [], [str(f) for f in result.errors]
    man = result.manifest
    assert man["config"] == name
    assert man["donation"]["n_donated"] is not None
    # every config except the purely-local ones moves SOMETHING across
    # the mesh (lm_dp/lm_adam trace without the typed-autodiff grad psum
    # on pre-vma jax; cnn_dp's epoch IS local SGD - its sync phase is the
    # separate cnn_sync config)
    if name not in ("lm_dp", "lm_adam", "cnn_dp"):
        assert man["collectives"], name


def test_zero_overlap_carry_is_sharded(n_devices):
    result = analysis.analyze_program(
        analysis.build_program("lm_zero_overlap")
    )
    man = result.manifest
    d, dp = man["param_bytes"], man["meta"]["dp"]
    carry = man["reduce_scatter_carry_bytes"]
    assert carry is not None
    # the in-scan accumulator holds the 1/dp shard (+ ceil padding + loss)
    assert carry < d // 2, (carry, d)
    assert carry >= d // dp, (carry, d, dp)


def test_zero_leak_lint_fires_on_full_size_carry(n_devices):
    prog = analysis.build_program("lm_zero_overlap")
    facts = analysis.collect_trace(prog.make_jaxpr())
    assert AL.replication_leak_lint(prog, facts) == []
    # fabricate a full-size carry: the lint must call it out
    facts.reduce_scatter_carry_bytes = prog.param_bytes()
    findings = AL.replication_leak_lint(prog, facts)
    assert findings and findings[0].code == "zero-leak"
    assert "full-size" in findings[0].message
    # and a missing reduce-scatter scan entirely
    facts.reduce_scatter_carry_bytes = None
    findings = AL.replication_leak_lint(prog, facts)
    assert findings and "reduce_scatter" in findings[0].message


# ----------------------------------------------------------- manifests


def test_manifest_roundtrip_and_diff(tmp_path, n_devices):
    result = analysis.analyze_program(analysis.build_program("lm_zero"))
    analysis.save_manifest(result.manifest, "lm_zero", str(tmp_path))
    loaded = analysis.load_manifest("lm_zero", str(tmp_path))
    assert analysis.diff_manifests(loaded, result.manifest) == []

    # a bumped count fails with the op/axes/bytes named
    mutated = analysis.load_manifest("lm_zero", str(tmp_path))
    entry = next(
        c for c in mutated["collectives"] if c["op"] == "all_gather"
    )
    entry["count"] += 1
    diffs = analysis.diff_manifests(mutated, result.manifest)
    assert diffs and "all_gather" in diffs[0]
    assert "data" in diffs[0] and "B/call" in diffs[0]

    # a version-mismatched manifest short-circuits with the regenerate hint
    stale = analysis.load_manifest("lm_zero", str(tmp_path))
    stale["jax_version"] = "0.0.1"
    diffs = analysis.diff_manifests(stale, result.manifest)
    assert len(diffs) == 1 and "regenerate" in diffs[0]


def test_missing_manifest_is_actionable(tmp_path):
    with pytest.raises(FileNotFoundError, match="--write-manifest"):
        analysis.load_manifest("lm_dp", str(tmp_path))


def test_injected_extra_collective_fails_check(monkeypatch, n_devices):
    """The acceptance probe: a deliberately injected extra all-reduce in
    the optimizer path must fail --check naming the op, axis, and bytes."""
    real_sgd = lmtrain.sgd_step

    def evil_sgd(params, mom, grads, lr, momentum):
        grads = jax.tree.map(lambda g: jax.lax.psum(g, "data"), grads)
        return real_sgd(params, mom, grads, lr, momentum)

    monkeypatch.setattr(lmtrain, "sgd_step", evil_sgd)
    result = analysis.analyze_program(analysis.build_program("lm_dp"))
    diffs = analysis.diff_manifests(
        analysis.load_manifest("lm_dp"), result.manifest
    )
    assert diffs, "extra psum went undetected"
    extra = [d for d in diffs if d.startswith("EXTRA collective")]
    assert extra and "psum" in extra[0] and "'data'" in extra[0]
    assert "B/call" in extra[0]


@pytest.mark.skipif(
    not os.path.exists(analysis.manifest_path("lm_dp")),
    reason="no checked-in manifests",
)
def test_checked_in_manifests_conform(n_devices):
    """python tools/shardlint.py --all --check, as the CI gate runs it."""
    pinned = analysis.load_manifest("lm_dp").get("jax_version")
    if pinned != jax.__version__:
        pytest.skip(
            f"manifests pinned to jax {pinned}, running {jax.__version__} "
            "- regenerate with --write-manifest to re-enable"
        )
    rc, report = analysis.run_shardlint(mode="check", verbose=False)
    assert rc == 0, report


# ------------------------------------------------------------------ CLI


def _load_cli():
    spec = importlib.util.spec_from_file_location(
        "shardlint_cli", os.path.join(ROOT, "tools", "shardlint.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_cli_list_and_check_roundtrip(tmp_path, capsys, n_devices):
    cli = _load_cli()
    assert cli.main(["--list"]) == 0
    out = capsys.readouterr().out
    assert "lm_zero_overlap" in out and "pp_gpipe" in out

    # write to a scratch dir, then check against it: exit 0
    rc = cli.main([
        "--config", "lm_dp", "--write-manifest",
        "--manifest-dir", str(tmp_path), "-q",
    ])
    assert rc == 0
    rc = cli.main([
        "--config", "lm_dp", "--check", "--manifest-dir", str(tmp_path),
        "-q",
    ])
    assert rc == 0
    # a missing manifest makes --check exit non-zero with the fix named
    rc = cli.main([
        "--config", "lm_zero", "--check", "--manifest-dir", str(tmp_path),
        "-q",
    ])
    assert rc == 1
    assert "--write-manifest" in capsys.readouterr().out


def test_cli_unknown_config_is_trace_error(capsys, n_devices):
    cli = _load_cli()
    rc = cli.main(["--config", "nonsense", "--manifest-dir", "/tmp", "-q"])
    assert rc == 2
    assert "unknown shardlint config" in capsys.readouterr().out


# ---------------------------------------------------------- StepProgram


def test_step_program_exposes_traceable_metadata(n_devices):
    prog = analysis.build_program("lm_zero_overlap")
    assert prog.donate == (0, 1)
    assert prog.meta["optimizer"] == "zero"
    assert prog.meta["grad_sync"] == "overlap"
    counts = prog.arg_leaf_counts()
    assert len(counts) == 4  # params, mom, tokens, targets
    assert counts[2] == counts[3] == 1
    assert prog.param_bytes() > 0


def test_engine_exposes_step_specs(n_devices):
    """train/engine.py publishes the spec metadata shardlint's CNN config
    audits."""
    prog = analysis.build_program("cnn_dp")
    assert prog.meta["family"] == "cnn"
    assert prog.donate == (1,)  # the epoch path donates momentum only
    result = analysis.analyze_program(prog)
    assert result.errors == []


# ------------------------------------- dynamic (while-loop) collectives


def _while_psum_program(extra_scan_psums: int = 0):
    """A toy step with a psum inside a while loop (a decode-style dynamic
    loop) and optionally a static scan psum next to it."""
    mesh = _toy_mesh()

    def body(x):
        def cond(state):
            i, _ = state
            return i < x.shape[0]

        def step(state):
            i, acc = state
            return i + 1, acc + jax.lax.psum(x.sum(), "data")

        _, acc = jax.lax.while_loop(cond, step, (0, 0.0))
        if extra_scan_psums:
            def s(c, _):
                return c + jax.lax.psum(x.sum(), "data"), None

            acc2, _ = jax.lax.scan(s, 0.0, None, length=extra_scan_psums)
            acc = acc + acc2
        return acc

    fn = jax.jit(
        compat.shard_map(
            body, mesh=mesh, in_specs=(P("data"),), out_specs=P(),
            check_vma=False,
        )
    )
    return _toy_program(fn, jax.ShapeDtypeStruct((8, 4), jnp.float32))


def test_dynamic_sites_excluded_from_total_surfaced_separately(n_devices):
    """A while-based loop must not zero out (or inflate) the per-step
    manifest total: dynamic sites carry per-iteration bytes on their own
    field."""
    facts = analysis.collect_trace(_while_psum_program().make_jaxpr())
    assert facts.has_dynamic_loop
    dyn = [c for c in facts.collectives if c.dynamic]
    assert dyn and all(c.op == "psum" for c in dyn)
    # the scalar psum: 4 B per call, once per loop iteration
    assert facts.total_collective_bytes() == 0
    assert facts.dynamic_collective_bytes_per_iter() == sum(
        c.total_bytes for c in dyn
    ) > 0


def test_dynamic_and_static_sites_coexist(n_devices):
    facts = analysis.collect_trace(
        _while_psum_program(extra_scan_psums=3).make_jaxpr()
    )
    # static total counts ONLY the x3 scan psums
    static = [c for c in facts.collectives if not c.dynamic]
    assert sum(c.count for c in static) == 3
    assert facts.total_collective_bytes() == sum(
        c.total_bytes for c in static
    )
    assert facts.dynamic_collective_bytes_per_iter() > 0


def test_manifest_pins_dynamic_bytes_separately(n_devices):
    prog = _while_psum_program()
    facts = analysis.collect_trace(prog.make_jaxpr())
    man = analysis.build_manifest(prog, facts)
    assert man["total_collective_bytes"] == 0
    assert man["dynamic_collective_bytes_per_iter"] > 0
    assert man["has_dynamic_loop"] is True
    # drift in the per-iteration bytes fails the diff with its own message
    other = dict(man, dynamic_collective_bytes_per_iter=0)
    diffs = analysis.diff_manifests(other, man)
    assert diffs and "per loop iteration" in diffs[0]
    # manifests written before the field existed compare as zero
    legacy = {k: v for k, v in man.items()
              if k != "dynamic_collective_bytes_per_iter"}
    diffs = analysis.diff_manifests(legacy, man)
    assert any("per loop iteration" in d for d in diffs)


# --------------------------------------------- per-site provenance paths


def test_sites_carry_provenance_paths(n_devices):
    facts = analysis.collect_trace(
        _while_psum_program(extra_scan_psums=3).make_jaxpr()
    )
    paths = {c.path for c in facts.sites}
    assert any("while" in p for p in paths)
    assert any("scan[x3]" in p for p in paths)
    # merged view still aggregates across paths with identical keys
    assert sum(c.count for c in facts.collectives) == sum(
        c.count for c in facts.sites
    )


def test_canonical_config_sites_locate_the_scan(n_devices):
    """Provenance attributes the ZeRO overlap schedule's reduce-scatters
    to where they actually run: microbatch 0's buckets before the
    accumulation scan, the remaining accum_steps-1 microbatches' inside
    it (accumulate_fwd_bwd_overlap peels the first iteration)."""
    prog = analysis.build_program("lm_zero_overlap")
    facts = analysis.collect_trace(prog.make_jaxpr())
    rs = [c for c in facts.sites if c.op == "reduce_scatter"]
    assert rs
    in_scan = [c for c in rs if "scan[x1]" in c.path]
    peeled = [c for c in rs if c.path.endswith("shard_map")]
    assert in_scan and peeled
    assert sum(c.count for c in in_scan) == sum(c.count for c in peeled)


def test_explain_sites_table(n_devices):
    from distributed_neural_network_tpu.analysis.runner import explain_sites

    facts = analysis.collect_trace(
        _while_psum_program(extra_scan_psums=3).make_jaxpr()
    )
    lines = explain_sites(facts)
    assert "where" in lines[0]
    assert any("yes" in ln and "while" in ln for ln in lines[1:])
    assert any("per while-loop iteration" in ln for ln in lines)


def test_cli_explain_flag(capsys, n_devices):
    cli = _load_cli()
    rc = cli.main(["--config", "lm_zero_overlap", "--explain"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "where" in out and "shard_map" in out


# ------------------------------------------- CLI config-list ergonomics


def test_cli_comma_separated_configs(tmp_path, capsys, n_devices):
    cli = _load_cli()
    rc = cli.main([
        "--config", "lm_dp,lm_zero", "--write-manifest",
        "--manifest-dir", str(tmp_path), "-q",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "lm_dp" in out and "lm_zero" in out
    assert os.path.exists(os.path.join(str(tmp_path), "lm_dp.json"))
    assert os.path.exists(os.path.join(str(tmp_path), "lm_zero.json"))


def test_cli_typo_exits_2_with_known_list(capsys, n_devices):
    cli = _load_cli()
    rc = cli.main(["--config", "lm_dp,lm_zzz", "-q"])
    out = capsys.readouterr().out
    assert rc == 2
    assert "lm_zzz" in out  # the typo is named
    assert "lm_zero_overlap" in out  # and the known list printed
