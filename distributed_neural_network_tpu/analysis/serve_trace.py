"""servelint: static audit + cost model of the serving bucket programs.

The serving engine (serve/engine.py) compiles a GRID of jitted programs
- decode / chunked-prefill / speculative draft+verify families, each
over power-of-two (batch, table-width) buckets - and ``warmup()``
pre-compiles all of them so no live request ever pays an XLA compile.
Nothing guarded that grid statically: a dropped KV-pool donation
doubles the engine's largest allocation, a silent bf16->f32 upcast
doubles a bucket's bytes, and an accidental new bucket dimension
multiplies compile count - all invisible until a runtime regression.

This module is the serve-side mirror of the shardlint pipeline
(trace -> lint -> manifest -> CI check, docs/STATIC_ANALYSIS.md):

- ``enumerate_grid(ecfg)`` reproduces warmup()'s compile set from an
  `EngineConfig` alone - pinned equal to the engine's actual fn-cache
  keys by test (cache-miss counting, tests/test_servelint.py);
- ``bucket_programs`` wraps every grid entry as a `ServeBucketProgram`
  whose jaxpr ``jax.make_jaxpr`` traces abstractly (ShapeDtypeStruct
  args - no pools materialize, no execution);
- the shardlint walker (trace.collect_trace) audits donation
  (pools + int8 scales MUST be donated in decode/prefill/verify;
  params must NEVER be; the read-only drafter is exempt), upcasts,
  and quantized-dtype declarations (the PR 13 quant pin), while
  ``collect_serve_costs`` walks the same jaxpr for FLOPs and
  gather/scatter traffic (the paged addressing);
- ``build_serve_manifest`` pins per-bucket facts + the grid itself
  into analysis/manifests/serve_<config>.json; ``--check`` re-traces
  and diffs, naming the bucket and the fact that moved;
- the per-bucket bytes/flops feed ``cost.serve_tick_seconds`` (the
  HardwareModel roofline) and ``cost.serve_capacity`` - static
  tokens/s, prefill TTFT, and KV-capacity figures the fleet twin
  (analysis/fleetsim.py) and the autoscaler can consume as a capacity
  planner, validated against the measured ``measure_serving`` bench
  row by ``tools/servelint.py --validate``.

HBM byte convention (documented so manifests are comparable): per call,
``hbm_bytes = weight_bytes + gather out-bytes + scatter update-bytes +
non-pool I/O bytes``. Weights stream once per call (the layer scan
reads every layer's slice exactly once); the paged pools are charged
by what the JAXPR touches - the gathered table span and the scattered
updates - never by pool size. That is a statement about the program as
written, not as compiled: a jaxpr walk cannot see what the compiler
adds to alias a donated pool, and until PR 25 the compiled programs
did move the pool whole (as the layer scan's xs/ys it was two buffers:
a pool copied, every layer's slab sliced out and written back - 44 % of
the device's busy time at 1.3 B, PERF.md section 6). What guards the
compiled side now is tests/test_serve_pool_inplace.py (no bucket
program's ``temp_size_in_bytes`` reaches a layer's slab, every donated
pool is aliased) and the live ``serve_program_temp_bytes{family}``
gauge. Elementwise FLOPs are excluded (matmul-dominated programs;
``flops`` counts dot_general only, scan multiplicity folded in).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from .trace import _aval_bytes, _sub_jaxprs

SERVE_MANIFEST_SCHEMA = 1

# tiny trace geometry: structure is what manifests pin, so the canonical
# serve configs trace a minimal dense model (mirrors configs.py TRACE_*)
SERVE_TRACE_MODEL = dict(
    vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
)
# decode_impl pinned "xla": the pallas route is backend-dependent
# (auto only takes it on TPU), and a manifest must trace identically
# on the CPU CI host and a dev TPU
SERVE_TRACE_ENGINE = dict(
    max_batch=4, num_blocks=9, block_size=4, max_seq_len=32,
    prefill_chunk=4, decode_impl="xla",
)


@dataclass(frozen=True)
class ServeConfigSpec:
    """One canonical serve config: model geometry + engine knobs +
    the declared quantization (lint.py quantized_dtype_lint)."""

    name: str
    model: dict
    engine: dict
    quant: str | None = None
    note: str = ""


def _spec(name, quant=None, note="", **engine_overrides):
    return ServeConfigSpec(
        name=name,
        model=dict(SERVE_TRACE_MODEL),
        engine={**SERVE_TRACE_ENGINE, **engine_overrides},
        quant=quant,
        note=note,
    )


SERVE_CONFIGS = {
    "serve_bf16": _spec(
        "serve_bf16",
        note="bf16 pool + weights: the PR 12 baseline engine",
    ),
    "serve_int8_kv": _spec(
        "serve_int8_kv", kv_dtype="int8", quant="int8-kv",
        note="quantized KV pool (per-(block, head) f32 scales donated "
        "with it)",
    ),
    "serve_int8_w": _spec(
        "serve_int8_w", weight_dtype="int8", quant="int8-w",
        note="int8 weights (ops/quant.py prequantized codes + scales)",
    ),
    "serve_spec_k4": _spec(
        "serve_spec_k4", spec_decode=4, spec_draft_layers=1,
        note="speculative decoding: draft (read-only) + 5-position "
        "verify families ride the same grid",
    ),
}


def serve_config_names() -> list:
    return list(SERVE_CONFIGS)


# ------------------------------------------------------ grid enumeration


def _pow2s(limit: int) -> list:
    out, v = [], 1
    while v <= limit:
        out.append(v)
        v *= 2
    return out


def enumerate_grid(ecfg, *, max_width_blocks: int | None = None,
                   latent: bool = False) -> dict:
    """The bucket grid ``warmup()`` compiles, from the `EngineConfig`
    alone - family -> [(bucket key), ...]. MUST mirror
    serve/engine.py warmup() exactly; the equality is pinned by
    cache-miss counting in tests/test_servelint.py (serving after
    warmup compiles zero new programs for every canonical config).
    ``latent``: the engine of a module that brings its own programs (a
    latent cache; KV rows beside convolution states), which have one table
    width, the widest (tests/test_pangu_ultra_moe.py and
    tests/test_lfm2_moe.py pin that grid)."""
    from ..serve.engine import _bucket, batch_buckets

    kv = ecfg.kv()
    widths = _pow2s(_bucket(max_width_blocks or kv.max_blocks_per_seq))
    if latent:
        widths = [_bucket(kv.max_blocks_per_seq)]
    batches = batch_buckets(ecfg.max_batch)
    grid = {"decode": [(B, W) for B in batches for W in widths]}
    if ecfg.prefill_chunk > 1:
        grid["prefill"] = [
            (C, W)
            for C in _pow2s(ecfg.prefill_chunk)
            for W in widths
            if C <= W * ecfg.block_size
        ]
    if ecfg.spec_decode:
        grid["draft"] = [(B, W) for B in batches for W in widths]
        grid["verify"] = [(B, W) for B in batches for W in widths]
    return grid


def grid_total(grid: dict) -> int:
    return sum(len(v) for v in grid.values())


# --------------------------------------------------------- the programs


class _HostMesh:
    """Serve programs are single-device; lint's mesh interface reduces
    to an empty axis dict."""

    shape: dict = {}


@dataclass
class ServeBucketProgram:
    """One bucket's jitted program + enough structure for the shardlint
    lint families (duck-types train/program.py StepProgram)."""

    name: str
    family: str
    bucket: tuple
    fn: object
    abstract_args: tuple
    donate: tuple
    donate_labels: tuple
    meta: dict
    specs: dict = field(default_factory=dict)
    mesh: object = field(default_factory=_HostMesh)

    def make_jaxpr(self):
        import jax

        return jax.make_jaxpr(self.fn)(*self.abstract_args)

    def arg_leaf_counts(self) -> list:
        import jax

        return [
            len(jax.tree_util.tree_leaves(a)) for a in self.abstract_args
        ]

    def param_bytes(self) -> int:
        import jax

        return sum(
            int(np.prod(leaf.shape, dtype=np.int64))
            * np.dtype(leaf.dtype).itemsize
            for leaf in jax.tree_util.tree_leaves(self.abstract_args[0])
            if hasattr(leaf, "shape")
        )


def build_serve_engine(name_or_spec):
    """A real (tiny) engine for one canonical serve config: the bucket
    closures live on the engine, so tracing borrows them from exactly
    the object production serves with. Seeded params at trace geometry
    - tracing never looks at values, but int8-w prequantization needs
    real arrays to code."""
    import jax
    import jax.numpy as jnp

    from ..models.transformer import TransformerConfig, init_params
    from ..serve.engine import EngineConfig, ServeEngine

    spec = (
        SERVE_CONFIGS[name_or_spec]
        if isinstance(name_or_spec, str) else name_or_spec
    )
    cfg = TransformerConfig(dtype=jnp.bfloat16, **spec.model)
    params = init_params(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(params, cfg, EngineConfig(**spec.engine))
    return engine, spec


def _sds_tree(tree):
    import jax

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), tree
    )


def bucket_program(engine, family: str, key: tuple, *,
                   config: str = "serve", quant: str | None = None,
                   probe: str | None = None) -> ServeBucketProgram:
    """Wrap one (family, bucket) of a live engine as a traceable
    program: the engine's own jitted closure + ShapeDtypeStruct args
    mirroring warmup()'s call shapes. ``probe`` injects a known defect
    for acceptance testing ('drop-donation' re-jits the bucket without
    donate_argnums; 'upcast' adds a silent bf16->f32 round-trip on the
    pool output) - tools/servelint.py --probe, the CI probe legs."""
    import jax
    import jax.numpy as jnp

    i32 = jnp.int32
    params = _sds_tree(
        engine.draft_params if family == "draft" else engine.params
    )
    # the donated operands as the engine hands them over: K and V pools
    # (and int8 scales), a latent module's one pool, or a KV pool and a
    # state pool (`engine.pool_labels`)
    pools = tuple(_sds_tree(p) for p in engine._pools())
    scales = ()

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt)

    if family == "decode":
        B, W = key
        fn = engine._decode_fn(B, W)
        tail = _sds_tree(engine.bucket_tail("decode", B, W))
        label = f"decode[B{B},W{W}]"
    elif family == "prefill":
        C, W = key
        fn = engine._prefill_fn(C, W)
        tail = _sds_tree(engine.bucket_tail("prefill", C, W))
        label = f"prefill[C{C},W{W}]"
    elif family == "draft":
        B, W = key
        fn = engine._draft_fn(B, W)
        tail = (sds((B,), i32), sds((B,), i32), sds((B, W), i32))
        label = f"draft[B{B},W{W}]"
    elif family == "verify":
        B, W = key
        K = engine.spec_k + 1
        fn = engine._verify_fn(B, W)
        tail = (sds((B, K), i32), sds((B,), i32), sds((B, W), i32))
        label = f"verify[B{B},W{W}]"
    else:
        raise ValueError(f"unknown bucket family {family!r}")

    donate = () if family == "draft" else tuple(range(1, 1 + len(pools)))
    labels = ("params",) + engine.pool_labels
    if probe == "drop-donation" and family != "draft":
        # an outer jit swallows the inner boundary's donated_invars:
        # exactly what a refactor that loses donate_argnums looks like
        inner = fn
        fn = jax.jit(lambda *a: inner(*a))
    elif probe == "upcast" and family != "draft":
        # a silent widen-and-narrow round trip on the first floating
        # output (bf16 -> f32 -> bf16, or f32 -> bf16 -> f32 for the
        # int8 configs whose pool is not float): numerically a no-op
        # in shape/dtype, but the widening convert is exactly what the
        # manifest's upcast pin exists to catch
        inner = fn

        def fn(*a, _inner=inner):
            out = list(_inner(*a))
            for i, o in enumerate(out):
                if not jnp.issubdtype(o.dtype, jnp.floating):
                    continue
                if o.dtype == jnp.float32:
                    out[i] = o.astype(jnp.bfloat16).astype(jnp.float32)
                else:
                    out[i] = o.astype(jnp.float32).astype(o.dtype)
                break
            return tuple(out)

    return ServeBucketProgram(
        name=f"{config}:{label}",
        family=family,
        bucket=tuple(key),
        fn=fn,
        abstract_args=(params,) + pools + scales + tail,
        donate=donate,
        donate_labels=labels,
        meta={
            "family": family,
            "bucket": list(key),
            "kv_dtype": engine.kv_dtype_name(),
            "weight_dtype": engine.weight_dtype_name(),
            "quant": quant,
            "serve": True,
        },
    )


def bucket_programs(engine, *, config: str = "serve",
                    quant: str | None = None, probe: str | None = None,
                    max_width_blocks: int | None = None) -> list:
    """Every program of the engine's warmup grid, enumeration order
    (the order ``warmup()`` compiles them in)."""
    if probe == "extra-bucket":
        # simulate an accidental grid dimension: one more width octave
        # than max_seq_len needs -> every family grows a bucket column
        max_width_blocks = 2 * engine.kv.cfg.max_blocks_per_seq
    grid = enumerate_grid(
        engine.ecfg, max_width_blocks=max_width_blocks
    )
    return [
        bucket_program(
            engine, fam, key, config=config, quant=quant,
            probe=probe,
        )
        for fam, keys in grid.items()
        for key in keys
    ]


# ------------------------------------------------- serve-side cost walk


@dataclass
class ServeCosts:
    """Per-call compute/traffic facts of one bucket program (static
    multiplicity folded in, scan trip counts included)."""

    flops: int = 0              # dot_general only (2*M*N*K convention)
    gather_count: int = 0       # paged reads (gather + dynamic_slice)
    gather_bytes: int = 0       # gathered output bytes
    scatter_count: int = 0      # paged writes (scatter* + dyn. update)
    scatter_bytes: int = 0      # scattered update bytes
    weight_bytes: int = 0       # abstract param-tree bytes (as stored)
    io_bytes: int = 0           # non-pool, non-param boundary traffic

    @property
    def hbm_bytes(self) -> int:
        """The documented per-call HBM traffic model (module docstring):
        weights stream once, pools are charged by touched bytes only."""
        return (
            self.weight_bytes + self.gather_bytes + self.scatter_bytes
            + self.io_bytes
        )


def _dot_flops(eqn) -> int:
    (lhs_c, _), _ = eqn.params["dimension_numbers"]
    out = getattr(eqn.outvars[0], "aval", None)
    lhs = getattr(eqn.invars[0], "aval", None)
    if out is None or lhs is None:
        return 0
    contracted = 1
    for d in lhs_c:
        contracted *= int(lhs.shape[d])
    return 2 * int(np.prod(out.shape, dtype=np.int64)) * contracted


def collect_serve_costs(closed_jaxpr, program=None) -> ServeCosts:
    """Walk a bucket program's jaxpr for FLOPs and gather/scatter
    traffic, multiplying through scan trip counts like the shardlint
    walker. Purely structural - nothing executes."""
    import jax

    costs = ServeCosts()

    def walk(jaxpr, mult: int):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "dot_general":
                costs.flops += mult * _dot_flops(eqn)
            elif name in ("gather", "dynamic_slice"):
                costs.gather_count += mult
                costs.gather_bytes += mult * sum(
                    _aval_bytes(v) for v in eqn.outvars
                )
            elif name.startswith("scatter") or name == "dynamic_update_slice":
                costs.scatter_count += mult
                # invars = (operand, indices, updates): charge the
                # written update bytes, never the whole operand
                upd = eqn.invars[-1]
                costs.scatter_bytes += mult * _aval_bytes(upd)
            if name == "scan":
                walk(
                    eqn.params["jaxpr"].jaxpr,
                    mult * int(eqn.params["length"]),
                )
            else:
                for sub, _ in _sub_jaxprs(eqn):
                    if name != "scan":
                        walk(sub, mult)

    walk(closed_jaxpr.jaxpr, 1)

    if program is not None:
        costs.weight_bytes = program.param_bytes()
        # pool args by POSITION (donate_labels covers params + pools +
        # scales positionally), independent of donation - the read-only
        # drafter's pool inputs are still pool traffic, not I/O
        pool_args = {
            i for i, lab in enumerate(program.donate_labels)
            if lab != "params"
        }
        pool_keys: dict = {}
        pool_bytes = 0
        total_in = 0
        for i, arg in enumerate(program.abstract_args):
            b = 0
            for leaf in jax.tree_util.tree_leaves(arg):
                if not hasattr(leaf, "shape"):
                    continue
                b += (
                    int(np.prod(leaf.shape, dtype=np.int64))
                    * np.dtype(leaf.dtype).itemsize
                )
                if i in pool_args:
                    key = (tuple(leaf.shape), np.dtype(leaf.dtype).name)
                    pool_keys[key] = pool_keys.get(key, 0) + 1
            total_in += b
            if i in pool_args:
                pool_bytes += b
        # outputs: a pool-shaped output rides out in place (donated
        # alias); everything else - logits / next tokens / drafts - is
        # boundary traffic
        out_bytes = 0
        for v in closed_jaxpr.jaxpr.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                key = (
                    tuple(aval.shape),
                    np.dtype(getattr(aval, "dtype", np.float32)).name,
                )
                if pool_keys.get(key, 0) > 0:
                    pool_keys[key] -= 1
                    continue
            out_bytes += _aval_bytes(v)
        costs.io_bytes = (
            max(0, total_in - costs.weight_bytes - pool_bytes) + out_bytes
        )
    return costs


# ------------------------------------------------------------ manifests


def serve_manifest_name(config: str) -> str:
    return config if config.startswith("serve_") else f"serve_{config}"


def build_serve_manifest(config: str, engine, results: list) -> dict:
    """The manifest document for one serve config: the grid itself,
    per-bucket facts, and the derived capacity block (informational -
    diff_serve_manifests compares facts, not prices)."""
    import jax

    from .. import compat
    from .cost import HARDWARE_MODELS, serve_capacity

    ecfg, kv = engine.ecfg, engine.kv.cfg
    grid = {
        fam: sorted({tuple(r["bucket"]) for r in results
                     if r["family"] == fam})
        for fam in {r["family"] for r in results}
    }
    doc = {
        "schema": SERVE_MANIFEST_SCHEMA,
        "config": config,
        "jax_version": jax.__version__,
        "trace_mode": compat.trace_mode(),
        "model": {
            "d_model": engine.cfg.d_model,
            "n_layers": engine.cfg.n_layers,
            "n_heads": engine.cfg.n_heads,
            "head_dim": engine.cfg.head_dim,
            "d_ff": engine.cfg.d_ff,
            "vocab_size": engine.cfg.vocab_size,
        },
        "engine": {
            "max_batch": ecfg.max_batch,
            "num_blocks": ecfg.num_blocks,
            "block_size": ecfg.block_size,
            "max_seq_len": ecfg.max_seq_len,
            "prefill_chunk": ecfg.prefill_chunk,
            "kv_dtype": ecfg.kv_dtype,
            "weight_dtype": ecfg.weight_dtype,
            "spec_decode": ecfg.spec_decode,
            "decode_impl": ecfg.decode_impl,
        },
        "kv": {
            "usable_blocks": kv.usable_blocks,
            "max_blocks_per_seq": kv.max_blocks_per_seq,
            "pool_slots": kv.pool_slots,
        },
        "weight_bytes": results[0]["weight_bytes"] if results else 0,
        "grid": {
            fam: [list(b) for b in buckets]
            for fam, buckets in sorted(grid.items())
        },
        "programs_total": len(results),
        "buckets": sorted(
            results, key=lambda r: (r["family"], r["bucket"])
        ),
    }
    # derived pricing (excluded from --check: pure arithmetic over the
    # pinned facts at a named hardware model - the capacity planner's
    # and fleetsim's consumable view)
    doc["capacity"] = {
        hw: serve_capacity(doc, HARDWARE_MODELS[hw])
        for hw in ("tpu-v5e", "cpu-host")
    }
    return doc


def bucket_doc(program, facts, costs) -> dict:
    donated = facts.donated_invars
    return {
        "family": program.family,
        "bucket": list(program.bucket),
        "name": program.name,
        "flops": int(costs.flops),
        "hbm_bytes": int(costs.hbm_bytes),
        "weight_bytes": int(costs.weight_bytes),
        "io_bytes": int(costs.io_bytes),
        "gather": {
            "count": int(costs.gather_count),
            "bytes": int(costs.gather_bytes),
        },
        "scatter": {
            "count": int(costs.scatter_count),
            "bytes": int(costs.scatter_bytes),
        },
        "upcasts": {k: dict(v) for k, v in sorted(facts.upcasts.items())},
        "quant_dtypes": {
            k: int(v) for k, v in sorted(facts.quant_dtypes.items())
        },
        "donation": {
            "argnums": list(program.donate),
            "n_donated": int(sum(donated)) if donated is not None else None,
            "n_args": len(donated) if donated is not None else None,
        },
    }


def serve_manifest_path(config: str, manifest_dir: str | None = None) -> str:
    from .manifest import manifest_path

    return manifest_path(serve_manifest_name(config), manifest_dir)


def save_serve_manifest(doc, config, manifest_dir=None) -> str:
    from .manifest import save_manifest

    return save_manifest(doc, serve_manifest_name(config), manifest_dir)


def load_serve_manifest(config, manifest_dir=None) -> dict:
    import json
    import os

    path = serve_manifest_path(config, manifest_dir)
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"no serve manifest for config {config!r} at {path} - "
            f"generate one with: python tools/servelint.py --config "
            f"{config} --write-manifest"
        )
    with open(path) as f:
        return json.load(f)


def _fmt_bucket(fam: str, bucket) -> str:
    dims = "C" if fam == "prefill" else "B"
    return f"{fam}[{dims}{bucket[0]},W{bucket[1]}]"


def diff_serve_manifests(expected: dict, actual: dict) -> list:
    """Human-actionable differences (empty == conforming). Environment
    mismatches short-circuit with a regenerate instruction; the bucket
    GRID is diffed first (the budget lint: an accidental new bucket
    dimension names the exact buckets that appeared), then per-bucket
    facts - flops, HBM bytes, gather/scatter traffic, upcasts,
    quantized dtypes, and the donation contract."""
    msgs = []
    for key in ("jax_version", "trace_mode"):
        if expected.get(key) != actual.get(key):
            return [
                f"serve manifest for {expected.get('config')!r} was "
                f"written under {key}={expected.get(key)!r} but this "
                f"run has {key}={actual.get(key)!r}: traces are not "
                "comparable across jax generations - regenerate with "
                "--write-manifest (docs/STATIC_ANALYSIS.md)"
            ]
    for key in ("model", "engine"):
        if expected.get(key) != actual.get(key):
            return [
                f"{key} geometry mismatch: manifest {expected.get(key)} "
                f"vs traced {actual.get(key)} - regenerate or fix the "
                "config"
            ]
    # --- the bucket-grid budget lint
    eg = {
        (fam, tuple(b))
        for fam, buckets in (expected.get("grid") or {}).items()
        for b in buckets
    }
    ag = {
        (fam, tuple(b))
        for fam, buckets in (actual.get("grid") or {}).items()
        for b in buckets
    }
    for fam, b in sorted(ag - eg):
        msgs.append(
            f"EXTRA bucket not in manifest grid: {_fmt_bucket(fam, b)} "
            "- a new bucket dimension compiles un-warmed programs "
            "(compile-count budget grew)"
        )
    for fam, b in sorted(eg - ag):
        msgs.append(
            f"MISSING bucket from manifest grid: {_fmt_bucket(fam, b)} "
            "- warmup() no longer compiles it; live traffic at this "
            "shape would pay a first-request XLA compile"
        )
    ep = expected.get("programs_total")
    ap = actual.get("programs_total")
    if ep != ap:
        msgs.append(
            f"compiled-program budget changed: manifest {ep} vs "
            f"traced {ap} programs"
        )
    # --- per-bucket facts, on the buckets both sides know
    exp = {
        (r["family"], tuple(r["bucket"])): r
        for r in expected.get("buckets", [])
    }
    act = {
        (r["family"], tuple(r["bucket"])): r
        for r in actual.get("buckets", [])
    }
    for key in sorted(set(exp) & set(act)):
        e, a = exp[key], act[key]
        label = _fmt_bucket(*key)
        for fact in ("flops", "hbm_bytes"):
            if e.get(fact) != a.get(fact):
                msgs.append(
                    f"{label}: {fact} changed "
                    f"{e.get(fact):,} -> {a.get(fact):,}"
                )
        for fact in ("gather", "scatter"):
            if e.get(fact) != a.get(fact):
                msgs.append(
                    f"{label}: {fact} traffic changed "
                    f"{e.get(fact)} -> {a.get(fact)} (the paged "
                    "addressing moved)"
                )
        if e.get("upcasts") != a.get("upcasts"):
            msgs.append(
                f"{label}: dtype upcasts changed: manifest "
                f"{e.get('upcasts')} vs traced {a.get('upcasts')} - a "
                "silent widen doubles the bucket's bytes"
            )
        if (e.get("quant_dtypes") or {}) != (a.get("quant_dtypes") or {}):
            msgs.append(
                f"{label}: quantized dtypes changed: manifest "
                f"{e.get('quant_dtypes') or '{}'} vs traced "
                f"{a.get('quant_dtypes') or '{}'} - the low-precision "
                "contract moved (lint codes quant-undeclared / "
                "quant-missing)"
            )
        if e.get("donation") != a.get("donation"):
            msgs.append(
                f"{label}: donation contract changed: manifest "
                f"{e.get('donation')} vs traced {a.get('donation')} - "
                "an un-donated KV pool double-buffers the engine's "
                "largest allocation"
            )
    return msgs


# -------------------------------------------------------------- pricing


def static_decode_tokens_per_s(engine, hw="cpu-host") -> dict:
    """Static steady-state decode throughput of a LIVE engine's full
    decode bucket (max batch x max table width), priced on the
    HardwareModel roofline - the ``static_predicted_tokens_per_s``
    column measure_serving attaches next to the measured figure, and
    the quantity ``tools/servelint.py --validate`` gates."""
    from ..serve.engine import _bucket
    from .cost import HARDWARE_MODELS, serve_tick_seconds
    from .trace import collect_trace

    hw = HARDWARE_MODELS[hw] if isinstance(hw, str) else hw
    # the largest grid bucket: the widest batch warmup compiles
    B = engine.ecfg.max_batch
    W = _bucket(engine.kv.cfg.max_blocks_per_seq)
    program = bucket_program(engine, "decode", (B, W))
    traced = program.make_jaxpr()
    costs = collect_serve_costs(traced, program)
    facts = collect_trace(traced)
    tick = serve_tick_seconds(
        {"flops": costs.flops, "hbm_bytes": costs.hbm_bytes}, hw
    )
    return {
        "bucket": [B, W],
        "hw": hw.name,
        "tick_s": tick.step_s,
        "tokens_per_s": B / tick.step_s,
        "bound": tick.bound,
        "flops": int(costs.flops),
        "hbm_bytes": int(costs.hbm_bytes),
        "donated": (
            int(sum(facts.donated_invars))
            if facts.donated_invars is not None else None
        ),
    }


# --------------------------------------------------------------- driver


@dataclass
class ServeAnalysis:
    program: object
    facts: object
    costs: object
    findings: list

    @property
    def errors(self):
        return [f for f in self.findings if f.severity == "error"]


def analyze_serve_program(program) -> ServeAnalysis:
    from .lint import lint_program
    from .trace import _np_dtype, _quant_dtype_name, collect_trace

    traced = program.make_jaxpr()
    facts = collect_trace(traced)
    # the trace walker counts quantized EQN OUTPUTS (values produced in
    # the step) - enough for int8-kv, whose appends emit int8 codes.
    # int8-w is the dual: the codes arrive as INPUTS (prequantized
    # weights) and are only ever dequantized in-step, so fold the
    # quantized input avals in too or the quant pin would miss them
    for aval in facts.in_avals:
        q = _quant_dtype_name(_np_dtype(getattr(aval, "dtype", None)))
        if q is not None:
            facts.quant_dtypes[q] = facts.quant_dtypes.get(q, 0) + 1
    costs = collect_serve_costs(traced, program)
    return ServeAnalysis(
        program=program,
        facts=facts,
        costs=costs,
        findings=lint_program(program, facts),
    )


def run_servelint(
    names=None,
    *,
    mode: str = "lint",
    manifest_dir: str | None = None,
    verbose: bool = True,
    explain: bool = False,
    probe: str | None = None,
    hw: str = "cpu-host",
):
    """Analyze serve configs; mode 'lint' / 'write' / 'check' (shardlint
    house semantics). Returns (exit_code, report): 0 conforming, 1
    findings or manifest mismatch, 2 a config could not be built or
    traced. ``probe`` injects a known defect ('drop-donation',
    'upcast', 'extra-bucket') so the failure path itself is testable -
    the CI probe leg asserts rc 1 with the bucket named."""
    import time

    from .cost import HARDWARE_MODELS, serve_tick_seconds

    if mode not in ("lint", "write", "check"):
        raise ValueError(f"mode must be lint/write/check, got {mode!r}")
    if probe not in (None, "drop-donation", "upcast", "extra-bucket"):
        raise ValueError(f"unknown probe {probe!r}")
    names = list(names) if names else serve_config_names()
    hwm = HARDWARE_MODELS[hw]
    lines = []
    worst = 0

    def fail(rc):
        nonlocal worst
        worst = max(worst, rc)

    for name in names:
        t0 = time.perf_counter()
        try:
            engine, spec = build_serve_engine(name)
            programs = bucket_programs(
                engine, config=name, quant=spec.quant, probe=probe
            )
            results = [analyze_serve_program(p) for p in programs]
        except Exception as e:
            fail(2)
            lines.append(f"{name}: TRACE FAILED - {type(e).__name__}: {e}")
            continue
        dt = time.perf_counter() - t0
        docs = [
            bucket_doc(r.program, r.facts, r.costs) for r in results
        ]
        manifest = build_serve_manifest(name, engine, docs)
        fams = {}
        for p in programs:
            fams[p.family] = fams.get(p.family, 0) + 1
        full = max(
            (r for r in results if r.program.family == "decode"),
            key=lambda r: r.program.bucket,
        )
        tick = serve_tick_seconds(
            {"flops": full.costs.flops, "hbm_bytes": full.costs.hbm_bytes},
            hwm,
        )
        n_findings = sum(len(r.findings) for r in results)
        fb, fw = full.program.bucket
        lines.append(
            f"{name}: {len(programs)} bucket program(s) ("
            + ", ".join(f"{k} {v}" for k, v in sorted(fams.items()))
            + f"), {n_findings} finding(s); full decode bucket "
            f"[B{fb},W{fw}] ticks {tick.step_s * 1e3:.3f} ms on "
            f"{hwm.name} ({fb / tick.step_s:,.0f} tok/s static) "
            f"[{dt:.1f}s]"
        )
        if explain:
            lines.append(
                f"    {'bucket':<16} {'flops':>12} {'hbm B':>12} "
                f"{'gathers':>8} {'scatters':>9} {'tick ms':>9}"
            )
            for r in results:
                t = serve_tick_seconds(
                    {
                        "flops": r.costs.flops,
                        "hbm_bytes": r.costs.hbm_bytes,
                    },
                    hwm,
                )
                lines.append(
                    f"    {_fmt_bucket(r.program.family, r.program.bucket):<16} "
                    f"{r.costs.flops:>12,} {r.costs.hbm_bytes:>12,} "
                    f"{r.costs.gather_count:>8} "
                    f"{r.costs.scatter_count:>9} "
                    f"{t.step_s * 1e3:>9.3f}"
                )
        for r in results:
            for f in r.findings:
                lines.append(f"    {f}")
        if any(r.errors for r in results):
            fail(1)
        if mode == "write":
            if any(r.errors for r in results):
                lines.append(
                    f"    {name}: NOT writing manifest while lint "
                    "errors are outstanding"
                )
            else:
                path = save_serve_manifest(manifest, name, manifest_dir)
                lines.append(f"    wrote {path}")
        elif mode == "check":
            try:
                expected = load_serve_manifest(name, manifest_dir)
            except FileNotFoundError as e:
                fail(1)
                lines.append(f"    {e}")
                continue
            diffs = diff_serve_manifests(expected, manifest)
            if diffs:
                fail(1)
                lines.append(f"    {name}: MANIFEST MISMATCH:")
                lines.extend(f"      - {d}" for d in diffs)
            else:
                lines.append(
                    f"    manifest conforms "
                    f"({serve_manifest_name(name)}.json)"
                )
    status = {0: "OK", 1: "FAIL", 2: "TRACE ERROR"}[worst]
    lines.append(f"servelint: {len(names)} config(s), {status}")
    return worst, "\n".join(lines)


# ------------------------------------------------------------ --validate

# Documented tolerance of the static-vs-measured gate: the prediction
# prices ONLY the jitted tick (roofline compute/HBM + the hardware
# model's dispatch floor), while the measured open-loop bench rides the
# whole serving stack - HTTP, SSE, scheduler Python, partially-filled
# batches during ramp - so on the CPU host the measured figure sits
# well below the static ceiling. The gate requires agreement within a
# FACTOR (|log ratio| bound), not a percentage: a regression that
# breaks the cost model shows up as an order of magnitude, not a few
# percent. Calibration on the cpu-host reference bench (the
# measure_serving geometry run_validate uses) puts the static/measured
# ratio at ~17x: the static tick is ~1 ms (dispatch-floor bound) while
# the full stack delivers an effective ~17 ms/tick of scheduler+HTTP
# Python around it. Factor 32 covers that with ~2x machine-to-machine
# headroom while still failing on any order-of-magnitude cost-model
# regression; the jit-tick-only micro-bench (tests/test_servelint.py)
# sits near ratio 1 and is gated by the same factor.
VALIDATE_TOLERANCE_FACTOR = 32.0


def validate_prediction(predicted: float, measured: float,
                        tolerance_factor: float = VALIDATE_TOLERANCE_FACTOR,
                        ) -> dict:
    """The --validate verdict: static prediction vs measured tokens/s
    within a multiplicative tolerance. Pure arithmetic (testable
    without a bench run)."""
    if predicted <= 0 or measured <= 0:
        return {
            "ok": False,
            "predicted_tokens_per_s": float(predicted),
            "measured_tokens_per_s": float(measured),
            "ratio": None,
            "tolerance_factor": float(tolerance_factor),
            "why": "non-positive throughput figure",
        }
    ratio = predicted / measured
    ok = (1.0 / tolerance_factor) <= ratio <= tolerance_factor
    return {
        "ok": bool(ok),
        "predicted_tokens_per_s": float(predicted),
        "measured_tokens_per_s": float(measured),
        "ratio": round(ratio, 4),
        "tolerance_factor": float(tolerance_factor),
        "why": (
            "static prediction within the documented factor"
            if ok else
            f"static/measured ratio {ratio:.2f} outside "
            f"[1/{tolerance_factor:g}, {tolerance_factor:g}] - the "
            "cost model and the serving stack have drifted apart"
        ),
    }


def run_validate(*, hw: str = "cpu-host",
                 tolerance_factor: float = VALIDATE_TOLERANCE_FACTOR,
                 bench_row: dict | None = None,
                 **measure_kwargs):
    """Gate the static tokens/s prediction against a measured
    ``measure_serving`` row. With ``bench_row`` (a recorded bench JSON
    row carrying both figures) the comparison is offline; otherwise
    measure_serving runs in-process at a reduced geometry (a real
    HTTP+SSE open-loop run, ~a minute on the CPU host). Returns
    (exit_code, report)."""
    if bench_row is None:
        from ..train.measure import measure_serving

        kwargs = dict(
            d_model=64, n_layers=2, n_heads=4, d_ff=128, vocab=64,
            rate=16.0, requests=8, prompt_lens=(8, 16), max_new=16,
            max_batch=4, num_blocks=17, block_size=8, max_seq_len=64,
            prefill_chunk=8,
        )
        kwargs.update(measure_kwargs)
        bench_row = measure_serving(**kwargs)
    measured = float(bench_row.get("tokens_per_s") or 0.0)
    predicted = float(
        bench_row.get("static_predicted_tokens_per_s") or 0.0
    )
    verdict = validate_prediction(predicted, measured, tolerance_factor)
    lines = [
        f"servelint --validate ({hw}): static "
        f"{verdict['predicted_tokens_per_s']:,.1f} tok/s vs measured "
        f"{verdict['measured_tokens_per_s']:,.1f} tok/s "
        f"(ratio {verdict['ratio']}, tolerance x{tolerance_factor:g})",
        f"    {'OK' if verdict['ok'] else 'FAIL'}: {verdict['why']}",
    ]
    return (0 if verdict["ok"] else 1), "\n".join(lines)
