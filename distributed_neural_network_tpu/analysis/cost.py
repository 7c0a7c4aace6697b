"""Static cost model: score a sharding plan from its abstract trace.

Input: a `StepProgram` (train/program.py) and the `TraceFacts` the
shardlint tracer (trace.py) computed for it - collective op/axes/bytes
with static multiplicity, scan-carry footprints, donation coverage. All
of it exists WITHOUT executing anything, which is what makes the
autoshard search (autoshard.py) cheap: scoring a candidate costs one
``jax.make_jaxpr`` trace, never a compile or a device.

The score (lower is better) combines four terms:

1. **Collective wire bytes.** Each static site's logical payload bytes
   (trace.py byte convention: input avals, except all_gather which counts
   its output) are converted to per-device wire bytes with the standard
   ring factors over the site's axis group size n = prod(mesh[axis]):
   psum (ring all-reduce) 2(n-1)/n, all_gather / reduce_scatter /
   all_to_all (n-1)/n, ppermute 1. Dynamic (while-loop) sites have no
   static trip count; they are surfaced in the breakdown but excluded
   from the score, matching the manifest convention.
2. **Per-device peak state bytes** vs an HBM budget: params + optimizer
   state sharded per the plan's PartitionSpecs (each leaf's bytes divided
   by the product of its spec's axis sizes) + the largest scan carry.
   Over budget = infeasible (the search prunes it); under budget a small
   pressure term still prefers leaner layouts.
3. **Donation coverage.** Un-donated state doubles its peak bytes during
   the step; the undonated fraction of state bytes is charged at
   ``donation_weight``.
4. **Replication-leak penalty.** A ZeRO overlap plan whose in-scan
   gradient carry is not O(D/dp) (lint.py's leak threshold: carry >= D/2)
   is charged the full leaked bytes - such a plan must never outrank a
   correctly sharded one.

On jax builds that trace through the pre-vma compat path
(``compat.trace_mode() == "compat"``), the typed-autodiff gradient psums
of `grad_sync="end"` steps are INVISIBLE in the trace. The model adds
them analytically (replicated param-leaf bytes, psum ring factor over
the sync axes) so end-sync data parallelism is never scored as free; on
native traces the same psums appear in `TraceFacts` and the analytic
term stays zero - never both.

`predicted_collective_bytes` (the logical per-step total) is by
construction EQUAL to the shardlint manifest's ``total_collective_bytes``
for the same config - one `TraceFacts` source, pinned by test.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

# ------------------------------------------------------- bytes per dtype
#
# The low-precision pricing table (ROADMAP item 3's closing clause): the
# HBM-feasibility gate, `step_seconds`, and the serving KV-capacity math
# all consult it, so autoshard can trade precision for parallelism (an
# int8 plan that fits where a bf16 plan did not) and the serving stack
# reports occupancy in the bytes it actually allocates. Quantized
# formats carry per-block f32 scales - `quantized_bytes` charges them,
# so a "free" 4x never appears in a feasibility decision.

DTYPE_BYTES = {
    "f32": 4, "float32": 4, "fp32": 4,
    "bf16": 2, "bfloat16": 2, "f16": 2, "float16": 2,
    "int8": 1, "fp8": 1, "fp8-e4m3": 1, "float8_e4m3fn": 1,
}
# formats that need a dequantization scale riding along
QUANTIZED_DTYPES = ("int8", "fp8", "fp8-e4m3", "float8_e4m3fn")
SCALE_BYTES = 4  # one f32 scale per quantization block


def dtype_bytes(name: str) -> int:
    try:
        return DTYPE_BYTES[str(name)]
    except KeyError:
        raise ValueError(
            f"unknown dtype name {name!r}; known: "
            f"{', '.join(sorted(DTYPE_BYTES))}"
        ) from None


def quantized_bytes(n_elements: int, dtype: str, *,
                    quant_block: int = 64) -> int:
    """Storage bytes of ``n_elements`` in ``dtype`` INCLUDING the per-
    block f32 scales quantized formats carry (one scale per
    ``quant_block`` elements) - the honest footprint the HBM gate and
    the KV-capacity math price."""
    total = n_elements * dtype_bytes(dtype)
    if str(dtype) in QUANTIZED_DTYPES:
        total += -(-n_elements // max(quant_block, 1)) * SCALE_BYTES
    return total


def kv_block_bytes(n_layers: int, n_heads: int, head_dim: int,
                   block_size: int, dtype: str = "bf16") -> int:
    """Device bytes of ONE paged-KV block (serve/kv_cache.py): K + V
    slabs for every layer, plus - for quantized dtypes - the
    per-(block, head) f32 scale pair each layer stores. The serving
    capacity multiplier is exactly bf16's figure over int8's."""
    elems = 2 * n_layers * block_size * n_heads * head_dim  # K and V
    total = elems * dtype_bytes(dtype)
    if str(dtype) in QUANTIZED_DTYPES:
        total += 2 * n_layers * n_heads * SCALE_BYTES
    return total


def latent_block_bytes(n_layers: int, row_width: int, block_size: int,
                       dtype: str = "bf16") -> int:
    """Device bytes of ONE paged block of an engine with one pool of rows
    (serve/engine.py): a module whose `CACHE` is "latent" (`n_layers` all
    its layers, one row for all heads) or "hybrid" (`n_layers` its
    attention layers alone, a row K and V of every KV head; its other
    layers keep a state a sequence in the state pool, and no block):
    `block_size`
    rows of `row_width` values in each of those layers, no scales."""
    return n_layers * block_size * row_width * dtype_bytes(dtype)


def kv_capacity_sequences(usable_blocks: int, block_size: int,
                          seq_len: int) -> int:
    """Concurrent sequences of ``seq_len`` tokens a pool of
    ``usable_blocks`` holds - the *effective* capacity figure the
    /metrics gauge and tools/live_top.py report instead of a raw block
    count."""
    if seq_len < 1:
        raise ValueError(f"seq_len must be >= 1, got {seq_len}")
    blocks_per_seq = -(-seq_len // block_size)
    return usable_blocks // blocks_per_seq


@dataclass(frozen=True)
class CostWeights:
    """Weights/budget for `score_program`. Defaults favour wire bytes as
    the primary signal (the quantity manifests already pin) with memory
    as a feasibility gate plus a mild pressure term."""

    wire_weight: float = 1.0  # per wire byte moved per step
    mem_weight: float = 0.01  # per peak state byte per device
    donation_weight: float = 0.5  # per un-donated state byte
    leak_weight: float = 4.0  # per leaked (unsharded ZeRO carry) byte
    hbm_bytes: int = 16 * 2**30  # per-device budget (v5e-class default)
    # price PARAM floating leaves as if stored in this dtype ("int8" /
    # "fp8" / "bf16"; None = as traced): the quantized-footprint knob
    # that lets the HBM-feasibility gate trade precision for parallelism
    # - an int8 plan fits meshes a bf16 plan prunes (tools/autoshard.py
    # --precision). Optimizer state is NEVER repriced (master weights /
    # moments stay wide; quantizing them is a different algorithm, not
    # a storage choice), and quantized formats are charged their
    # per-block scale overhead (`quantized_bytes`).
    param_precision: str | None = None
    quant_block: int = 64  # elements per quantization scale


# ring wire factor per logical payload byte, by op, for axis group size n
def wire_factor(op: str, n: int) -> float:
    if n <= 1:
        return 0.0
    if op == "psum":
        return 2.0 * (n - 1) / n
    if op in ("all_gather", "reduce_scatter", "all_to_all"):
        return (n - 1) / n
    if op == "ppermute":
        return 1.0
    return 1.0


@dataclass
class CostBreakdown:
    """One plan's score with every term exposed (the --explain payload)."""

    plan: str
    mesh: dict
    feasible: bool = True
    infeasible_reason: str = ""
    # term 1: collectives
    collective_bytes: int = 0  # logical, static sites == manifest total
    dynamic_collective_bytes: int = 0  # per while-iteration, unscored
    wire_bytes: float = 0.0  # ring-weighted, traced sites
    wire_bytes_by_axes: dict = field(default_factory=dict)
    untraced_grad_sync_bytes: float = 0.0  # analytic compat-trace term
    # term 2: memory
    param_bytes_per_device: int = 0
    opt_bytes_per_device: int = 0
    scan_carry_bytes: int = 0
    peak_state_bytes: int = 0
    hbm_bytes: int = 0
    param_precision: str = ""  # "" = as traced; else the priced dtype
    # term 3: donation
    state_bytes_total: int = 0
    undonated_state_bytes: int = 0
    # term 4: leak
    leaked_carry_bytes: int = 0
    score: float = float("inf")

    def why(self) -> str:
        """Human-readable breakdown, one line per term."""
        if not self.feasible:
            return (
                f"{self.plan}: INFEASIBLE - {self.infeasible_reason}"
            )
        lines = [
            f"{self.plan}: score {self.score:,.1f}",
            f"  wire bytes/step      {self.wire_bytes:>14,.1f}  "
            f"(logical {self.collective_bytes:,} B over "
            + (
                ", ".join(
                    f"{'+'.join(a) or 'local'}: {b:,.1f}"
                    for a, b in sorted(self.wire_bytes_by_axes.items())
                )
                or "no collectives"
            )
            + ")",
        ]
        if self.untraced_grad_sync_bytes:
            lines.append(
                f"  + grad-sync (analytic) {self.untraced_grad_sync_bytes:>12,.1f}  "
                "(end-sync psums invisible to the compat trace)"
            )
        if self.dynamic_collective_bytes:
            lines.append(
                f"  dynamic bytes/iter   {self.dynamic_collective_bytes:>14,}  "
                "(while-loop sites, excluded from the score)"
            )
        lines.append(
            f"  peak state B/device  {self.peak_state_bytes:>14,}  "
            f"(params {self.param_bytes_per_device:,}"
            + (f" @{self.param_precision}" if self.param_precision else "")
            + f" + opt {self.opt_bytes_per_device:,} + carry "
            f"{self.scan_carry_bytes:,}; budget {self.hbm_bytes:,})"
        )
        if self.undonated_state_bytes:
            lines.append(
                f"  un-donated state B   {self.undonated_state_bytes:>14,}  "
                "(double-buffered during the step)"
            )
        if self.leaked_carry_bytes:
            lines.append(
                f"  ZeRO leak penalty B  {self.leaked_carry_bytes:>14,}  "
                "(in-scan carry not O(D/dp))"
            )
        return "\n".join(lines)


def sharded_leaf_bytes(avals, specs, mesh_axes, *,
                       precision: str | None = None,
                       quant_block: int = 64) -> int:
    """Per-device bytes of an abstract state tree under a spec tree: each
    leaf's bytes divided by the product of its spec's axis sizes (the
    spec may be a pytree prefix, shard_map's broadcast rule).

    ``precision`` reprices FLOATING leaves as if stored in that dtype
    (per-block scale overhead included) - the quantized-footprint view
    of the same tree; integer leaves (token buffers, indices) keep
    their traced bytes."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec

    def is_spec(s):
        return isinstance(s, PartitionSpec)

    spec_leaves, treedef = jax.tree_util.tree_flatten(specs, is_leaf=is_spec)
    aval_groups = treedef.flatten_up_to(avals)
    total = 0
    for spec, group in zip(spec_leaves, aval_groups):
        shards = 1
        for entry in tuple(spec):
            if entry is None:
                continue
            for a in (entry,) if isinstance(entry, str) else tuple(entry):
                shards *= int(mesh_axes.get(a, 1))
        for leaf in jax.tree_util.tree_leaves(group):
            if not hasattr(leaf, "shape"):
                continue
            n = int(np.prod(leaf.shape, dtype=np.int64))
            if precision is not None and jnp.issubdtype(
                leaf.dtype, jnp.floating
            ):
                # ceil-shard the ELEMENTS, then price at the target
                # dtype (+ scale overhead): padding is real memory
                total += quantized_bytes(
                    -(-n // shards), precision, quant_block=quant_block
                )
            else:
                nbytes = n * np.dtype(leaf.dtype).itemsize
                total += -(-nbytes // shards)
    return total


def replicated_param_bytes(program) -> int:
    """Bytes of param leaves whose spec is fully replicated (no mesh axis
    named) - the leaves whose end-sync gradients psum over the sync axes."""
    import jax
    from jax.sharding import PartitionSpec

    specs = (program.specs or {}).get("params")
    if specs is None or not program.abstract_args:
        return 0

    def is_spec(s):
        return isinstance(s, PartitionSpec)

    spec_leaves, treedef = jax.tree_util.tree_flatten(specs, is_leaf=is_spec)
    aval_groups = treedef.flatten_up_to(program.abstract_args[0])
    total = 0
    for spec, group in zip(spec_leaves, aval_groups):
        if any(e is not None for e in tuple(spec)):
            continue
        for leaf in jax.tree_util.tree_leaves(group):
            if hasattr(leaf, "shape"):
                total += int(
                    np.prod(leaf.shape, dtype=np.int64)
                ) * np.dtype(leaf.dtype).itemsize
    return total


def untraced_grad_sync_wire_bytes(program, facts) -> float:
    """Analytic wire bytes of the end-sync gradient psums the COMPAT trace
    cannot see (pre-vma jax traces no typed-autodiff psums). Zero on
    native traces (the psums are in `facts`), zero under overlap sync
    (its collectives are explicit and traced), zero when no sync axis has
    size > 1."""
    from .. import compat

    if compat.trace_mode() != "compat":
        return 0.0
    meta = program.meta or {}
    if meta.get("family") not in ("lm", "pp"):
        return 0.0
    if meta.get("grad_sync") == "overlap" and int(meta.get("accum_steps", 1)) > 1:
        return 0.0
    mesh_axes = dict(program.mesh.shape)
    sync_axes = [
        a for a in (meta.get("sync_axes") or []) if mesh_axes.get(a, 1) > 1
    ]
    if not sync_axes:
        return 0.0
    n = 1
    for a in sync_axes:
        n *= int(mesh_axes[a])
    rep = replicated_param_bytes(program)
    if str(meta.get("optimizer", "")).startswith("zero"):
        # ZeRO end-sync reduces with reduce_scatter + all_gather instead
        # of a full all-reduce; same (n-1)/n each way = same 2(n-1)/n
        # total, so the psum factor is the right analytic stand-in
        pass
    return rep * wire_factor("psum", n)


def score_program(program, facts, weights: CostWeights | None = None,
                  plan: str | None = None) -> CostBreakdown:
    """Score one traced plan. Never raises on a scoreable program; memory
    over budget marks the breakdown infeasible (score stays +inf)."""
    w = weights or CostWeights()
    mesh_axes = {str(k): int(v) for k, v in program.mesh.shape.items()}
    bd = CostBreakdown(
        plan=plan or program.name, mesh=mesh_axes,
        hbm_bytes=int(w.hbm_bytes),
        param_precision=w.param_precision or "",
    )

    # --- term 1: collectives -------------------------------------------
    bd.collective_bytes = facts.total_collective_bytes()
    bd.dynamic_collective_bytes = facts.dynamic_collective_bytes_per_iter()
    by_axes = {}
    for c in facts.collectives:
        if c.dynamic:
            continue
        n = 1
        for a in c.axes:
            n *= int(mesh_axes.get(a, 1))
        wb = c.total_bytes * wire_factor(c.op, n)
        bd.wire_bytes += wb
        by_axes[c.axes] = by_axes.get(c.axes, 0.0) + wb
    bd.wire_bytes_by_axes = by_axes
    bd.untraced_grad_sync_bytes = untraced_grad_sync_wire_bytes(
        program, facts
    )

    # --- term 2: memory -------------------------------------------------
    args = program.abstract_args
    specs = program.specs or {}
    if args and "params" in specs:
        bd.param_bytes_per_device = sharded_leaf_bytes(
            args[0], specs["params"], mesh_axes,
            precision=w.param_precision, quant_block=w.quant_block,
        )
    if len(args) > 1 and "opt" in specs:
        bd.opt_bytes_per_device = sharded_leaf_bytes(
            args[1], specs["opt"], mesh_axes
        )
    bd.scan_carry_bytes = int(facts.scan_carry_max_bytes)
    bd.peak_state_bytes = (
        bd.param_bytes_per_device + bd.opt_bytes_per_device
        + bd.scan_carry_bytes
    )
    if bd.peak_state_bytes > w.hbm_bytes:
        bd.feasible = False
        bd.infeasible_reason = (
            f"peak state {bd.peak_state_bytes:,} B/device exceeds the HBM "
            f"budget {int(w.hbm_bytes):,} B (params "
            f"{bd.param_bytes_per_device:,} + optimizer "
            f"{bd.opt_bytes_per_device:,} + scan carry "
            f"{bd.scan_carry_bytes:,})"
        )
        return bd

    # --- term 3: donation ----------------------------------------------
    bd.state_bytes_total = bd.param_bytes_per_device + bd.opt_bytes_per_device
    donated = facts.donated_invars
    if donated is not None and program.donate:
        counts = program.arg_leaf_counts()
        if sum(counts) == len(donated):
            offsets = [0]
            for cnt in counts:
                offsets.append(offsets[-1] + cnt)
            state_bytes = [bd.param_bytes_per_device, bd.opt_bytes_per_device]
            for argnum in program.donate:
                if argnum >= len(counts) or argnum >= len(state_bytes):
                    continue
                flags = donated[offsets[argnum]:offsets[argnum + 1]]
                if flags and not all(flags):
                    frac = 1.0 - sum(flags) / len(flags)
                    bd.undonated_state_bytes += int(
                        state_bytes[argnum] * frac
                    )
    elif donated is None and program.donate:
        # no jit boundary found: charge the full state conservatively
        bd.undonated_state_bytes = bd.state_bytes_total

    # --- term 4: ZeRO replication leak ----------------------------------
    meta = program.meta or {}
    if (
        str(meta.get("optimizer", "")).startswith("zero")
        and meta.get("grad_sync") == "overlap"
        and int(meta.get("accum_steps", 1)) > 1
    ):
        dp = int(meta.get("dp", 1))
        d_bytes = program.param_bytes()
        carry = facts.reduce_scatter_carry_bytes
        if carry is None:
            bd.leaked_carry_bytes = d_bytes  # schedule not running at all
        elif dp > 1 and carry >= d_bytes // 2:
            bd.leaked_carry_bytes = carry - d_bytes // dp

    bd.score = (
        w.wire_weight * (bd.wire_bytes + bd.untraced_grad_sync_bytes)
        + w.mem_weight * bd.peak_state_bytes
        + w.donation_weight * bd.undonated_state_bytes
        + w.leak_weight * bd.leaked_carry_bytes
    )
    return bd


# ------------------------------------------------------ per-step seconds
#
# The score above RANKS plans; the fleet digital twin
# (analysis/fleetsim.py) needs SECONDS - a predicted steady-step time it
# can multiply into goodput under a failure process. `step_seconds`
# converts the same byte/flop terms into a first-order roofline estimate:
# compute and HBM weight-streaming overlap (the max rules), collective
# wire time is charged serially on top (the conservative bound for
# unoverlapped end-sync; the overlap schedule hides part of it, which the
# estimate deliberately does not credit). Pure arithmetic over a
# `CostBreakdown` OR a checked-in plan manifest's "chosen" dict - no jax,
# so a supervisor-side tool can price a plan without a runtime.


@dataclass(frozen=True)
class HardwareModel:
    """Nominal per-chip rates for step-time pricing. The defaults are
    v5e-class datasheet numbers; calibrate against a measured record
    (the twin prefers the measured step-time distribution whenever one
    exists - this model is for fleets/plans never executed)."""

    name: str = "tpu-v5e"
    flops_per_s: float = 197e12  # bf16 peak, per chip
    hbm_bytes_per_s: float = 819e9  # HBM bandwidth, per chip
    ici_bytes_per_s: float = 45e9  # per-link ICI wire bandwidth
    step_overhead_s: float = 50e-6  # dispatch/launch floor per step


# named hardware presets for the CLIs (tools/fleetsim.py --hw)
HARDWARE_MODELS = {
    "tpu-v5e": HardwareModel(),
    "tpu-v4": HardwareModel(
        name="tpu-v4", flops_per_s=275e12, hbm_bytes_per_s=1228e9,
        ici_bytes_per_s=100e9,
    ),
    "cpu-host": HardwareModel(
        name="cpu-host", flops_per_s=2e11, hbm_bytes_per_s=40e9,
        ici_bytes_per_s=10e9, step_overhead_s=1e-3,
    ),
}


@dataclass
class StepTime:
    """One plan's predicted steady-step seconds, every term exposed."""

    step_s: float
    compute_s: float
    memory_s: float
    comm_s: float
    overhead_s: float
    bound: str  # "compute" | "memory" | "comm"
    flops_per_step: float
    hw: str

    def why(self) -> str:
        return (
            f"step {self.step_s * 1e3:,.3f} ms on {self.hw} "
            f"({self.bound}-bound: compute {self.compute_s * 1e3:,.3f} + "
            f"hbm {self.memory_s * 1e3:,.3f} [max] + wire "
            f"{self.comm_s * 1e3:,.3f} + overhead "
            f"{self.overhead_s * 1e3:,.3f} ms)"
        )


def dense_step_flops(param_count: float, tokens_per_step: float) -> float:
    """First-order dense-transformer training flops per step: 6 x params
    x tokens (fwd 2PT + bwd 4PT, the standard accounting)."""
    return 6.0 * float(param_count) * float(tokens_per_step)


def serve_tick_seconds(
    bucket, hw: HardwareModel | None = None
) -> StepTime:
    """Predicted seconds of ONE serve bucket call (decode / chunked
    prefill / spec verify) from its traced facts - the serving analogue
    of `step_seconds`, consumed by the servelint capacity planner
    (analysis/serve_trace.py) and the fleet twin.

    ``bucket`` is any mapping exposing ``flops`` and ``hbm_bytes`` - a
    serve manifest's per-bucket doc qualifies, so a supervisor-side
    tool can price a config it never compiled. Model: compute and HBM
    streaming overlap (take the max - the weights stream while the MXU
    works), plus the dispatch floor; serve programs are single-device,
    so there is no wire term."""
    hw = hw or HardwareModel()

    def get(key):
        if isinstance(bucket, dict):
            return float(bucket.get(key) or 0.0)
        return float(getattr(bucket, key, 0.0) or 0.0)

    compute_s = get("flops") / hw.flops_per_s
    memory_s = get("hbm_bytes") / hw.hbm_bytes_per_s
    return StepTime(
        step_s=max(compute_s, memory_s) + hw.step_overhead_s,
        compute_s=compute_s,
        memory_s=memory_s,
        comm_s=0.0,
        overhead_s=hw.step_overhead_s,
        bound="compute" if compute_s >= memory_s else "memory",
        flops_per_step=get("flops"),
        hw=hw.name,
    )


def _full_bucket(manifest: dict, family: str) -> dict | None:
    """The largest (last-sorted) bucket doc of one family, or None."""
    docs = [
        b for b in manifest.get("buckets", []) if b.get("family") == family
    ]
    if not docs:
        return None
    return max(docs, key=lambda b: tuple(b["bucket"]))


def serve_capacity(manifest: dict, hw: HardwareModel | None = None) -> dict:
    """Static capacity curves of one serve config from its servelint
    manifest (analysis/serve_trace.py) - the planner view ROADMAP item
    1 asks for, consumable by analysis/fleetsim.py and the autoscaler
    sizing logic (`replicas_for_target`):

    - steady-state decode ``tokens_per_s`` at the FULL decode bucket
      (every slot busy - the per-replica throughput ceiling);
    - static prefill TTFT per pow2 prompt length: ceil(P / C) chunked
      prefill calls at the full chunk bucket plus the first decode tick
      (without chunked prefill, P token-at-a-time decode ticks);
    - concurrent-sequence KV capacity per prompt+generation length
      (`kv_capacity_sequences` over the manifest's pool geometry).

    Pure arithmetic over pinned facts - no jax, no engine."""
    hw = hw or HardwareModel()
    eng = manifest.get("engine", {})
    kv = manifest.get("kv", {})
    out: dict = {"hw": hw.name}

    dec = _full_bucket(manifest, "decode")
    if dec is not None:
        tick = serve_tick_seconds(dec, hw)
        B = int(dec["bucket"][0])
        out["decode"] = {
            "bucket": list(dec["bucket"]),
            "tick_s": tick.step_s,
            "bound": tick.bound,
            "tokens_per_s": B / tick.step_s,
        }

    pre = _full_bucket(manifest, "prefill")
    chunk = int(pre["bucket"][0]) if pre is not None else 0
    if pre is not None:
        ptick = serve_tick_seconds(pre, hw)
        out["prefill"] = {
            "bucket": list(pre["bucket"]),
            "tick_s": ptick.step_s,
            "tokens_per_s": chunk / ptick.step_s,
        }

    max_seq = int(eng.get("max_seq_len") or 0)
    block_size = int(eng.get("block_size") or 1)
    usable = int(kv.get("usable_blocks") or 0)
    ttft: dict = {}
    kv_cap: dict = {}
    if dec is not None and max_seq:
        dtick = serve_tick_seconds(dec, hw).step_s
        p = 1
        lens = []
        while p < max_seq:
            lens.append(p)
            p *= 2
        lens.append(max_seq)
        for P in lens:
            if pre is not None and chunk:
                n_calls = -(-P // chunk)
                ttft[str(P)] = n_calls * ptick.step_s + dtick
            else:
                ttft[str(P)] = P * dtick + dtick
            kv_cap[str(P)] = kv_capacity_sequences(usable, block_size, P)
    out["ttft_s"] = ttft
    out["kv_capacity_sequences"] = kv_cap
    return out


def replicas_for_target(
    capacity: dict,
    *,
    target_rps: float,
    mean_new_tokens: float,
    prompt_len: int = 0,
    target_ttft_s: float | None = None,
) -> dict:
    """Replica count for a target request rate - the capacity-planner
    arithmetic the PR 18 autoscaler's ``min_replicas`` should be seeded
    from (serve/fleet.py autoscale_decision enforces it at runtime;
    this answers it BEFORE provisioning).

    ``capacity`` is `serve_capacity`'s output (or a manifest's pinned
    ``capacity[hw]`` block). The demand is ``target_rps *
    mean_new_tokens`` decode tokens/s against the per-replica ceiling;
    a ``target_ttft_s`` is checked against the STATIC prefill floor at
    ``prompt_len`` - a floor above the target is infeasible at any
    replica count (queueing only adds to it), which the planner reports
    instead of scaling forever."""
    dec = capacity.get("decode") or {}
    per_replica = float(dec.get("tokens_per_s") or 0.0)
    if per_replica <= 0:
        raise ValueError(
            "capacity has no decode tokens_per_s figure - pass "
            "serve_capacity() output or a manifest capacity block"
        )
    import math

    demand = float(target_rps) * float(mean_new_tokens)
    replicas = max(1, math.ceil(demand / per_replica))
    out = {
        "replicas": int(replicas),
        "demand_tokens_per_s": demand,
        "per_replica_tokens_per_s": per_replica,
        "utilization_at_n": demand / (replicas * per_replica),
        "feasible": True,
        # provenance: this figure ignores queueing - scripts must not
        # confuse it with the serve twin's dynamic answer
        # (analysis/fleetsim.py replicas_for_dynamic, which is >= this)
        "static_only": True,
        "why": (
            f"{demand:,.0f} tok/s demand / {per_replica:,.0f} tok/s "
            f"per replica -> {replicas} replica(s)"
        ),
    }
    if target_ttft_s is not None and prompt_len:
        curve = capacity.get("ttft_s") or {}
        floor = None
        for key in sorted(curve, key=int):
            if int(key) >= int(prompt_len):
                floor = float(curve[key])
                break
        if floor is None and curve:
            floor = float(curve[max(curve, key=int)])
        out["ttft_floor_s"] = floor
        if floor is not None and floor > float(target_ttft_s):
            out["feasible"] = False
            out["why"] += (
                f"; INFEASIBLE: static TTFT floor {floor * 1e3:,.1f} ms "
                f"at prompt {prompt_len} exceeds the "
                f"{float(target_ttft_s) * 1e3:,.1f} ms target - no "
                "replica count fixes a per-request floor (shrink the "
                "model, grow prefill_chunk, or relax the SLO)"
            )
    return out


def step_seconds(
    bd, hw: HardwareModel | None = None, *, flops_per_step: float = 0.0
) -> StepTime:
    """Predicted steady-step seconds from a plan's byte/flop terms.

    ``bd`` is a `CostBreakdown` or any mapping exposing ``wire_bytes``,
    ``untraced_grad_sync_bytes``, and ``peak_state_bytes`` (a plan
    manifest's ``chosen`` block qualifies). Model: compute time and
    HBM state-streaming time overlap (take the max - a step reads its
    params+optimizer state at least once), collective wire time and the
    dispatch floor are additive."""
    hw = hw or HardwareModel()

    def get(key):
        if isinstance(bd, dict):
            return float(bd.get(key) or 0.0)
        return float(getattr(bd, key, 0.0) or 0.0)

    compute_s = float(flops_per_step) / hw.flops_per_s
    memory_s = get("peak_state_bytes") / hw.hbm_bytes_per_s
    comm_s = (
        get("wire_bytes") + get("untraced_grad_sync_bytes")
    ) / hw.ici_bytes_per_s
    body = max(compute_s, memory_s)
    if comm_s > body:
        bound = "comm"
    elif compute_s >= memory_s:
        bound = "compute"
    else:
        bound = "memory"
    return StepTime(
        step_s=body + comm_s + hw.step_overhead_s,
        compute_s=compute_s,
        memory_s=memory_s,
        comm_s=comm_s,
        overhead_s=hw.step_overhead_s,
        bound=bound,
        flops_per_step=float(flops_per_step),
        hw=hw.name,
    )
