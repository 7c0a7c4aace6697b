"""Canonical train-step configs the static analyzer traces.

One entry per parallel regime x gradient-sync schedule the framework
ships: dp / tp / zero / zero-adam / pp, each under the end and (where it
exists) overlap schedules, plus the CNN engine's fused epoch program. Each
builder returns a `StepProgram` (train/program.py) over a TINY model - the
analyzer pins collective STRUCTURE (which ops, which axes, how many, in
what ratio to the parameter bytes), not production shapes, so traces stay
sub-second on a laptop CPU and the manifests stay readable.

The steps are only traced, never executed.

Meshes use 8 devices (the repo-standard
``--xla_force_host_platform_device_count=8`` virtual CPU mesh; tests get
it from conftest.py, tools/shardlint.py sets it before importing jax).
"""

from __future__ import annotations

import jax

# tiny trace model: big enough that every leaf family (embed/head/norms/
# attention/mlp) is present and dims divide an 8-device mesh, small enough
# to trace in well under a second
TRACE_VOCAB = 64
TRACE_D_MODEL = 32
TRACE_HEADS = 4
TRACE_LAYERS = 2
TRACE_D_FF = 64
TRACE_BATCH = 8
TRACE_SEQ = 16
# small cap so the tiny tree still splits into >1 bucket per spec group -
# the overlap manifests then pin the BUCKETED shape of the schedule
TRACE_BUCKET_MB = 0.002


def _trace_cfg(**cfg_kwargs):
    from ..models import transformer as tfm

    return tfm.TransformerConfig(
        vocab_size=TRACE_VOCAB, d_model=TRACE_D_MODEL, n_heads=TRACE_HEADS,
        n_layers=TRACE_LAYERS, d_ff=TRACE_D_FF, **cfg_kwargs,
    )


def _require_devices(n: int):
    if jax.device_count() < n:
        raise RuntimeError(
            f"shardlint configs need {n} devices, have {jax.device_count()} "
            "- run with XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "set BEFORE jax is imported (tools/shardlint.py does this)"
        )


# config name -> the structured recipe behind its builder: family, mesh
# factors, optimizer, extra step kwargs. The autoshard search
# (analysis/autoshard.py) re-builds the same program at OTHER mesh
# factorizations from these, so search candidates can never drift from
# what shardlint traces.
BLUEPRINTS: dict = {}


def _lm(name, *, dp=4, sp=1, tp=1, optimizer="sgd", cfg_kwargs=None, **kw):
    from ..train import lm as lmtrain

    BLUEPRINTS[name] = {
        "family": "lm", "dp": dp, "sp": sp, "tp": tp,
        "optimizer": optimizer, "kwargs": dict(kw),
        "cfg_kwargs": dict(cfg_kwargs or {}),
    }

    def build():
        _require_devices(dp * sp * tp)
        cfg = _trace_cfg(**(cfg_kwargs or {}))
        mesh = lmtrain.create_lm_mesh(dp, sp, tp)
        return lmtrain.lm_step_program(
            cfg, mesh, batch=TRACE_BATCH, seq_len=TRACE_SEQ, name=name,
            optimizer=optimizer, bucket_mb=TRACE_BUCKET_MB, **kw,
        )

    return build


def _pp(name, *, dp=2, pp=2, optimizer="sgd", **kw):
    from ..parallel import pipeline as ppl

    BLUEPRINTS[name] = {
        "family": "pp", "dp": dp, "pp": pp, "tp": 1,
        "optimizer": optimizer,
        "kwargs": dict(kw, n_microbatches=2),
    }

    def build():
        _require_devices(dp * pp)
        cfg = _trace_cfg()
        mesh = ppl.create_pp_mesh(dp, pp, 1)
        return ppl.pp_step_program(
            cfg, mesh, batch=TRACE_BATCH, seq_len=TRACE_SEQ, name=name,
            optimizer=optimizer, n_microbatches=2,
            bucket_mb=TRACE_BUCKET_MB, **kw,
        )

    return build


def _reshard(name, *, dp=4):
    from ..parallel import reshard
    from ..train import lm as lmtrain

    def build():
        _require_devices(dp)
        cfg = _trace_cfg()
        mesh = lmtrain.create_lm_mesh(dp, 1, 1)
        return reshard.reshard_step_program(cfg, mesh, name=name)

    return build


def _reshard_pp(name, *, dp=2, pp=2):
    from ..parallel import pipeline as ppl, reshard

    def build():
        _require_devices(dp * pp)
        cfg = _trace_cfg()
        mesh = ppl.create_pp_mesh(dp, pp, 1)
        return reshard.reshard_pp_step_program(cfg, mesh, name=name)

    return build


def _cnn(name, phase):
    def build():
        _require_devices(4)
        from ..data.cifar10 import load_split
        from ..train.engine import Engine, TrainConfig

        engine = Engine(
            TrainConfig(nb_proc=4, batch_size=8, epochs=1),
            load_split(True, source="synthetic", synthetic_size=64),
            None,
        )
        progs = {p.name: p for p in engine.step_programs()}
        if phase not in progs:
            raise RuntimeError(
                f"{name}: engine exposed no {phase!r} program "
                f"(has {list(progs)})"
            )
        prog = progs[phase]
        object.__setattr__(prog, "name", name)
        return prog

    return build


OVERLAP = dict(accum_steps=2, grad_sync="overlap")

CANONICAL_CONFIGS = {
    # dp: replicated params, grad sync over 'data' (+ the end/overlap pair)
    "lm_dp": _lm("lm_dp"),
    "lm_dp_overlap": _lm("lm_dp_overlap", **OVERLAP),
    # adam: same sync, 2x state in the donation contract
    "lm_adam": _lm("lm_adam", optimizer="adam"),
    # tp: per-block forward psums over 'model'
    "lm_tp": _lm("lm_tp", dp=2, tp=2),
    # ZeRO-1 family: per-leaf all_gather reassembly; overlap adds the
    # in-scan bucketed reduce-scatter with the O(D/dp) shard carry
    "lm_zero": _lm("lm_zero", optimizer="zero"),
    "lm_zero_overlap": _lm("lm_zero_overlap", optimizer="zero", **OVERLAP),
    "lm_zero_adam": _lm("lm_zero_adam", optimizer="zero-adam"),
    "lm_zero_adam_overlap": _lm(
        "lm_zero_adam_overlap", optimizer="zero-adam", **OVERLAP
    ),
    # the fp8/int8 fast path (ROADMAP item 3): the same dp step with
    # quantized attention matmuls - the manifest pins the int8/fp8 value
    # counts AND the wide-accumulate upcasts (fp8->f32 appears in the
    # upcast table), so a silently-dropped low-precision path or a
    # silently-dropped accumulation upcast both fail --check
    "lm_quant_fp8": _lm(
        "lm_quant_fp8", cfg_kwargs=dict(attn_quant="fp8")
    ),
    "lm_quant_int8": _lm(
        "lm_quant_int8", cfg_kwargs=dict(attn_quant="int8")
    ),
    # pipeline: per-tick ppermute ring + the exit all_to_all
    "pp_gpipe": _pp("pp_gpipe"),
    "pp_overlap": _pp("pp_overlap", **OVERLAP),
    "pp_zero": _pp("pp_zero", optimizer="zero"),
    # elastic resharder (parallel/reshard.py): the same-mesh collective
    # form of the ZeRO reassembly - one tiled all_gather per state leaf
    # over 'data' - so the reshard transfer's collective bytes are pinned
    # like every training step's
    "lm_reshard_zero_gather": _reshard("lm_reshard_zero_gather"),
    # the ZeRO-under-pp resharder: per pipe-sharded leaf one data-axis
    # segment gather + one pipe-axis stage concat (stage order explicit),
    # per replicated leaf the mesh path's single data gather - pinned so
    # the elastic path's transfer schedule cannot regress silently
    "pp_reshard_zero_gather": _reshard_pp("pp_reshard_zero_gather"),
    # the CNN engine: the sharded local-SGD epoch (no collectives by
    # design - local training) and the fault-masked parameter-average
    # sync phase (where the epoch-edge psums live)
    "cnn_dp": _cnn("cnn_dp", "cnn_train_epoch"),
    "cnn_sync": _cnn("cnn_sync", "cnn_sync"),
}


def config_names() -> list:
    return list(CANONICAL_CONFIGS)


def searchable_config_names() -> list:
    """Configs the autoshard search covers: the lm/pp TRAINING steps,
    whose mesh factorization is a free choice. The CNN engine's programs
    (batch-axis only) and the reshard transfer program (mesh fixed by the
    checkpoint) have nothing to search over."""
    return [n for n, bp in BLUEPRINTS.items() if bp["family"] in ("lm", "pp")]


def build_program(name: str):
    try:
        build = CANONICAL_CONFIGS[name]
    except KeyError:
        raise KeyError(
            f"unknown shardlint config {name!r}; known configs: "
            f"{', '.join(CANONICAL_CONFIGS)}"
        ) from None
    return build()
