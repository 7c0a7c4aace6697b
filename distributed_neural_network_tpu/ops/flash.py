"""Flash attention dispatch for local (per-device) long-context attention.

The plain local kernel (`parallel/ring.py attention`) materializes the
(B, H, S, S) score matrix, so single-chip long-context is HBM-bound.
This module picks the flash implementation:

- **"own"** (default): this framework's Pallas kernels
  (`ops/flash_pallas.py`) - vma-typed outputs, so they compose with
  dp x tp shard_map under check_vma=True (the library kernel cannot), and
  the backward block sizes are first-class tunables (the r3-diagnosed MFU
  bottleneck).
- **"lib"**: the Pallas kernel that ships with JAX
  (`jax.experimental.pallas.ops.tpu.flash_attention`) - kept as the A/B
  baseline for `tools/tune_flash.py` and as a fallback; single-device
  only (no vma typing).
- Off-TPU both fall back to the plain kernel (Pallas TPU kernels are
  Mosaic-only; the interpreter is not shard_map-compatible). The decision
  is `runtime.on_tpu()`, and the entry points print which side they took.

Select with `DNN_TPU_FLASH_IMPL=own|lib` or the `impl=` argument. Block
sizes: `tools/tune_flash.py` writes `tools/flash_tune_<device>_s<seq>.json`;
`tuned_blocks()` loads the matching file's best own-kernel blocks at call
time (cached), else `FlashBlocks()` defaults.

Block-size tuning status: the checked-in tune files date from
2026-08-01 and have not been re-measured on the current code or jax
(ROADMAP.md "What the records say"). What is solid is that flash never
materializes the (B, H, S, S) score matrix, so the LM can drop --remat
(the S^2 buffers were what forced it).

Sits alongside the mesh-level answers to long context (ring / Ulysses /
zigzag sequence parallelism, `parallel/ring.py`): flash bounds the
per-chip attention memory at O(S); the seq axis scales beyond it.
"""

from __future__ import annotations

import functools
import glob
import json
import math
import os

import jax

from ..parallel.ring import attention
from ..runtime import on_tpu
from .flash_pallas import FlashBlocks, flash_mha


@functools.cache
def _lib_available() -> bool:
    if not on_tpu():
        return False
    try:
        from jax.experimental.pallas.ops.tpu import flash_attention  # noqa: F401

        return True
    except ImportError:
        return False


# where tune files live; module-level so tests can point it at a tmp dir
# (tuned_blocks is cached - tests must also cache_clear())
_TUNE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "tools")


@functools.cache
def tuned_blocks(s: int, head_dim: int,
                 device_kind: str | None = None) -> FlashBlocks:
    """Best own-kernel blocks for (seq s, head_dim) from the tuner's JSON,
    else defaults. A tune file applies only when it was measured on THIS
    device kind (`device_kind`, default the attached device's) at THIS
    head_dim (mismatched tunings were never measured), and its seq must
    equal s or divide it (divisor-tuned blocks still tile s;
    `FlashBlocks.resolve` keeps them legal). Exact-seq files win; among
    divisor files the largest seq wins."""
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    dev = device_kind.replace(" ", "_")
    pat = os.path.join(_TUNE_DIR, "flash_tune_*.json")
    best, best_seq = None, -1
    for path in glob.glob(pat):
        try:
            with open(path) as f:
                data = json.load(f)
            own = data.get("best_own")
            shape = data.get("shape", {})
            seq = shape.get("seq", 0)
        except (OSError, json.JSONDecodeError):
            continue
        if (not own or data.get("device") != dev
                or shape.get("head_dim") != head_dim):
            continue
        if seq == s or (seq and s % seq == 0):
            if best_seq != s and (seq == s or seq > best_seq):
                best, best_seq = own, seq
    if not best:
        return FlashBlocks()
    return FlashBlocks(**{k: int(v) for k, v in best.items()
                          if k in FlashBlocks.__dataclass_fields__})


@functools.cache
def _lib_block_sizes(s: int, head_dim: int = 64):
    """Uniform provisional blocks for the LIBRARY kernel, or None for its
    defaults (the 1024-uniform choice is provisional: it has not been
    re-measured on the current code). The kernel's `_verify_block` requires
    every block to divide the sequence length, so the size is the largest
    power-of-two divisor of S in [128, 1024]; None when none exists or
    head_dim != 64 (never measured)."""
    if head_dim != 64:
        return None
    for b in (1024, 512, 256, 128):
        if s % b == 0:
            break
    else:
        return None
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

    return BlockSizes(
        block_q=b, block_k_major=b, block_k=b, block_b=1,
        block_q_major_dkv=b, block_k_major_dkv=b,
        block_q_dkv=b, block_k_dkv=b,
        block_q_dq=b, block_k_dq=b, block_k_major_dq=b,
    )


def _lib_flash(q, k, v, *, causal: bool):
    from jax.experimental.pallas.ops.tpu.flash_attention import flash_attention

    d = q.shape[-1]
    out = flash_attention(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        causal=causal,
        sm_scale=1.0 / math.sqrt(d),
        block_sizes=_lib_block_sizes(q.shape[1], d),
    )
    return out.transpose(0, 2, 1, 3)


def flash_local_attention(q, k, v, *, causal: bool = True,
                          impl: str | None = None,
                          quant: str | None = None):
    """q/k/v (B, S, H, D) -> (B, S, H, D); Pallas flash on TPU, plain
    attention elsewhere. Numerics match `attention` to blockwise-softmax
    reassociation tolerance. `impl`: "own" (default; shard_map-composable)
    or "lib" (library kernel, A/B baseline), overridable via
    DNN_TPU_FLASH_IMPL.

    ``quant`` ("int8" | "fp8") selects the low-precision forward
    (`TransformerConfig.attn_quant` / ``--precision``): on TPU the own
    kernel's quantized path (`ops/flash_pallas.py`); off-TPU the XLA
    reference `ops/quant.py quantized_attention` - REAL int8/fp8 dots
    either way, so CPU CI exercises the same quantized numerics the
    chip runs. The library kernel has no quantized path (one more
    reason the kernels are owned - module docstring)."""
    if quant is not None:
        from .quant import QUANT_FORMATS, quantized_attention

        if quant not in QUANT_FORMATS:
            raise ValueError(
                f"unknown quant format {quant!r}; supported: "
                f"{', '.join(QUANT_FORMATS)}"
            )
        if (impl or os.environ.get("DNN_TPU_FLASH_IMPL", "own")) == "lib":
            raise ValueError(
                "the library flash kernel has no quantized path; use "
                "impl='own' (default) for attn quantization"
            )
        if not on_tpu():
            return quantized_attention(q, k, v, causal=causal, fmt=quant)
        return flash_mha(q, k, v, causal=causal,
                         blocks=tuned_blocks(q.shape[1], q.shape[-1]),
                         quant=quant)
    if not on_tpu():
        return attention(q, k, v, causal=causal)
    impl = impl or os.environ.get("DNN_TPU_FLASH_IMPL", "own")
    if impl == "lib":
        if not _lib_available():
            raise RuntimeError(
                "flash impl 'lib' requested (DNN_TPU_FLASH_IMPL?) but the "
                "library kernel failed to import on this backend; unset "
                "it to use the own kernel"
            )
        return _lib_flash(q, k, v, causal=causal)
    if impl != "own":
        raise ValueError(f"unknown flash impl {impl!r} (use 'own' or 'lib')")
    return flash_mha(q, k, v, causal=causal,
                     blocks=tuned_blocks(q.shape[1], q.shape[-1]))


def grouped_query_flash_attention(q, k, v, *, causal: bool = True):
    """q (B, S, H, D) against k/v (B, S, H_kv, D), H a multiple of H_kv:
    query head h reads KV head h // (H / H_kv). The kernels behind
    `flash_local_attention` assume H_kv == H, so each KV head is repeated
    for its query heads first: a copy of (H / H_kv - 1) x the K and V bytes
    (and its sum in the backward pass) that a grouped index map in the
    kernels' block specs would save (`ops/flash_pallas.py`)."""
    import jax.numpy as jnp

    h, h_kv = q.shape[2], k.shape[2]
    if h % h_kv:
        raise ValueError(
            f"{h} query heads do not divide into {h_kv} key-value heads")
    if h != h_kv:
        k = jnp.repeat(k, h // h_kv, axis=2)
        v = jnp.repeat(v, h // h_kv, axis=2)
    return flash_local_attention(q, k, v, causal=causal)
