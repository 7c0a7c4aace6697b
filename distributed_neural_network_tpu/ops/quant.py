"""Low-precision quantization primitives: the fp8/int8 fast path's core.

Beyond bf16 scheduling, the next multiplier on v5e is PRECISION: int8/fp8 operands halve HBM traffic and
double MXU throughput on hardware with native low-precision matmul
units, and an int8 KV cache directly doubles the serving stack's
concurrent-sequence capacity (serve/kv_cache.py). This module is the
shared numerics layer under all of it:

- **quantize / dequantize**: symmetric per-block scaling (one f32 scale
  per ``block`` elements of the quantized axis; ``block=None`` = one
  scale per row, the "per-token" granularity) for two target formats -
  ``int8`` (round-to-nearest onto [-127, 127], zero always exact) and
  ``fp8`` (float8_e4m3fn, scales chosen so the block amax lands at the
  format's max finite 448 - values beyond it would become NaN, not inf,
  so the clamp is load-bearing). An asymmetric (scale + zero-point)
  int8 variant exists for one-sided distributions; the attention/KV
  paths use the symmetric form (K/V are zero-centered projections).
- **roundtrip_error**: the honesty helper - quantize, dequantize, and
  report mae / max abs / relative error so tests and the bench parity
  gate state error BOUNDS instead of vibes.
- **quantized_matmul / quantized_attention**: the XLA reference
  implementations of the quantized kernels (ops/flash_pallas.py's
  ``quant=`` path and ops/decode_pallas.py's int8 stream). Real
  low-precision dots - ``int8 x int8 -> int32`` and ``fp8 x fp8 -> f32``
  via ``preferred_element_type`` - with the accumulate UPCAST to
  f32/bf16 explicit, so the shardlint precision lint can pin it in a
  manifest (analysis/lint.py: a silently-dropped upcast fails
  ``--check``). Off-TPU (CI, laptops) these ARE the quantized path;
  on TPU they are the parity oracle the Pallas kernels are tested
  against.

Numerics contract (what the bench parity gate enforces,
docs/MEASUREMENT.md): per-row symmetric int8 keeps attention-score
round-trip error ~2^-7 relative per operand; fp8-e4m3 ~2^-3. Both are
inside the documented logit-MAE / final-loss-delta tolerances of
``measure_quant_parity`` and the >= 99% per-token top-1 agreement of
the int8 KV serving gate.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# quantized formats: name -> (storage dtype, max representable magnitude)
INT8_MAX = 127.0
FP8_MAX = 448.0  # float8_e4m3fn largest finite; beyond it casts to NaN
QUANT_FORMATS = {
    "int8": (jnp.int8, INT8_MAX),
    "fp8": (jnp.float8_e4m3fn, FP8_MAX),
}
# smallest scale: keeps 1/scale finite and an all-zero block exact
_EPS = 1e-30


def quant_dtype(fmt: str):
    """Storage dtype of a quantized format name ('int8' | 'fp8')."""
    _check_fmt(fmt)
    return QUANT_FORMATS[fmt][0]


def _check_fmt(fmt: str) -> None:
    if fmt not in QUANT_FORMATS:
        raise ValueError(
            f"unknown quantized format {fmt!r}; supported: "
            f"{', '.join(QUANT_FORMATS)}"
        )


def _block_view(x, block: int):
    """(..., n) -> (..., n//block, block); n must divide by block."""
    n = x.shape[-1]
    if n % block:
        raise ValueError(
            f"quantization block {block} must divide the quantized axis "
            f"({n})"
        )
    return x.reshape(*x.shape[:-1], n // block, block)


def quantize(x, fmt: str = "int8", *, block: int | None = None):
    """Symmetric quantization of the LAST axis.

    Returns ``(q, scale)``: ``q`` in the format's storage dtype with
    ``x ~= q * scale`` (scale broadcast over each block). ``block=None``
    uses one scale per row (block = whole last axis - the per-token
    granularity the attention paths use); otherwise one f32 scale per
    ``block`` consecutive elements, shaped ``x.shape[:-1] + (n//block,)``.
    Scales are strictly positive (an all-zero block gets scale ~0 and
    exact-zero codes), so dequantization never divides by zero.
    """
    _check_fmt(fmt)
    dtype, qmax = QUANT_FORMATS[fmt]
    xf = x.astype(jnp.float32)
    blocked = block is not None
    if blocked:
        xf = _block_view(xf, block)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, _EPS) / qmax
    q = xf / scale
    if fmt == "int8":
        q = jnp.clip(jnp.round(q), -INT8_MAX, INT8_MAX)
    else:
        # e4m3's max finite is 448: anything beyond saturates to NaN on
        # cast, so clamp first (scale puts amax exactly at 448 already;
        # the clip guards float slop)
        q = jnp.clip(q, -FP8_MAX, FP8_MAX)
    q = q.astype(dtype)
    if blocked:
        q = q.reshape(x.shape)
        scale = scale[..., 0]
    else:
        scale = scale[..., 0]
    return q, scale


def dequantize(q, scale, *, block: int | None = None):
    """Inverse of `quantize`: f32 reconstruction ``q * scale`` with the
    same block layout (``scale`` shaped as `quantize` returned it)."""
    qf = q.astype(jnp.float32)
    if block is None:
        return qf * scale[..., None]
    return (_block_view(qf, block) * scale[..., None]).reshape(q.shape)


def quantize_asymmetric(x, *, block: int | None = None):
    """Asymmetric int8: ``x ~= (q - zero_point) * scale`` with q in
    [0, 255] stored as uint8. One (scale, zero_point) pair per row
    (``block=None``) or per ``block`` elements - the one-sided-
    distribution variant (e.g. post-gelu activations); the attention/KV
    paths use the symmetric form."""
    xf = x.astype(jnp.float32)
    blocked = block is not None
    if blocked:
        xf = _block_view(xf, block)
    lo = jnp.min(xf, axis=-1, keepdims=True)
    hi = jnp.max(xf, axis=-1, keepdims=True)
    scale = jnp.maximum(hi - lo, _EPS) / 255.0
    zp = jnp.round(-lo / scale)
    q = jnp.clip(jnp.round(xf / scale) + zp, 0, 255).astype(jnp.uint8)
    if blocked:
        q = q.reshape(x.shape)
    return q, scale[..., 0], zp[..., 0]


def dequantize_asymmetric(q, scale, zero_point, *, block: int | None = None):
    qf = q.astype(jnp.float32)
    if block is None:
        return (qf - zero_point[..., None]) * scale[..., None]
    v = (_block_view(qf, block) - zero_point[..., None]) * scale[..., None]
    return v.reshape(q.shape)


def roundtrip_error(x, fmt: str = "int8", *, block: int | None = None) -> dict:
    """Quantize -> dequantize -> error report: ``{"mae", "max_abs",
    "rel"}`` (rel = max_abs over the tensor amax). The parity gates and
    tests consume this instead of re-deriving error math."""
    q, scale = quantize(x, fmt, block=block)
    back = dequantize(q, scale, block=block)
    err = jnp.abs(back - x.astype(jnp.float32))
    amax = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32))), _EPS)
    return {
        "mae": float(jnp.mean(err)),
        "max_abs": float(jnp.max(err)),
        "rel": float(jnp.max(err) / amax),
    }


# ------------------------------------------------------- quantized matmul


def _low_precision_dot(a_q, b_q, fmt: str, dn):
    """The quantized MXU dot: int8 x int8 accumulates in int32, fp8 x
    fp8 in f32 (``preferred_element_type``); both return f32. THE
    accumulate upcast lives here - never accumulate in the storage
    dtype (int8 overflows at k > 2 elements; fp8 loses the mantissa)."""
    if fmt == "int8":
        acc = jax.lax.dot_general(
            a_q, b_q, dn, preferred_element_type=jnp.int32
        )
        return acc.astype(jnp.float32)
    return jax.lax.dot_general(
        a_q, b_q, dn, preferred_element_type=jnp.float32
    )


def prequantize_weight(w, fmt: str = "int8"):
    """Quantize a ``(k, n)`` weight ONCE for reuse across many matmuls:
    per-COLUMN symmetric codes stored transposed as ``(n, k)`` plus the
    ``(n,)`` f32 scales - exactly the layout `quantized_matmul` builds
    for its right operand on every call. Leading batch/layer axes pass
    through (a stacked ``(L, k, n)`` weight yields ``(L, n, k)`` codes
    + ``(L, n)`` scales - only the last two axes swap). Serving's
    ``--precision int8-w`` quantizes each weight at engine init and
    feeds the pair back via ``b=(w_q, w_scale)``, so the per-step cost
    drops to quantizing the (tiny) activation rows."""
    _check_fmt(fmt)
    return quantize(jnp.swapaxes(w, -1, -2), fmt)


def quantized_matmul(a, b, fmt: str = "int8", *,
                     weight_only: bool = False):
    """``a (m, k) @ b (k, n)`` through per-row symmetric quantization of
    both operands (b quantized per COLUMN - its contraction axis is
    rows), low-precision dot, f32 dequantized result. ``b`` may also be
    a ``(b_q, b_scale)`` pair from `prequantize_weight` - same numerics,
    weight-side quantization amortized to zero. The XLA reference for
    the Pallas quantized matmul paths, and a usable building block on
    backends without them.

    ``weight_only=True`` is the W8A16 serving recipe: ONLY the weight
    is quantized (codes read from int8 storage, dequantized by the
    per-column scale inside the dot); the activation rows stay at full
    precision. Decode matmuls are bandwidth-bound, so int8 storage
    already buys the 2x HBM win, while skipping activation quantization
    keeps per-token top-1 agreement at the >= 99% gate (the dual-int8
    dot's activation rounding costs ~6% of argmaxes on these model
    scales - fine for training parity tolerances, not for serving's
    token-exactness bar)."""
    _check_fmt(fmt)
    if isinstance(b, tuple):
        b_q, sb = b                               # (n, k), (n,) stored
    else:
        b_q, sb = quantize(b.T, fmt)              # (n, k), (n,)
    if weight_only:
        acc = jax.lax.dot_general(
            a.astype(jnp.float32), b_q.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
        )                                         # (m, n) f32
        return acc * sb[None, :]
    a_q, sa = quantize(a, fmt)                    # (m, k), (m,)
    acc = _low_precision_dot(
        a_q, b_q, fmt, (((1,), (1,)), ((), ()))
    )                                             # (m, n) f32
    return acc * sa[:, None] * sb[None, :]


# ---------------------------------------------------- quantized attention

_NEG_BIG = -1e30


def quantized_attention(q, k, v, *, causal: bool = True, fmt: str = "int8",
                        scale=None):
    """Quantized scaled-dot-product attention, (B, S, H, D) -> same.

    The XLA reference for the quantized flash path
    (`ops/flash_pallas.py flash_mha(quant=...)`) and the off-TPU
    execution path of ``attn_quant`` training (`models/transformer.py`).
    Per-row (per-token, per-head) symmetric scales on q/k/v; QK^T and
    PV both run as true low-precision dots:

    - scores: ``int8/fp8 q-hat @ k-hat`` accumulated wide, dequantized
      by the rank-1 scale outer product, softmaxed in f32 (the standard
      flash numerics);
    - PV: v's per-row scale is FOLDED INTO P (``sum_j p_ij sv_j v-hat_jd
      = sum_j (p_ij sv_j) v-hat_jd``), then the folded P is itself
      quantized per row with a dynamic scale so the second dot is
      low-precision too - exactly the scheme the Pallas kernel carries
      through its online-softmax rescale.

    Gradients flow straight-through jax's autodiff of the same graph
    (round/clip have zero-or-identity derivatives where defined); the
    training parity gate (train/measure.py measure_quant_parity) bounds
    the end effect on loss and logits.
    """
    _check_fmt(fmt)
    b, s, h, d = q.shape
    sc = (1.0 / np.sqrt(d)) if scale is None else float(scale)
    # (B, H, S, D): rows = tokens, the per-row quantized axis is D
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    q_q, sq = quantize(qt, fmt)   # scales (B, H, S)
    k_q, sk = quantize(kt, fmt)
    v_q, sv = quantize(vt, fmt)
    dn = (((3,), (3,)), ((0, 1), (0, 1)))  # contract D, batch (B, H)
    s_int = _low_precision_dot(q_q, k_q, fmt, dn)  # (B, H, S, S) f32
    scores = s_int * sq[..., :, None] * sk[..., None, :] * sc
    if causal:
        rows = jnp.arange(s)[:, None]
        cols = jnp.arange(s)[None, :]
        scores = jnp.where(rows >= cols, scores, _NEG_BIG)
    p = jax.nn.softmax(scores, axis=-1)  # f32
    # fold v's per-row scale into p, then quantize the folded p per row
    p_f = p * sv[..., None, :]
    p_q, sp = quantize(p_f, fmt)
    dn_pv = (((3,), (2,)), ((0, 1), (0, 1)))  # (B,H,S,S) x (B,H,S,D)
    o = _low_precision_dot(p_q, v_q, fmt, dn_pv) * sp[..., None]
    return o.astype(q.dtype).transpose(0, 2, 1, 3)
