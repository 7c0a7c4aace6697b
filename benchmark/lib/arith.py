"""The yardstick's arithmetic that no family owns: the peaks of the chip and
the least time a piece of work could take on it. The operations and bytes that
a model and its kernels need are its family's (`families/<family>/arith.py`).

Copied in spirit from `train/measure.py` (`PEAK_TFLOPS_BF16`); kept here so
that no later PR can move it.
"""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise SystemExit(
            f"device kind {device_kind!r} is not in benchmark/lib/arith.py "
            f"PEAKS ({sorted(PEAKS)}): no number is reported against a "
            "guessed peak")
    return PEAKS[device_kind]


def roofline_seconds(flops: float, nbytes: float, device_kind: str) -> tuple:
    """(least seconds, which of the two bounds it)."""
    p = peaks(device_kind)
    t_c, t_m = flops / p["flops_bf16"], nbytes / p["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
