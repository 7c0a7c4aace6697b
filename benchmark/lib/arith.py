"""The yardstick's arithmetic: peaks of the chip, and the operations and bytes
that the model and its kernels need, from shapes alone.

Copied in spirit from `train/measure.py` (`PEAK_TFLOPS_BF16`, the 6N + attention
model-FLOP convention); kept here so that no later PR can move it.
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


def matmul_params(model: dict) -> int:
    """Parameters that a token is multiplied by (the embedding is a lookup)."""
    d, L, f, v = (model["n_embd"], model["n_layer"], model["n_inner"],
                  model["vocab_size"])
    return L * (4 * d * d + 2 * d * f) + d * v


def param_count(model: dict) -> int:
    d, L, f, v = (model["n_embd"], model["n_layer"], model["n_inner"],
                  model["vocab_size"])
    return 2 * v * d + 2 * d + L * (4 * d * d + 2 * d * f + f + 5 * d)


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward and backward, recomputation not counted: 6 per matmul
    parameter, and causal attention at 2*S*d forward per layer (half of the
    full square), three times that with its backward."""
    return 6.0 * matmul_params(model) + (
        6.0 * model["n_layer"] * seq * model["n_embd"])


def forward_flops(model: dict, n_tokens: int, context_sum: int) -> float:
    """Serving: 2 per matmul parameter a token, and 4*d per layer for each
    (query, cached key) pair; `context_sum` is the sum over processed tokens
    of the positions each attends to."""
    return 2.0 * matmul_params(model) * n_tokens + (
        4.0 * model["n_layer"] * model["n_embd"] * context_sum)


def flash_train_flops(model: dict, batch: int, seq: int) -> float:
    """What the flash kernels of one training step need: forward two matmuls
    over the causal half, 4*B*H*S*S*Dh*0.5; backward five (scores again, dP,
    dV, dQ, dK) = 2.5 times the forward. Recomputation under remat is the
    program's choice and is not counted."""
    d = model["n_embd"]
    fwd = 2.0 * batch * seq * seq * d
    return model["n_layer"] * 3.5 * fwd


def flash_train_bytes(model: dict, batch: int, seq: int,
                      itemsize: int = 2) -> float:
    """Least HBM traffic: forward reads q, k, v and writes o; backward reads
    q, k, v, o, do and writes dq, dk, dv (row statistics left out)."""
    return model["n_layer"] * 12.0 * batch * seq * model["n_embd"] * itemsize


def roofline_seconds(flops: float, nbytes: float, device_kind: str) -> tuple:
    """(least seconds, which of the two bounds it)."""
    p = peaks(device_kind)
    t_c, t_m = flops / p["flops_bf16"], nbytes / p["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
