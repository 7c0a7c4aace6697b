"""GPT-2's arithmetic: the operations and bytes that the model and the kernels
on its path need, from the configuration's shapes and from the program's
counters. (The 6N + attention model-FLOP convention of `train/measure.py`,
kept here so that no later PR can move it.)
"""

from __future__ import annotations


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


def flash_train_flops(model: dict, batch: int, seq: int, tp: int = 1) -> float:
    """What the flash kernels of one training step need on a chip that holds
    `batch` rows and one `tp`-th of the heads: forward two matmuls over the
    causal half, 4*B*H*S*S*Dh*0.5; backward five (scores again, dP, dV, dQ,
    dK) = 2.5 times the forward. Recomputation under remat is the program's
    choice and is not counted."""
    d = model["n_embd"] // tp
    fwd = 2.0 * batch * seq * seq * d
    return model["n_layer"] * 3.5 * fwd


def flash_train_bytes(model: dict, batch: int, seq: int, tp: int = 1,
                      itemsize: int = 2) -> float:
    """Least HBM traffic: forward reads q, k, v and writes o; backward reads
    q, k, v, o, do and writes dq, dk, dv (row statistics left out)."""
    d = model["n_embd"] // tp
    return model["n_layer"] * 12.0 * batch * seq * d * itemsize


def decode_attn_flops(model: dict, live: float) -> float:
    """One query a sequence: 2*d for its scores and 2*d for the weighted sum
    of the values, per layer, for each cached position it attends to; `live`
    is the growth of `serve_decode_positions_total{kind="live"}`."""
    return 4.0 * model["n_layer"] * model["n_embd"] * live


def decode_attn_bytes(model: dict, live: float, itemsize: int = 2) -> float:
    """Least HBM traffic: K and V of every live position read once per layer
    (the query, the output and the block table are left out)."""
    return kv_bytes_per_token(model, itemsize) * live


def kv_bytes_per_token(model: dict, itemsize: int = 2) -> float:
    """The cache's bytes a token: K and V of width d in every layer."""
    return 2.0 * model["n_layer"] * model["n_embd"] * itemsize
