"""The `nemotron_h` family's arithmetic: the parameters a chip's share holds,
and the operations and bytes that a training step and its flash kernels need,
from the configuration's shapes. Recomputation is never counted.
"""

from __future__ import annotations

import math

from . import weights


def _per_layer(model: dict) -> dict:
    """letter -> (matrices every token is multiplied by, one routed expert's
    matrices, everything else) of one layer of that kind, in parameters."""
    z = weights.sizes(model)
    d, di, cd = z["d"], z["d_inner"], z["conv_dim"]
    hq, hkv = z["heads"] * z["head_dim"], z["kv_heads"] * z["head_dim"]
    return {
        "M": (d * (di + cd + z["h"]) + di * d, 0,
              d + z["conv_kernel"] * cd + cd + 3 * z["h"] + di),
        "E": (d * z["routed"] + 2 * d * z["fs"], 2 * d * z["f"],
              d + z["routed"]),
        "*": (2 * d * hq + 2 * d * hkv, 0, d),
    }


def param_count(model: dict) -> int:
    """Every parameter held here: the sum of `weights.shapes`."""
    z, per = weights.sizes(model), _per_layer(model)
    layers = sum(dense + z["held"] * expert + rest
                 for dense, expert, rest in
                 (per[letter] for letter in weights.pattern(model)))
    return layers + 2 * model["vocab_size"] * z["d"] + z["d"]


def matmul_params(model: dict) -> int:
    """The matrices held here that a token may be multiplied by: every
    projection, the router, the shared expert, all the routed experts held,
    and the head (the embedding is a lookup; the depthwise convolution, the
    gains and the per-head scalars are not matrices)."""
    z, per = weights.sizes(model), _per_layer(model)
    return sum(per[c][0] + z["held"] * per[c][1]
               for c in weights.pattern(model)) + z["d"] * model["vocab_size"]


def active_matmul_params(model: dict) -> float:
    """What one token IS multiplied by here, on average: the routed experts
    at `top_k * held / routed` of one expert a token (each of a token's
    top_k choices lands on a held expert with probability held / routed)."""
    z, per = weights.sizes(model), _per_layer(model)
    share = z["top_k"] * z["held"] / z["routed"]
    return sum(per[c][0] + share * per[c][1]
               for c in weights.pattern(model)) + z["d"] * model["vocab_size"]


def scan_flops_per_token(model: dict) -> float:
    """Forward products of one Mamba-2 layer's chunked scan, a token, at
    chunk Q: C B^T over the causal half of a chunk (Q N G), that times x
    (Q H P), the chunk's state (2 H P N) and what the inherited state gives
    (2 H P N). The pass from chunk to chunk is H P N / Q and left out."""
    z, q = weights.sizes(model), model["chunk_size"]
    hp = z["h"] * z["p"]
    return q * z["n"] * z["g"] + q * hp + 4.0 * hp * z["n"]


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward and backward: 6 per matrix parameter a token is multiplied
    by, causal attention at 2 S (heads x head_dim) forward per attention
    layer (half of the full square) and three times that with its backward,
    and three times the scan's forward products per Mamba-2 layer."""
    z, pat = weights.sizes(model), weights.pattern(model)
    attn = 6.0 * pat.count("*") * seq * z["heads"] * z["head_dim"]
    scan = 3.0 * pat.count("M") * scan_flops_per_token(model)
    return 6.0 * active_matmul_params(model) + attn + scan


def flash_train_flops(model: dict, batch: int, seq: int, tp: int = 1) -> float:
    """What the flash kernels of one training step need on a chip that holds
    `batch` rows: per attention layer, forward two matmuls over the causal
    half for every query head, backward five = 2.5 times the forward."""
    z = weights.sizes(model)
    fwd = 2.0 * batch * seq * seq * (z["heads"] // tp) * z["head_dim"]
    return weights.pattern(model).count("*") * 3.5 * fwd


def flash_train_bytes(model: dict, batch: int, seq: int, tp: int = 1,
                      itemsize: int = 2) -> float:
    """Least HBM traffic: forward reads q, k, v and writes o; backward reads
    q, k, v, o, do and writes dq, dk, dv; K, V and their gradients at the
    key-value heads' width (the repeat in front of the kernels is the
    program's choice and is not counted)."""
    z = weights.sizes(model)
    width = 6.0 * (z["heads"] // tp) + 6.0 * math.ceil(z["kv_heads"] / tp)
    return (weights.pattern(model).count("*") * batch * seq * width
            * z["head_dim"] * itemsize)
