"""The `lfm2_moe` family's arithmetic: the parameters held, the operations
and bytes that serving a token and the grouped-query decode kernel need, and
what a program of the serving engine cannot avoid reading from HBM
(`program_read_bytes`, the whole step's roofline: at 64 sequences a tick a
decode program is its experts' matrices), from the configuration's shapes
and the program's counters.
"""

from __future__ import annotations

from . import weights


def _per_kind(model: dict) -> dict:
    """kind -> (matrices every token of such a sublayer is multiplied by,
    one routed expert's matrices, everything else: gains, taps, the bias)
    of ONE entry of that kind, in parameters."""
    z = weights.sizes(model)
    d, hd = z["d"], z["hd"]
    return {
        "conv": (d * 3 * d + d * d, 0, d + d * z["taps"]),
        "attn": (2 * d * z["h"] * hd + 2 * d * z["kv"] * hd, 0, d + 2 * hd),
        "dense": (3 * d * z["ff"], 0, d),
        "moe": (d * z["routed"], 3 * d * z["f"], d + z["routed"]),
    }


def param_count(model: dict) -> int:
    """Every parameter held here: the sum of `weights.shapes` (the embedding
    counts once: it is also the head)."""
    z, per = weights.sizes(model), _per_kind(model)
    return sum(z[kind] * (per[kind][0] + z["held"] * per[kind][1]
                          + per[kind][2]) for kind in weights.KINDS) + (
        model["vocab_size"] * z["d"] + z["d"])


def matmul_params(model: dict) -> int:
    """The matrices held here that a token may be multiplied by: every
    projection, the router, all the routed experts held, and the embedding
    as the head (the taps and the gains are not matrices)."""
    z, per = weights.sizes(model), _per_kind(model)
    return sum(z[kind] * (per[kind][0] + z["held"] * per[kind][1])
               for kind in weights.KINDS) + z["d"] * model["vocab_size"]


def active_matmul_params(model: dict) -> float:
    """What one token IS multiplied by here: the routed experts at `top_k *
    held / routed` of one expert a token (all of top_k where every expert
    is held)."""
    z, per = weights.sizes(model), _per_kind(model)
    share = z["top_k"] * z["held"] / z["routed"]
    return sum(z[kind] * (per[kind][0] + share * per[kind][1])
               for kind in weights.KINDS) + z["d"] * model["vocab_size"]


def pair_flops(model: dict) -> float:
    """One (query, key) pair in one attention layer: scores and the weighted
    sum over a head, every query head."""
    z = weights.sizes(model)
    return 4.0 * z["h"] * z["hd"]


def forward_flops(model: dict, n_tokens: int, context_sum: int) -> float:
    """Serving: 2 per matrix parameter a token is multiplied by (1.296 GFLOP
    a token at the cell's size), the convolution's taps, and the attention
    layers' products for each (query, cached key) pair; `context_sum` is the
    sum over processed tokens of the positions each attends to."""
    z = weights.sizes(model)
    return ((2.0 * active_matmul_params(model)
             + 2.0 * z["conv"] * z["d"] * (z["taps"] + 1)) * n_tokens
            + z["attn"] * pair_flops(model) * context_sum)


def decode_attn_flops(model: dict, live: float) -> float:
    """One query a sequence: every query head scores a cached key over its
    head and weighs a cached value: 4 H head_dim a live position an
    attention layer (8,192 at the published widths); `live` is the growth of
    the program's counter of live cached positions."""
    return weights.sizes(model)["attn"] * pair_flops(model) * live


def decode_attn_bytes(model: dict, live: float, itemsize: int = 2) -> float:
    """Least HBM traffic: K and V of every KV head of every live position
    read once an attention layer, for all the query heads of its group."""
    return kv_bytes_per_token(model, itemsize) * live


def kv_bytes_per_token(model: dict, itemsize: int = 2) -> float:
    """The cache's bytes a token: K and V of the KV heads in the attention
    layers alone (4,096 at the cell's size; the convolution layers keep a
    state a sequence, `state_bytes_per_sequence`)."""
    z = weights.sizes(model)
    return float(z["attn"] * 2 * z["kv"] * z["hd"] * itemsize)


def state_bytes_per_sequence(model: dict, itemsize: int = 2) -> float:
    """The convolution layers' state a sequence, whatever its length."""
    z = weights.sizes(model)
    return float(z["conv"] * (z["taps"] - 1) * z["d"] * itemsize)


def program_read_bytes(model: dict, kind: str, experts_read: float,
                       positions: float, programs: float = 1.0,
                       itemsize: int = 2) -> float:
    """What `programs` programs of `kind` ("decode" or "prefill") cannot
    avoid reading from HBM: every matrix outside the routed experts once a
    program (the embedding as the head in decode alone: a prefill program
    makes no logits, and the rows a token looks up are not counted); the
    three matrices of each expert that owns a row, `experts_read` summed
    over the programs' expert layers; and the cache rows of `positions`
    live positions. A lower bound: an expert with more rows than a tile is
    read once a tile, activations and the state are left out."""
    z, per = weights.sizes(model), _per_kind(model)
    always = sum(z[k] * (per[k][0] + per[k][2]) for k in weights.KINDS)
    if kind == "decode":
        always += z["d"] * model["vocab_size"]
    elif kind != "prefill":
        raise ValueError(f"program kind {kind!r}: 'decode' or 'prefill'")
    return itemsize * (programs * always
                       + experts_read * per["moe"][1]) + (
        kv_bytes_per_token(model, itemsize) * positions)
