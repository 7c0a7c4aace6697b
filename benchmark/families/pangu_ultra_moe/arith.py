"""The `pangu_ultra_moe` family's arithmetic: the parameters a chip's share
holds, and the operations and bytes that serving a token and the latent
attention kernels need, from the configuration's shapes and the program's
counters.
"""

from __future__ import annotations

from . import weights


def _attention(z: dict) -> tuple:
    """(matrices, gains) of one layer's attention and sandwich norms."""
    d, h = z["d"], z["h"]
    matrices = (d * z["q_rank"] + z["q_rank"] * h * (z["nope"] + z["rope"])
                + d * z["row"] + z["kv_rank"] * h * (z["nope"] + z["v"])
                + h * z["v"] * d)
    return matrices, z["q_rank"] + z["kv_rank"] + 4 * d


def _per_layer(model: dict) -> dict:
    """kind -> (matrices every token is multiplied by, one routed expert's
    matrices, everything else) of one layer of that kind, in parameters."""
    z = weights.sizes(model)
    attn, gains = _attention(z)
    d = z["d"]
    return {
        "dense": (attn + 3 * d * z["ff"], 0, gains),
        "moe": (attn + d * z["routed"] + 3 * d * z["fs"], 3 * d * z["f"],
                gains),
    }


def param_count(model: dict) -> int:
    """Every parameter held here: the sum of `weights.shapes`."""
    z, per = weights.sizes(model), _per_layer(model)
    layers = sum(z[kind] * (per[kind][0] + z["held"] * per[kind][1]
                            + per[kind][2]) for kind in weights.KINDS)
    return layers + 2 * model["vocab_size"] * z["d"] + z["d"]


def matmul_params(model: dict) -> int:
    """The matrices held here that a token may be multiplied by: every
    projection, the router, the shared expert, all the routed experts held,
    and the head (the embedding is a lookup, the gains are not matrices)."""
    z, per = weights.sizes(model), _per_layer(model)
    return sum(z[kind] * (per[kind][0] + z["held"] * per[kind][1])
               for kind in weights.KINDS) + z["d"] * model["vocab_size"]


def active_matmul_params(model: dict) -> float:
    """What one token IS multiplied by here, on average: the routed experts
    at `top_k * held / routed` of one expert a token (each of a token's
    top_k choices lands on a held expert with probability held / routed)."""
    z, per = weights.sizes(model), _per_layer(model)
    share = z["top_k"] * z["held"] / z["routed"]
    return sum(z[kind] * (per[kind][0] + share * per[kind][1])
               for kind in weights.KINDS) + z["d"] * model["vocab_size"]


def pair_flops(model: dict) -> float:
    """The expanded form's products for one (query, key) pair in one layer:
    scores over qk_nope + qk_rope and the weighted sum over v, every head."""
    z = weights.sizes(model)
    return 2.0 * z["h"] * (z["nope"] + z["rope"] + z["v"])


def forward_flops(model: dict, n_tokens: int, context_sum: int) -> float:
    """Serving: 2 per matrix parameter a token is multiplied by, and the
    expanded form's products for each (query, cached key) pair a layer;
    `context_sum` is the sum over processed tokens of the positions each
    attends to. (The expansion of the cached latents and the absorbed
    form's wider products are the program's choices and are not counted.)"""
    z = weights.sizes(model)
    return (2.0 * active_matmul_params(model) * n_tokens
            + z["layers"] * pair_flops(model) * context_sum)


def decode_attn_flops(model: dict, live: float) -> float:
    """The absorbed form, one query a sequence: every head scores a cached
    row over its whole width (kv_rank + qk_rope) and weighs its first
    kv_rank values: 2 H (row + kv_rank) a live position a layer (278,528
    at the published widths); `live` is the growth of the program's counter
    of live cached positions."""
    z = weights.sizes(model)
    return 2.0 * z["h"] * (z["row"] + z["kv_rank"]) * z["layers"] * live


def decode_attn_bytes(model: dict, live: float, itemsize: int = 2) -> float:
    """Least HBM traffic: the cache row of every live position read once a
    layer, for all heads (K and V are the same bytes)."""
    return kv_bytes_per_token(model, itemsize) * live


def prefill_attn_flops(model: dict, pairs: float) -> float:
    """The expanded form over `pairs` live (query, key) pairs, every layer
    (the up-projection of the cached latents is not counted)."""
    return weights.sizes(model)["layers"] * pair_flops(model) * pairs


def prefill_attn_bytes(model: dict, pairs: float, chunk: int,
                       itemsize: int = 2) -> float:
    """Least HBM traffic of a prefill kernel that reads the latent cache: a
    chunk of `chunk` queries reads each of its keys' rows once a layer."""
    return kv_bytes_per_token(model, itemsize) * pairs / chunk


def kv_bytes_per_token(model: dict, itemsize: int = 2) -> float:
    """The cache's bytes a token: one latent row in every layer."""
    z = weights.sizes(model)
    return float(z["layers"] * z["row"] * itemsize)
