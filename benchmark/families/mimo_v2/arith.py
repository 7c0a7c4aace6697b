"""The `mimo_v2` family's arithmetic: the parameters held, the operations
and bytes that serving a token and the full layers' decode kernel need, and
what a program of the serving engine cannot avoid reading from HBM
(`program_read_bytes`), from the configuration's shapes and the program's
counters.
"""

from __future__ import annotations

from . import weights


def _per_kind(model: dict) -> dict:
    """kind -> (matrices every token of such a sublayer is multiplied by,
    one routed expert's matrices, everything else: gains, sinks, the bias)
    of ONE entry of that kind, in parameters."""
    z = weights.sizes(model)
    d, h = z["d"], z["h"]

    def attn(kv):
        return d * (h * z["qk"] + kv * (z["qk"] + z["v"])) + h * z["v"] * d

    return {
        "full": (attn(z["kv_full"]), 0, d),
        "window": (attn(z["kv_window"]), 0, d + h),
        "dense": (3 * d * z["ff"], 0, d),
        "moe": (d * z["routed"], 3 * d * z["f"], d + z["routed"]),
    }


def param_count(model: dict) -> int:
    """Every parameter held here: the sum of `weights.shapes` (embedding and
    head apart: they are untied)."""
    z, per = weights.sizes(model), _per_kind(model)
    return sum(z[kind] * (per[kind][0] + z["held"] * per[kind][1]
                          + per[kind][2]) for kind in weights.KINDS) + (
        2 * model["vocab_size"] * z["d"] + z["d"])


def matmul_params(model: dict) -> int:
    """The matrices held here that a token may be multiplied by: every
    projection, the router, all the routed experts held, and the head (the
    embedding is looked up, not multiplied)."""
    z, per = weights.sizes(model), _per_kind(model)
    return sum(z[kind] * (per[kind][0] + z["held"] * per[kind][1])
               for kind in weights.KINDS) + z["d"] * model["vocab_size"]


def active_matmul_params(model: dict) -> float:
    """What one token IS multiplied by here: the routed experts at `top_k *
    held / routed` of one expert a token."""
    z, per = weights.sizes(model), _per_kind(model)
    share = z["top_k"] * z["held"] / z["routed"]
    return sum(z[kind] * (per[kind][0] + share * per[kind][1])
               for kind in weights.KINDS) + z["d"] * model["vocab_size"]


def pair_flops(model: dict) -> float:
    """One (query, key) pair in one attention layer of either kind: a score
    over qk and a weighted value over v, every query head, 2 FLOPs a
    multiply-add (40,960 at the published widths)."""
    z = weights.sizes(model)
    return 2.0 * z["h"] * (z["qk"] + z["v"])


def forward_flops(model: dict, n_tokens: int, context_sum: int) -> float:
    """Serving: 2 per matrix parameter a token is multiplied by, and the
    attention products for each (query, cached key) pair; `context_sum` is
    the sum over processed tokens of the positions each attends to, which a
    full layer takes whole and a window layer up to its window: counted as
    min(context_sum, window x n_tokens), exact where every token's context is
    under the window or every one over it."""
    z = weights.sizes(model)
    window_pairs = min(context_sum, z["window_len"] * n_tokens)
    return (2.0 * active_matmul_params(model) * n_tokens
            + pair_flops(model) * (z["full"] * context_sum
                                   + z["window"] * window_pairs))


def decode_attn_flops(model: dict, live: float) -> float:
    """The full layers' decode attention, the work of the Mosaic kernel:
    `pair_flops` a live position a full layer; `live` is the growth of the
    program's counter of live cached positions (the window layers' 128 keys
    a sequence are plain XLA and not counted)."""
    return weights.sizes(model)["full"] * pair_flops(model) * live


def decode_attn_bytes(model: dict, live: float, itemsize: int = 2) -> float:
    """Least HBM traffic of the kernel: each full layer's row of every live
    position read once, for all the query heads (1,280 values a position a
    layer at the published widths)."""
    return kv_bytes_per_token(model, itemsize) * live


def kv_bytes_per_token(model: dict, itemsize: int = 2) -> float:
    """The paged cache's bytes a token: keys and values of the KV heads in
    the full layers alone (5,120 at the cell's size; the window layers keep
    a ring a sequence, `ring_bytes_per_sequence`)."""
    z = weights.sizes(model)
    return float(z["full"] * z["kv_full"] * (z["qk"] + z["v"]) * itemsize)


def ring_bytes_per_sequence(model: dict, itemsize: int = 2) -> float:
    """The window layers' rings a sequence, whatever its length."""
    z = weights.sizes(model)
    return float(z["window"] * z["window_len"] * z["kv_window"] * (
        z["qk"] + z["v"]) * itemsize)


def program_read_bytes(model: dict, kind: str, experts_read: float,
                       positions: float, programs: float = 1.0,
                       itemsize: int = 2) -> float:
    """What `programs` programs of `kind` ("decode" or "prefill") cannot
    avoid reading from HBM: every matrix outside the routed experts once a
    program (the head in decode alone: a prefill program makes no logits,
    and the embedding's rows a token looks up are not counted); the three
    matrices of each expert that owns a row, `experts_read` summed over the
    programs' expert layers; and the full layers' rows of `positions`
    fetched positions. A lower bound: the window layers' rings (0.16 GB at
    most a program), activations and an expert read once a tile are left
    out."""
    z, per = weights.sizes(model), _per_kind(model)
    always = sum(z[k] * (per[k][0] + per[k][2]) for k in weights.KINDS)
    if kind == "decode":
        always += z["d"] * model["vocab_size"]
    elif kind != "prefill":
        raise ValueError(f"program kind {kind!r}: 'decode' or 'prefill'")
    return itemsize * (programs * always
                       + experts_read * per["moe"][1]) + (
        kv_bytes_per_token(model, itemsize) * positions)
