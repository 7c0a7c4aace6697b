"""The comparison that decides `correct`: the program's numbers against the
plain reference's, each with a limit of its own (`limits/<workload>.json`)."""

from __future__ import annotations

import statistics

import numpy as np


def flat_norms(tree) -> dict:
    """{"layers/wq/3": norm, "embed": norm, ...} from a `leaf_norms` tree."""
    out = {}
    for key, val in tree.items():
        if isinstance(val, dict):
            for name, vec in val.items():
                for i, x in enumerate(np.asarray(vec, np.float64).ravel()):
                    out[f"{key}/{name}/{i}"] = float(x)
        else:
            out[key] = float(np.asarray(val))
    return out


def worst_norm_gap(prog: dict, ref: dict, leaves=None) -> tuple:
    """Largest over the leaves of |program's norm - reference's norm| over the
    reference's norm of that leaf or of the median leaf, whichever is larger.
    Returns (gap, leaf)."""
    leaves = list(ref) if leaves is None else list(leaves)
    med = statistics.median(ref[k] for k in leaves)
    worst, where = 0.0, ""
    for k in leaves:
        gap = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if not gap <= worst:  # keeps a NaN
            worst, where = gap, k
    return worst, where


def moving_leaves(ref_grad: dict) -> list:
    """Leaves whose gradient is not nought to rounding in the reference:
    under Adam the others move by round-off alone and are left out of the
    change (rule: under a thousandth of the median leaf's gradient norm)."""
    med = statistics.median(ref_grad.values())
    return [k for k, g in ref_grad.items() if g >= 1e-3 * med]


def train_numbers(prog: dict, ref: dict) -> dict:
    """prog / ref: {"losses": [l1, l2, l3], "grad": flat norms of the first
    gradient, "change": flat norms of the parameters' change after the
    steps}. Returns name -> (value, detail)."""
    out = {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        out[f"loss_step{i}"] = (abs(a - b) / abs(b), f"{a} vs {b}")
    gap, leaf = worst_norm_gap(prog["grad"], ref["grad"])
    out["grad_norm_gap"] = (gap, leaf)
    gap, leaf = worst_norm_gap(prog["change"], ref["change"],
                               moving_leaves(ref["grad"]))
    out["change_norm_gap"] = (gap, leaf)
    if "grad_diff" in prog:
        out["grad_diff_norm"] = worst_diff_norm(prog["grad_diff"], ref["grad"])
    return out


def worst_diff_norm(diff: dict, ref: dict) -> tuple:
    """Largest over the leaves of the norm of (program's first gradient less
    the reference's) over the reference's norm of that leaf or of the median
    leaf, whichever is larger. Rounding noise reaches a norm's gap only in
    the second order (the norm of g + e is |g| (1 + |e|^2 / 2|g|^2) for noise
    across g), and this number in the first: it is the one that tells the
    precision below the configuration's from the configuration's own."""
    med = statistics.median(ref.values())
    worst, where = 0.0, ""
    for k, r in ref.items():
        gap = diff[k] / max(r, med, 1e-30)
        if not gap <= worst:  # keeps a NaN
            worst, where = gap, k
    return worst, where


def served_gap(logits: np.ndarray, tokens) -> np.ndarray:
    """By how much each served token's reference logit lies below the
    reference's best at that position (0 = the reference's own choice)."""
    logits = np.asarray(logits, np.float64)
    rows = np.arange(len(tokens))
    return logits.max(axis=-1) - logits[rows, np.asarray(tokens)]


def with_limits(numbers: dict, limits: dict) -> dict:
    """Only the numbers that have a limit are compared; the others are
    printed with limit null by the caller if it wants them seen."""
    out = {}
    for name, (value, _detail) in numbers.items():
        if name in limits:
            out[name] = {"value": float(value), "limit": limits[name]}
    return out
