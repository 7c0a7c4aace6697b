"""The second family's counts (its cells in the test report no per-layer
metric, so only the parameters are counted)."""

from __future__ import annotations


def matmul_params(model: dict) -> int:
    d, L, f, v = model["width"], model["depth"], model["mlp"], model["vocab"]
    return L * (4 * d * d + 2 * d * f) + d * v


def param_count(model: dict) -> int:
    d, L, f, v = model["width"], model["depth"], model["mlp"], model["vocab"]
    return 2 * v * d + 2 * d + L * (4 * d * d + 2 * d * f + f + 5 * d)
