"""Model FLOPs of forward and backward as the cell's family counts them
(`arith.train_flops_per_token`, recomputation not counted) times the traced
window's tokens per second, over chips times the bf16 peak."""
from lib import arith


def read(obs):
    if not obs.get("traced_rate"):
        return None
    need = obs["family"].arith.train_flops_per_token(
        obs["model"], obs["traffic"]["seq"])
    peak = arith.peaks(obs["device_kind"])["flops_bf16"] * obs["chips"]
    return 100.0 * need * obs["traced_rate"] / peak
