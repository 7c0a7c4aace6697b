"""The least time the chip could take for the attention the traced steps
need on one chip (the larger of FLOPs over peak and bytes over bandwidth, from
shapes, as the cell's family counts them: `arith.flash_train_flops`, `_bytes`;
at these shapes compute bounds it) over the Mosaic kernels' summed time."""
from lib import arith


def read(obs):
    t = obs.get("trace") or {}
    if not t.get("mosaic_s") or not obs.get("traced_steps"):
        return None
    tr = obs["traffic"]
    rows, work = tr["batch"] // tr["dp"], obs["family"].arith
    least, _ = arith.roofline_seconds(
        work.flash_train_flops(obs["model"], rows, tr["seq"], tr["tp"]),
        work.flash_train_bytes(obs["model"], rows, tr["seq"], tr["tp"]),
        obs["device_kind"])
    return 100.0 * least * obs["traced_steps"] / t["mosaic_s"]
