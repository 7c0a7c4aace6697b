"""The least time the chip could take for the attention the traced steps
need (the larger of FLOPs over peak and bytes over bandwidth, from shapes;
at these shapes compute bounds it) over the Mosaic kernels' summed time."""
from lib import arith


def read(obs):
    t = obs.get("trace") or {}
    if not t.get("mosaic_s") or not obs.get("traced_steps"):
        return None
    tr = obs["traffic"]
    rows = tr["batch"] // tr["dp"]
    model = dict(obs["model"], n_embd=obs["model"]["n_embd"] // tr["tp"])
    least, _ = arith.roofline_seconds(
        arith.flash_train_flops(model, rows, tr["seq"]),
        arith.flash_train_bytes(model, rows, tr["seq"]), obs["device_kind"])
    return 100.0 * least * obs["traced_steps"] / t["mosaic_s"]
