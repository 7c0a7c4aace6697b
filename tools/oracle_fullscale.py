#!/usr/bin/env python
"""Reference-scale oracle trajectory parity: the round-4 accuracy-claim
evidence artifact (r3 VERDICT item 5).

Real CIFAR-10 does not exist in this environment (no files, no egress), so
the 63-66% accuracy band (BASELINE.md, Project_Report.pdf section 5) cannot
be reproduced directly. What CAN be proven is stronger than a smoke test:
that the engine computes the reference's exact algorithm at the
reference's exact scale - 25 epochs x 50,000 training rows x 8 workers x
batch 16 (Table 1's row count and epoch count) - by matching the
pure-numpy oracle (tests/oracle_numpy.py) epoch by epoch on parameters and
global train loss. On real data the trajectory, and therefore the accuracy
band, follows from the data alone.

Runs on the 8-virtual-device CPU mesh (JAX_PLATFORMS=cpu; no TPU claim -
this is an algorithm-identity check, not a perf measurement). Wall cost is
~1 h, dominated by the float64 numpy oracle; run detached:

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python tools/oracle_fullscale.py

Writes tools/oracle_fullscale_result.json: per-epoch oracle/engine train
loss, their abs diff, and the max param rel err - the drift curve of f32
XLA vs f64 numpy over the full 25-epoch horizon, which report.py's
accuracy-parity section cites.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

EPOCHS = int(os.environ.get("ORACLE_EPOCHS", "25"))
ROWS = int(os.environ.get("ORACLE_ROWS", "50000"))
WORKERS = int(os.environ.get("ORACLE_WORKERS", "8"))
BATCH = 16
LR, MOMENTUM, SEED = 0.001, 0.9, 0


def _host_tree(t):
    import numpy as np

    return {k: _host_tree(v) if isinstance(v, dict) else np.asarray(v)
            for k, v in t.items()}


def _max_rel_err(a, b):
    import numpy as np

    worst = 0.0
    for k in a:
        if isinstance(a[k], dict):
            worst = max(worst, _max_rel_err(a[k], b[k]))
        else:
            x, y = np.asarray(a[k], np.float64), np.asarray(b[k], np.float64)
            worst = max(worst, float(
                np.abs(x - y).max() / max(np.abs(y).max(), 1e-12)
            ))
    return worst


def main() -> int:
    import jax
    import numpy as np

    assert jax.default_backend() == "cpu", (
        "run with JAX_PLATFORMS=cpu - this artifact must not claim the TPU"
    )
    from distributed_neural_network_tpu.data.cifar10 import load_split
    from distributed_neural_network_tpu.train.engine import Engine, TrainConfig
    from oracle_numpy import reference_trajectory, to_f64
    from test_oracle import _engine_orders

    t_start = time.time()
    split = load_split(True, source="synthetic", synthetic_size=ROWS, seed=3)
    cfg = TrainConfig(
        lr=LR, momentum=MOMENTUM, batch_size=BATCH, epochs=EPOCHS,
        regime="data_parallel", sync_mode="epoch", reset_momentum=True,
        seed=SEED, nb_proc=WORKERS,
    )
    eng = Engine(cfg, split, None)
    params0 = _host_tree(eng.params)
    orders = _engine_orders(SEED, EPOCHS, WORKERS, eng.local_train_rows)

    print(f"[oracle_fullscale] engine: {EPOCHS} epochs x {ROWS} rows x "
          f"{WORKERS} workers (bs {BATCH})", flush=True)
    engine_hist = []
    for e in range(EPOCHS):
        m = eng.run_epoch(e, do_eval=False)
        engine_hist.append(
            {"train_loss": float(m.train_loss), "params": _host_tree(eng.params)}
        )
        print(f"[oracle_fullscale] engine epoch {e}: loss {m.train_loss:.6f} "
              f"({time.time() - t_start:.0f}s)", flush=True)

    print("[oracle_fullscale] oracle (float64 numpy)...", flush=True)
    oracle_hist = reference_trajectory(
        to_f64(params0), split.images, split.labels, n_workers=WORKERS,
        batch_size=BATCH, epochs=EPOCHS, lr=LR, momentum=MOMENTUM,
        orders=orders, regime="data_parallel",
    )

    epochs_out, worst_loss, worst_param = [], 0.0, 0.0
    for e in range(EPOCHS):
        dl = abs(engine_hist[e]["train_loss"] - oracle_hist[e]["train_loss"])
        dp = _max_rel_err(engine_hist[e]["params"], oracle_hist[e]["params"])
        worst_loss, worst_param = max(worst_loss, dl), max(worst_param, dp)
        epochs_out.append({
            "epoch": e,
            "engine_loss": round(engine_hist[e]["train_loss"], 6),
            "oracle_loss": round(oracle_hist[e]["train_loss"], 6),
            "loss_abs_diff": round(dl, 6),
            "param_max_rel_err": round(dp, 6),
        })
        print(f"[oracle_fullscale] epoch {e}: engine "
              f"{engine_hist[e]['train_loss']:.6f} oracle "
              f"{oracle_hist[e]['train_loss']:.6f} dloss {dl:.2e} "
              f"dparam {dp:.2e}", flush=True)

    ok = worst_loss < 1e-2 and worst_param < 0.02
    out = {
        "scale": {"epochs": EPOCHS, "rows": ROWS, "workers": WORKERS,
                  "batch_size": BATCH, "lr": LR, "momentum": MOMENTUM},
        "ok": ok,
        "worst_loss_abs_diff": worst_loss,
        "worst_param_max_rel_err": worst_param,
        "note": (
            "engine = f32 XLA on the 8-device CPU mesh; oracle = f64 numpy "
            "(tests/oracle_numpy.py - the reference algorithm, "
            "/root/reference/data_parallelism_train.py:49-53,187-203,"
            "238-244). Diffs are float-precision drift of the SAME "
            "algorithm over the full horizon, not algorithmic divergence."
        ),
        "wall_s": round(time.time() - t_start, 1),
        "epochs": epochs_out,
    }
    path = os.path.join(REPO, "tools", "oracle_fullscale_result.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"[oracle_fullscale] ok={ok} worst dloss {worst_loss:.2e} worst "
          f"dparam {worst_param:.2e} -> {path} "
          f"({out['wall_s']:.0f}s)", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
