#!/usr/bin/env python3
"""Run one cell several times in one call and keep every run's last line:
what the bounds, the limits and the hunt for a stray run are read from.

  python3 benchmark/hunt.py --workload W --seeds 1,2,3 --seconds 20 \
      [--trace 0] [--repeat 2] [--tag name] [-- extra run.py args]

Each run is a new process (the chip belongs to one at a time). Lines go to
`chiprun_out/hunt/<tag>.jsonl`, the per-step files stay under
`benchmark_out/` and are copied beside them.
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--repeat", type=int, default=1)
    ap.add_argument("--tag", default="hunt")
    ap.add_argument("--control", default="f32")
    args = ap.parse_args()
    out_dir = os.path.join(ROOT, "chiprun_out", "hunt")
    os.makedirs(out_dir, exist_ok=True)
    out = os.path.join(out_dir, args.tag + ".jsonl")
    rc = 0
    for rep in range(args.repeat):
        for seed in args.seeds.split(","):
            cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
                   "--workload", args.workload, "--seed", seed,
                   "--seconds", str(args.seconds), "--trace", str(args.trace),
                   "--control", args.control]
            t0 = time.monotonic()
            p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
            wall = time.monotonic() - t0
            lines = p.stdout.strip().splitlines()
            try:
                doc = json.loads(lines[-1])
            except (IndexError, ValueError):
                doc = {"stdout_tail": p.stdout[-2000:]}
            doc.update(seed=int(seed), rep=rep, rc=p.returncode,
                       wall_s=round(wall, 2), trace=args.trace,
                       seconds=args.seconds, workload=args.workload)
            if p.returncode != 0 or not doc.get("correct"):
                rc = 1
                doc["stderr_tail"] = p.stderr[-3000:]
            with open(out, "a") as f:
                f.write(json.dumps(doc) + "\n")
            brief = {k: doc.get(k) for k in ("seed", "rep", "rc", "correct",
                                             "wall_s", "steps", "window_s")}
            brief["metrics"] = {k: v["value"] for k, v in
                                doc.get("metrics", {}).items()}
            brief["numbers"] = {k: v[0] for k, v in
                                doc.get("numbers", {}).items()}
            print(json.dumps(brief), flush=True)
    src = os.path.join(ROOT, "benchmark_out", args.workload)
    if os.path.isdir(src):
        shutil.copytree(src, os.path.join(out_dir, args.tag + "_files"),
                        dirs_exist_ok=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
