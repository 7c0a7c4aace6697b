#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

  python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process: it holds the cell's chips, makes weights and inputs from the
seed, warms up (set-up), measures for `--seconds`, compares what the timed
path produced with the plain reference, and prints the result as the last
line of standard output. The cell's kind ("train" or "serve") is named in its
traffic file, and its model's family (`families/<family>/`: weights, plain
reference, program configuration, arithmetic) in its configuration's file;
everything else about a cell is data under `benchmark/`.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)                    # lib
sys.path.insert(0, os.path.dirname(HERE))   # the system under test


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default="f32", choices=("f32", "bf16", "fp8"),
                    help="serving cells, for setting limits: also read the "
                    "control's gap, the reference at this lower precision in "
                    "the program's place (training cells: control.py)")
    args = ap.parse_args(argv)

    from lib import harness

    spec = harness.load_spec(args.workload)
    device = harness.need_tpu(spec["cell"]["chips"])
    kind = spec["traffic"]["kind"]
    if kind == "train":
        from lib import train as cell
    elif kind == "serve":
        from lib import serve as cell
    else:
        raise SystemExit(f"traffic kind {kind!r}: 'train' or 'serve'")
    extra = {"precision": args.control} if kind == "serve" else {}
    cell.run(spec, seed=args.seed, seconds=args.seconds, trace=args.trace,
             device=device, t_start=T_START, **extra)
    return 0


if __name__ == "__main__":
    sys.exit(main())
