#!/usr/bin/env python3
"""A training cell's control and faults on the chip, at the cell's own size,
each judged as a run of the program is: the control (the plain reference
computed in float8, the precision below the configuration's bfloat16, put in
the program's place) and the faults that can be planted in it (half of the
batch left out, the mean taken over the rest; the state left unchanged; and
on a tensor-parallel cell the exchange between the chips left out). Each one's
numbers go through the cell's own limits (`compare.with_limits`,
`harness.judge`) and have to come out not correct: the exit code is 1 where
one comes out correct. (A serving cell's control is read in a run of the cell:
`run.py --control fp8`.)

  python3 benchmark/control.py --workload gpt2-medium.pretrain-1k --seeds 1,2,3
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--precisions", default="fp8")
    ap.add_argument("--faults", default="half_batch,state_unchanged,"
                    "no_tp_exchange")
    args = ap.parse_args()

    from lib import compare, harness, train, weights

    spec = harness.load_spec(args.workload)
    device = harness.need_tpu(spec["cell"]["chips"])
    import jax
    import jax.numpy as jnp

    from distributed_neural_network_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    model, tr, family = spec["config"], spec["traffic"], spec["family"]
    out_dir = os.path.join(harness.ROOT, "chiprun_out", "control")
    os.makedirs(out_dir, exist_ok=True)
    passed_wrongly = []
    for seed in (int(s) for s in args.seeds.split(",")):
        batch_fn = weights.make_batch_fn(seed, batch=tr["batch"], seq=tr["seq"],
                                         vocab=family.weights.vocab(model))

        def half(i):
            tok, tgt = batch_fn(i)
            h = tok.shape[0] // 2
            return (jnp.concatenate([tok[:h], tok[:h]]),
                    jnp.concatenate([tgt[:h], tgt[:h]]))

        devs = jax.devices()[:device["count"]]

        ref = train.reference_steps(seed, family, model, tr, batch_fn,
                                    devices=devs, keep_first_grad=True)
        ref_grad = ref.pop("first_grad")

        def steps(fn=batch_fn, prec="f32", fault=""):
            return train.reference_steps(seed, family, model, tr, fn, prec,
                                         devices=devs, fault=fault,
                                         against=ref_grad)

        rows = {}
        for prec in args.precisions.split(","):
            rows["control_" + prec] = compare.train_numbers(
                steps(prec=prec), ref)
        for fault in args.faults.split(","):
            if fault == "no_tp_exchange" and tr["tp"] == 1:
                continue
            run = steps(half) if fault == "half_batch" else steps(fault=fault)
            rows["fault_" + fault] = compare.train_numbers(run, ref)
        doc = {"workload": args.workload, "seed": seed}
        for name, nums in rows.items():
            compared = compare.with_limits(nums, spec["limits"])
            doc[name] = {"correct": harness.judge(compared),
                         "failed": sorted(k for k, c in compared.items()
                                          if not c["value"] <= c["limit"]),
                         **{n: v[0] for n, v in nums.items()}}
            if doc[name]["correct"]:
                passed_wrongly.append((seed, name))
        print(json.dumps(doc), flush=True)
        with open(os.path.join(out_dir, args.workload + ".jsonl"), "a") as f:
            f.write(json.dumps(doc) + "\n")
    for seed, name in passed_wrongly:
        print(f"seed {seed}: {name} came out correct", file=sys.stderr)
    return 1 if passed_wrongly else 0


if __name__ == "__main__":
    sys.exit(main())
