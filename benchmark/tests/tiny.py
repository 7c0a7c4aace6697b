"""Tiny cells for the CPU: the harness's own path with the look for a chip
skipped (`device` is handed in), at sizes a test run can hold. Every family
under `benchmark/families/` brings its tiny configuration (`tiny.json`), and
the tests that take `family` run once for each."""

import functools
import json
import os
import time

from lib import harness

DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
FAMILIES = harness.families_present()
TRAIN = {"kind": "train", "batch": 4, "seq": 32, "dp": 1, "tp": 1,
         "optimizer": "adam", "lr": 3e-4, "b1": 0.9, "attn": "flash",
         "remat": True, "remat_policy": "dots_saveable", "check_steps": 3,
         "reference_rows_per_block": 2,
         "warmup": {"agree_steps": 3, "tolerance": 0.5, "max_seconds": 1},
         "trace_seconds": 1}
SERVE = {"kind": "serve", "loop": "closed", "callers": 4, "pool": 8,
         "pool_seed": 7,
         "prompt_len": {"median": 24, "sigma": 0.5, "min": 8, "max": 48},
         "answer_len": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
         "total_max": 64, "temperature": 0.0, "preroll_s": 0.5,
         "stagger_s": 0.01, "grace_s": 30,
         "engine": {"max_batch": 4, "block_size": 8, "max_seq_len": 64,
                    "prefill_chunk": 8, "decode_impl": "auto",
                    "num_blocks": 33, "max_queue": 64},
         "check_requests": 3, "trace_seconds": 1}
NO_LIMIT = 1e9


def model(family: str) -> dict:
    return harness.load_json("families", family, "tiny.json")


@functools.lru_cache(maxsize=None)
def loaded(family: str, kind: str):
    """One load a family and kind: a reload would drop what its modules
    have jitted."""
    return harness.load_family(family, kind)


TRAIN_NUMBERS = ("loss_step1", "loss_step2", "loss_step3", "grad_norm_gap",
                 "change_norm_gap", "grad_diff_norm")


def train_spec(family, limits=None, **changes):
    traffic = dict(TRAIN, **changes)
    chips = traffic["dp"] * traffic["tp"]
    return {"cell": {"name": f"tiny.{family}.train{chips}", "chips": chips},
            "config": model(family),
            "family": loaded(family, "train"),
            "traffic": traffic,
            "limits": limits or dict.fromkeys(TRAIN_NUMBERS, NO_LIMIT),
            "end_to_end": [], "per_layer": []}


def serve_spec(family, tmp_path, limits=None, **changes):
    traffic = dict(SERVE, **changes)
    path = os.path.join(str(tmp_path), "traffic.json")
    with open(path, "w") as f:
        json.dump(traffic, f)
    return {"cell": {"name": f"tiny.{family}.serve", "chips": 1,
                     "traffic": "tiny"},
            "traffic_file": path, "config": model(family),
            "family": loaded(family, "serve"),
            "traffic": traffic,
            "limits": limits or {"served_logit_gap": NO_LIMIT,
                                 "requests_short": 0},
            "end_to_end": [{"name": "serve_tokens_per_s", "unit": "tokens/s"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": []}


def run_train(spec, seed=7, **kw):
    from lib import train
    device = dict(DEVICE, count=spec["cell"]["chips"])
    return train.run(spec, seed=seed, seconds=0.3, trace=0, device=device,
                     t_start=time.monotonic(), **kw)


def run_serve(spec, seed=7, **kw):
    from lib import serve
    return serve.run(spec, seed=seed, seconds=1.5, trace=0, device=DEVICE,
                     t_start=time.monotonic(), **kw)


def values(line):
    return {k: v[0] for k, v in line["numbers"].items()}
