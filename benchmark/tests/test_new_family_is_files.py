"""Files alone add an architecture. In a copy of `benchmark/` a second family
(`tests/second_family/`: GPT-2's equations under other names, weights drawn a
layer at a time), a configuration that names it, traffic and limits files and
two more cells in `BENCHMARK.json` are ADDED, no file that was there is
changed, and the tiny training and serving cells run through `load_spec` and
the cells' own `run`. Then one equation of that family's reference is changed
(RMS in place of LayerNorm) while its program stays: its cells come out not
correct, because each configuration is held to its own copy of the reference."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import tiny
from conftest import BENCH, ROOT
from lib import compare, harness, serve, train

FAMILY = "layerwise"
CONFIG = "tiny-layerwise"
CELLS = {"train": CONFIG + ".tiny-train", "serve": CONFIG + ".tiny-serve"}
RATE = {"train": "train_tokens_per_s", "serve": "serve_tokens_per_s"}
TEMPLATE = os.path.join(BENCH, "tests", "second_family")
SERVE_NUMBERS = {"served_logit_gap": tiny.NO_LIMIT, "requests_short": 0}


def digest(root) -> dict:
    out = {}
    for d, _, files in os.walk(root):
        for name in files:
            path = os.path.join(d, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def write(path, doc) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)


def add_the_family(tree, limits=None, reference_edit=None) -> None:
    """What a `model_config` PR brings, into the checkout at `tree`."""
    bench = os.path.join(tree, "benchmark")
    fam = os.path.join(bench, "families", FAMILY)
    os.makedirs(fam, exist_ok=True)
    for name in os.listdir(TEMPLATE):
        if name.endswith((".py", ".json")):
            shutil.copy(os.path.join(TEMPLATE, name), fam)
    if reference_edit:
        path = os.path.join(fam, "reference.py")
        with open(path) as f:
            src = f.read()
        assert src.count(reference_edit[0]) == 1
        with open(path, "w") as f:
            f.write(src.replace(*reference_edit))
    with open(os.path.join(fam, "tiny.json")) as f:
        write(os.path.join(bench, "configs", CONFIG + ".json"), json.load(f))
    write(os.path.join(bench, "traffic", "tiny-train.json"), tiny.TRAIN)
    write(os.path.join(bench, "traffic", "tiny-serve.json"), tiny.SERVE)
    limits = limits or {"train": dict.fromkeys(tiny.TRAIN_NUMBERS,
                                               tiny.NO_LIMIT),
                        "serve": SERVE_NUMBERS}
    for kind, cell in CELLS.items():
        write(os.path.join(bench, "limits", cell + ".json"), limits[kind])
    with open(os.path.join(tree, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["configs"].append({
        "name": CONFIG, "source": "benchmark/tests/second_family",
        "file": f"benchmark/configs/{CONFIG}.json", "reduced": [],
        "why": "a second family, for the test"})
    for kind, cell in CELLS.items():
        doc["workloads"].append({
            "name": cell, "config": CONFIG, "traffic": "tiny-" + kind,
            "chips": 1, "why": "a tiny cell of the second family"})
        for m in doc["end_to_end"]:
            if m["name"] == RATE[kind]:
                m["workloads"].append(cell)
    write(os.path.join(tree, "BENCHMARK.json"), doc)


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    """A copy of what git would commit of the benchmark, with the harness's
    look for its files pointed at it."""
    tree = str(tmp_path / "checkout")
    shutil.copytree(BENCH, os.path.join(tree, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tree)
    monkeypatch.setattr(harness, "ROOT", tree)
    monkeypatch.setattr(harness, "BENCH_DIR", os.path.join(tree, "benchmark"))
    monkeypatch.setattr(harness, "OUT_DIR", os.path.join(tree, "out"))
    return tree


def run_cell(kind: str) -> dict:
    spec = harness.load_spec(CELLS[kind])
    assert spec["family"].name == FAMILY
    cell, seconds = (train, 0.3) if kind == "train" else (serve, 1.5)
    return cell.run(spec, seed=7, seconds=seconds, trace=0,
                    device=tiny.DEVICE, t_start=time.monotonic())


def test_a_second_family_runs_from_added_files_and_is_held_to_its_own(
        checkout):
    before = digest(checkout)
    add_the_family(checkout)
    after = digest(checkout)
    assert {k: after[k] for k in before if k != "BENCHMARK.json"} == {
        k: v for k, v in before.items() if k != "BENCHMARK.json"}
    assert FAMILY in harness.families_present()

    sound = {kind: run_cell(kind) for kind in CELLS}
    for kind, line in sound.items():
        assert line["correct"], (kind, line["compared"])
        assert RATE[kind] in line["metrics"] or kind == "train"
    # the cells' limits as a cell's are set: three times the sound reading
    values = {k: tiny.values(v) for k, v in sound.items()}
    limits = {"train": {k: max(3.0 * values["train"][k], 1e-6)
                        for k in tiny.TRAIN_NUMBERS},
              "serve": {"served_logit_gap": max(
                  3.0 * values["serve"]["served_logit_gap"], 1e-3),
                  "requests_short": 0}}
    for kind in CELLS:
        held = compare.with_limits(
            {k: (v, "") for k, v in values[kind].items()}, limits[kind])
        assert harness.judge(held), (kind, held)

    # one equation of ITS reference changed; the program and gpt2's reference
    # are as they were
    add_the_family(checkout, limits, reference_edit=(
        "mu = jnp.mean(x, axis=-1, keepdims=True)",
        "mu = 0.0  # RMS in place of LayerNorm"))
    for kind in CELLS:
        line = run_cell(kind)
        assert line["correct"] is False, (kind, line["compared"])
    # and the families that were there are untouched by all of it
    gpt2 = tiny.run_train(tiny.train_spec("gpt2"))
    assert gpt2["correct"]


def run_py(tree, cell):
    return subprocess.run(
        [sys.executable, os.path.join(tree, "benchmark", "run.py"),
         "--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=tree,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT))


def test_a_family_missing_a_part_ends_the_run_with_a_message(checkout):
    add_the_family(checkout)
    os.remove(os.path.join(checkout, "benchmark", "families", FAMILY,
                           "arith.py"))
    p = run_py(checkout, CELLS["train"])
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "lacks ['arith.py']" in p.stderr


def test_a_family_missing_an_entry_ends_the_run_with_a_message(checkout):
    add_the_family(checkout, reference_edit=("def served_logits(",
                                             "def served(" ))
    p = run_py(checkout, CELLS["serve"])
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "reference.py lacks ['served_logits']" in p.stderr


def test_a_configuration_without_a_family_ends_the_run_with_a_message(
        checkout):
    add_the_family(checkout)
    path = os.path.join(checkout, "benchmark", "configs", CONFIG + ".json")
    with open(path) as f:
        doc = json.load(f)
    del doc["family"]
    write(path, doc)
    p = run_py(checkout, CELLS["serve"])
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert 'names no "family"' in p.stderr and "gpt2" in p.stderr
