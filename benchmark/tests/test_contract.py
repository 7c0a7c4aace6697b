"""What `BENCHMARK.json` names is there: every cell loads, every
configuration's family has its four parts and the entries its cells call,
every per-layer metric has a reader, every name under a `workloads` list is a
cell, and each family's parameter count is the sum of its tree's shapes at
the published widths. No tensor is made."""
import json
import math
import os

import jax
import pytest

from conftest import BENCH, ROOT
from lib import harness
from lib.weights import is_shape

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    DOC = json.load(f)
CELLS = [w["name"] for w in DOC["workloads"]]
CONFIGS = {c["name"]: c for c in DOC["configs"]}
# 405.1 M and 1.41 B, as PERF.md section 4 gives them
COUNT = {"gpt2-medium": 405_139_456, "cerebras-gpt-1.3b": 1_414_258_688}


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_loads_with_its_family(cell):
    spec = harness.load_spec(cell)
    assert spec["cell"]["name"] == cell
    assert spec["family"].name == spec["config"]["family"]
    assert spec["traffic"]["kind"] in harness.FAMILY_ENTRIES
    assert spec["limits"] and any(
        m["name"] == "setup_s" for m in spec["end_to_end"])
    assert len(spec["end_to_end"]) >= 2 and spec["per_layer"]


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_every_configurations_family_has_its_four_parts(config):
    with open(os.path.join(ROOT, CONFIGS[config]["file"])) as f:
        model = json.load(f)
    assert model["family"] in harness.families_present()
    kinds = {harness.load_json("traffic", w["traffic"] + ".json")["kind"]
             for w in DOC["workloads"] if w["config"] == config}
    for kind in kinds:  # load_family ends the run where an entry is missing
        family = harness.load_family(model["family"], kind)
        for part in harness.FAMILY_PARTS:
            assert getattr(family, part).__file__ == os.path.join(
                BENCH, "families", model["family"], part + ".py")
    assert os.path.isfile(os.path.join(BENCH, "families", model["family"],
                                       "tiny.json"))


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_the_parameter_count_is_the_sum_of_the_trees_shapes(config):
    with open(os.path.join(ROOT, CONFIGS[config]["file"])) as f:
        model = json.load(f)
    family = harness.load_family(model["family"])
    shapes = jax.tree.leaves(family.weights.shapes(model), is_leaf=is_shape)
    assert family.arith.param_count(model) == sum(map(math.prod, shapes))
    assert family.arith.param_count(model) == COUNT[config]
    # what a token is multiplied by: every leaf of two or more axes but the
    # embedding, which is a lookup
    named = jax.tree.leaves_with_path(family.weights.shapes(model),
                                      is_leaf=is_shape)
    stacked = lambda p: getattr(p[0], "key", None) == "layers"
    assert family.arith.matmul_params(model) == sum(
        math.prod(s) for p, s in named
        if len(s) - stacked(p) == 2 and p[-1].key != "embed")


def test_every_per_layer_metric_has_a_reader_and_moves_a_metric():
    rates = {m["name"] for m in DOC["end_to_end"]}
    for m in DOC["per_layer"]:
        path = os.path.join(BENCH, "metrics", m["name"] + ".py")
        assert os.path.isfile(path), m["name"]
        with open(path) as f:
            assert "def read(obs)" in f.read(), m["name"]
        assert m["moves"] in rates, m["name"]


def test_every_name_under_a_workloads_list_is_a_cell():
    for m in DOC["end_to_end"] + DOC["per_layer"]:
        assert set(m.get("workloads", CELLS)) <= set(CELLS), m["name"]
    for w in DOC["workloads"]:
        assert w["config"] in CONFIGS
        for part in ("traffic", "limits"):
            name = w["traffic"] if part == "traffic" else w["name"]
            assert os.path.isfile(os.path.join(BENCH, part, name + ".json"))


def test_no_file_outside_a_family_names_the_model_or_the_program():
    """`lib/`, `metrics/` and the entry points hold no key of a
    `config.json` and do not import the program's model; no family's
    reference imports the program."""
    keys = ("n_embd", "n_layer", "n_inner", "n_head", "models.transformer",
            "models import transformer")
    for d in ("lib", "metrics", "."):
        for name in sorted(os.listdir(os.path.join(BENCH, d))):
            if name.endswith(".py"):
                with open(os.path.join(BENCH, d, name)) as f:
                    src = f.read()
                assert not [k for k in keys if k in src], (d, name)
    for family in harness.families_present():
        with open(os.path.join(BENCH, "families", family,
                               "reference.py")) as f:
            assert "distributed_neural_network_tpu" not in f.read(), family
