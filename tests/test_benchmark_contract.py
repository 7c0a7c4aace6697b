"""The benchmark's own contract in the tier-1 suite (owed since ISSUE 26):
`benchmark/tests/test_contract.py` (what `BENCHMARK.json` names is there) and
`test_moved_family.py` (the gpt2 family is the parent's code, to the bit),
imported, not copied. `benchmark/` and `benchmark/tests` are on `sys.path`
only while this module imports them. One case of `test_contract.py` is left
behind, `test_the_parameter_count_is_the_sum_of_the_trees_shapes`: it looks
every configuration's count up in a table of its own that names the two GPT-2
configurations, so it cannot pass for a configuration added since
(`PERF.md` section 7)."""
import json
import math
import os
import sys

import jax
import pytest

from conftest import BENCH, ROOT

sys.path[:0] = [os.path.join(BENCH, "tests"), BENCH]
try:
    from lib import harness
    from lib.weights import is_shape
    from test_contract import (  # noqa: F401
        CONFIGS,
        test_every_cell_loads_with_its_family,
        test_every_configurations_family_has_its_four_parts,
        test_every_name_under_a_workloads_list_is_a_cell,
        test_every_per_layer_metric_has_a_reader_and_moves_a_metric,
        test_no_file_outside_a_family_names_the_model_or_the_program,
    )
    from test_moved_family import (  # noqa: F401
        gpt2,
        test_the_references_first_steps_are_the_parents,
        test_the_seeded_tree_and_batches_are_the_parents,
        test_the_served_logits_are_the_parents,
    )
finally:
    del sys.path[:2]


def test_the_whole_model_by_the_same_count_is_the_published_size():
    """The share's arithmetic, given the published depth, experts and
    vocabulary, counts the published model: 31.6 B parameters."""
    with open(os.path.join(
            ROOT, CONFIGS["nemotron-3-nano-30b-a3b"]["file"])) as f:
        model = json.load(f)
    pub = model["published"]
    whole = dict(model, published={},
                 hybrid_override_pattern=pub["hybrid_override_pattern"],
                 n_routed_experts=pub["n_routed_experts"],
                 vocab_size=pub["vocab_size"])
    family = harness.load_family("nemotron_h")
    # one chip's share (9 layers, 8 of 128 experts, 16384 rows): 667.0 M
    shapes = jax.tree.leaves(family.weights.shapes(model), is_leaf=is_shape)
    assert (family.arith.param_count(model) == sum(map(math.prod, shapes))
            == 666_963_456)
    assert len(pub["hybrid_override_pattern"]) == pub["num_hidden_layers"]
    assert pub["hybrid_override_pattern"].startswith(
        model["hybrid_override_pattern"])
    assert 31.5e9 < family.arith.param_count(whole) < 31.7e9
    # the step's model FLOPs a token, as the issue reckons them
    assert family.arith.train_flops_per_token(model, 8192) == pytest.approx(
        2.145e9, rel=2e-3)
