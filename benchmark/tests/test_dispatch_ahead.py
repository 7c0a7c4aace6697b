"""The reader of `serve_dispatch_ahead_pct.tput` on a hand-made observation
with a known answer, and on the registry of a commit that lacks its family."""
import json
import os

import pytest

from lib import harness

NAME = "serve_dispatch_ahead_pct.tput"
CELLS = ["cerebras-gpt-1.3b.serve-longdoc",
         "openpangu-ultra-moe-718b.serve-docqa-6k"]
AHEAD = 'serve_dispatch_ahead_total{outcome="ahead"}'
DRAINED = 'serve_dispatch_ahead_total{outcome="drained"}'


def entry():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (found,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    return found


def read(counters_open, counters_close):
    return harness.read_per_layer(
        {"per_layer": [entry()]},
        {"counters_traced": (counters_open, counters_close)})


def test_the_benchmark_lists_it_for_both_serving_cells():
    found = entry()
    assert found["workloads"] == CELLS
    assert (found["unit"], found["better"], found["source"], found["layer"],
            found["moves"]) == ("%", "higher", "program_counter", "engine",
                                "serve_tokens_per_s")
    for cell in CELLS:
        assert NAME in {m["name"] for m in harness.load_spec(cell)["per_layer"]}


def test_growth_over_the_traced_window():
    # 1,000 ticks before the window; in it 176 ahead and 4 drained
    got = read(
        {AHEAD: 990.0, DRAINED: 10.0, "serve_engine_steps_total": 1000.0},
        {AHEAD: 1166.0, DRAINED: 14.0, "serve_engine_steps_total": 1180.0})
    assert got[NAME]["value"] == pytest.approx(100 * 176 / 180, rel=1e-12)
    assert got[NAME]["unit"] == "%"
    # a speculative engine: every tick lands before the next is dispatched
    assert read({AHEAD: 0.0, DRAINED: 10.0}, {AHEAD: 0.0, DRAINED: 90.0})[
        NAME]["value"] == 0.0


def test_nothing_to_read():
    # a commit without the family, a window without a tick, no traced counters
    parent = {"serve_engine_steps_total": 116.0,
              'serve_tokens_total{kind="decode"}': 900.0}
    assert read(parent, parent) == {}
    assert read({AHEAD: 5.0, DRAINED: 1.0}, {AHEAD: 5.0, DRAINED: 1.0}) == {}
    assert harness.read_per_layer({"per_layer": [entry()]}, {}) == {}
