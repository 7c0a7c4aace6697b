"""`benchmark/metrics/serve_dispatch_ahead_pct.tput.py`, the reader of
`serve_dispatch_ahead_total{outcome}` (serve/scheduler.py `_publish_tick`):
None where the program publishes no such counter, as a commit before the tick
in flight does, and 100 x ahead / (ahead + drained) over the traced window
where it does. `benchmark/` is on `sys.path` only while the reader is
imported."""
import importlib.util
import json
import os
import sys

import pytest

from conftest import BENCH

NAME = "serve_dispatch_ahead_pct.tput"
KEY = 'serve_dispatch_ahead_total{outcome="%s"}'


@pytest.fixture(scope="module")
def reader():
    sys.path.insert(0, BENCH)
    try:
        spec = importlib.util.spec_from_file_location(
            "dispatch_ahead_reader",
            os.path.join(BENCH, "metrics", NAME + ".py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    finally:
        sys.path.remove(BENCH)
    return module


def _obs(before, after):
    return {"counters_traced": (before, after)}


@pytest.mark.parametrize("obs", [
    {},                                           # an untraced run
    _obs({}, {}),                                 # no serving counters
    _obs({"serve_engine_steps_total": 100.0},
         {"serve_engine_steps_total": 280.0}),    # the parent's
    _obs({KEY % "ahead": 5.0, KEY % "drained": 1.0},
         {KEY % "ahead": 5.0, KEY % "drained": 1.0}),   # no tick in the window
])
def test_reader_finds_nothing_without_the_counter(reader, obs):
    assert reader.read(obs) is None


@pytest.mark.parametrize("ahead,drained,want", [
    (176.0, 4.0, 100.0 * 176 / 180),
    (180.0, 0.0, 100.0),
    (0.0, 80.0, 0.0),       # a speculative engine: every tick lands first
])
def test_reader_is_the_share_of_ticks_dispatched_ahead(reader, ahead,
                                                       drained, want):
    obs = _obs({KEY % "ahead": 990.0, KEY % "drained": 10.0},
               {KEY % "ahead": 990.0 + ahead, KEY % "drained": 10.0 + drained})
    assert reader.read(obs) == pytest.approx(want, rel=1e-12)


def test_the_benchmark_names_the_reader_for_every_serving_cell():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "engine",
        "moves": "serve_tokens_per_s",
        "workloads": ["cerebras-gpt-1.3b.serve-longdoc",
                      "openpangu-ultra-moe-718b.serve-docqa-6k",
                      "lfm2-24b-a2b.serve-reason-1k",
                      "mimo-v2.5.serve-mixed-32k"],
    }
