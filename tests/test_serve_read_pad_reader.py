"""`benchmark/metrics/serve_decode_read_pad_pct.tput.py`, the reader of
`serve_decode_positions_total{kind="read"}` (serve/scheduler.py
`_publish_tick`): None where the program publishes no such counter, as a
commit before the paged decode kernel does, and 100 x (1 - live / read) over
the traced window where it does. `benchmark/` is on `sys.path` only while
the reader is imported."""
import importlib.util
import os
import sys

import pytest

from conftest import BENCH

NAME = "serve_decode_read_pad_pct.tput"
KEY = 'serve_decode_positions_total{kind="%s"}'


@pytest.fixture(scope="module")
def reader():
    sys.path.insert(0, BENCH)
    try:
        spec = importlib.util.spec_from_file_location(
            "read_pad_reader", os.path.join(BENCH, "metrics", NAME + ".py"))
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
    _obs({KEY % "live": 10.0, KEY % "padded": 64.0},
         {KEY % "live": 110.0, KEY % "padded": 704.0}),   # the parent's
    _obs({KEY % "live": 5.0, KEY % "read": 8.0},
         {KEY % "live": 5.0, KEY % "read": 8.0}),  # no decode in the window
])
def test_reader_finds_nothing_without_the_counter(reader, obs):
    assert reader.read(obs) is None


def test_reader_is_the_share_of_fetched_positions_left_unattended(reader):
    obs = _obs(
        {KEY % "live": 100.0, KEY % "read": 128.0, KEY % "padded": 512.0},
        {KEY % "live": 1000.0, KEY % "read": 1128.0, KEY % "padded": 4608.0},
    )
    assert reader.read(obs) == pytest.approx(100.0 * (1 - 900.0 / 1000.0))
    # the xla route reads the whole bucket: the reader is then the bucket's
    # padding, `serve_decode_pad_pct.tput`
    obs = _obs({}, {KEY % "live": 300.0, KEY % "read": 1200.0,
                    KEY % "padded": 1200.0})
    assert reader.read(obs) == pytest.approx(75.0)


def test_the_benchmark_names_the_reader_for_the_longdoc_cell():
    import json

    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == NAME)
    # (cells added since are appended to the list: PR 31's latent decode
    # kernel publishes the same counter)
    cells = entry.pop("workloads")
    assert cells[0] == "cerebras-gpt-1.3b.serve-longdoc"
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "decode kernel",
        "moves": "serve_tokens_per_s",
    }
