"""`benchmark/metrics/serve_full_attn_roofline.tput.py`, the full-attention
kernels' share of their roofline in the mixed-32k cell: the least time for
the decode kernel's live positions (memory-bound) and for the prefill
kernel's live query-key pairs (`serve_attn_kernel_pairs_total{path=
"prefill"}`, compute-bound, every full layer) over the Mosaic calls' time.
None without that time, and where no prefill pair went to a kernel: the
parent of the kernel publishes the counter and never grows it. Made-up
`obs` at the cell's published widths; `benchmark/` is on `sys.path` only
while the reader and the family are imported."""
import importlib.util
import json
import os
import sys

import pytest

from conftest import BENCH

NAME = "serve_full_attn_roofline.tput"
PREFILL = 'serve_attn_kernel_pairs_total{path="prefill"}'
LIVE = 'serve_decode_positions_total{kind="live"}'
CELL = "mimo-v2.5.serve-mixed-32k"
# a (query, key) pair a full layer: a score over 192 and a value over 128
# for each of 64 query heads, 2 FLOPs a multiply-add; two full layers
PAIR = 2 * 64 * (192 + 128) * 2
# a live decode position: both full layers' rows, 4 KV heads x (192 + 128)
# bfloat16 values each, read once
ROW = 2 * 4 * (192 + 128) * 2
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9


@pytest.fixture(scope="module")
def reader():
    sys.path.insert(0, BENCH)
    try:
        from lib import harness

        spec = importlib.util.spec_from_file_location(
            "full_attn_roofline_reader",
            os.path.join(BENCH, "metrics", NAME + ".py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        family = harness.load_family("mimo_v2", "serve")
        model = harness.load_json("configs", "mimo-v2.5.json")
    finally:
        sys.path.remove(BENCH)
    return module, family, model


def _obs(reader, before, after, mosaic_s=1.0):
    _, family, model = reader
    return {"counters_traced": (before, after), "family": family,
            "model": model, "device_kind": "TPU v5 lite",
            "trace": {"mosaic_s": mosaic_s, "busy_s": 3.0}}


def test_reader_finds_nothing_without_the_time_or_the_counter(reader):
    read = reader[0].read
    grown = ({PREFILL: 10.0, LIVE: 5.0}, {PREFILL: 1e6, LIVE: 1e4})
    assert read({}) is None                                 # untraced
    assert read(_obs(reader, *grown, mosaic_s=0.0)) is None
    assert read(dict(_obs(reader, *grown), trace={})) is None
    assert read(_obs(reader, {}, {})) is None               # no counters
    # the parent: the counter is published and never grows
    assert read(_obs(reader, {PREFILL: 0.0, LIVE: 5.0},
                     {PREFILL: 0.0, LIVE: 1e4})) is None


def test_reader_is_the_least_time_of_both_kernels_over_the_mosaic_time(
        reader):
    read = reader[0].read
    before = {PREFILL: 2e8, LIVE: 1e6}
    after = {PREFILL: 1.2e9, LIVE: 3e6}
    least = 1e9 * PAIR / PEAK_FLOPS + 2e6 * ROW / PEAK_BYTES
    assert least == pytest.approx(0.41584 + 0.01250, rel=1e-4)
    assert read(_obs(reader, before, after, mosaic_s=1.0)) == pytest.approx(
        100.0 * least)
    assert read(_obs(reader, before, after, mosaic_s=least)) == (
        pytest.approx(100.0))
    # no decode position in the window: the prefill term alone
    assert read(_obs(reader, {PREFILL: 0.0}, {PREFILL: 1e9})) == (
        pytest.approx(100.0 * 1e9 * PAIR / PEAK_FLOPS))


def test_the_benchmark_names_the_reader_for_the_mixed_cell():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        doc = json.load(f)
    entry = next(m for m in doc["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "full-attention kernels",
        "moves": "serve_tokens_per_s", "workloads": [CELL],
    }
