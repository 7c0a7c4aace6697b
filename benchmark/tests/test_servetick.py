"""The nine readers of the serving tick on a hand-made observation with a
known answer, and on the registry of a commit that lacks their counters."""
import json
import os

import pytest

from lib import harness

CEREBRAS = harness.load_json("configs", "cerebras-gpt-1.3b.json")
FAMILY = harness.load_family(CEREBRAS["family"])
CELL = "cerebras-gpt-1.3b.serve-longdoc"

# 16 ticks in the traced window; the registry already held 100
OPEN = {
    "serve_engine_steps_total": 100.0,
    'serve_loop_seconds_total{phase="admit"}': 1.0,
    'serve_loop_seconds_total{phase="books"}': 2.0,
    'serve_loop_seconds_total{phase="prefill_host"}': 3.0,
    'serve_loop_seconds_total{phase="decode_host"}': 4.0,
    'serve_loop_seconds_total{phase="fetch"}': 50.0,
    'serve_loop_seconds_total{phase="emit"}': 5.0,
    'serve_decode_positions_total{kind="live"}': 1_000_000.0,
    'serve_decode_positions_total{kind="padded"}': 2_000_000.0,
    'serve_prefill_positions_total{kind="live"}': 3_000_000.0,
    'serve_prefill_positions_total{kind="padded"}': 9_000_000.0,
}
GROWTH = {
    "serve_engine_steps_total": 16.0,
    'serve_loop_seconds_total{phase="admit"}': 0.016,
    'serve_loop_seconds_total{phase="books"}': 0.032,
    'serve_loop_seconds_total{phase="prefill_host"}': 0.064,
    'serve_loop_seconds_total{phase="decode_host"}': 0.128,
    'serve_loop_seconds_total{phase="fetch"}': 3.2,
    'serve_loop_seconds_total{phase="emit"}': 0.08,
    # 10 sequences a tick at about 1,000 positions, in buckets of 16 x 2,048
    'serve_decode_positions_total{kind="live"}': 160_000.0,
    'serve_decode_positions_total{kind="padded"}': 16 * 16 * 2048.0,
    # a chunk of 128 at position 512 a tick, in buckets of 128 x 1,024
    'serve_prefill_positions_total{kind="live"}': 16 * (128 * 512 + 8256.0),
    'serve_prefill_positions_total{kind="padded"}': 16 * 128 * 1024.0,
}
WANT = {
    "serve_loop_admit_ms.tput": 1.0,
    "serve_loop_books_ms.tput": 2.0,
    "serve_loop_prefill_host_ms.tput": 4.0,
    "serve_loop_decode_host_ms.tput": 8.0,
    "serve_loop_fetch_ms.tput": 200.0,
    "serve_loop_emit_ms.tput": 5.0,
    "serve_decode_pad_pct.tput": 100 * (1 - 160_000 / 524_288),
    "serve_prefill_pad_pct.tput": 100 * (1 - 73_792 / 131_072),
    # 160,000 positions x 2 (K, V) x 24 layers x 2,048 x 2 bytes = 31.46 GB
    # at 819 GB/s = 38.41 ms (compute: 4 x 24 x 2,048 x 160,000 FLOPs at
    # 197 TFLOP/s = 0.16 ms), over 96 ms in the Mosaic calls
    "serve_decode_attn_roofline.tput": 100 * (
        160_000 * 196_608 / 819e9) / 0.096,
}


def observation(counters_close):
    return {"counters_traced": (OPEN, counters_close), "model": CEREBRAS,
            "family": FAMILY, "device_kind": "TPU v5 lite",
            "trace": {"window_s": 4.0, "busy_s": 3.1, "mosaic_s": 0.096}}


def entries():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return [m for m in bench["per_layer"] if m["name"] in WANT]


def test_the_benchmark_lists_the_nine_for_the_longdoc_cell_alone():
    found = entries()
    assert sorted(m["name"] for m in found) == sorted(WANT)
    for m in found:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"
    assert {m["name"] for m in harness.load_spec(CELL)["per_layer"]} >= set(
        WANT)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_a_known_observation(name):
    close = {k: OPEN[k] + v for k, v in GROWTH.items()}
    spec = {"per_layer": [m for m in entries() if m["name"] == name]}
    got = harness.read_per_layer(spec, observation(close))
    assert got[name]["value"] == pytest.approx(WANT[name], rel=1e-9)
    assert got[name]["unit"] == spec["per_layer"][0]["unit"]


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_finds_nothing_on_a_commit_without_the_family(name):
    spec = {"per_layer": [m for m in entries() if m["name"] == name]}
    parent = {"serve_engine_steps_total": 116.0,
              'serve_tokens_total{kind="decode"}': 900.0}
    assert harness.read_per_layer(spec, observation(parent)) == {}
    # and on an observation with no traced counters at all
    assert harness.read_per_layer(spec, {"model": CEREBRAS}) == {}


def test_the_phases_sum_to_the_tick_and_the_roofline_is_memory_bound():
    from lib import servetick

    close = {k: OPEN[k] + v for k, v in GROWTH.items()}
    obs = observation(close)
    total = sum(servetick.phase_ms_a_tick(obs, p) for p in (
        "admit", "books", "prefill_host", "decode_host", "fetch", "emit"))
    assert total == pytest.approx(220.0)
    least, bound = servetick.decode_attn_least_seconds(obs)
    assert bound == "memory"
    assert least == pytest.approx(0.03841, rel=1e-3)
    assert FAMILY.arith.decode_attn_bytes(CEREBRAS, 1) == 196_608
    assert FAMILY.arith.decode_attn_flops(CEREBRAS, 1) == 196_608
