"""The trace reduction on small traces with known answers."""
import json
import os

import pytest

from conftest import BENCH
from lib import xtrace as x


def test_self_times_busy_and_gaps_by_hand():
    ev = [("while", 0, 100), ("fusion.1", 10, 20), ("k__mosaic_", 40, 30),
          ("all-reduce.3", 120, 10), ("fusion.2", 130, 5)]
    trace = x.Trace({0: ev}, [("bench.wait_step", 95, 30)],
                    {0: [("all-gather-start.1", 50, 30)]})
    assert dict(x.self_times(ev))["while"] == 50
    s = x.summarize(trace, 0, 140)
    assert s["busy_s"] == pytest.approx(115e-9)        # 0-100 and 120-135
    assert s["mosaic_s"] == pytest.approx(30e-9)
    assert s["collective_s"] == pytest.approx(40e-9)   # 10 serial, 30 beside
    assert s["collective_exposed_s"] == pytest.approx(20e-9)  # 120-130, 70-80
    assert dict(map(tuple, s["idle_gaps"])) == {
        "bench.wait_step": pytest.approx(20e-9),       # 100-120
        "unattributed": pytest.approx(5e-9)}           # 135-140
    assert s["device_ops"][0] == ["while", pytest.approx(50e-9)]


def test_a_gap_is_split_among_the_innermost_spans_by_overlap():
    # one idle gap, 100-200, under a window that covers everything, a tick
    # 90-210 and two of its phases: 100-130 goes to the one, 130-180 to the
    # other, 180-200 to the tick itself; 300-340 lies outside every tick
    ev = [("fusion.1", 0, 100), ("fusion.2", 200, 100)]
    host = [("bench.traced_window", 0, 340), ("serve.tick", 90, 120),
            ("serve.prefill_host", 95, 35), ("serve.decode_host", 130, 50)]
    assert x.idle_gaps(ev, host, 0, 340) == {
        "serve.prefill_host": 30, "serve.decode_host": 50, "serve.tick": 20,
        "bench.traced_window": 40}
    # the window's edge cuts a nest: the part inside still goes to the inner
    assert x.idle_gaps(ev, host, 120, 340) == {
        "serve.prefill_host": 10, "serve.decode_host": 50, "serve.tick": 20,
        "bench.traced_window": 40}
    s = x.summarize(x.Trace({0: ev}, host, {}), 0, 340)
    assert s["busy_s"] == pytest.approx(200e-9)        # the seconds stay
    assert s["idle_gaps"][0] == ["serve.decode_host", pytest.approx(50e-9)]
    assert x.HOST_PREFIX == ("bench.", "serve.")


def test_clip_cuts_events_at_the_window():
    assert x.clip([("a", 0, 10), ("b", 20, 10)], 5, 25) == [
        ("a", 5, 5), ("b", 20, 5)]


def test_recorded_trace_gives_its_recorded_numbers():
    path = os.path.join(BENCH, "tests", "recorded_trace.json")
    with open(path) as f:
        doc = json.load(f)
    s = x.summarize(x.Trace.from_json(doc["trace"]), doc["lo_ns"], doc["hi_ns"])
    for key, want in doc["expect"].items():
        assert s[key] == pytest.approx(want, rel=1e-9), key
