"""What `test_correct.py` and `test_servetick.py` cover for the families
before it, covered for `pangu_ultra_moe`, which is served and not trained
(so `test_correct.py`'s training cases cannot pass for it): a sound tiny
serving run through the harness's own path is `correct`, its float8 control
and an altered served token are not; and the four readers this family's cell
brings read what the program's counters say on a hand-made observation, and
None on the registry of a commit that lacks them."""
import importlib.util
import json
import os

import pytest

import tiny
from lib import harness

FAMILY = "pangu_ultra_moe"
CELL = "openpangu-ultra-moe-718b.serve-docqa-6k"
MODEL = harness.load_json("configs", "openpangu-ultra-moe-718b.json")


def test_sound_serving_run_is_correct_and_its_control_is_not(tmp_path):
    many = dict(check_requests=24)
    sound = tiny.values(tiny.run_serve(
        tiny.serve_spec(FAMILY, tmp_path, **many)))
    # the served weights are bfloat16 and the reference's float32: the
    # sound gap is a few thousandths here, the control's ten times that
    limit = max(3 * sound["served_logit_gap"], 1e-3)
    spec = tiny.serve_spec(FAMILY, tmp_path, limits={
        "served_logit_gap": limit, "requests_short": 0}, **many)
    line = tiny.run_serve(spec, precision="fp8")
    assert line["correct"] and line["failed"] == 0
    assert tiny.values(line)["tokens_compared"] >= 100
    held = line["control"]["compared"]["served_logit_gap"]
    assert line["control"]["correct"] is False and held["value"] > limit


def test_altered_token_makes_the_serving_run_not_correct(tmp_path):
    spec = tiny.serve_spec(FAMILY, tmp_path)
    vocab = spec["family"].weights.vocab(spec["config"])

    def alter(engine):
        emit = engine._emit

        def wrong(seq, tok):
            # every request's third token is replaced where it is produced
            return emit(seq, (tok + 1) % vocab if len(seq.out) == 2 else tok)
        engine._emit = wrong

    sound = tiny.values(tiny.run_serve(spec))
    limit = max(3 * sound["served_logit_gap"], 0.02)
    spec = tiny.serve_spec(FAMILY, tmp_path, limits={
        "served_logit_gap": limit, "requests_short": 0})
    line = tiny.run_serve(spec, wrap_engine=alter)
    assert line["correct"] is False, line["compared"]


# ---------------------------------------------------------- the four readers

# 16 ticks in the traced window; the registry already held some
OPEN = {
    "serve_engine_steps_total": 100.0,
    'serve_attn_kernel_positions_total{path="decode"}': 5_000_000.0,
    'serve_attn_kernel_pairs_total{path="prefill"}': 0.0,
    'serve_moe_pairs_total{where="held"}': 10_000.0,
    'serve_moe_pairs_total{where="absent"}': 150_000.0,
    'serve_moe_rows_total{kind="owned"}': 10_000.0,
    'serve_moe_rows_total{kind="multiplied"}': 40_000.0,
}
GROWTH = {
    "serve_engine_steps_total": 16.0,
    # 20 sequences a tick at 8,000 positions
    'serve_attn_kernel_positions_total{path="decode"}': 16 * 160_000.0,
    'serve_attn_kernel_pairs_total{path="prefill"}': 0.0,
    # 16 ticks of 20 + 512 tokens, 4 expert layers, 8 pairs a token
    'serve_moe_pairs_total{where="held"}': 17_000.0,
    'serve_moe_pairs_total{where="absent"}': 255_384.0,
    'serve_moe_rows_total{kind="owned"}': 17_000.0,
    'serve_moe_rows_total{kind="multiplied"}': 68_000.0,
}
# 2,560,000 positions x 5 layers: 278,528 FLOPs each = 3.565e12 (18.10 ms at
# 197 TFLOP/s), 1,152 bytes each = 14.75 GB (18.00 ms at 819 GB/s): compute
# bounds it, by a hair; over 40 ms in the Mosaic calls
WANT = {
    "serve_mla_attn_time_pct.tput": 100 * 0.040 / 3.1,
    "serve_mla_attn_roofline.tput": 100 * (
        2_560_000 * 5 * 278_528 / 197e12) / 0.040,
    "serve_moe_held_pairs_pct.tput": 100 * 17_000 / 272_384,
    "serve_moe_rows_pad_pct.tput": 75.0,
}


def observation(counters_close):
    return {"counters_traced": (OPEN, counters_close), "model": MODEL,
            "family": harness.load_family(MODEL["family"]),
            "traffic": harness.load_json("traffic", "serve-docqa-6k.json"),
            "device_kind": "TPU v5 lite",
            "trace": {"window_s": 4.0, "busy_s": 3.1, "mosaic_s": 0.040}}


def reader(name):
    path = os.path.join(harness.BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name[:-5], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_benchmark_lists_the_four_for_the_docqa_cell_alone():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    found = [m for m in bench["per_layer"] if m["name"] in WANT]
    assert sorted(m["name"] for m in found) == sorted(WANT)
    for m in found:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"
    names = {m["name"] for m in harness.load_spec(CELL)["per_layer"]}
    assert names >= set(WANT) | {"serve_step_mfu_pct.tput",
                                 "serve_decode_read_pad_pct.tput"}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_reads_what_the_counters_say(name):
    close = {k: OPEN[k] + v for k, v in GROWTH.items()}
    assert reader(name)(observation(close)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_reads_none_without_its_counters(name):
    """The parent's registry: steps and the older families only. The time
    share needs no counter and still reads; without a trace it does not."""
    parent = {"serve_engine_steps_total": 116.0}
    obs = observation(parent)
    obs["counters_traced"] = ({"serve_engine_steps_total": 100.0}, parent)
    if name == "serve_mla_attn_time_pct.tput":
        assert reader(name)(obs) == pytest.approx(WANT[name])
        obs["trace"] = None
    assert reader(name)(obs) is None


def test_the_roofline_share_cannot_pass_a_hundred():
    """Its work is counted from the very positions the Mosaic calls were
    handed, at the least bytes and operations an absorbed decode needs: a
    kernel at both peaks at once reads 100."""
    close = {k: OPEN[k] + v for k, v in GROWTH.items()}
    obs = observation(close)
    work = obs["family"].arith
    n = GROWTH['serve_attn_kernel_positions_total{path="decode"}']
    least = max(work.decode_attn_flops(MODEL, n) / 197e12,
                work.decode_attn_bytes(MODEL, n) / 819e9)
    obs["trace"]["mosaic_s"] = least
    assert reader("serve_mla_attn_roofline.tput")(obs) == pytest.approx(100.0)
