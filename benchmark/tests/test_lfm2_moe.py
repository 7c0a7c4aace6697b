"""What `test_correct.py` and `test_servetick.py` cover for the families
before it, covered for `lfm2_moe`, which is served and not trained (so
`test_correct.py`'s training cases cannot pass for it): a sound tiny serving
run through the harness's own path is `correct`, its float8 control and an
altered served token are not; and the two readers this family's cell brings
read what the program's counters say on a recorded observation, None on the
registry of a commit that lacks them, and the share of the HBM's peak cannot
pass 100."""
import importlib.util
import json
import os

import pytest

import tiny
from lib import harness

FAMILY = "lfm2_moe"
CELL = "lfm2-24b-a2b.serve-reason-1k"
MODEL = harness.load_json("configs", "lfm2-24b-a2b.json")


def test_sound_serving_run_is_correct_and_its_control_is_not(tmp_path):
    many = dict(check_requests=24)
    sound = tiny.values(tiny.run_serve(
        tiny.serve_spec(FAMILY, tmp_path, **many)))
    # the served weights are bfloat16 and the reference's float32. With 2 of
    # 8 experts a token and no shared expert a routing choice that flips
    # moves half a layer's output, at either precision: the WIDEST gap reads
    # 0.002-0.013 sound and 0.02-0.025 under float8 and cannot tell them
    # apart; the MEAN does (0.00004-0.00025 against 0.0016), as in the cell
    limits = {"served_logit_gap": max(3 * sound["served_logit_gap"], 1e-3),
              "served_logit_gap_mean": max(
                  3 * sound["served_logit_gap_mean"], 1e-4),
              "requests_short": 0}
    spec = tiny.serve_spec(FAMILY, tmp_path, limits=limits, **many)
    line = tiny.run_serve(spec, precision="fp8")
    assert line["correct"] and line["failed"] == 0
    assert tiny.values(line)["tokens_compared"] >= 100
    held = line["control"]["compared"]["served_logit_gap_mean"]
    assert line["control"]["correct"] is False
    assert held["value"] > limits["served_logit_gap_mean"]


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


# ----------------------------------------------------------- the two readers

# 200 ticks in the traced window, 25 of them with a prefill chunk of 512; the
# registry already held some
OPEN = {
    "serve_engine_steps_total": 1_000.0,
    'serve_decode_calls_total{batch="64",width_blocks="128"}': 900.0,
    'serve_decode_calls_total{batch="32",width_blocks="128"}': 100.0,
    'serve_prefill_calls_total{chunk="512",width_blocks="16"}': 120.0,
    'serve_moe_experts_total{kind="read"}': 550_000.0,
    'serve_moe_experts_total{kind="held"}': 573_440.0,
    'serve_decode_positions_total{kind="read"}': 90_000_000.0,
    'serve_decode_positions_total{kind="live"}': 88_000_000.0,
}
GROWTH = {
    "serve_engine_steps_total": 200.0,
    'serve_decode_calls_total{batch="64",width_blocks="128"}': 190.0,
    'serve_decode_calls_total{batch="32",width_blocks="128"}': 10.0,
    'serve_prefill_calls_total{chunk="512",width_blocks="16"}': 25.0,
    # 225 programs of 8 expert layers of 64: the decode programs read 63 of
    # 64, the prefill programs all
    'serve_moe_experts_total{kind="read"}': 200 * 8 * 63.0 + 25 * 8 * 64,
    'serve_moe_experts_total{kind="held"}': 225 * 8 * 64.0,
    'serve_decode_positions_total{kind="read"}': 200 * 162_000.0,
    'serve_decode_positions_total{kind="live"}': 200 * 160_000.0,
}
# every matrix outside the routed experts, gains, taps and biases with them
REST = (7 * 16_783_360 + 2 * 10_485_888 + 72_351_744 + 8 * (131_072 + 64)
        + 18 * 2_048)
EXPERT, HEAD = 3 * 2_048 * 1_536, 65_536 * 2_048
BYTES = 2 * (200 * (REST + HEAD) + 25 * REST
             + GROWTH['serve_moe_experts_total{kind="read"}'] * EXPERT) + (
    4_096 * 200 * 162_000.0)
WANT = {
    "serve_step_hbm_pct.tput": 100 * BYTES / 819e9 / 3.9,
    "serve_moe_experts_read_pct.tput": 100 * (100_800 + 12_800) / 115_200,
}


def observation(counters_close, busy_s=3.9):
    return {"counters_traced": (OPEN, counters_close), "model": MODEL,
            "family": harness.load_family(MODEL["family"]),
            "traffic": harness.load_json("traffic", "serve-reason-1k.json"),
            "device_kind": "TPU v5 lite",
            "trace": {"window_s": 4.0, "busy_s": busy_s, "mosaic_s": 0.05}}


def reader(name):
    path = os.path.join(harness.BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name[:-5], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_benchmark_lists_the_two_for_the_new_cell_alone():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    found = [m for m in bench["per_layer"] if m["name"] in WANT]
    assert sorted(m["name"] for m in found) == sorted(WANT)
    for m in found:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"
    assert bench["per_layer"][-2:] == found          # appended, at the end
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == "lfm2-24b-a2b"
    names = {m["name"] for m in harness.load_spec(CELL)["per_layer"]}
    assert names >= set(WANT) | {
        "serve_step_mfu_pct.tput", "serve_decode_attn_roofline.tput",
        "serve_decode_attn_time_pct.tput", "serve_moe_rows_pad_pct.tput",
        "serve_decode_read_pad_pct.tput", "serve_dispatch_ahead_pct.tput"}
    assert len(names) == 22
    assert not {n for n in names if "mla" in n or "held_pairs" in n}


def test_the_cells_traffic_is_the_issues():
    tr = harness.load_json("traffic", "serve-reason-1k.json")
    assert (tr["loop"], tr["callers"], tr["pool"], tr["pool_seed"]) == (
        "closed", 64, 128, 35)
    assert tr["prompt_len"] == {"median": 1024, "sigma": 0.6, "min": 128,
                                "max": 4096}
    assert tr["answer_len"] == {"median": 1024, "sigma": 0.5, "min": 256,
                                "max": 2048}
    assert tr["engine"] == {
        "max_batch": 64, "block_size": 64, "max_seq_len": 6144,
        "prefill_chunk": 512, "decode_impl": "auto", "num_blocks": 6145,
        "max_queue": 128}
    assert (tr["total_max"], tr["preroll_s"], tr["grace_s"], tr["stagger_s"],
            tr["check_requests"], tr["trace_seconds"]) == (
        6144, 20, 90, 0.05, 4, 4)
    # every caller's longest request fits the pool: nothing is preempted
    assert (tr["engine"]["num_blocks"] - 1) * 64 >= 64 * tr["total_max"]


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_reads_what_the_counters_say(name):
    close = {k: OPEN[k] + v for k, v in GROWTH.items()}
    assert reader(name)(observation(close)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_reads_none_without_its_counters(name):
    """The parent's registry: the calls and the positions, no count of the
    experts read. Neither reads; nor does the share of the peak without a
    trace."""
    close = {k: OPEN[k] + v for k, v in GROWTH.items() if "experts" not in k}
    obs = observation(close)
    obs["counters_traced"] = (
        {k: v for k, v in OPEN.items() if "experts" not in k}, close)
    assert reader(name)(obs) is None
    if name == "serve_step_hbm_pct.tput":
        full = observation({k: OPEN[k] + v for k, v in GROWTH.items()})
        full["trace"] = None
        assert reader(name)(full) is None
        # a family whose arithmetic does not count a program's bytes
        other = observation({k: OPEN[k] + v for k, v in GROWTH.items()})
        other["family"] = harness.load_family("pangu_ultra_moe")
        assert reader(name)(other) is None


def test_the_share_of_the_hbm_peak_cannot_pass_a_hundred():
    """The bytes are a lower bound of what the programs read: a device that
    took exactly the HBM's time for them reads 100, and it cannot take
    less."""
    close = {k: OPEN[k] + v for k, v in GROWTH.items()}
    least = BYTES / 819e9
    assert 2.5 < least < 3.9            # 13-14 ms a decode program
    assert reader("serve_step_hbm_pct.tput")(
        observation(close, busy_s=least)) == pytest.approx(100.0)
    assert WANT["serve_step_hbm_pct.tput"] < 100.0
