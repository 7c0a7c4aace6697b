"""What `test_correct.py` and `test_servetick.py` cover for the families
before it, covered for `mimo_v2`, which is served and not trained: a sound
tiny serving run through the harness's own path is `correct`, its float8
control and an altered served token are not; the family's files hold the
configuration and arithmetic of its cell; and the reader this family's cell
brings reads what the program's counters say on a recorded observation, None
on the registry of a commit that lacks them."""
import importlib.util
import json
import math
import os

import jax
import pytest

import tiny
from lib import harness
from lib.weights import is_shape

FAMILY = "mimo_v2"
CELL = "mimo-v2.5.serve-mixed-32k"
MODEL = harness.load_json("configs", "mimo-v2.5.json")
NAME = "serve_attn_pairs_pad_pct.tput"


def test_sound_serving_run_is_correct_and_its_control_is_not(tmp_path):
    many = dict(check_requests=24)
    sound = tiny.values(tiny.run_serve(
        tiny.serve_spec(FAMILY, tmp_path, **many)))
    limits = {"served_logit_gap": max(3 * sound["served_logit_gap"], 1e-3),
              "served_logit_gap_mean": max(
                  3 * sound["served_logit_gap_mean"], 1e-4),
              "requests_short": 0}
    spec = tiny.serve_spec(FAMILY, tmp_path, limits=limits, **many)
    line = tiny.run_serve(spec, precision="fp8")
    assert line["correct"] and line["failed"] == 0
    assert tiny.values(line)["tokens_compared"] >= 100
    assert line["control"]["correct"] is False


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


# ------------------------------------------------------- the family's files

def test_the_count_of_the_file_is_the_sum_of_its_shapes():
    """`param_count` of the configuration's file = the sum of
    `weights.shapes` = 3,429,955,392 (6.86 GB in bfloat16), and with what
    `published` states put back the whole model: 308.78 B, 15.4 B active
    (published as 309B-A15B)."""
    family = harness.load_family(FAMILY)
    shapes = jax.tree.leaves(family.weights.shapes(MODEL), is_leaf=is_shape)
    arith = family.arith
    assert (arith.param_count(MODEL) == sum(map(math.prod, shapes))
            == 3_429_955_392)
    pub = MODEL["published"]
    whole = dict(MODEL, published={}, **{k: pub[k] for k in (
        "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
        "n_routed_experts", "vocab_size")})
    assert arith.param_count(whole) == pytest.approx(308.78e9, abs=5e6)
    # active: what a token is multiplied by, and the embedding's row it
    # looks up
    assert arith.active_matmul_params(whole) + 152_576 * 4_096 == \
        pytest.approx(15.4e9, 0.01)
    assert MODEL["hybrid_layer_pattern"] == [pub["hybrid_layer_pattern"][0]] \
        + pub["hybrid_layer_pattern"][6:12]
    assert MODEL["moe_layer_freq"] == [pub["moe_layer_freq"][0]] + pub[
        "moe_layer_freq"][6:12]
    assert set(MODEL["reduced"]) == set(MODEL["reduced_how"]) == {
        "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
        "n_routed_experts", "vocab_size"}
    # the work the cell's readers count
    assert arith.pair_flops(MODEL) == 40_960
    assert arith.decode_attn_flops(MODEL, 1) == 2 * 40_960
    assert arith.decode_attn_bytes(MODEL, 1) == 2 * 1_280 * 2
    assert arith.kv_bytes_per_token(MODEL) == 5_120
    assert arith.ring_bytes_per_sequence(MODEL) == 5 * 655_360
    # window layers at min(context, 128) keys a token, full layers at all
    one = arith.forward_flops(MODEL, 1, 0)
    assert arith.forward_flops(MODEL, 1, 4_000) == pytest.approx(
        one + 40_960 * (2 * 4_000 + 5 * 128))
    assert arith.forward_flops(MODEL, 1, 100) == pytest.approx(
        one + 40_960 * 7 * 100)
    # a decode program reading 12 of 16 experts in 6 layers over 200,000
    # fetched positions: every other matrix once, the head with them
    rest = 3_429_955_392 - 2 * 19_072 * 4_096 - 4_096 - 6 * 16 * 25_165_824
    read = arith.program_read_bytes(MODEL, "decode", 6 * 12, 200_000)
    assert read == 2 * (rest + 19_072 * 4_096 + 72 * 25_165_824) + (
        5_120 * 200_000)
    assert arith.program_read_bytes(MODEL, "prefill", 0, 0) == 2 * rest


def test_the_configuration_keeps_the_catalogs_keys():
    """Every published key of the row is in the file; the five `reduced`
    keys alone differ from `published`, and the row's mechanisms are what
    the program is built from."""
    family = harness.load_family(FAMILY)
    cfg = family.program.config(MODEL, {}, "bfloat16")
    assert (cfg.n_full, cfg.n_window, cfg.n_dense, cfg.n_moe) == (2, 5, 1, 6)
    assert (cfg.qk_head, cfg.v_head, cfg.rope_dim, cfg.window) == (
        192, 128, 64, 128)
    assert (cfg.n_kv_full, cfg.n_kv_window, cfg.experts_held, cfg.n_routed,
            cfg.top_k) == (4, 8, (0, 16), 256, 8)
    assert (cfg.rope_theta, cfg.window_rope_theta, cfg.value_scale) == (
        10_000_000, 10_000, 0.707)
    assert MODEL["source"].endswith("XiaomiMiMo/MiMo-V2.5/blob/main/"
                                    "config.json")
    for key in ("n_shared_experts", "n_group", "add_full_attention_sink_bias",
                "attention_chunk_size", "attention_projection_layout"):
        assert key in MODEL


def test_the_cells_traffic_mixes_short_and_long_prompts():
    tr = harness.load_json("traffic", "serve-mixed-32k.json")
    assert (tr["loop"], tr["callers"], tr["pool"], tr["pool_seed"],
            tr["stagger_s"]) == ("closed", 48, 96, 40, 0.05)
    assert tr["prompt_len"] == {"median": 2048, "sigma": 1.2, "min": 256,
                                "max": 32768}
    assert tr["answer_len"] == {"median": 512, "sigma": 0.5, "min": 128,
                                "max": 1536}
    assert tr["engine"] == {
        "max_batch": 48, "block_size": 64, "max_seq_len": 34304,
        "prefill_chunk": 512, "decode_impl": "auto", "num_blocks": 12289,
        "max_queue": 96}
    assert (tr["total_max"], tr["preroll_s"], tr["grace_s"],
            tr["check_requests"], tr["trace_seconds"], tr["temperature"]) == (
        34304, 20, 90, 4, 4, 0.0)


# ----------------------------------------------------------- the new reader

OPEN = {'serve_attn_pairs_total{kind="%s",layers="%s"}' % (k, l): 1e9
        for k in ("live", "scored") for l in ("full", "window")}
GROWTH = {
    'serve_attn_pairs_total{kind="live",layers="full"}': 2 * 3.0e8,
    'serve_attn_pairs_total{kind="scored",layers="full"}': 2 * 4.0e8,
    'serve_attn_pairs_total{kind="live",layers="window"}': 5 * 6.0e7,
    'serve_attn_pairs_total{kind="scored",layers="window"}': 5 * 3.2e8,
}


def reader():
    path = os.path.join(harness.BENCH_DIR, "metrics", NAME + ".py")
    spec = importlib.util.spec_from_file_location("m_pairs", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_reader_reads_what_the_counters_say():
    close = {k: OPEN[k] + v for k, v in GROWTH.items()}
    obs = {"counters_traced": (OPEN, close)}
    want = 100 * (1 - (6e8 + 3e8) / (8e8 + 16e8))
    assert reader()(obs) == pytest.approx(want)


def test_reader_reads_none_without_its_counters():
    """The parent's registry has no such counter; nor a window that
    scored nothing."""
    assert reader()({"counters_traced": ({}, {})}) is None
    assert reader()({"counters_traced": (OPEN, OPEN)}) is None
    assert reader()({}) is None


def test_the_benchmark_lists_the_new_cell_and_reader():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["configs"][-1]["name"] == "mimo-v2.5"
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["workloads"][-1]["chips"] == 1
    assert bench["per_layer"][-1] == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "attention masks",
        "moves": "serve_tokens_per_s", "workloads": [CELL]}
    names = {m["name"] for m in harness.load_spec(CELL)["per_layer"]}
    assert len(names) == 24
    assert NAME in names and "serve_step_hbm_pct.tput" in names
    assert not {n for n in names if "mla" in n or "found_idle" in n
                or "serve_host_" in n or "release" in n}
