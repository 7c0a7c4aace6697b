from lib import traffic as tgen
from tiny import SERVE

LONGDOC = {"pool": 64, "pool_seed": 23, "total_max": 2048, "temperature": 0.0,
           "prompt_len": {"median": 1024, "sigma": 0.6, "min": 256, "max": 1792},
           "answer_len": {"median": 128, "sigma": 0.6, "min": 32, "max": 256}}


def test_lengths_keep_to_their_clips_and_median():
    xs = tgen.stratified_lengths(LONGDOC["prompt_len"], 64)
    assert min(xs) >= 256 and max(xs) <= 1792
    assert xs == sorted(xs) and 960 <= sorted(xs)[32] <= 1090


def test_pool_is_deterministic_in_the_seed():
    a = tgen.request_pool(LONGDOC, 2**31 + 5, 50257)
    b = tgen.request_pool(LONGDOC, 2**31 + 5, 50257)
    c = tgen.request_pool(LONGDOC, 6, 50257)
    assert a == b and a != c
    for r in a:
        assert len(r["prompt"]) + r["max_new_tokens"] <= 2048
        assert 32 <= r["max_new_tokens"] <= 256
        assert all(0 <= t < 50257 for t in r["prompt"])


def test_every_seed_gets_the_same_sizes_in_the_same_order():
    def sizes(seed):
        return [(len(r["prompt"]), r["max_new_tokens"])
                for r in tgen.request_pool(LONGDOC, seed, 50257)]
    assert sizes(1) == sizes(2**31 + 9)
    assert sizes(1) != sorted(sizes(1))


def test_window_edges_lie_half_way_between_two_ticks_ends():
    from lib import serve
    ends = [0.1 * i for i in range(60)]
    lo, hi = serve.window_edges(ends, 1.01, 2.0)
    assert abs(lo - 1.05) < 1e-9          # the first middle at or after 1.01
    assert abs(hi - 3.05) < 1e-9 and hi - lo >= 2.0 - 1e-9
    recs = [{"due": 1.5, "sent": 1.5, "status": "completed", "tokens": [1, 2],
             "max_new_tokens": 2, "token_t": [lo - 0.001, hi - 0.001]}]
    lat = serve.latency_numbers(recs, 1.01, 2.0, (lo, hi))
    assert lat["tokens_in_window"] == 1 and abs(lat["window_s"] - 2.0) < 1e-6
    assert serve.window_edges([], 1.0, 2.0) == (1.0, 3.0)
    assert serve.window_edges([1.5], 1.0, 2.0) == (1.0, 3.0)


def test_tick_watch_reads_the_beat_from_the_registry_counters():
    import time

    from distributed_neural_network_tpu.utils.obs import MetricsRegistry
    from lib import serve
    reg = MetricsRegistry()
    steps = reg.counter("serve_engine_steps_total")
    decode = reg.counter("serve_tokens_total").labels(kind="decode")
    watch = serve.TickWatch(reg)
    for n in (3, 5, 4):                    # as the scheduler counts a tick
        time.sleep(0.03)
        steps.inc()
        decode.inc(n)
    time.sleep(0.03)
    watch.close()
    ticks = watch.ticks()
    assert [t[2] for t in ticks] == [5, 4]  # the first has no tick before it
    assert all(0.02 < t[1] - t[0] < 0.08 for t in ticks)


def test_arrivals_same_gaps_every_seed_and_the_stated_rate():
    tr = dict(SERVE, rate_per_s=20.0, gap_block=64)
    a = tgen.arrival_times(tr, 1, 64 / 20.0 + 1e-9)
    b = tgen.arrival_times(tr, 2, 64 / 20.0 + 1e-9)
    assert a == tgen.arrival_times(tr, 1, 64 / 20.0 + 1e-9) and a != b
    gaps = lambda ts: sorted(round(y - x, 9) for x, y in zip([0.0] + ts, ts))
    assert gaps(a) == gaps(b)
    assert abs(a[-1] - 63 / 20.0) < 0.2  # 63 or 64 arrivals in 3.2 s
