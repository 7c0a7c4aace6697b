"""Serving stack end to end: scheduler admission/fairness, HTTP + SSE
streaming, the serving goodput ledger, and the tools surface
(tools/loadgen.py as a library, tools/goodput.py on serve records,
tools/live_top.py serving view).

Bars:
- streamed completions over real HTTP equal the offline `generate()`
  oracle under concurrent mixed-length load;
- queue overflow and tenant rate limits answer 429 (with Retry-After),
  malformed/over-long requests answer 400, and neither crashes anything;
- a client disconnect mid-stream cancels the sequence and frees its KV
  blocks;
- the serving ledger conserves wall-clock over the serve taxonomy, the
  record renders/gates through tools/goodput.py, and the committed
  serving baseline is self-consistent;
- /metrics carries the serve_* series and live_top renders the serving
  view from them;
- the loop thread's time is partitioned into phases that sum to its
  wall time, the bucket and position counters equal a hand count, and
  the same phases are nested spans in a profiler trace.
"""

import http.client
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_neural_network_tpu.models import transformer as tfm
from distributed_neural_network_tpu.serve import (
    AdmissionError,
    EngineConfig,
    SchedulerConfig,
    ServeEngine,
    ServeRequest,
    ServeScheduler,
)
from distributed_neural_network_tpu.serve.http import ServeServer
from distributed_neural_network_tpu.utils.obs import MetricsRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = tfm.TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64
)
SEED = 0


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(jax.random.key(SEED), CFG)


@pytest.fixture()
def stack(params):
    """Fresh engine + scheduler + registry (no HTTP) per test."""
    registry = MetricsRegistry()
    engine = ServeEngine(params, CFG, EngineConfig(
        max_batch=4, num_blocks=32, block_size=4, max_seq_len=64,
    ))
    scheduler = ServeScheduler(
        engine, SchedulerConfig(max_queue=8), registry=registry,
    ).start()
    yield engine, scheduler, registry
    scheduler.close(finalize=False)


@pytest.fixture(scope="module")
def server(params):
    """One shared HTTP server for the transport-level tests."""
    registry = MetricsRegistry()
    engine = ServeEngine(params, CFG, EngineConfig(
        max_batch=4, num_blocks=64, block_size=4, max_seq_len=64,
    ))
    scheduler = ServeScheduler(
        engine, SchedulerConfig(max_queue=16), registry=registry,
    ).start()
    srv = ServeServer(scheduler, registry, port=0)
    yield srv
    scheduler.close(finalize=False)
    srv.close()


def _prompt(key, n, vocab=64):
    return np.asarray(
        jax.random.randint(jax.random.key(key), (n,), 2, vocab)
    ).tolist()


def _oracle(params, prompt, n_new):
    return [int(x) for x in np.asarray(tfm.generate(
        params, jnp.asarray([prompt], jnp.int32), CFG,
        max_new_tokens=n_new,
    ))[0, len(prompt):]]


def _post(srv, body, timeout=60):
    c = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=timeout)
    c.request("POST", "/v1/generate", json.dumps(body),
              {"Content-Type": "application/json"})
    return c, c.getresponse()


def _read_sse(resp):
    toks, done = [], None
    buf = b""
    while True:
        chunk = resp.read(64)
        if not chunk:
            break
        buf += chunk
        while b"\n\n" in buf:
            frame, buf = buf.split(b"\n\n", 1)
            doc = json.loads(frame.decode().removeprefix("data: "))
            if "token" in doc:
                toks.append(doc["token"])
            if doc.get("done"):
                done = doc
        if done:
            break
    return toks, done


# ----------------------------------------------------- scheduler (no HTTP)


def test_concurrent_mixed_lengths_stream_oracle_tokens(stack, params,
                                                       n_devices):
    _, scheduler, _ = stack
    reqs = [
        scheduler.submit(ServeRequest(
            prompt=_prompt(100 + i, ln), max_new_tokens=6,
            api_key=f"tenant{i % 2}",
        ))
        for i, ln in enumerate([3, 9, 5, 7])
    ]
    for r in reqs:
        toks = []
        while True:
            kind, payload = r.events.get(timeout=60)
            if kind == "token":
                toks.append(payload)
            elif kind == "done":
                break
            else:
                raise AssertionError(payload)
        assert toks == _oracle(params, r.prompt, 6)
        assert payload["status"] == "done"
        assert payload["ttft_s"] is not None


def test_queue_overflow_429_and_metrics(stack, n_devices):
    engine, scheduler, registry = stack
    # one slot's worth of long work + a full queue
    held = [scheduler.submit(ServeRequest(
        prompt=_prompt(200 + i, 4), max_new_tokens=40,
    )) for i in range(4)]
    with pytest.raises(AdmissionError) as ei:
        for i in range(scheduler.cfg.max_queue + 4):
            scheduler.submit(ServeRequest(
                prompt=_prompt(300 + i, 4), max_new_tokens=40,
            ))
    assert ei.value.status == 429 and ei.value.reason == "queue_full"
    text = registry.render()
    assert 'serve_rejected_total{reason="queue_full"}' in text
    for r in held:
        r.cancelled.set()


def test_tenant_token_bucket_rate_limit(params, n_devices):
    registry = MetricsRegistry()
    engine = ServeEngine(params, CFG, EngineConfig(
        max_batch=2, num_blocks=16, block_size=4, max_seq_len=32,
    ))
    scheduler = ServeScheduler(
        engine,
        SchedulerConfig(max_queue=64, tenant_rate=0.001, tenant_burst=2),
        registry=registry,
    )
    try:
        ok = rejected = 0
        for i in range(4):
            try:
                scheduler.submit(ServeRequest(
                    prompt=[2, 3], max_new_tokens=1, api_key="greedy",
                ))
                ok += 1
            except AdmissionError as e:
                assert e.status == 429 and e.reason == "rate_limited"
                rejected += 1
        assert ok == 2 and rejected == 2  # burst honored, then limited
        # a DIFFERENT tenant is untouched by the greedy one's bucket
        scheduler.submit(ServeRequest(
            prompt=[2, 3], max_new_tokens=1, api_key="polite",
        ))
    finally:
        scheduler.close(finalize=False)


def test_round_robin_tenant_fairness(params, n_devices):
    """9 queued from tenant A, 1 from tenant B, one slot: B's request
    must be admitted 2nd (round-robin), not 10th (global FIFO)."""
    registry = MetricsRegistry()
    engine = ServeEngine(params, CFG, EngineConfig(
        max_batch=1, num_blocks=32, block_size=4, max_seq_len=32,
    ))
    scheduler = ServeScheduler(
        engine, SchedulerConfig(max_queue=16), registry=registry,
    )
    order = []
    reqs = []
    for i in range(9):
        reqs.append(scheduler.submit(ServeRequest(
            prompt=_prompt(400 + i, 3), max_new_tokens=2, api_key="A",
        )))
    reqs.append(scheduler.submit(ServeRequest(
        prompt=_prompt(500, 3), max_new_tokens=2, api_key="B",
    )))
    scheduler.start()
    try:
        deadline = time.monotonic() + 120
        for r in reqs:
            while r.status not in ("done", "error"):
                assert time.monotonic() < deadline
                time.sleep(0.01)
        done_order = sorted(reqs, key=lambda r: r.t_admitted)
        order = [r.api_key for r in done_order]
        assert order[1] == "B", order
    finally:
        scheduler.close(finalize=False)


def test_serving_ledger_conserves_and_renders(params, tmp_path,
                                              n_devices):
    record_path = str(tmp_path / "serve_record.json")
    registry = MetricsRegistry()
    engine = ServeEngine(params, CFG, EngineConfig(
        max_batch=4, num_blocks=32, block_size=4, max_seq_len=64,
    ))
    scheduler = ServeScheduler(
        engine,
        SchedulerConfig(max_queue=8, run_record=record_path),
        registry=registry,
    ).start()
    reqs = [scheduler.submit(ServeRequest(
        prompt=_prompt(600 + i, 5), max_new_tokens=8,
    )) for i in range(3)]
    for r in reqs:
        while True:
            kind, _ = r.events.get(timeout=60)
            if kind == "done":
                break
    rec = scheduler.close()  # finalize asserts conservation internally
    assert rec["taxonomy"] == "serve" and rec["kind"] == "serve"
    total = rec["goodput_s"] + sum(rec["badput_s"].values())
    assert total == pytest.approx(rec["wall_s"], rel=1e-6)
    assert rec["badput_s"]["prefill"] > 0
    assert rec["goodput_s"] > 0  # decode happened
    # the armed write-through record landed and matches
    on_disk = json.load(open(record_path))
    assert on_disk["taxonomy"] == "serve" and on_disk["final"] is True
    # live registry export carried the serve taxonomy
    text = registry.render()
    assert "goodput_ratio" in text
    assert 'badput_seconds_total{cause="prefill"}' in text
    # tools/goodput.py renders and self-gates the record
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "goodput.py"),
         record_path],
        capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stderr
    assert "decode" in r.stdout and "<- goodput" in r.stdout
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "goodput.py"),
         "--check", record_path, "--baseline", record_path],
        capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    # gating a serve record against the TRAIN baseline is a usage error
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "goodput.py"),
         "--check", record_path, "--baseline",
         os.path.join(REPO, "tools", "goodput_baseline.json")],
        capture_output=True, text=True,
    )
    assert r.returncode == 2
    assert "taxonomy mismatch" in r.stderr


def _wait_done(req, timeout=60):
    while True:
        kind, payload = req.events.get(timeout=timeout)
        if kind == "done":
            return payload
        assert kind == "token", payload


def _family(registry, name):
    """{label tuple: value} of one counter family, as a scrape shows it."""
    out = {}
    for line in registry.render().splitlines():
        if line.startswith(name + "{"):
            labels, _, value = line[len(name):].rpartition(" ")
            out[labels] = float(value)
    return out


def test_loop_phases_and_positions_over_a_scripted_run(params, n_devices):
    """Two sequences of known lengths (prompts 9 and 5, answers 3 and
    2; chunks and blocks of 4), both queued before the loop starts, so
    the schedule is fixed: tick 1 prefills A[0:4] (nothing decodes),
    tick 2 A[4:8] and decodes A, tick 3 B[0:4] and decodes A and B, tick
    4 decodes both and retires both."""
    from distributed_neural_network_tpu.serve.engine import HOST_PARTS
    from distributed_neural_network_tpu.serve.scheduler import LOOP_PHASES

    registry = MetricsRegistry()
    engine = ServeEngine(params, CFG, EngineConfig(
        max_batch=4, num_blocks=32, block_size=4, max_seq_len=64,
        prefill_chunk=4,
    ))
    scheduler = ServeScheduler(
        engine, SchedulerConfig(max_queue=8), registry=registry,
    )
    loop_s = registry.get("serve_loop_seconds_total")

    def published():
        return sum(loop_s.labels(phase=p).value for p in LOOP_PHASES)

    # at a step's entry the registry holds whole ticks up to the last
    # step's end, which the wrapper read on the scheduler's own clock
    inner, ends, sums = engine.step, [], []

    def step():
        sums.append(published())
        st = inner()
        ends.append(scheduler.ledger.now())
        return st

    engine.step = step
    reqs = [scheduler.submit(ServeRequest(
        prompt=_prompt(700 + i, n), max_new_tokens=new,
    )) for i, (n, new) in enumerate([(9, 3), (5, 2)])]
    scheduler.start()
    for r in reqs:
        assert _wait_done(r)["status"] == "done"
    scheduler.close(finalize=False)

    assert registry.counter("serve_engine_steps_total").value == 4
    assert len(ends) == 4
    # the phases between the first and the last tick's end sum to the
    # loop thread's wall time there (the last tick is published whole,
    # its `release` with it)
    wall = ends[-1] - ends[0]
    assert published() - sums[1] == pytest.approx(wall, rel=0.02)
    phases = _family(registry, "serve_loop_seconds_total")
    assert set(phases) == {'{phase="%s"}' % p for p in LOOP_PHASES}
    assert phases['{phase="spec"}'] == 0.0
    assert all(v >= 0.0 for v in phases.values())
    for p in ("admit", "books", "prefill_host", "decode_host", "fetch",
              "emit", "release"):
        assert phases['{phase="%s"}' % p] > 0.0, p
    # the host phases' parts, and each of the six bucket programs found
    # the device idle or busy
    parts = _family(registry, "serve_host_seconds_total")
    assert set(parts) == {'{part="%s"}' % p for p in HOST_PARTS}
    assert sum(parts.values()) <= (phases['{phase="prefill_host"}']
                                   + phases['{phase="decode_host"}'])
    assert all(v > 0.0 for v in parts.values()), parts
    found = _family(registry, "serve_dispatch_found_total")
    assert sum(found.values()) == 3 + 3

    # the hand count: a prompt token at position p attends to p + 1, a
    # decode at position p likewise (in closed form, whatever the chunks:
    # P (P - 1) / 2 a prompt, N P + N (N - 1) / 2 an answer); padded is
    # the bucket's shape
    assert (4 * 0 + 10) + (4 * 4 + 10) + (4 * 0 + 10) == 9 * 8 // 2 + 5 * 4 // 2
    assert 9 + (10 + 5) + (11 + 6) == (3 * 9 + 3) + (2 * 5 + 1)
    assert _family(registry, "serve_prefill_positions_total") == {
        '{kind="live"}': (4 * 0 + 10) + (4 * 4 + 10) + (4 * 0 + 10),
        '{kind="padded"}': 4 * 1 * 4 + 4 * 2 * 4 + 4 * 1 * 4,
    }
    assert _family(registry, "serve_decode_positions_total") == {
        '{kind="live"}': 9 + (10 + 5) + (11 + 6),
        # the xla route gathers the whole bucket: read is padded
        '{kind="read"}': 1 * 4 * 4 + 2 * 4 * 4 + 2 * 4 * 4,
        '{kind="padded"}': 1 * 4 * 4 + 2 * 4 * 4 + 2 * 4 * 4,
    }
    assert _family(registry, "serve_prefill_calls_total") == {
        '{chunk="4",width_blocks="1"}': 2,
        '{chunk="4",width_blocks="2"}': 1,
    }
    assert _family(registry, "serve_decode_calls_total") == {
        '{batch="1",width_blocks="4"}': 1,
        '{batch="2",width_blocks="4"}': 2,
    }


def test_publish_tick_counts_what_the_decode_program_read(params,
                                                          n_devices):
    """A tick's ``decode_call`` is ``(B, W, live, read)``: `_publish_tick`
    grows ``serve_decode_positions_total{kind="read"}`` by the fourth, as
    the paged kernel's route hands it (live rounded up to whole pages),
    between ``live`` and the bucket's ``padded``."""
    registry = MetricsRegistry()
    engine = ServeEngine(params, CFG, EngineConfig(
        max_batch=4, num_blocks=32, block_size=4, max_seq_len=64,
    ))
    scheduler = ServeScheduler(
        engine, SchedulerConfig(max_queue=8), registry=registry,
    )
    for call in ((2, 4, 9, 12), (2, 4, 11, 16), None):
        scheduler._publish_tick({}, {"decode_call": call,
                                     "prefill_calls": []})
    assert _family(registry, "serve_decode_positions_total") == {
        '{kind="live"}': 9 + 11,
        '{kind="read"}': 12 + 16,
        '{kind="padded"}': 2 * (2 * 4 * 4),
    }


def test_profile_nests_fetch_in_step_in_tick(params, tmp_path, n_devices):
    """The spans land in the profiler's own trace, nested by containment
    on the loop thread, the tick's number in the event's stats."""
    import glob

    from jax.profiler import ProfileData

    registry = MetricsRegistry()
    engine = ServeEngine(params, CFG, EngineConfig(
        max_batch=4, num_blocks=32, block_size=4, max_seq_len=64,
    ))
    scheduler = ServeScheduler(
        engine, SchedulerConfig(max_queue=8), registry=registry,
    ).start()

    def three_ticks(upto):
        req = scheduler.submit(ServeRequest(
            prompt=_prompt(710, 2), max_new_tokens=2))
        assert _wait_done(req)["status"] == "done"
        # the answer's last token leaves inside the step; the beat is
        # the last of the tick's books
        deadline = time.monotonic() + 30
        while registry.last_step() != upto:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        time.sleep(0.05)

    three_ticks(3)  # the same shapes once before: nothing compiles below
    jax.profiler.start_trace(str(tmp_path))
    try:
        three_ticks(6)
    finally:
        jax.profiler.stop_trace()
        scheduler.close(finalize=False)
    (path,) = glob.glob(str(
        tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    lines = [
        [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
          dict(ev.stats)) for ev in line.events
         if ev.name.startswith("serve.")]
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:") for line in plane.lines
    ]
    (events,) = [ev for ev in lines if ev]  # one thread carries them all

    def inside(child, parents):
        return [p for p in parents
                if p[1] <= child[1] and child[2] <= p[2]]

    by_name = {}
    for ev in events:
        by_name.setdefault(ev[0], []).append(ev)
    assert len(by_name["serve.tick"]) == len(by_name["serve.step"]) == 3
    assert [t[3]["tick"] for t in by_name["serve.tick"]] == [3, 4, 5]
    for fetch in by_name["serve.fetch"]:
        (step,) = inside(fetch, by_name["serve.step"])
        (tick,) = inside(step, by_name["serve.tick"])
    assert len(by_name["serve.fetch"]) == 3
    for name in ("serve.admit", "serve.books"):
        assert all(inside(ev, by_name["serve.tick"])
                   for ev in by_name[name]), name
    for name in ("serve.decode_host", "serve.emit", "serve.release"):
        assert all(inside(ev, by_name["serve.step"])
                   for ev in by_name[name]), name
    host = by_name["serve.prefill_host"] + by_name["serve.decode_host"]
    for name in ("serve.select", "serve.stage", "serve.dispatch"):
        assert by_name[name] and all(
            inside(ev, host) for ev in by_name[name]), name


def test_null_registry_loop_runs_and_publishes_nothing(params, n_devices):
    from distributed_neural_network_tpu.utils.obs import NULL_REGISTRY

    engine = ServeEngine(params, CFG, EngineConfig(
        max_batch=4, num_blocks=32, block_size=4, max_seq_len=64,
        prefill_chunk=4,
    ))
    scheduler = ServeScheduler(engine, SchedulerConfig(max_queue=8)).start()
    assert scheduler.registry is NULL_REGISTRY
    prompt = _prompt(720, 7)
    req = scheduler.submit(ServeRequest(prompt=prompt, max_new_tokens=4))
    done = _wait_done(req)
    scheduler.close(finalize=False)
    assert done["tokens"] == _oracle(params, prompt, 4)
    assert NULL_REGISTRY.render() == ""
    assert NULL_REGISTRY.get("serve_loop_seconds_total") is None
    assert all(c.value == 0.0 for c in scheduler._m_loop_s.values())


def test_committed_serve_baseline_is_valid():
    """The checked-in serving baseline (the CI serve-smoke gate) must
    parse, carry the serve taxonomy + tolerances, and pass a
    self-check."""
    from distributed_neural_network_tpu.utils.goodput import (
        SERVE_BADPUT_CAUSES,
        check_record,
        read_record,
    )

    path = os.path.join(REPO, "tools", "goodput_serve_baseline.json")
    base = read_record(path)
    assert base["taxonomy"] == "serve"
    assert base.get("check_tolerances"), "baseline must pin tolerances"
    assert check_record(base, base) == []
    for cause in base["badput_s"]:
        assert cause in SERVE_BADPUT_CAUSES


# ------------------------------------------------------------- HTTP layer


def test_http_sse_stream_matches_oracle(server, params, n_devices):
    prompt = _prompt(700, 6)
    conn, resp = _post(server, {"prompt": prompt, "max_new_tokens": 7})
    assert resp.status == 200
    assert resp.getheader("Content-Type") == "text/event-stream"
    toks, done = _read_sse(resp)
    conn.close()
    assert toks == _oracle(params, prompt, 7)
    assert done["done"] is True and done["n_tokens"] == 7
    assert done["tokens"] == toks


def test_http_non_stream_and_status(server, params, n_devices):
    prompt = _prompt(701, 4)
    conn, resp = _post(server, {
        "prompt": prompt, "max_new_tokens": 5, "stream": False,
    })
    doc = json.loads(resp.read())
    conn.close()
    assert resp.status == 200
    assert doc["tokens"] == _oracle(params, prompt, 5)
    c = http.client.HTTPConnection("127.0.0.1", server.port, timeout=10)
    c.request("GET", "/v1/status")
    st = json.loads(c.getresponse().read())
    c.close()
    assert st["kv_blocks_total"] == 63
    assert st["decode_tokens"] >= 5


def test_http_400s(server, n_devices):
    for body, reason in [
        ({"prompt": [2], "max_new_tokens": 100}, "too_long"),
        ({"prompt": [2], "max_new_tokens": 0}, "bad_max_new_tokens"),
        ({"prompt": [], "max_new_tokens": 2}, "empty_prompt"),
        ({"prompt": [9999], "max_new_tokens": 2}, "bad_token"),
        ({"max_new_tokens": 2}, "bad_prompt"),
        ({"text": "hi", "max_new_tokens": 2}, "no_text_tokens"),
    ]:
        conn, resp = _post(server, body)
        doc = json.loads(resp.read())
        conn.close()
        assert resp.status == 400, (body, doc)
        assert doc["reason"] == reason
    # malformed JSON entirely
    c = http.client.HTTPConnection("127.0.0.1", server.port, timeout=10)
    c.request("POST", "/v1/generate", b"{not json",
              {"Content-Type": "application/json"})
    resp = c.getresponse()
    assert resp.status == 400
    assert json.loads(resp.read())["reason"] == "bad_json"
    c.close()


def test_http_429_carries_retry_after(params, n_devices):
    registry = MetricsRegistry()
    engine = ServeEngine(params, CFG, EngineConfig(
        max_batch=1, num_blocks=32, block_size=4, max_seq_len=64,
    ))
    scheduler = ServeScheduler(
        engine, SchedulerConfig(max_queue=1), registry=registry,
    ).start()
    srv = ServeServer(scheduler, registry, port=0)
    try:
        import threading

        results = []

        def one(i):
            c = http.client.HTTPConnection(
                "127.0.0.1", srv.port, timeout=60
            )
            c.request("POST", "/v1/generate", json.dumps({
                "prompt": _prompt(800 + i, 4), "max_new_tokens": 30,
            }), {"Content-Type": "application/json"})
            r = c.getresponse()
            results.append(
                (r.status, r.getheader("Retry-After"))
            )
            r.read()
            c.close()

        ts = [threading.Thread(target=one, args=(i,)) for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        saw_429 = [x for x in results if x[0] == 429]
        assert saw_429, results
        assert all(ra == "1" for _, ra in saw_429)
    finally:
        scheduler.close(finalize=False)
        srv.close()


def test_client_disconnect_cancels_and_frees_blocks(params, n_devices):
    registry = MetricsRegistry()
    engine = ServeEngine(params, CFG, EngineConfig(
        max_batch=2, num_blocks=32, block_size=2, max_seq_len=64,
    ))
    scheduler = ServeScheduler(
        engine, SchedulerConfig(max_queue=8), registry=registry,
    ).start()
    srv = ServeServer(scheduler, registry, port=0)
    try:
        conn, resp = _post(srv, {
            "prompt": _prompt(900, 4), "max_new_tokens": 50,
        })
        # read two token frames, then vanish
        got = 0
        buf = b""
        while got < 2:
            buf += resp.read(32)
            got = buf.count(b"\n\n")
        # hard client disconnect mid-stream (the response owns the
        # socket once Connection: close is in play)
        resp.close()
        conn.close()
        deadline = time.monotonic() + 60
        while engine.kv.blocks_in_use > 0:
            assert time.monotonic() < deadline, "blocks never freed"
            time.sleep(0.02)
        assert not engine.has_work()
        text = registry.render()
        assert 'serve_requests_total{status="cancelled"} 1' in text
    finally:
        scheduler.close(finalize=False)
        srv.close()


def test_text_prompt_byte_tokenization(n_devices):
    cfg = tfm.TransformerConfig(
        vocab_size=256, d_model=32, n_heads=4, n_layers=2, d_ff=64
    )
    params = tfm.init_params(jax.random.key(0), cfg)
    registry = MetricsRegistry()
    engine = ServeEngine(params, cfg, EngineConfig(
        max_batch=2, num_blocks=16, block_size=4, max_seq_len=64,
    ))
    scheduler = ServeScheduler(
        engine, SchedulerConfig(max_queue=4), registry=registry,
    ).start()
    srv = ServeServer(scheduler, registry, port=0)
    try:
        conn, resp = _post(srv, {
            "text": "hello", "max_new_tokens": 4, "stream": False,
        })
        doc = json.loads(resp.read())
        conn.close()
        assert resp.status == 200
        assert len(doc["tokens"]) == 4
        assert isinstance(doc["text"], str)
    finally:
        scheduler.close(finalize=False)
        srv.close()


def test_metrics_series_and_live_top_serving_view(server, n_devices):
    """After traffic, /metrics carries the serving series and the
    live_top dashboard renders the serving block from them."""
    conn, resp = _post(server, {
        "prompt": _prompt(1000, 4), "max_new_tokens": 4, "stream": False,
    })
    resp.read()
    conn.close()
    c = http.client.HTTPConnection("127.0.0.1", server.port, timeout=10)
    c.request("GET", "/metrics")
    text = c.getresponse().read().decode()
    c.close()
    for series in (
        "serve_requests_total", "serve_tokens_total",
        "serve_ttft_seconds_bucket", "serve_intertoken_seconds_bucket",
        "serve_kv_blocks_in_use", "serve_kv_blocks_total",
        "serve_queue_depth", "serve_active_sequences",
        "serve_engine_steps_total",
    ):
        assert series in text, series
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import live_top

    snap = {
        "metrics": live_top.parse_prometheus(text),
        "health": {"alive": True, "ready": True},
        "qps_history": [1.0, 2.0],
        "ttft_history": [0.05, 0.04],
        "source": "test",
    }
    frame = live_top.render(snap, color=False)
    assert "serving" in frame
    assert "req/s" in frame
    assert "kv " in frame and "blocks" in frame
    assert "ttft" in frame
    # color banding flips with utilization
    snap["metrics"]["serve_kv_blocks_in_use"] = {(): 60.0}
    snap["metrics"]["serve_kv_blocks_total"] = {(): 63.0}
    frame_hot = live_top.render(snap, color=True)
    assert "\x1b[33m" in frame_hot or "\x1b[31m" in frame_hot


def test_loadgen_library_burst_and_percentiles(server, n_devices):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import loadgen

    summary = loadgen.run_load(
        server.url, rate=20.0, n_requests=6, duration=None,
        prompt_lens=[3, 5], max_new=4, vocab=64, seed=1,
        api_keys=["a", "b"], temperature=0.0, burst=0,
        cancel_one=False, timeout=120.0, poisson=False,
    )
    assert summary["by_status"].get("completed") == 6
    assert summary["ttft_p50_s"] is not None
    assert summary["ttft_p99_s"] >= summary["ttft_p50_s"]
    assert summary["tokens_streamed"] == 24
    assert loadgen.percentile([], 0.5) is None
    assert loadgen.percentile([1.0, 2.0, 3.0, 4.0], 0.5) == 2.0
