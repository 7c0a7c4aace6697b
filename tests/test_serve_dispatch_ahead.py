"""One tick is always in flight (serve/engine.py `step`, `_dispatch`,
`_land`, `flush`; serve/scheduler.py `_books`, `_drain_sweep`).

Bars:
- with tick n + 1 dispatched before tick n's tokens are fetched, the tokens
  are `generate()`'s for greedy requests and the host-derived draw's for
  sampled ones: mixed prompt lengths, chunked prefill, a batch that changes
  every few ticks;
- a sequence that ends by count is not in the tick dispatched while its
  last token is in flight;
- an end token found one tick late costs one decoded position, which is
  neither emitted nor streamed; `on_token` sees `done` once; the blocks go
  when that tick has landed and not before. A cancel takes the same route;
- nothing is preempted under a tick in flight, and the replay is exact;
- a speculative engine never dispatches ahead;
- a drain for migration lands the tick in flight first;
- `serve_dispatch_ahead_total` counts what the engine did, and
  `serve_engine_steps_total` still beats once a landed tick;
- a bucket program called once the device has run dry finds it idle
  (`serve_dispatch_found_total`), and every bucket program is counted
  idle or busy.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_neural_network_tpu.models import transformer as tfm
from distributed_neural_network_tpu.serve import engine as engine_mod
from distributed_neural_network_tpu.serve.engine import (
    EngineConfig,
    Sequence,
    ServeEngine,
    resume_sequence,
)
from distributed_neural_network_tpu.serve.scheduler import (
    SchedulerConfig,
    ServeRequest,
    ServeScheduler,
)
from distributed_neural_network_tpu.utils.obs import MetricsRegistry

CFG = tfm.TransformerConfig(
    vocab_size=32, d_model=32, n_heads=4, n_layers=2, d_ff=64
)


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(jax.random.key(0), CFG)


def _prompt(key, n):
    return [int(t) for t in np.asarray(
        jax.random.randint(jax.random.key(key), (n,), 2, 32))]


def _oracle(params, prompt, n_new, temperature=0.0, seed=0):
    """Greedy: offline `generate()`. Sampled: the whole sequence through
    the model a token at a time, each draw under the host-derived key of
    (seed, position) - the definition `_row_keys` is pinned to."""
    if temperature == 0.0:
        return [int(x) for x in np.asarray(tfm.generate(
            params, jnp.asarray([prompt], jnp.int32), CFG,
            max_new_tokens=n_new,
        ))[0, len(prompt):]]
    toks = list(prompt)
    for _ in range(n_new):
        logits = tfm.apply(params, jnp.asarray([toks], jnp.int32), CFG)[0, -1]
        key = jax.random.fold_in(
            jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF)), len(toks) - 1)
        toks.append(int(jax.random.categorical(key, logits / temperature)))
    return toks[len(prompt):]


def _engine(params, **kw):
    base = dict(max_batch=4, num_blocks=64, block_size=4, max_seq_len=64)
    return ServeEngine(params, CFG, EngineConfig(**dict(base, **kw)))


def _streaming(streamed, dones):
    def on_token(seq, tok, done):
        streamed.setdefault(seq.seq_id, []).append(tok)
        if done:
            dones[seq.seq_id] = dones.get(seq.seq_id, 0) + 1
    return on_token


# ------------------------------------------------------------ (a) exact


@pytest.mark.parametrize("chunk", [1, 4])
def test_tokens_with_a_tick_in_flight_are_the_oracles(params, n_devices,
                                                      chunk):
    """Six requests, greedy and sampled, prompts of 3-13 tokens, joining
    at ticks 0, 0, 2, 5, 9 and 9 behind four slots: the batch and its
    bucket change every few ticks, and every tick but the first is
    dispatched before the tick before it is fetched."""
    eng = _engine(params, prefill_chunk=chunk)
    streamed, dones = {}, {}
    plan = [  # (joins at tick, prompt length, new tokens, temperature)
        (0, 13, 9, 0.0), (0, 5, 4, 1.0), (2, 3, 7, 0.0),
        (5, 9, 3, 0.7), (9, 7, 6, 1.0), (9, 4, 8, 0.0),
    ]
    seqs = [Sequence(i, _prompt(200 + i, n), new, temperature=t,
                     seed=2**31 + 7 * i,
                     on_token=_streaming(streamed, dones))
            for i, (_, n, new, t) in enumerate(plan)]
    waiting = list(zip((p[0] for p in plan), seqs))
    tick, how = 0, {"ahead": 0, "drained": 0, None: 0}
    while waiting or eng.has_work():
        while waiting and waiting[0][0] <= tick and (
                len(eng.active) < eng.ecfg.max_batch):
            eng.add(waiting.pop(0)[1])
        how[eng.step()["dispatch"]] += 1
        tick += 1
        assert tick < 500
    for s, (_, _, new, t) in zip(seqs, plan):
        want = _oracle(params, s.prompt, new, t, s.seed)
        assert s.out == want, (s.seq_id, chunk)
        assert streamed[s.seq_id] == want and dones[s.seq_id] == 1
    assert eng.kv.blocks_in_use == 0 and eng._inflight is None
    assert how["drained"] == 1 and how[None] == 0 and how["ahead"] > 15, how


# ------------------------------------------------- (b) ending by count


def test_sequence_ending_by_count_is_left_out_of_the_next_dispatch(
        params, n_devices):
    eng = _engine(params)
    short = Sequence(0, _prompt(210, 4), 3)
    long = Sequence(1, _prompt(211, 4), 9)
    eng.add(short)
    eng.add(long)
    rows = {0: 0, 1: 0}
    seen_last_in_flight = False
    while eng.has_work():
        st = eng.step()
        for sid, d in st["per_seq"].items():
            rows[sid] += d["decode"] + d["prefill"]
        tick = eng._inflight
        if short.dispatched_all and not short.finished:
            # its last token is on the device: the tick dispatched beside
            # it does not hold the sequence, though it is still active
            seen_last_in_flight = True
            assert short in eng.active
        if short.dispatched_all and tick is not None and (
                short.seq_id in tick.touched):
            # ... the one that holds its last row is the last that does
            assert short.pos == short.prompt_len - 1 + 3
    assert seen_last_in_flight
    # every position once, none past the count
    assert rows == {0: 4 - 1 + 3, 1: 4 - 1 + 9}
    assert short.out == _oracle(params, short.prompt, 3)
    assert long.out == _oracle(params, long.prompt, 9)


# --------------------------------------- (c) the token decides, (d) cancel


def test_end_token_found_late_drops_one_position(params, n_devices):
    p = _prompt(60, 5)
    want = _oracle(params, p, 16)
    other = Sequence(1, _prompt(61, 3), 30)
    other_want = _oracle(params, other.prompt, 30)
    # an end token that this stream meets first at k, and the stream
    # beside it only later
    k = next(i for i in range(1, 16) if want[i] not in want[:i]
             and want[i] not in other_want[: i + 6])
    eng = _engine(params, eos_token=want[k])
    streamed, dones = {}, {}
    s = Sequence(0, p, 16, on_token=_streaming(streamed, dones))
    eng.add(s)
    eng.add(other)
    while not s.finished:
        eng.step()
    # the call that fetched the end token had dispatched the next tick
    # already, with a row for this sequence: a position past its end.
    # That program writes the sequence's blocks, so they are still its own
    late = eng._inflight
    assert late is not None and s.seq_id in late.touched
    assert s in eng.active and eng.kv.seq_block_ids(s.seq_id)
    assert s.out == want[: k + 1] and dones == {0: 1}
    decoded = eng.decode_tokens
    st = eng.step()
    # that tick has landed: the position is dropped (not counted, not
    # emitted, not streamed) and the blocks are free
    assert s.seq_id not in st["per_seq"] and st["finished"] == 1
    assert eng.decode_tokens - decoded == st["decode_tokens"] == 1
    assert s not in eng.active and not eng.kv.seq_block_ids(s.seq_id)
    assert s.out == streamed[0] == want[: k + 1] and dones == {0: 1}
    while eng.has_work():
        eng.step()
    cut = other_want.index(want[k]) + 1 if want[k] in other_want else 30
    assert other.out == other_want[:cut]
    assert streamed[0] == want[: k + 1] and dones[0] == 1
    assert eng.kv.blocks_in_use == 0


def test_cancel_under_a_row_in_flight_frees_when_it_has_landed(params,
                                                               n_devices):
    eng = _engine(params)
    streamed, dones = {}, {}
    a = Sequence(0, _prompt(220, 6), 20, on_token=_streaming(streamed, dones))
    b = Sequence(1, _prompt(221, 4), 12, on_token=_streaming(streamed, dones))
    eng.add(a)
    eng.add(b)
    while len(a.out) < 3:
        eng.step()
    assert a.seq_id in eng._inflight.touched
    held = eng.kv.seq_block_ids(a.seq_id)
    seen = list(streamed[0])
    assert eng.cancel(0) is True
    # ended, but the program in flight still writes its row
    assert a.finished and eng.kv.seq_block_ids(a.seq_id) == held
    eng.step()
    assert a not in eng.active and not eng.kv.seq_block_ids(a.seq_id)
    assert eng.cancel(0) is False
    while eng.has_work():
        eng.step()
    # nothing reached the client after the cancel, and no `done`
    assert streamed[0] == seen == a.out and 0 not in dones
    assert b.out == streamed[1] == _oracle(params, b.prompt, 12)
    assert eng.kv.blocks_in_use == 0


def test_cancel_of_a_sequence_not_in_flight_frees_at_once(params, n_devices):
    eng = _engine(params)
    a = Sequence(0, _prompt(222, 5), 8)
    eng.add(a)
    eng.step()
    late = Sequence(1, _prompt(223, 5), 8)
    eng.add(late)       # admitted after the tick in flight was built
    assert eng.cancel(1) is True
    assert late not in eng.active and not eng.kv.seq_block_ids(1)
    while eng.has_work():
        eng.step()
    assert a.out == _oracle(params, a.prompt, 8)


# ------------------------------------------------ (e) parked and preempted


@pytest.mark.parametrize("chunk,blocks", [(1, 6), (2, 8)])
def test_nothing_is_preempted_under_a_tick_in_flight(params, n_devices,
                                                     chunk, blocks):
    """`test_preemption_replays_exactly_and_never_restreams`' pool (two
    blocks more under chunked prefill, where that one's never lets a
    request end, before this change or after): every candidate parks, the
    tick in flight lands (the call dispatches nothing), and only the next
    call evicts; the replay is exact."""
    eng = ServeEngine(params, CFG, EngineConfig(
        max_batch=4, num_blocks=blocks, block_size=2, max_seq_len=16,
        prefill_chunk=chunk,
    ))
    evict = eng._preempt_youngest

    def checked(parked):
        assert eng._inflight is None
        # no row of any tick is unfetched: every position has its token
        assert not any(s.input_in_flight for s in eng.active)
        return evict(parked)

    eng._preempt_youngest = checked
    streamed, dones = {}, {}
    seqs = [Sequence(i, _prompt(30 + i, 4), 6,
                     on_token=_streaming(streamed, dones)) for i in range(3)]
    for s in seqs:
        eng.add(s)
    ticks = evictions = 0
    while (eng.has_work() or eng.preempted) and ticks < 1000:
        ticks += 1
        st = eng.step()
        if st["preempted"]:
            evictions += 1
            assert st["dispatch"] is None and st["batch"] == 0
        if eng.preempted and eng.kv.can_fit(4):
            eng.add(eng.preempted.popleft())
    assert evictions > 0 and evictions == sum(s.preemptions for s in seqs)
    for s in seqs:
        want = _oracle(params, s.prompt, 6)
        assert s.out == streamed[s.seq_id] == want and dones[s.seq_id] == 1
    assert eng.kv.blocks_in_use == 0


# ------------------------------------------------------- (f) speculation


def test_speculative_engine_never_dispatches_ahead(params, n_devices):
    eng = _engine(params, spec_decode=2)
    seqs = [Sequence(0, _prompt(230, 6), 9),
            Sequence(1, _prompt(231, 4), 7, temperature=1.0, seed=3)]
    for s in seqs:
        eng.add(s)
    while eng.has_work():
        st = eng.step()
        # the tick landed in the call that dispatched it
        assert st["dispatch"] == "drained" and eng._inflight is None
        assert not any(s.input_in_flight for s in seqs if not s.finished)
    assert seqs[0].out == _oracle(params, seqs[0].prompt, 9)
    assert seqs[1].out == _oracle(params, seqs[1].prompt, 7, 1.0, 3)


# ------------------------------------------- the programs and their feed


def test_feed_program_takes_the_devices_token_or_the_hosts(n_devices):
    # the tick in flight ran two rows, the largest bucket has four, and
    # the next tick's batch has three
    board = engine_mod._widen(jnp.asarray([11, 12], jnp.int32), 4)
    assert np.asarray(board).tolist() == [11, 12, 0, 0]
    src = np.array([1, -1, 0], np.int32)
    tok = np.array([5, 6, 7], np.int32)
    got = np.asarray(engine_mod._feed_tokens(board, src, tok))
    assert got.dtype == np.int32 and got.tolist() == [12, 6, 11]


def test_warmup_covers_what_a_tick_in_flight_runs(params, n_devices):
    """After `warmup()` a run with ticks in flight traces nothing new: one
    feed program a batch bucket and one widening program a bucket below
    the largest, beside the key programs."""
    eng = _engine(params, prefill_chunk=4)
    eng.warmup()

    def small():
        return (engine_mod._feed_tokens._cache_size(),
                engine_mod._widen._cache_size(),
                engine_mod._row_keys._cache_size(), eng.compiled_programs())

    before = small()
    for i, n in enumerate((13, 5, 9, 2)):
        eng.add(Sequence(i, _prompt(240 + i, n), 4 + i,
                         temperature=float(i % 2), seed=i))
    fed = 0
    while eng.has_work():
        fed += eng.step()["dispatch"] == "ahead"
    assert fed > 5
    assert before == small()


# ------------------------------------------- (h) the scheduler's counters


def _counter(registry, name):
    out = {}
    for line in registry.render().splitlines():
        if line.startswith(name):
            key, _, value = line.rpartition(" ")
            out[key[len(name):]] = float(value)
    return out


def _collect(req, timeout=60.0):
    toks, t_end = [], time.monotonic() + timeout
    while time.monotonic() < t_end:
        kind, val = req.events.get(timeout=timeout)
        if kind == "token":
            toks.append(val)
        else:
            return toks, kind, val
    raise AssertionError("request did not end")


def test_scheduler_counts_ahead_and_drained_ticks(params, n_devices):
    registry = MetricsRegistry()
    eng = _engine(params, prefill_chunk=4)
    sched = ServeScheduler(eng, SchedulerConfig(), registry=registry).start()
    try:
        reqs = [sched.submit(ServeRequest(
            prompt=_prompt(250 + i, n), max_new_tokens=new,
            temperature=t, seed=40 + i))
            for i, (n, new, t) in enumerate(
                [(11, 8, 0.0), (4, 6, 1.0), (7, 10, 0.0)])]
        got = [_collect(r) for r in reqs]
    finally:
        sched.close()
    for r, (toks, kind, _) in zip(reqs, got):
        assert kind == "done"
        assert toks == _oracle(params, r.prompt, r.max_new_tokens,
                               r.temperature, r.seed)
    how = _counter(registry, "serve_dispatch_ahead_total")
    steps = _counter(registry, "serve_engine_steps_total")[""]
    ahead, drained = how['{outcome="ahead"}'], how['{outcome="drained"}']
    # every landed tick dispatched programs here (nothing parks), the
    # first with nothing in flight
    assert ahead + drained == steps and drained >= 1 and ahead > 3 * drained
    # a beat of the step counter is a tick whose tokens reached the clients
    tokens = _counter(registry, "serve_tokens_total")
    assert tokens['{kind="decode"}'] == 8 + 6 + 10
    assert tokens['{kind="prefill"}'] == 11 + 4 + 7 - 3
    # the loop's phases still cover its ticks: the engine's five are there
    loop = _counter(registry, "serve_loop_seconds_total")
    assert loop['{phase="fetch"}'] > 0 and loop['{phase="decode_host"}'] > 0


def test_a_dispatch_finds_the_device_idle_once_it_has_run_dry(params,
                                                              n_devices):
    """Before each call the test waits for the device to finish all it was
    handed; the first bucket program the call dispatches then finds it
    idle. The found states are published with the tick that dispatched
    them, as the calls' buckets are, so the two families count the same
    programs."""
    registry = MetricsRegistry()
    eng = _engine(params, prefill_chunk=4)
    sched = ServeScheduler(eng, SchedulerConfig(), registry=registry)
    for i, n in enumerate((9, 5, 6)):
        eng.add(Sequence(i, _prompt(270 + i, n), 5))
    firsts = []
    while eng.has_work():
        jax.block_until_ready(eng._pools())
        stats = eng.step()
        sched._publish_tick(stats["phase_s"], stats)
        if stats["found"]:
            firsts.append(stats["found"][0])
    sched.close(finalize=False)
    # the first call dispatches two ticks, the second behind the first's
    # programs; every later call one, after the wait
    del firsts[1]
    assert firsts and set(firsts) <= {("prefill", "idle"), ("decode", "idle")}
    found = _counter(registry, "serve_dispatch_found_total")
    calls = [_counter(registry, name) for name in (
        "serve_prefill_calls_total", "serve_decode_calls_total")]
    assert sum(found.values()) == sum(sum(c.values()) for c in calls) > 0
    assert found['{device="idle",program="prefill"}'] >= 1
    assert found['{device="idle",program="decode"}'] >= 1


def test_drain_for_migration_lands_the_tick_in_flight_first(params,
                                                            n_devices):
    """No loop thread: the test drives the engine, leaves a tick in flight
    and drains. The descriptor holds that tick's token too, nothing stays
    on the device, and the resumed stream is the oracle's."""
    registry = MetricsRegistry()
    eng = _engine(params)
    sched = ServeScheduler(eng, SchedulerConfig(), registry=registry)
    try:
        req = sched.submit(ServeRequest(prompt=_prompt(260, 6),
                                        max_new_tokens=12))
        sched._admit_ready()
        seq = req._seq
        while len(seq.out) < 4:
            eng.step()
        assert eng._inflight is not None and seq.input_in_flight
        out = sched.drain(timeout=5.0)
    finally:
        sched.close()
    assert out["completed"] and eng._inflight is None
    assert eng.kv.blocks_in_use == 0 and not eng.has_work()
    (desc,) = out["migrated"]
    assert desc["emitted"] == req.tokens == seq.out and len(seq.out) == 5
    assert _counter(registry, "serve_engine_steps_total")[""] == 1
    peer = _engine(params)
    rest = resume_sequence(desc)
    peer.add(rest)
    while peer.has_work():
        peer.step()
    assert desc["emitted"] + rest.out == _oracle(params, req.prompt, 12)
