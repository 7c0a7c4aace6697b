"""Continuous-batching engine (serve/engine.py).

Bars:
- sequences JOIN at arbitrary step boundaries and RETIRE without
  draining anyone - every sequence's tokens equal its single-sequence
  `generate()` oracle regardless of what shared the batch;
- chunked prefill (prefill_chunk > 1) produces the same greedy tokens
  as the exact token-at-a-time path;
- KV exhaustion preempts rather than crashes, the replay is exact, and
  streamed tokens are never duplicated;
- sampling is deterministic per (seed, position) - preemption-safe -
  under the very keys the host would derive, though a program on the
  device derives them, and the admission-time validation rejects what
  could never run;
- a tick that decodes reads the device once (the fetch) and runs its
  bucket programs, the key program and nothing else;
- every `step()` partitions its own wall time into the documented
  phases and reports the buckets it dispatched with their live positions.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_neural_network_tpu.models import transformer as tfm
from distributed_neural_network_tpu.serve import engine as engine_mod
from distributed_neural_network_tpu.serve.engine import (
    HOST_PARTS,
    STEP_PHASES,
    EngineConfig,
    Sequence,
    ServeEngine,
)

CFG = tfm.TransformerConfig(
    vocab_size=32, d_model=32, n_heads=4, n_layers=2, d_ff=64
)


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(jax.random.key(0), CFG)


def _prompt(key, n):
    return list(
        np.asarray(jax.random.randint(jax.random.key(key), (n,), 2, 32))
    )


def _oracle(params, prompt, n_new):
    return [int(x) for x in np.asarray(tfm.generate(
        params, jnp.asarray([prompt], jnp.int32), CFG,
        max_new_tokens=n_new,
    ))[0, len(prompt):]]


def _drain(eng, max_ticks=1000):
    t = 0
    while eng.has_work() and t < max_ticks:
        eng.step()
        t += 1
    assert not eng.has_work()


def test_staggered_joins_and_retires_match_oracle(params, n_devices):
    """Token-level continuous batching: a long request is mid-decode
    when two shorter ones join; the short ones retire first; nobody's
    tokens change. (Join at any step boundary, retire without
    draining.)"""
    eng = ServeEngine(params, CFG, EngineConfig(
        max_batch=4, num_blocks=32, block_size=4, max_seq_len=64,
    ))
    long = Sequence(0, _prompt(10, 4), 20)
    eng.add(long)
    for _ in range(6):
        eng.step()
    short_a = Sequence(1, _prompt(11, 7), 4)
    short_b = Sequence(2, _prompt(12, 3), 4)
    eng.add(short_a)
    eng.add(short_b)
    # the short ones retire while the long one keeps decoding
    while not (short_a.finished and short_b.finished):
        eng.step()
    assert not long.finished
    _drain(eng)
    for s in (long, short_a, short_b):
        assert s.out == _oracle(params, s.prompt, s.max_new_tokens), (
            f"seq {s.seq_id}"
        )
    assert eng.kv.blocks_in_use == 0


def test_chunked_prefill_matches_token_at_a_time(params, n_devices):
    prompts = [_prompt(20, 13), _prompt(21, 9), _prompt(22, 1)]
    for chunk in (4, 8):
        eng = ServeEngine(params, CFG, EngineConfig(
            max_batch=4, num_blocks=32, block_size=4, max_seq_len=64,
            prefill_chunk=chunk,
        ))
        seqs = [Sequence(i, p, 6) for i, p in enumerate(prompts)]
        for s in seqs:
            eng.add(s)
        _drain(eng)
        for s in seqs:
            assert s.out == _oracle(params, s.prompt, 6), (
                f"chunk {chunk}, seq {s.seq_id}"
            )


def test_preemption_replays_exactly_and_never_restreams(params,
                                                        n_devices):
    """5 usable blocks x 2 slots for three 10-token requests: the pool
    cannot hold everyone, so sequences get preempted (blocks freed,
    position reset) and re-admitted; final tokens and the STREAMED
    sequence must both equal the uncontended oracle."""
    eng = ServeEngine(params, CFG, EngineConfig(
        max_batch=4, num_blocks=6, block_size=2, max_seq_len=16,
    ))
    prompts = [_prompt(30 + i, 4) for i in range(3)]
    streamed = {i: [] for i in range(3)}
    seqs = []
    for i, p in enumerate(prompts):
        s = Sequence(i, p, 6,
                     on_token=lambda sq, t, d: streamed[sq.seq_id].append(t))
        seqs.append(s)
        eng.add(s)
    ticks = 0
    while (eng.has_work() or eng.preempted) and ticks < 1000:
        ticks += 1
        eng.step()
        if eng.preempted and eng.kv.can_fit(4):
            eng.add(eng.preempted.popleft())
    assert all(s.finished for s in seqs)
    assert sum(s.preemptions for s in seqs) > 0, "pool was never tight"
    assert eng.stall_events > 0
    for i, s in enumerate(seqs):
        want = _oracle(params, s.prompt, 6)
        assert s.out == want
        assert streamed[i] == want  # no duplicates, no gaps
    assert eng.kv.blocks_in_use == 0


def test_sampling_deterministic_per_seed(params, n_devices):
    def run(seed):
        eng = ServeEngine(params, CFG, EngineConfig(
            max_batch=2, num_blocks=16, block_size=4, max_seq_len=64,
        ))
        s = Sequence(0, _prompt(40, 4), 12, temperature=1.0, seed=seed)
        eng.add(s)
        _drain(eng)
        return list(s.out)

    a1, a2, b = run(7), run(7), run(8)
    assert a1 == a2  # per-(seed, position) keys: replayable
    assert a1 != b   # a different seed actually samples differently
    assert all(0 <= t < 32 for t in a1)


def _host_key(seed, pos):
    # the definition the engine's host code had until PR 28, written out
    return np.asarray(
        jax.random.fold_in(jax.random.PRNGKey(seed), pos), np.uint32
    )


@pytest.mark.parametrize("pos", [0, 1, 127, 2047])
@pytest.mark.parametrize(
    "seed", [0, 1, 2**31 - 1, 2**31, 2**32 - 1, 2**32 + 5, -1]
)
def test_program_derives_the_hosts_key(n_devices, seed, pos):
    """What `step` hands the key program (the seed's low 32 bits) and
    what the program makes of it, against the per-sequence derivation on
    the host, bit for bit; the other rows of the batch do not matter."""
    seeds = np.array([9, seed & 0xFFFFFFFF, 0], np.uint32)
    poss = np.array([3, pos, 0], np.int32)
    got = np.asarray(engine_mod._row_keys(seeds, poss))
    assert got.dtype == np.uint32 and got.shape == (3, 2)
    np.testing.assert_array_equal(got[1], _host_key(seed, pos))


def test_sampled_tokens_are_categorical_under_the_hosts_key(params,
                                                            n_devices):
    """Engine level: the keys the decode program is handed are the
    host-derived keys of (each row's seed, its position), and every
    token a temperature-1 row gets is `jax.random.categorical` under
    that key over the logits the program returned for the row; a greedy
    row beside it is the argmax."""
    eng = ServeEngine(params, CFG, EngineConfig(
        max_batch=4, num_blocks=32, block_size=4, max_seq_len=64,
    ))
    seqs = [
        Sequence(0, _prompt(40, 6), 10, temperature=1.0, seed=2**32 + 5),
        Sequence(1, _prompt(41, 3), 10, temperature=1.0, seed=-1),
        Sequence(2, _prompt(42, 5), 10),
    ]
    calls = []
    run = eng._run_writer

    def recording(fn, *tail):
        out = run(fn, *tail)
        calls.append([np.asarray(a) for a in (*tail, *out)])
        return out

    eng._run_writer = recording
    for s in seqs:
        eng.add(s)
    want = {s.seq_id: [] for s in seqs}
    tick = 0
    while eng.has_work():
        # token at a time and blocks for all: every sequence decodes until
        # its count is full. A call lands the tick dispatched a call
        # before, so the programs' records are taken oldest first
        rows = [s for s in seqs if tick < s.prompt_len - 1 + 10]
        st = eng.step()
        tick += 1
        assert st["batch"] == len(rows) and len(st["per_seq"]) == len(rows)
        fed, pos, _, temps, keys, nxt, logits = calls.pop(0)
        for i, s in enumerate(rows):
            # the token a row consumes past its prompt is the one it was
            # given a tick before, moved there on the device
            if pos[i] >= s.prompt_len:
                assert fed[i] == want[s.seq_id][pos[i] - s.prompt_len]
            key = _host_key(s.seed, int(pos[i]))
            np.testing.assert_array_equal(keys[i], key)
            assert temps[i] == s.temperature
            if s.temperature > 0:
                tok = int(jax.random.categorical(key, logits[i]))
            else:
                tok = int(np.argmax(logits[i]))
            assert int(nxt[i]) == tok, (s.seq_id, int(pos[i]))
            if pos[i] >= s.prompt_len - 1:
                want[s.seq_id].append(tok)
    assert not calls
    for s in seqs:
        assert s.out == want[s.seq_id] and len(s.out) == 10


class _Noted:
    """A module as the engine's code sees it (`np`, `jnp`, `jax`): every
    function of it that the engine CALLS goes to `log` as (dotted name,
    arguments). Types (dtypes) pass through untouched."""

    def __init__(self, mod, name, log):
        self._mod, self._name, self._log = mod, name, log

    def __getattr__(self, attr):
        val, name = getattr(self._mod, attr), f"{self._name}.{attr}"
        if isinstance(val, types.ModuleType):
            return _Noted(val, name, self._log)
        if not callable(val) or isinstance(val, type):
            return val

        def noted(*args, **kw):
            self._log.append((name, args))
            return val(*args, **kw)

        return noted


@pytest.mark.parametrize("temperature", [0.0, 1.0])
@pytest.mark.parametrize("chunk", [1, 8])
def test_decoding_tick_reads_the_device_once(params, n_devices,
                                             monkeypatch, chunk,
                                             temperature):
    """Between a tick's prefill dispatch and its decode dispatch the
    host must not wait on the device: over the ticks that carry a decode
    batch, the engine turns a device array into a host array exactly
    once (the fetch), and the only programs it runs are the tick's
    prefill bucket programs, the key program and the decode bucket
    program, in that order (its other `jax` calls are the host-to-device
    transfers of the operands)."""
    eng = ServeEngine(params, CFG, EngineConfig(
        max_batch=4, num_blocks=32, block_size=4, max_seq_len=64,
        prefill_chunk=chunk,
    ))
    eng.warmup()  # every bucket traced before the modules are stood in for
    log, ran = [], []

    def counted(family, fn):
        def run(*a):
            ran.append(family)
            log.append(("ran", ()))
            return fn(*a)
        return run

    for name in ("np", "jnp", "jax"):
        monkeypatch.setattr(
            engine_mod, name, _Noted(getattr(engine_mod, name), name, log)
        )
    for family, cache in (("decode", eng._step_fns),
                          ("prefill", eng._prefill_fns)):
        for key, fn in cache.items():
            cache[key] = counted(family, fn)
    monkeypatch.setattr(
        engine_mod, "_row_keys", counted("keys", engine_mod._row_keys)
    )
    for small in ("feed", "widen"):
        name = {"feed": "_feed_tokens", "widen": "_widen"}[small]
        monkeypatch.setattr(
            engine_mod, name, counted(small, getattr(engine_mod, name)))
    for i, n in enumerate((13, 5, 9)):
        eng.add(Sequence(i, _prompt(60 + i, n), 6,
                         temperature=temperature, seed=100 + i))
    eng.step()  # (with nothing in flight a call dispatches two ticks)
    decoding = fed = 0
    while eng.has_work():
        del log[:], ran[:]
        st = eng.step()
        # the call dispatched the next tick's programs (none at the end)
        # and then landed the tick that `st` describes
        ahead = eng._inflight
        if ahead is None:
            assert ran == []
        else:
            # the tokens the tick in flight owes go in on the device: one
            # small program, two where that tick's bucket is a smaller one
            small = [r for r in ran if r in ("feed", "widen")]
            fed += bool(small)
            assert small in ([], ["feed"], ["widen", "feed"])
            assert [r for r in ran if r not in small] == (
                ["prefill"] * len(ahead.stats["prefill_calls"])
                + ["keys", "decode"] * bool(ahead.rows)
            )
        if st["decode_call"] is None:
            continue
        decoding += 1
        reads = [i for i, (n, a) in enumerate(log)
                 if n == "np.asarray" and isinstance(a[0], jax.Array)]
        assert len(reads) == 1, log
        # ... and the one read comes after every dispatch of the call
        assert reads[0] > max(
            [i for i, (n, _) in enumerate(log) if n == "ran"], default=-1)
        others = {n for n, _ in log if not n.startswith("np.")} - {"ran"}
        assert others <= {"jnp.asarray"}, others
    assert decoding >= 6 and fed >= 5


def test_warmup_leaves_state_clean(params, n_devices):
    """Warmup's dummy calls write only the scratch block; a decode
    after warmup must match the cold-engine tokens."""
    eng = ServeEngine(params, CFG, EngineConfig(
        max_batch=4, num_blocks=8, block_size=4, max_seq_len=32,
    ))
    n = eng.warmup()
    assert n >= 4
    s = Sequence(0, _prompt(50, 5), 8)
    eng.add(s)
    _drain(eng)
    assert s.out == _oracle(params, s.prompt, 8)


def test_eos_retires_early(params, n_devices):
    p = _prompt(60, 5)
    want = _oracle(params, p, 16)
    # the eos id must FIRST occur at the cut position, or the stream
    # stops sooner than the test expects
    k = next(i for i in range(1, 16) if want[i] not in want[:i])
    eng = ServeEngine(params, CFG, EngineConfig(
        max_batch=2, num_blocks=16, block_size=4, max_seq_len=64,
        eos_token=want[k],
    ))
    s = Sequence(0, p, 16)
    eng.add(s)
    _drain(eng)
    assert s.out == want[: k + 1]
    assert s.finished


def test_admission_validation(params, n_devices):
    eng = ServeEngine(params, CFG, EngineConfig(
        max_batch=1, num_blocks=8, block_size=4, max_seq_len=16,
    ))
    with pytest.raises(ValueError, match="max_seq_len"):
        eng.add(Sequence(0, _prompt(70, 10), 10))
    with pytest.raises(ValueError, match="empty"):
        eng.add(Sequence(1, [], 4))
    eng.add(Sequence(2, _prompt(71, 4), 4))
    with pytest.raises(ValueError, match="engine full"):
        eng.add(Sequence(3, _prompt(72, 4), 4))
    moe_cfg = tfm.TransformerConfig(
        vocab_size=32, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        n_experts=2,
    )
    with pytest.raises(ValueError, match="dense"):
        ServeEngine(tfm.init_params(jax.random.key(0), moe_cfg),
                    moe_cfg, EngineConfig())


def test_cancel_frees_blocks_mid_flight(params, n_devices):
    eng = ServeEngine(params, CFG, EngineConfig(
        max_batch=2, num_blocks=16, block_size=2, max_seq_len=32,
    ))
    s = Sequence(0, _prompt(80, 6), 20)
    eng.add(s)
    for _ in range(4):
        eng.step()
    assert eng.kv.blocks_in_use > 0
    assert eng.cancel(0) is True
    # its row is in the tick in flight, whose program still writes its
    # blocks: they go when that tick has landed
    assert eng.kv.blocks_in_use > 0 and eng.has_work()
    eng.step()
    assert eng.kv.blocks_in_use == 0
    assert not eng.has_work()
    assert eng.cancel(0) is False  # idempotent


# ------------------------------------------- phases, buckets and positions


def _timed_step(eng):
    t0 = eng.clock()
    st = eng.step()
    return st, eng.clock() - t0


def _check_phases(st, wall):
    ph, host = st["phase_s"], st["host_s"]
    assert tuple(ph) == STEP_PHASES == (
        "prefill_host", "decode_host", "fetch", "emit", "spec", "release")
    assert tuple(host) == HOST_PARTS == ("select", "stage", "dispatch")
    assert all(v >= 0.0 for v in (*ph.values(), *host.values())), (ph, host)
    # entry to return, every instant in exactly one phase
    assert abs(sum(ph.values()) - wall) <= max(0.05 * wall, 0.002), (ph, wall)
    # the parts are nested in the two host phases
    assert sum(host.values()) <= ph["prefill_host"] + ph["decode_host"]


@pytest.mark.parametrize("chunk,spec", [(1, 0), (4, 0), (4, 2)])
def test_step_phase_seconds_partition_the_call(params, n_devices, chunk,
                                               spec):
    eng = ServeEngine(params, CFG, EngineConfig(
        max_batch=4, num_blocks=32, block_size=4, max_seq_len=64,
        prefill_chunk=chunk, spec_decode=spec,
    ))
    for i, n in enumerate((9, 5)):
        eng.add(Sequence(i, _prompt(40 + i, n), 4))
    seen = dict.fromkeys(STEP_PHASES, 0.0)
    parts = dict.fromkeys(HOST_PARTS, 0.0)
    steps = 0
    while eng.has_work():
        st, wall = _timed_step(eng)
        _check_phases(st, wall)
        for k, v in st["phase_s"].items():
            seen[k] += v
        for k, v in st["host_s"].items():
            parts[k] += v
        steps += 1
        assert steps < 100
    assert seen["prefill_host"] > 0 and seen["decode_host"] > 0
    assert seen["emit"] > 0 and seen["release"] > 0
    # greedy slots from their prompt's last token on go through
    # `_spec_step` whole, which fetches for itself
    assert (seen["spec"] > 0) == bool(spec)
    assert (seen["fetch"] > 0) == (not spec)
    # what the host does to build and hand over a tick is its three parts
    host = seen["prefill_host"] + seen["decode_host"]
    assert sum(parts.values()) == pytest.approx(host, rel=0.02), (parts, host)
    assert parts["select"] > 0 and parts["stage"] > 0
    assert parts["dispatch"] > 0


def test_step_reports_buckets_and_live_positions(params, n_devices):
    """One sequence, prompt 13, chunk 4, blocks of 4: three prefill
    chunks at positions 0, 4, 8 (the first two ticks dispatch no decode:
    the sequence is mid-prefill), then decodes from position 12."""
    eng = ServeEngine(params, CFG, EngineConfig(
        max_batch=4, num_blocks=32, block_size=4, max_seq_len=64,
        prefill_chunk=4,
    ))
    eng.add(Sequence(0, _prompt(50, 13), 2))
    got = []
    while eng.has_work():
        st = eng.step()
        got.append((st["prefill_calls"], st["decode_call"]))
    assert got == [
        ([(4, 1, 4 * 0 + 10)], None),
        ([(4, 2, 4 * 4 + 10)], None),
        ([(4, 4, 4 * 8 + 10)], (1, 4, 13, 16)),
        ([], (1, 4, 14, 16)),
    ]
    # live positions do not depend on the chunking: a token at position
    # p attends to p + 1, so the prompt's first 12 sum to 12 * 13 / 2
    assert sum(c[2] for calls, _ in got for c in calls) == 12 * 13 // 2


def _run_tight_pool(params, impl):
    """`test_preemption_replays_exactly_and_never_restreams`' pool under
    one decode route: (tokens a sequence, preemptions, decode calls)."""
    eng = ServeEngine(params, CFG, EngineConfig(
        max_batch=4, num_blocks=6, block_size=2, max_seq_len=16,
        decode_impl=impl,
    ))
    seqs = [Sequence(i, _prompt(30 + i, 2 + i), 6) for i in range(3)]
    for s in seqs:
        eng.add(s)
    calls = []
    ticks = 0
    while (eng.has_work() or eng.preempted) and ticks < 1000:
        ticks += 1
        calls.append(eng.step()["decode_call"])
        if eng.preempted and eng.kv.can_fit(4):
            eng.add(eng.preempted.popleft())
    assert all(s.finished for s in seqs)
    return ([s.out for s in seqs], sum(s.preemptions for s in seqs),
            [c for c in calls if c is not None])


def test_paged_kernel_route_emits_the_xla_routes_tokens(params, n_devices):
    """decode_impl="pallas" (the paged kernel, interpreted off the TPU)
    against the oracle route at float32: prompts of three lengths, so
    the batch sits at mixed positions, through preemptions and their
    replays. And what each tick's ``decode_call`` says the program read:
    the pages' positions under the kernel, the whole bucket under XLA."""
    bs = 2
    xla, pallas = (_run_tight_pool(params, impl) for impl in ("xla",
                                                             "pallas"))
    assert pallas[0] == xla[0]
    assert pallas[1] == xla[1] > 0, "pool was never tight"
    assert [c[:3] for c in pallas[2]] == [c[:3] for c in xla[2]]
    for B, W, live, read in xla[2]:
        assert read == B * W * bs
    for B, W, live, read in pallas[2]:
        assert live <= read <= B * W * bs
        assert read % bs == 0
    # the kernel leaves dead pages alone: somewhere the bucket is wider
    assert sum(c[3] for c in pallas[2]) < sum(c[3] for c in xla[2])


def test_pallas_route_refuses_what_the_kernel_does_not_read(params,
                                                            n_devices):
    eng = ServeEngine(params, CFG, EngineConfig(
        max_batch=2, num_blocks=8, block_size=4, max_seq_len=16,
        kv_dtype="int8", decode_impl="pallas",
    ))
    with pytest.raises(ValueError, match="does not read a int8 pool"):
        eng.decode_route()
    # under auto the same pool takes the oracle route, on any backend
    eng = ServeEngine(params, CFG, EngineConfig(
        max_batch=2, num_blocks=8, block_size=4, max_seq_len=16,
        kv_dtype="int8",
    ))
    assert eng.decode_route() == "xla"


def test_all_parked_tick_still_partitions(params, n_devices):
    """The early return (nothing could run, the youngest is preempted)
    carries the same keys: its time is prefill_host, decode_host and
    release."""
    eng = ServeEngine(params, CFG, EngineConfig(
        max_batch=4, num_blocks=6, block_size=2, max_seq_len=16,
    ))
    for i in range(3):
        eng.add(Sequence(i, _prompt(30 + i, 4), 6))
    early = 0
    for _ in range(200):
        if not (eng.has_work() or eng.preempted):
            break
        st, wall = _timed_step(eng)
        _check_phases(st, wall)
        if st["batch"] == 0:
            early += 1
            assert st["decode_call"] is None
            assert st["phase_s"]["fetch"] == st["phase_s"]["emit"] == 0.0
        if eng.preempted and eng.kv.can_fit(4):
            eng.add(eng.preempted.popleft())
    assert early > 0, "no tick was ever all parked"
