"""The `mimo_v2` module and its serving path against the family's plain
reference (`benchmark/families/mimo_v2/reference.py`: float32, attention as
one softmax over the sequence's own keys under a causal or banded mask,
never a cache or a ring, nothing of the program imported), at a small size
on the CPU, seeded random weights, float32.

Tolerances. The program and the reference compute the same function in
another order (a ring of the last `window` rows against the band of the
whole sequence, an online softmax over key blocks and pages against one
softmax, grouped heads, experts over sorted tiles against every expert under
a mask), so logits of deviation 0.2 agree to float32 reassociation: read
1.2e-7 (whole forward and through both pools, either route); the limit is
`TOL` = 2e-6. A planted departure (the sink dropped, the window one longer,
the value scale dropped, the two thetas swapped, rotary over the whole head,
the ring read without its mask by position) moves the logits by 7e-4 to
0.07, three hundred times `TOL` and more.
"""
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH, ROOT
from distributed_neural_network_tpu.models import mimo_v2 as mm
import distributed_neural_network_tpu.ops.decode_pallas as dp
from distributed_neural_network_tpu.ops.decode_pallas import (
    paged_read_positions,
    split_gqa_decode_attention,
    split_gqa_decode_ok,
    split_gqa_prefill_ok,
    split_gqa_prefill_pairs,
)
from distributed_neural_network_tpu.parallel.moe import moe_held_gated_serve
from distributed_neural_network_tpu.serve.engine import (
    EngineConfig,
    Sequence,
    ServeEngine,
    batch_buckets,
)
from distributed_neural_network_tpu.serve.scheduler import (
    SchedulerConfig,
    ServeScheduler,
)
from distributed_neural_network_tpu.utils.obs import MetricsRegistry

sys.path[:0] = [BENCH]
try:
    from lib import harness
    from lib.weights import seed_key

    FAMILY = harness.load_family("mimo_v2", "serve")
finally:
    del sys.path[:1]
# the reference's blocks are sized for 34k rows on the chip; they change no
# value, and a test's sequences are a few dozen tokens
FAMILY.reference.ROW_BLOCK, FAMILY.reference.QUERY_BLOCK = 64, 16

TOL = 2e-6
SEED = 5
MODEL = harness.load_json("families", "mimo_v2", "tiny.json")
CFG = FAMILY.program.config(MODEL, {}, jnp.float32)
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs", "mimo-v2.5.json")


@pytest.fixture(scope="module")
def params():
    return FAMILY.weights.make(SEED, MODEL)


def reference_logits(tokens, rows, fault=""):
    """The reference's full forward of one sequence at `rows`."""
    return FAMILY.reference.served_logits(
        SEED, MODEL, np.asarray(tokens)[None], np.asarray(rows)[None],
        fault=fault)[0]


def some_tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, MODEL["vocab_size"], size=n).astype(np.int32)


def whole_forward(params, tok):
    return np.asarray(jax.jit(lambda p, t: mm.apply(p, t, CFG))(params, tok))


# ------------------------------------------------------------- the module

def test_the_configuration_walks_the_published_pattern():
    assert CFG.layer_types == ("full", "window", "window", "full", "window")
    assert CFG.ff_types == ("dense", "moe", "moe", "moe", "moe")
    assert mm.layer_plan(CFG) == (
        ("full", 0, "dense", 0), ("window", 0, "moe", 0),
        ("window", 1, "moe", 1), ("full", 1, "moe", 2),
        ("window", 2, "moe", 3))
    # a full layer's row: 1 KV head of 24 + 16; a window layer's ring: 8
    # rows of 2 KV heads
    assert mm.cache_shapes(CFG) == {"kv": (2, 40), "state": (3, 8, 80)}
    assert (CFG.rope_dim, CFG.window, CFG.experts_held, CFG.n_routed) == (
        8, 8, (0, 4), 16)
    assert mm.POOLS == {"full": "rows", "window": "state"}


@pytest.mark.parametrize("change,word", [
    (dict(add_full_attention_sink_bias=True), "add_full_attention_sink_bias"),
    (dict(n_shared_experts=1), "n_shared_experts"),
    (dict(n_group=8), "n_group"),
    (dict(num_hidden_layers=4), "hybrid_layer_pattern"),
    (dict(moe_layer_freq=[0, 1, 1]), "moe_layer_freq"),
    (dict(add_swa_attention_sink_bias=False), "without its sink"),
    (dict(swa_head_dim=128), "heads differ"),
])
def test_from_published_refuses_what_is_not_built_by_name(change, word):
    with pytest.raises(ValueError, match=f"mimo_v2: .*{word}"):
        mm.from_published(dict(MODEL, **change))


def test_whole_forward_matches_the_reference(params):
    tok = some_tokens(40)
    got = whole_forward(params, tok)
    assert np.abs(got - reference_logits(tok, np.arange(40))).max() < TOL


@pytest.mark.parametrize("fault", FAMILY.reference.FAULTS[1:])
def test_a_departure_from_the_equations_is_seen(params, fault):
    """The reference with the window layers' sink dropped, their window one
    position longer, the value scale dropped, the full and window thetas
    swapped, or the rotation over the whole head lies a hundred `TOL` and
    more from the program: the comparison that passes above would not pass
    a program that did one of these."""
    tok = some_tokens(40)
    got = whole_forward(params, tok)
    ref = reference_logits(tok, np.arange(40), fault)
    assert np.abs(got - ref).max() > 100 * TOL


def test_prefill_attention_blocked_over_keys_is_the_unblocked(params):
    lp = mm.layer_params(params, "full", 0)
    x = jax.random.normal(jax.random.key(4), (64, CFG.d_model))
    pos = jnp.arange(64)
    q, rows = mm.full_in(x, lp, CFG, pos)
    one = mm.prefill_attention(q, pos, lambda j: rows, 64, CFG, key_block=64)
    blocked = mm.prefill_attention(
        q, pos, lambda j: jax.lax.dynamic_slice_in_dim(rows, j * 16, 16), 64,
        CFG, key_block=16)
    assert np.abs(np.asarray(one - blocked)).max() < 1e-6
    # one query a sequence over its causal prefix: the decode oracle
    live = pos[None, :] <= pos[:, None]
    dec = mm.decode_attention(
        q, jnp.broadcast_to(rows, (64,) + rows.shape), live, CFG)
    assert np.abs(np.asarray(one - dec)).max() < 1e-6


def test_a_row_is_the_keys_parts_apart_and_back():
    k = jax.random.normal(jax.random.key(1), (5, 2, CFG.qk_head))
    v = jax.random.normal(jax.random.key(2), (5, 2, CFG.v_head))
    row = mm.to_row(k, v, CFG)
    # [k_nope of both heads ; k_rope of both heads ; v of both heads]
    assert np.array_equal(row[:, :16], k[:, 0, 8:])
    assert np.array_equal(row[:, 32:40], k[:, 0, :8])
    assert np.array_equal(row[:, 48:64], v[:, 0])
    k2, v2 = mm.split_row(row, CFG)
    assert np.array_equal(k2, k) and np.array_equal(v2, v)


def test_ring_positions():
    """Row j holds the largest position <= last that is j mod window; at
    last < window - 1 the rows past it hold no position of the sequence."""
    got = np.asarray(mm.ring_positions(jnp.asarray([3, 8, 21]), CFG))
    assert got.tolist() == [[0, 1, 2, 3, -4, -3, -2, -1],
                            [8, 1, 2, 3, 4, 5, 6, 7],
                            [16, 17, 18, 19, 20, 21, 14, 15]]


def test_window_steps_over_the_ring_are_the_band(params):
    """A window layer's output position by position (`window_decode`, one
    slot, the ring wrapping twice) and in chunks of 5 and 13 valid tokens in
    buckets of 8 and 16, shorter and longer than the window
    (`window_prefill`), from a slot full of another sequence's rows, against
    the whole sequence under the band (`apply`'s operator)."""
    lp = mm.layer_params(params, "window", 1)
    x = jax.random.normal(jax.random.key(5), (24, CFG.d_model))
    pos = jnp.arange(24)
    q, k, v = mm.qkv(x, lp, CFG, "window", pos)
    band = (pos[None, :] <= pos[:, None]) & (pos[None, :] > pos[:, None] - 8)
    whole = mm.attn_out(x, mm.attend(q, k, v, band, CFG, lp["sink"]), lp, CFG)
    stale = jnp.full((1, 2, 8, CFG.row("window")), 3.0)
    pool, steps = stale, []
    for t in range(24):
        y, pool = mm.window_decode(x[t:t + 1], lp, 0, CFG, pool,
                                   jnp.asarray([1]), jnp.asarray([t]))
        steps.append(y[0])
    assert np.abs(np.asarray(jnp.stack(steps) - whole)).max() < 1e-5
    pool, out, at = stale, [], 0
    for n, bucket in ((5, 8), (13, 16), (6, 8)):
        chunk = jnp.pad(x[at:at + n], ((0, bucket - n), (0, 0)))
        y, pool = mm.window_prefill(chunk, lp, 0, CFG, pool, 1, at, n)
        out.append(y[:n])
        at += n
    assert np.abs(np.asarray(jnp.concatenate(out) - whole)).max() < 1e-5
    # the ring then holds positions 16..23, and the decode step goes on
    y, _ = mm.window_decode(x[23:24], lp, 0, CFG, pool, jnp.asarray([1]),
                            jnp.asarray([23]))
    assert np.abs(np.asarray(y[0] - whole[23])).max() < 1e-5


def test_attn_pairs_counts_what_the_masks_keep():
    pairs = mm.attn_pairs(CFG, 10, 5, 8, 8 * 64)
    assert pairs[("full", "live")] == 2 * (5 * 10 + 15)
    assert pairs[("full", "scored")] == 2 * 8 * 64
    assert pairs[("window", "live")] == 3 * 5 * 8     # each keeps 8
    assert pairs[("window", "scored")] == 3 * 8 * (8 + 8)
    assert mm.attn_pairs(CFG, 0, 3, 4, 64)[("window", "live")] == 3 * 6


# ---------------------------------------------------------- the expert layer

def test_the_held_shares_add_up_to_the_uncut_layer():
    """Each of 4 chips holds 4 of 16 experts: their layers' outputs sum to
    the layer that holds all 16 (the reference's), bias and all, every pair
    on one of them; the layer told `first` 0 with all held is the
    reference's."""
    model = dict(MODEL, n_routed_experts=16)
    lp = jax.jit(lambda k: FAMILY.weights.draw_layer(k, model, "moe", 0))(
        seed_key(SEED, 1))
    u = jax.random.normal(jax.random.key(6), (40, CFG.d_model))
    ref = FAMILY.reference.expert_layer(u, lp, model, "f32")

    def held(first, count):
        return moe_held_gated_serve(
            u, lp["router"], lp["e_gate"][first:first + count],
            lp["e_up"][first:first + count],
            lp["e_down"][first:first + count], None, bias=lp["bias"],
            first=first, top_k=CFG.top_k, scale=1.0, tile=8)

    whole, stats = held(0, 16)
    assert np.abs(np.asarray(whole - ref)).max() < TOL
    assert int(stats["held"]) == 40 * CFG.top_k
    shares = [held(first, 4) for first in (0, 4, 8, 12)]
    assert np.abs(np.asarray(sum(y for y, _ in shares) - ref)).max() < TOL
    assert sum(int(s["held"]) for _, s in shares) == 40 * CFG.top_k
    one = FAMILY.reference.expert_layer(u, lp, model, "f32", held=(4, 4))
    assert np.abs(np.asarray(shares[1][0] - one)).max() < TOL


# ------------------------------------------------------------- the kernel

def kernel_case(dtype, kv=4, per=16, qk=192, rope=64, v=128):
    L, nb, bs = 2, 40, 8
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(L, nb * bs, kv * (qk + v))), dtype)
    q = jnp.asarray(rng.normal(size=(4, kv * per, qk)), dtype)
    table = np.zeros((4, 16), np.int32)
    table[0, :12] = np.arange(3, 15)
    table[1, :2] = [1, 2]
    table[2, :1] = [20]
    return pool, q, jnp.asarray(table), jnp.asarray([91, 9, 0, 0], jnp.int32)


@pytest.mark.parametrize("dtype,tol,kv,per,qk,rope,v", [
    (jnp.float32, 1e-5, 4, 16, 192, 64, 128),
    (jnp.bfloat16, 3e-2, 4, 16, 192, 64, 128),
    (jnp.float32, 1e-5, 1, 4, 24, 8, 16)])
def test_kernel_interpreted_matches_the_xla_oracle(dtype, tol, kv, per, qk,
                                                   rope, v):
    """16 queries a KV head, keys of 192 (128 unrotated + 64 rotated) and
    values of 128, the served widths (and the tiny configuration's): pages
    through the table, a traced layer, sequences that end inside a page,
    more than a fetch step (`_GQA_STEP_POSITIONS` cut to 64), a spare row
    on the scratch block; the oracle is the engine's `xla` route
    (`decode_attention` over the gathered span). bfloat16: the
    probabilities are rounded to the pool's type before they weigh the
    values, 2^-9 a term."""
    import distributed_neural_network_tpu.ops.decode_pallas as dp

    pool, q, table, pos = kernel_case(dtype, kv, per, qk, rope, v)
    cfg = mm.MiMoV2Config(d_model=64, n_heads=kv * per, qk_head=qk,
                          v_head=v, rope_dim=rope, n_kv_full=kv,
                          n_kv_window=kv, dtype=dtype)
    bs = 8

    @jax.jit
    def both(layer):
        o = mm.decode_kernel(q, pool, layer, table, pos, cfg, block_size=bs,
                             interpret=True)
        idx = (table[:, :, None] * bs + jnp.arange(bs)).reshape(4, -1)
        live = jnp.arange(16 * bs)[None] <= pos[:, None]
        f32 = mm.MiMoV2Config(**dict(cfg.__dict__, dtype=jnp.float32))
        return o, mm.decode_attention(
            q.astype(jnp.float32), pool[layer][idx].astype(jnp.float32),
            live, f32)

    old, dp._GQA_STEP_POSITIONS = dp._GQA_STEP_POSITIONS, 64
    try:
        for layer in (0, 1):
            o, ref = both(jnp.int32(layer))
            assert o.dtype == dtype and o.shape == (4, kv * per, v)
            assert np.abs(
                np.asarray(o, np.float32) - np.asarray(ref)).max() < tol
    finally:
        dp._GQA_STEP_POSITIONS = old


def test_kernel_reads_no_page_past_pos():
    """Pages past `pos` hold NaN: a kernel that fetched one would carry it
    into the output (0 x NaN). What it fetches is `paged_read_positions`,
    the engine's `serve_decode_positions_total{kind="read"}`."""
    bs, row = 8, 4 * 320
    pool = np.full((1, 12 * bs, row), np.nan, np.float32)
    rng = np.random.default_rng(1)
    pool[0, 3 * bs: 5 * bs] = rng.normal(size=(2 * bs, row))  # blocks 3, 4
    table = jnp.asarray([[3, 4, 7, 9]], jnp.int32)            # 7, 9: unread
    pos = np.asarray([11], np.int32)                          # ends in block 4
    o = split_gqa_decode_attention(
        jnp.asarray(rng.normal(size=(1, 64, 192)), jnp.float32),
        jnp.asarray(pool), 0, table, jnp.asarray(pos), block_size=bs,
        n_kv_heads=4, rope=64, v_dim=128, interpret=True)
    assert np.isfinite(np.asarray(o)).all()
    assert paged_read_positions(pos, bs) == 16


def test_kernel_gate():
    assert split_gqa_decode_ok(64, 4, 16, 192, 64, 128, jnp.bfloat16)
    assert split_gqa_decode_ok(8, 4, 16, 192, 64, 128, jnp.float32)
    assert not split_gqa_decode_ok(8, 4, 16, 192, 64, 128, jnp.bfloat16)
    assert not split_gqa_decode_ok(64, 4, 24, 192, 64, 128, jnp.bfloat16)
    assert not split_gqa_decode_ok(64, 1, 4, 24, 8, 16, jnp.float32)
    assert not split_gqa_decode_ok(64, 3, 16, 192, 64, 128, jnp.bfloat16)
    assert not split_gqa_decode_ok(64, 4, 16, 192, 64, 128, jnp.int8)


# the prefill kernel's tiles at a test's sizes: fetch steps of two pages of
# 8 and blocks of 4 query positions a KV head's 4 query heads, so that a
# chunk of 16 is four blocks and a prefix of 45 three steps
PREFILL_TILES = {"_SPLIT_PREFILL_KEYS": 16, "_SPLIT_PREFILL_ROWS": 16}
_prefill_calls = {}


def _prefill_kernel(dtype, chunk):
    """The kernel at 4 KV heads of 4 queries, keys of 80 (16 unrotated, 64
    rotated: two heads' rotated parts a 128-lane tile, as at the served
    widths) and values of 16, jitted once a chunk and type (`pos0` and
    `n_keys` traced)."""
    key = (dtype, chunk)
    if key not in _prefill_calls:
        _prefill_calls[key] = jax.jit(
            lambda q, pool, table, pos0, n_keys:
            dp.split_gqa_prefill_attention(
                q, pool, 1, table, pos0, n_keys, block_size=8, n_kv_heads=4,
                rope=64, v_dim=16, interpret=True))
    return _prefill_calls[key]


@pytest.mark.parametrize("dtype,tol,chunk,pos0,n_valid,scored", [
    (jnp.float32, 1e-5, 1, 0, 1, 16),      # a sequence's first token
    (jnp.float32, 1e-5, 16, 0, 16, 4 * 16 * 4),   # a chunk against itself
    (jnp.bfloat16, 2e-2, 16, 0, 16, 4 * 16 * 4),    # two bfloat16 steps
    (jnp.float32, 1e-5, 16, 13, 11, 3 * 4 * 32),   # mid-page, dead tail
    (jnp.float32, 1e-5, 16, 45, 16, 4 * 4 * 64),    # past 3 steps
    (jnp.float32, 1e-5, 1, 45, 1, 48)])
def test_prefill_kernel_interpreted_matches_the_blocked_oracle(
        monkeypatch, dtype, tol, chunk, pos0, n_valid, scored):
    """`split_gqa_prefill_attention` against `prefill_attention` (the
    engine's `xla` route, blocked over keys of 16) at a row laid out as the
    served one, a layer of two, through a scrambled table: a chunk of one
    and a whole chunk, from position 0, from inside a page, past several
    fetch steps, a bucket's dead tail (its rows are not compared). Pages
    wholly past the last key, and blocks not in the table, hold NaN: a
    fetch of one would carry it into the output. `scored` is what the
    kernel scores, counted by hand: every block of 4 query positions that
    holds a token against the whole steps of 16 keys up to its last
    position (`split_gqa_prefill_pairs`). bfloat16: the probabilities are
    rounded to the pool's type before they weigh the values, as the oracle
    rounds them, and the output to bfloat16."""
    for name, value in PREFILL_TILES.items():
        monkeypatch.setattr(dp, name, value)
    bs, n_keys = 8, pos0 + n_valid
    rng = np.random.default_rng(pos0 + chunk)
    pool = rng.normal(size=(2, 40 * bs, 4 * (80 + 16))).astype(np.float32)
    table = (1 + rng.permutation(39)[:16]).astype(np.int32)
    poisoned = np.full_like(pool, np.nan)
    for b in table[:-(-n_keys // bs)]:
        poisoned[:, b * bs:(b + 1) * bs] = pool[:, b * bs:(b + 1) * bs]
    q = jnp.asarray(rng.normal(size=(chunk, 16, 80)), dtype)
    o = _prefill_kernel(dtype, chunk)(
        q, jnp.asarray(poisoned, dtype), jnp.asarray(table), jnp.int32(pos0),
        jnp.int32(n_keys))
    cfg = mm.MiMoV2Config(d_model=64, n_heads=16, qk_head=80, v_head=16,
                          rope_dim=64, n_kv_full=4, n_kv_window=4,
                          dtype=dtype)
    rows = jnp.asarray(pool[1], dtype)

    def read_rows(j):
        blk = jax.lax.dynamic_slice_in_dim(jnp.asarray(table), 2 * j, 2)
        return rows[(blk[:, None] * bs + jnp.arange(bs)).reshape(-1)]
    ref = mm.prefill_attention(q, pos0 + jnp.arange(chunk), read_rows,
                               n_keys, cfg, key_block=16)
    assert o.dtype == dtype and o.shape == (chunk, 16, 16)
    assert np.isfinite(np.asarray(o, np.float32)).all()
    assert np.abs(np.asarray(o, np.float32)[:n_valid] - np.asarray(
        ref, np.float32)[:n_valid]).max() < tol
    assert split_gqa_prefill_pairs(pos0, n_valid, chunk, 4, 16, bs) == scored
    assert mm.prefill_kernel_scored(cfg, pos0, n_valid, chunk, block_size=bs,
                                    width=16) == scored


def test_prefill_kernel_gate():
    """It reads the rows the decode kernel reads, so it compiles where that
    one does, whatever the chunk."""
    for args in [(64, 4, 16, 192, 64, 128, jnp.bfloat16),
                 (8, 4, 16, 192, 64, 128, jnp.bfloat16),
                 (64, 1, 4, 24, 8, 16, jnp.float32),
                 (64, 4, 16, 192, 64, 128, jnp.int8)]:
        assert split_gqa_prefill_ok(*args) == split_gqa_decode_ok(*args)
    assert mm.prefill_kernel_gate(CFG, 8, jnp.float32) is False
    with open(CONFIG_FILE) as f:
        served = FAMILY.program.config(json.load(f), {}, jnp.bfloat16)
    assert mm.prefill_kernel_gate(served, 64, jnp.bfloat16) is True


# ------------------------------------------------------------- the engine

def _engine(params, **kw):
    base = dict(max_batch=4, num_blocks=40, block_size=8, max_seq_len=64,
                prefill_chunk=16, decode_impl="xla")
    return ServeEngine(params, CFG, EngineConfig(**dict(base, **kw)))


def _drive(eng, seqs, later=()):
    """Run the engine dry, re-admitting what it preempts and admitting
    `later` = [(tick, sequence)] when their tick comes; returns {(seq id,
    position): the decode program's logits there}, a replayed position's
    last reading."""
    seen = {}
    run = eng._run_writer

    def recording(fn, *tail):
        out = run(fn, *tail)
        if len(tail) == 6:                    # a decode dispatch
            first = {eng.kv.seq_block_ids(s.seq_id)[0]: s.seq_id
                     for s in eng.active if eng.kv.seq_block_ids(s.seq_id)}
            pos, table = np.asarray(tail[1]), np.asarray(tail[2])
            logits = np.asarray(out[1])
            for i, blk in enumerate(table[:, 0]):
                if blk in first:
                    seen[(first[blk], int(pos[i]))] = logits[i]
        return out

    eng._run_writer = recording
    for s in seqs:
        eng.add(s)
    later = sorted(later, key=lambda p: p[0])
    ticks = 0
    while (eng.has_work() or eng.preempted or later) and ticks < 2000:
        while later and later[0][0] <= ticks and (
                len(eng.active) < eng.ecfg.max_batch):
            eng.add(later.pop(0)[1])
        eng.step()
        ticks += 1
        if eng.preempted and eng.kv.can_fit(4):
            eng.add(eng.preempted.popleft())
    assert ticks < 2000
    return seen


def _against_the_reference(seqs, seen, sound=True):
    """The widest gap between the logits the decode programs gave and the
    reference's whole forward of the tokens served."""
    worst = 0.0
    for s in seqs:
        assert len(s.out) == s.max_new_tokens
        full = np.asarray(s.prompt + s.out, np.int32)
        rows = np.arange(s.prompt_len - 1, len(full) - 1)
        ref = reference_logits(full, rows)
        got = np.stack([seen[(s.seq_id, int(r))] for r in rows])
        worst = max(worst, float(np.abs(got - ref).max()))
        if sound:
            assert list(ref.argmax(-1)) == s.out    # greedy, token for token
    return worst


def _mixed(lens, seed=10):
    return [Sequence(seq_id=i, prompt=list(map(int, some_tokens(n, seed + i))),
                     max_new_tokens=m) for i, (n, m) in enumerate(lens)]


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefill_then_decode_through_both_pools_matches_the_reference(
        params, impl):
    """Chunked prefill (chunks of 16, blocks of 8, a window of 8) then
    decode through the KV pool and the rings: a batch of mixed lengths
    whose rings wrap several times, chunks longer than the window and a
    last chunk shorter than it (a prompt of 19: 16 + 2), a prompt of one
    token (decode from position 0), in a pool too small for all of it, so
    that a sequence is preempted and replayed (which rebuilds its rings),
    and two sequences that arrive later and take the state slots of
    finished ones while a tick is in flight. Every logit the decode programs
    gave, at every position of every sequence, against the reference's
    whole forward of that sequence's tokens."""
    seqs = _mixed([(19, 12), (10, 20), (33, 9), (26, 14)])
    more = _mixed([(1, 7), (21, 5)], seed=30)
    for i, s in enumerate(more):
        s.seq_id = 10 + i
    eng = _engine(params, num_blocks=10, decode_impl=impl)
    slots_seen, ahead = set(), []
    take, dispatch = eng.kv._take_state, eng._dispatch

    def noting(seq_id):
        take(seq_id)
        slots_seen.add((seq_id, eng.kv._seq_state.get(seq_id), ahead[-1]))

    def dispatching(prev):
        ahead.append(prev is not None)      # a tick is in flight
        return dispatch(prev)
    eng.kv._take_state, eng._dispatch = noting, dispatching
    seen = _drive(eng, seqs, later=[(12, more[0]), (14, more[1])])
    assert sum(s.preemptions for s in seqs + more) > 0, "pool was never tight"
    # a later sequence took a slot an earlier one had held, with a tick in
    # flight, and nobody zeroed it
    early = {slot for sid, slot, _ in slots_seen if sid < 10}
    assert any(sid >= 10 and slot in early and inflight
               for sid, slot, inflight in slots_seen)
    assert _against_the_reference(seqs + more, seen) < TOL
    assert eng.kv.state_slots_in_use == 0 and eng.kv.blocks_in_use == 0


def test_prefill_kernel_serves_the_blocked_loops_tokens_and_counts_its_pairs(
        params, monkeypatch):
    """The engine's chunked prefill on the interpreted kernel (`decode_impl`
    "pallas": the module declares it, `_Cache.prefill_kernel_ok`) against
    the same engine on the `xla` route (the blocked loop, held to the
    reference above), fetch steps cut to two pages so that a prompt of 40
    spans three of them: the same greedy tokens from logits within `TOL`;
    on the kernel
    `serve_attn_kernel_pairs_total{path="prefill"}` grows by the chunks'
    live pairs (on the blocked loop not at all), and the full layers'
    scored pairs are the kernel's walk, `prefill_kernel_scored`, where the
    blocked loop's are the chunk against its key blocks of 64."""
    for name, value in PREFILL_TILES.items():
        monkeypatch.setattr(dp, name, value)
    count, calls = mm.attn_pairs, []

    def counting(cfg, pos0, n, chunk, scored):
        calls.append((pos0, n, chunk, scored))
        return count(cfg, pos0, n, chunk, scored)
    monkeypatch.setattr(mm, "attn_pairs", counting)
    out, seen, published = {}, {}, {}
    for impl in ("xla", "pallas"):
        calls.clear()
        eng = _engine(params, decode_impl=impl)
        assert eng._prefill_route() == impl
        registry = MetricsRegistry()
        scheduler = ServeScheduler(eng, SchedulerConfig(), registry=registry)
        step = eng.step

        def publishing():
            stats = step()
            scheduler._publish_tick(stats["phase_s"], stats)
            return stats
        eng.step = publishing
        seqs = _mixed([(40, 3)], seed=50)
        try:
            seen[impl] = _drive(eng, seqs)
        finally:
            scheduler.close()
        out[impl] = [s.out for s in seqs]
        published[impl] = {
            line.rsplit(" ", 1)[0]: float(line.rsplit(" ", 1)[1])
            for line in registry.render().splitlines()
            if line.startswith("serve_attn_")}
        live = sum(n * p0 + n * (n + 1) // 2 for p0, n, _, _ in calls)
        kernel = published[impl][
            'serve_attn_kernel_pairs_total{path="prefill"}']
        scored = published[impl][
            'serve_attn_pairs_total{kind="scored",layers="full"}']
        assert [(p0, n) for p0, n, _, _ in calls] == [(0, 16), (16, 16),
                                                      (32, 7)]
        if impl == "xla":
            assert kernel == 0
            assert all(s == c * 64 for _, _, c, s in calls)
        else:
            assert kernel == live
            assert all(s == mm.prefill_kernel_scored(
                CFG, p0, n, c, block_size=8, width=8)
                for p0, n, c, s in calls)
            # the last chunk's two blocks of 4 queries, 32-35 and 36-38,
            # against three steps of 16 keys
            assert calls[2] == (32, 7, 8, 4 * 48 + 4 * 48)
        assert scored == CFG.n_full * sum(s for _, _, _, s in calls)
    assert out["xla"] == out["pallas"] and len(out["xla"][0]) == 3
    assert seen["xla"].keys() == seen["pallas"].keys()
    assert max(np.abs(seen["xla"][k] - seen["pallas"][k]).max()
               for k in seen["xla"]) < TOL


def test_a_reused_slot_is_masked_by_position_in_the_program(params):
    """The rings are left full of large values, as a last owner might have
    left them: every sequence's programs keep only the rows its own
    positions wrote, and nothing on the host clears them."""
    eng = _engine(params)
    eng.state_pool = jnp.full_like(eng.state_pool, 50.0)
    seqs = _mixed([(1, 5), (12, 6), (3, 4)])
    assert _against_the_reference(seqs, _drive(eng, seqs)) < TOL


def test_an_unmasked_ring_is_seen(params, monkeypatch):
    """A ring read whole, whatever position its rows hold (a slot's stale
    rows, or noughts before the sequence has written them), lies far from
    the reference."""
    monkeypatch.setattr(mm, "ring_positions",
                        lambda last, cfg: jnp.zeros(
                            jnp.shape(last) + (cfg.window,), jnp.int32))
    eng = _engine(params)
    eng.state_pool = jnp.full_like(eng.state_pool, 0.5)
    seqs = _mixed([(12, 6), (3, 5)])
    assert _against_the_reference(seqs, _drive(eng, seqs),
                                  sound=False) > 100 * TOL


def test_engine_takes_the_module_and_refuses_what_it_does_not_run(params):
    eng = _engine(params)
    assert not eng.latent and eng.v_pool is None and eng.k_scale is None
    # the full layers' rows in one pool under the K pool's name, the window
    # layers' rings beside it: a slot a sequence and scratch
    assert eng.k_pool.shape == (CFG.n_full, 40 * 8, 40)
    assert eng.state_pool.shape == (CFG.n_window, 4 + 1, 8, 80)
    assert eng.pool_labels == ("kv_pool", "state_pool")
    assert eng._cache.kinds == (("full", "rows"), ("window", "state"))
    assert eng.kv_block_bytes() == CFG.n_full * 8 * 40 * 4
    assert eng.decode_route() == eng._prefill_route() == "xla"
    assert _engine(params, decode_impl="pallas").decode_route() == "pallas"
    assert eng._bucket_widths() == [8]              # one width: the widest
    for kw, word in [(dict(spec_decode=2), "spec_decode"),
                     (dict(kv_dtype="int8"), "kv_dtype int8"),
                     (dict(weight_dtype="int8"), "weight_dtype int8")]:
        with pytest.raises(ValueError, match=f"mimo_v2: {word}"):
            _engine(params, **kw)


def test_both_pools_are_updated_in_place_in_the_compiled_programs(params):
    """tests/test_serve_pool_inplace.py's contract for the KV pool and the
    rings: pools that dwarf the program, donated and threaded through the
    layer walk: a compiled program holds no temporary of a layer's slab and
    aliases both pools whole to its outputs."""
    eng = _engine(params, max_batch=2, num_blocks=1024, block_size=16,
                  max_seq_len=64)
    eng.state_pool = jnp.zeros(
        (CFG.n_window, 4096) + eng.state_pool.shape[2:], CFG.dtype)
    w = eng._bucket_widths()[0]
    programs = {"decode": (eng._decode_fn(2, w), 2),
                "prefill": (eng._prefill_fn(16, w), 16)}
    slab = min(eng.k_pool[0].nbytes, eng.state_pool[0].nbytes)
    pools = eng.k_pool.nbytes + eng.state_pool.nbytes
    for family, (fn, n) in programs.items():
        mem = fn.lower(eng.params, *eng._pools(),
                       *eng.bucket_tail(family, n, w)).compile(
            ).memory_analysis()
        assert mem.temp_size_in_bytes < slab, family
        assert mem.alias_size_in_bytes >= pools, family


def test_a_batch_bucket_of_max_batch_is_warmed():
    """A `max_batch` that is no power of two is a bucket of its own (a
    batch of 5 of 6 runs in it), so that warmup compiles every program a
    tick can ask for; servelint's grid mirrors it."""
    from distributed_neural_network_tpu.analysis import serve_trace as st

    assert batch_buckets(48) == [1, 2, 4, 8, 16, 32, 48]
    assert batch_buckets(64) == [1, 2, 4, 8, 16, 32, 64]
    assert batch_buckets(1) == [1]
    ecfg = EngineConfig(max_batch=6, num_blocks=40, block_size=8,
                        max_seq_len=64, prefill_chunk=8)
    assert st.enumerate_grid(ecfg)["decode"][-1][0] == 6


def test_servelint_audits_both_pools_donation(params):
    """analysis/serve_trace.py's walker on the programs: both pools are
    donated (and nothing else), params are not, and the grid it enumerates
    from the `EngineConfig` is the grid `warmup()` builds."""
    from distributed_neural_network_tpu.analysis import serve_trace as st

    eng = _engine(params, max_batch=3)
    grid = st.enumerate_grid(eng.ecfg, latent=True)
    for family, key in (("decode", (2, 8)), ("prefill", (8, 8))):
        program = st.bucket_program(eng, family, key)
        assert program.donate == (1, 2)
        assert program.donate_labels == ("params", "kv_pool", "state_pool")
        analysis = st.analyze_serve_program(program)
        assert not analysis.errors, analysis.errors
        assert sum(analysis.facts.donated_invars) == 2
    assert eng.warmup() == st.grid_total(grid) == 3 + 5


def test_the_tick_publishes_its_counters(params, monkeypatch):
    calls = []
    count = mm.attn_pairs

    def counting(cfg, pos0, n, chunk, scored):
        calls.append((pos0, n, chunk, scored))
        return count(cfg, pos0, n, chunk, scored)
    monkeypatch.setattr(mm, "attn_pairs", counting)
    eng = _engine(params, decode_impl="pallas")
    n = eng.warmup()
    registry = MetricsRegistry()
    scheduler = ServeScheduler(eng, SchedulerConfig(), registry=registry)
    try:
        seqs = _mixed([(20, 6), (20, 6), (12, 6)], seed=0)
        for s in seqs:
            eng.add(s)
        live = read = held = pairs = 0
        peak_slots = 0
        while eng.has_work():
            stats = eng.step()
            scheduler._publish_tick(stats["phase_s"], stats)
            scheduler._account_step(stats, 0.0, 1e-3, 0)
            peak_slots = max(peak_slots, eng.kv.state_slots_in_use)
            if stats["decode_call"]:
                live += stats["decode_call"][2]
                read += stats["decode_call"][3]
            if "moe" in stats:
                held += stats["moe"]["held"]
                pairs += stats["moe"]["held"] + stats["moe"]["absent"]
        assert eng.compiled_programs()["total"] == n  # nothing new compiled
        text = registry.render()
    finally:
        scheduler.close()
    # every token of every program chose top_k experts in 4 expert layers,
    # a quarter of them on the 4 held here under even routing
    tokens = sum(s.prompt_len - 1 + len(s.out) for s in seqs)
    assert pairs == tokens * CFG.top_k * CFG.n_moe and 0 < held < pairs
    assert peak_slots == 3 and read >= live > 0
    # each prefill program counted once, in its chunk bucket, at what the
    # prefill kernel walks over the one table width of 8 blocks: 19 + 19 +
    # 11 prompt tokens in chunks of up to 16 a tick
    assert sum(m for _, m, _, _ in calls) == 19 + 19 + 11
    assert all(c == 1 << (m - 1).bit_length()
               and k == mm.prefill_kernel_scored(CFG, p0, m, c, block_size=8,
                                                 width=8)
               for p0, m, c, k in calls)
    want = {}
    for call in calls:
        for k, v in count(CFG, *call).items():
            want[k] = want.get(k, 0) + v
    for line in (
        f'serve_moe_pairs_total{{where="held"}} {held}',
        f'serve_decode_positions_total{{kind="live"}} {live}',
        f'serve_decode_positions_total{{kind="read"}} {read}',
        f'serve_attn_kernel_positions_total{{path="decode"}} {live}',
        'serve_state_slots_in_use 0',
    ) + tuple(f'serve_attn_pairs_total{{kind="{kind}",layers="{layers}"}} '
              f'{v}' for (layers, kind), v in want.items()):
        assert line in text, line


def test_the_server_takes_the_configuration_file(params):
    """`python -m distributed_neural_network_tpu.serve --model-config`'s
    own assembly of the model: the family's module by name, built from the
    file, served by the engine."""
    from distributed_neural_network_tpu import models

    family = models.family_module(MODEL["family"])
    assert family is mm and family.CACHE == "hybrid"
    cfg = family.from_published(MODEL, dtype=jnp.float32)
    tree = family.init_params(jax.random.key(0), cfg)
    want = jax.tree.map(lambda s: s, mm.param_shapes(cfg),
                        is_leaf=lambda x: isinstance(x, tuple))
    assert jax.tree.map(lambda a: a.shape, tree) == want
    assert jax.tree.map(lambda a: a.shape, params) == want
    eng = ServeEngine(tree, cfg, EngineConfig(
        max_batch=2, num_blocks=16, block_size=8, max_seq_len=32,
        prefill_chunk=8))
    seq = Sequence(seq_id=0, prompt=[3, 1, 4, 1, 5, 9, 2, 6, 5, 3],
                   max_new_tokens=4)
    eng.add(seq)
    while eng.has_work():
        eng.step()
    logits = np.asarray(mm.apply(
        tree, jnp.asarray(seq.prompt + seq.out, jnp.int32), cfg))
    assert list(logits[9:13].argmax(-1)) == seq.out


def test_the_file_is_the_published_block_cut_by_depth_experts_and_rows():
    """The configuration's file builds the row's block at its published
    widths: 64 heads of 192 over 4 (full) and 8 (window) KV heads, values of
    128, 64 rotated; one dense full layer and one 5 : 1 period; the decode
    kernel compiles for its pool; 3,429,955,392 parameters."""
    with open(CONFIG_FILE) as f:
        model = json.load(f)
    cfg = FAMILY.program.config(model, {}, jnp.bfloat16)
    assert cfg.layer_types == ("full",) + ("window",) * 5 + ("full",)
    assert cfg.ff_types == ("dense",) + ("moe",) * 6
    assert mm.cache_shapes(cfg) == {"kv": (2, 1280),
                                    "state": (5, 128, 2560)}
    assert mm.kernel_gate(cfg, 64, jnp.bfloat16)[0]
    assert sum(math.prod(s) for s in jax.tree.leaves(
        mm.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    ) == FAMILY.arith.param_count(model) == 3_429_955_392
