"""The `lfm2_moe` module and its serving path against the family's plain
reference (`benchmark/families/lfm2_moe/reference.py`: float32, the
convolution as a whole-sequence sum and the attention as one causal softmax,
nothing of the program imported), at a small size on the CPU, seeded random
weights, float32.

Tolerances. The program and the reference compute the same function in
another order (a convolution step from a carried state against the
whole-sequence sum, an online softmax over key blocks and pages against one
softmax, grouped heads against repeated KV heads, experts over sorted tiles
against every expert under a mask), so logits of deviation 0.03 (the tied
embedding is drawn small, `weights.py`) agree to float32 reassociation: read
1.2e-7 (whole forward) and 1.1e-7 (through both pools, either route); the
limit is `TOL` = 2e-6. A planted departure (a dropped head norm, a rotation
where there is none or none where there is one, a query head on the wrong KV
head, a router without its bias, a bucket's dead tail in the state) moves the
logits by 0.03 to 0.14, ten thousand times `TOL` and more.
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
from distributed_neural_network_tpu.models import lfm2_moe as lm
from distributed_neural_network_tpu.ops.decode_pallas import (
    gqa_decode_attention,
    gqa_decode_ok,
    paged_read_positions,
)
from distributed_neural_network_tpu.parallel.moe import moe_held_gated_serve
from distributed_neural_network_tpu.serve.engine import (
    EngineConfig,
    Sequence,
    ServeEngine,
)
from distributed_neural_network_tpu.serve.kv_cache import (
    KVCacheConfig,
    OutOfBlocks,
    PagedKVCache,
)
from distributed_neural_network_tpu.serve.scheduler import (
    SchedulerConfig,
    ServeScheduler,
)
from distributed_neural_network_tpu.utils.obs import MetricsRegistry

sys.path[:0] = [BENCH]
try:
    from lib import harness
    from lib.weights import is_shape, seed_key

    FAMILY = harness.load_family("lfm2_moe", "serve")
finally:
    del sys.path[:1]
# the reference's blocks are sized for 8k rows on the chip; they change no
# value, and a test's sequences are a few dozen tokens
FAMILY.reference.ROW_BLOCK, FAMILY.reference.QUERY_BLOCK = 64, 32

TOL = 2e-6
SEED = 5
MODEL = harness.load_json("families", "lfm2_moe", "tiny.json")
CFG = FAMILY.program.config(MODEL, {}, jnp.float32)
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs", "lfm2-24b-a2b.json")


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
    return np.asarray(jax.jit(lambda p, t: lm.apply(p, t, CFG))(params, tok))


# ------------------------------------------------------------- the module

def test_the_configuration_walks_the_published_order():
    assert CFG.layer_types == ("conv", "attn", "conv", "conv", "attn")
    assert lm.layer_plan(CFG) == (
        ("conv", 0, "dense", 0), ("attn", 0, "moe", 0), ("conv", 1, "moe", 1),
        ("conv", 2, "moe", 2), ("attn", 1, "moe", 3))
    assert lm.cache_shapes(CFG) == {"kv": (2, 64), "state": (3, 2, 64)}
    assert (CFG.head_dim, CFG.n_kv_heads, CFG.experts_held) == (16, 2, (0, 8))
    with pytest.raises(ValueError, match="lfm2_moe: layer_types"):
        lm.Lfm2MoEConfig(layer_types=("conv", "mamba"))
    with pytest.raises(ValueError, match="layer_types for num_hidden_layers"):
        lm.from_published(dict(MODEL, num_hidden_layers=4))


def test_whole_forward_matches_the_reference(params):
    tok = some_tokens(48)
    got = whole_forward(params, tok)
    assert np.abs(got - reference_logits(tok, np.arange(48))).max() < TOL


@pytest.mark.parametrize("fault", FAMILY.reference.FAULTS[1:])
def test_a_departure_from_the_equations_is_seen(params, fault):
    """The reference with the head norms dropped, the attention's rotation
    dropped or a rotation put into the convolution layers, the query heads
    read against the wrong KV head, or the router's bias left out lies a
    hundred `TOL` and more from the program: the comparison that passes
    above would not pass a program that did one of these."""
    tok = some_tokens(48)
    got = whole_forward(params, tok)
    ref = reference_logits(tok, np.arange(48), fault)
    assert np.abs(got - ref).max() > 100 * TOL


def test_a_convolution_step_from_the_carried_state_is_the_whole_sum(params):
    """Position by position from a state of noughts, each step `conv_mix` of
    one row behind the state `next_state` left, against the whole-sequence
    convolution; and a chunk that starts at 17 from the state behind 16."""
    lp = lm.layer_params(params, "conv", 1)
    x = jax.random.normal(jax.random.key(3), (64, CFG.d_model))
    gate, z = lm.conv_in(x, lp, CFG)
    nought = jnp.zeros((2, CFG.d_model))
    whole, _ = lm.conv_mix(nought, z, lp, CFG)
    state, steps = nought, []
    for t in range(64):
        c, zz = lm.conv_mix(state, z[t:t + 1], lp, CFG)
        state = lm.next_state(zz, 1, CFG)
        steps.append(c[0])
        if t == 16:
            at_17 = state
    assert np.abs(np.asarray(jnp.stack(steps) - whole)).max() < 1e-6
    chunk, zz = lm.conv_mix(at_17, z[17:33], lp, CFG)
    assert np.abs(np.asarray(chunk - whole[17:33])).max() < 1e-6
    # the state behind 9 valid rows of the chunk is z of rows 24 and 25
    assert np.abs(np.asarray(lm.next_state(zz, 9, CFG) - z[24:26])).max() == 0
    # the reference's whole-sequence operator on the same leaves
    ref = FAMILY.reference.short_conv(x, lp, MODEL, "f32")
    got = lm.short_conv(x, lp, CFG) - x
    assert np.abs(np.asarray(got - ref)).max() < 1e-6


def test_prefill_attention_blocked_over_keys_is_the_unblocked(params):
    lp = lm.layer_params(params, "attn", 0)
    x = jax.random.normal(jax.random.key(4), (64, CFG.d_model))
    pos = jnp.arange(64)
    q, rows = lm.attn_in(x, lp, CFG, pos)
    one = lm.prefill_attention(q, pos, lambda j: rows, 64, CFG, key_block=64)
    blocked = lm.prefill_attention(
        q, pos, lambda j: jax.lax.dynamic_slice_in_dim(rows, j * 16, 16), 64,
        CFG, key_block=16)
    assert np.abs(np.asarray(one - blocked)).max() < 1e-6
    # one query a sequence over its causal prefix: the decode oracle
    live = pos[None, :] <= pos[:, None]
    dec = lm.decode_attention(
        q, jnp.broadcast_to(rows, (64,) + rows.shape), live, CFG)
    assert np.abs(np.asarray(one - dec)).max() < 1e-6


# ---------------------------------------------------------- the expert layer

def expert_leaves():
    return jax.jit(lambda k: FAMILY.weights.draw_layer(k, MODEL, "moe", 0))(
        seed_key(SEED, 1))


def held_layer(u, lp, first, count, **kw):
    return moe_held_gated_serve(
        u, lp["router"], lp["e_gate"][first:first + count],
        lp["e_up"][first:first + count], lp["e_down"][first:first + count],
        None, bias=lp["bias"], first=first, top_k=CFG.top_k,
        scale=CFG.routed_scale, tile=8, sum_eps=lm.ROUTE_SUM_EPS, **kw)


def test_the_held_layer_is_the_references_and_its_halves_add_up():
    """No expert is cut: the held layer told `first` 0 with all 8 held is
    the reference's expert layer, bias and all, with no shared expert; and
    its two halves (4 held each) add up to it, every pair on one of them."""
    lp = expert_leaves()
    u = jax.random.normal(jax.random.key(6), (40, CFG.d_model))
    ref = FAMILY.reference.expert_layer(u, lp, MODEL, "f32")
    whole, stats = held_layer(u, lp, 0, 8)
    assert np.abs(np.asarray(whole - ref)).max() < TOL
    assert int(stats["held"]) == 40 * CFG.top_k and int(stats["absent"]) == 0
    halves = [held_layer(u, lp, first, 4) for first in (0, 4)]
    total = halves[0][0] + halves[1][0]
    assert np.abs(np.asarray(total - ref)).max() < TOL
    assert sum(int(s["held"]) for _, s in halves) == 40 * CFG.top_k
    # a router without its bias chooses other experts
    no_bias = FAMILY.reference.expert_layer(u, lp, MODEL, "f32",
                                            fault="no_bias")
    assert np.abs(np.asarray(no_bias - ref)).max() > 0.1 * np.abs(
        np.asarray(ref)).max()


def test_the_docqa_layers_operations_are_as_they_were():
    """`bias=None`, a shared expert and no `sum_eps` trace what the layer
    traced before it took them: the same equations in the jaxpr."""
    lp = expert_leaves()
    u = jax.random.normal(jax.random.key(6), (12, CFG.d_model))
    shared = (lp["e_gate"][0], lp["e_up"][0], lp["e_down"][0])
    args = (u, lp["router"], lp["e_gate"], lp["e_up"], lp["e_down"], shared)
    kw = dict(first=0, top_k=2, scale=2.5, tile=4)
    plain = jax.make_jaxpr(lambda: moe_held_gated_serve(*args, **kw))()
    asked = jax.make_jaxpr(lambda: moe_held_gated_serve(
        *args, bias=None, sum_eps=0.0, **kw))()
    assert str(plain) == str(asked)
    assert "logistic" in str(plain)


def test_expert_tile_follows_the_rows():
    with open(CONFIG_FILE) as f:
        big = FAMILY.program.config(json.load(f), {}, jnp.bfloat16)
    assert [lm.expert_tile(big, n) for n in (1, 64, 128, 256, 512)] == [
        16, 16, 16, 32, 64]


def test_the_count_of_the_file_is_the_sum_of_its_shapes():
    """`param_count` of the configuration's file = the sum of
    `weights.shapes` = 5,177,950,976, and with what `published` states put
    back the whole model: 23.5 to 24.5 B; the file's layers are the
    published list's layer 1 followed by its layers 2-9."""
    with open(CONFIG_FILE) as f:
        model = json.load(f)
    shapes = jax.tree.leaves(FAMILY.weights.shapes(model), is_leaf=is_shape)
    assert (FAMILY.arith.param_count(model) == sum(map(math.prod, shapes))
            == 5_177_950_976)
    pub = model["published"]
    whole = dict(model, published={}, **{k: pub[k] for k in (
        "num_hidden_layers", "num_dense_layers", "layer_types")})
    assert 23.5e9 < FAMILY.arith.param_count(whole) < 24.5e9
    assert 2.2e9 < FAMILY.arith.active_matmul_params(whole) < 2.4e9
    assert len(pub["layer_types"]) == pub["num_hidden_layers"] == 40
    assert model["layer_types"] == [pub["layer_types"][1]] + pub[
        "layer_types"][2:10]
    assert set(model["reduced"]) == set(model["reduced_how"]) == {
        "num_hidden_layers", "num_dense_layers", "layer_types"}
    # the work, as the issue reckons it
    arith = FAMILY.arith
    assert arith.forward_flops(model, 1, 0) == pytest.approx(1.296e9, 1e-3)
    assert arith.decode_attn_flops(model, 1) == 2 * 8_192
    assert arith.decode_attn_bytes(model, 1) == 2 * 1_024 * 2
    assert arith.kv_bytes_per_token(model) == 4_096
    assert arith.state_bytes_per_sequence(model) == 7 * 2 * 2048 * 2
    # a decode program that reads 63 of 64 experts in 8 layers and 160,000
    # positions: the matrices outside the experts once, the head with them
    read = arith.program_read_bytes(model, "decode", 8 * 63, 160_000)
    assert read == pytest.approx(10.86e9, 1e-3)
    assert arith.program_read_bytes(model, "prefill", 0, 0) == read - 2 * (
        8 * 63 * 3 * 2048 * 1536 + 65_536 * 2048) - 4_096 * 160_000
    cfg = FAMILY.program.config(model, {}, jnp.bfloat16)
    assert (cfg.n_dense, cfg.n_moe, cfg.n_conv, cfg.n_attn) == (1, 8, 7, 2)
    assert (cfg.experts_held, cfg.n_routed, cfg.kv_row) == ((0, 64), 64, 1024)
    assert lm.cache_shapes(cfg) == {"kv": (2, 1024), "state": (7, 2, 2048)}


# ------------------------------------------------------------- the kernel

def kernel_case(dtype, hd=16, kv=2, per=2):
    L, nb, bs = 2, 40, 8
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(L, nb * bs, kv * 2 * hd)), dtype)
    q = jnp.asarray(rng.normal(size=(4, kv * per, hd)), dtype)
    table = np.zeros((4, 16), np.int32)
    table[0, :12] = np.arange(3, 15)
    table[1, :2] = [1, 2]
    table[2, :1] = [20]
    return pool, q, jnp.asarray(table), jnp.asarray([91, 9, 0, 0], jnp.int32)


@pytest.mark.parametrize("dtype,tol,hd,kv,per", [
    (jnp.float32, 1e-5, 16, 2, 2), (jnp.float32, 1e-5, 64, 2, 4),
    (jnp.bfloat16, 3e-2, 64, 4, 4)])
def test_kernel_interpreted_matches_the_xla_oracle(dtype, tol, hd, kv, per):
    """Pages through the table, a traced layer, sequences that end inside a
    page, a fetch step's worth and more (64 pages of 8 rows a step would be
    all of it: `_GQA_STEP_POSITIONS` cut to 64 makes one sequence take two
    steps), a spare row on the scratch block; the oracle is the engine's
    `xla` route (`decode_attention` over the gathered span). bfloat16: the
    probabilities are rounded to the pool's type before they weigh the
    values, 2^-9 a term."""
    import distributed_neural_network_tpu.ops.decode_pallas as dp

    pool, q, table, pos = kernel_case(dtype, hd, kv, per)
    cfg = lm.Lfm2MoEConfig(d_model=kv * per * hd, n_heads=kv * per,
                           n_kv_heads=kv, head_dim=hd, dtype=dtype)
    bs = 8

    @jax.jit
    def both(layer):
        o = gqa_decode_attention(q, pool, layer, table, pos, block_size=bs,
                                 n_kv_heads=kv, interpret=True)
        idx = (table[:, :, None] * bs + jnp.arange(bs)).reshape(4, -1)
        live = jnp.arange(16 * bs)[None] <= pos[:, None]
        return o, lm.decode_attention(
            q.astype(jnp.float32), pool[layer][idx].astype(jnp.float32),
            live, lm.Lfm2MoEConfig(**dict(cfg.__dict__, dtype=jnp.float32)))

    old, dp._GQA_STEP_POSITIONS = dp._GQA_STEP_POSITIONS, 64
    try:
        for layer in (0, 1):
            o, ref = both(jnp.int32(layer))
            assert o.dtype == dtype and o.shape == q.shape
            assert np.abs(
                np.asarray(o, np.float32) - np.asarray(ref)).max() < tol
    finally:
        dp._GQA_STEP_POSITIONS = old


def test_kernel_reads_no_page_past_pos():
    """Pages past `pos` hold NaN: a kernel that fetched one would carry it
    into the output (0 x NaN). What it fetches is `paged_read_positions`,
    the engine's `serve_decode_positions_total{kind="read"}`."""
    bs, row = 8, 2 * 2 * 16
    pool = np.full((1, 12 * bs, row), np.nan, np.float32)
    rng = np.random.default_rng(1)
    pool[0, 3 * bs: 5 * bs] = rng.normal(size=(2 * bs, row))  # blocks 3, 4
    table = jnp.asarray([[3, 4, 7, 9]], jnp.int32)            # 7, 9: unread
    pos = np.asarray([11], np.int32)                          # ends in block 4
    o = gqa_decode_attention(
        jnp.asarray(rng.normal(size=(1, 4, 16)), jnp.float32),
        jnp.asarray(pool), 0, table, jnp.asarray(pos), block_size=bs,
        n_kv_heads=2, interpret=True)
    assert np.isfinite(np.asarray(o)).all()
    assert paged_read_positions(pos, bs) == 16


def test_kernel_gate():
    assert gqa_decode_ok(64, 8, 4, 64, jnp.bfloat16)      # the served shape
    assert gqa_decode_ok(8, 2, 8, 64, jnp.float32)
    assert not gqa_decode_ok(8, 8, 4, 64, jnp.bfloat16)   # half a tile
    assert not gqa_decode_ok(64, 8, 4, 128, jnp.bfloat16)  # [k ; v] two tiles
    assert not gqa_decode_ok(64, 8, 16, 64, jnp.bfloat16)  # 16 queries a head
    assert not gqa_decode_ok(64, 8, 4, 64, jnp.int8)


# ----------------------------------------------------------- the allocator

def test_a_state_slot_comes_with_the_first_block_and_goes_with_the_last():
    kv = PagedKVCache(KVCacheConfig(num_blocks=9, block_size=4,
                                    max_seq_len=16, state_slots=3))
    kv.ensure(7, 0)
    kv.ensure_range(8, 5)
    assert kv.state_slots_in_use == 2
    assert sorted(kv.state_rows([7, 8])) == [1, 2]       # 0 is scratch
    assert list(kv.state_rows([-1, 8, 99])) == [0, kv.state_rows([8])[0], 0]
    kv.ensure(7, 4)                                      # a second block
    assert kv.state_slots_in_use == 2
    with pytest.raises(OutOfBlocks):                     # no third slot
        kv.ensure(9, 0)
    assert kv.seq_block_ids(9) == [] and kv.state_slots_in_use == 2
    freed = kv.state_rows([7])[0]
    kv.free(7)
    kv.ensure(9, 0)
    assert kv.state_rows([9])[0] == freed                # handed on as it was
    kv.rewind(8, 0)                                      # to nothing: gone
    assert kv.state_slots_in_use == 1
    # a pool without state slots hands out none
    plain = PagedKVCache(KVCacheConfig(num_blocks=9, block_size=4))
    plain.ensure(1, 0)
    assert plain.state_slots_in_use == 0 and list(plain.state_rows([1])) == [0]


# ------------------------------------------------------------- the engine

def _engine(params, **kw):
    base = dict(max_batch=4, num_blocks=40, block_size=8, max_seq_len=64,
                prefill_chunk=8, decode_impl="xla")
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
    """Chunked prefill (chunks of 8, blocks of 8) then decode through the KV
    pool and the state pool: a batch of mixed lengths that crosses block and
    chunk boundaries, a prompt of 10 whose second chunk is ONE valid token
    (9 go to prefill: 8 + 1), a prompt of one token (decode at position 0
    from a state of noughts), in a pool too small for all of it, so that a
    sequence is preempted and replayed (which rebuilds its state), and two
    sequences that arrive later and take the state slots of finished ones
    while a tick is in flight. Every logit the decode programs gave, at
    every position of every sequence, against the reference's whole forward
    of that sequence's tokens."""
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


def test_a_dead_tail_written_into_the_state_is_seen(params, monkeypatch):
    """A prefill program that left the z of its BUCKET's last rows as the
    state (a chunk of 3 valid tokens runs in a bucket of 4) lies far from
    the reference."""
    seqs = _mixed([(12, 6), (20, 5)])                # chunks 8 + 3, 8 + 8 + 3
    monkeypatch.setattr(
        lm, "next_state",
        lambda zz, n_valid, cfg: zz[zz.shape[0] - (cfg.conv_taps - 1):])
    seen = _drive(_engine(params), seqs)
    assert _against_the_reference(seqs, seen, sound=False) > 100 * TOL


def test_a_reused_slot_starts_from_noughts_in_the_program(params):
    """The state pool is left full of large values, as a last owner might
    have left it: the first program of every sequence ignores what its slot
    holds, and nothing on the host clears it."""
    eng = _engine(params)
    eng.state_pool = jnp.full_like(eng.state_pool, 50.0)
    seqs = _mixed([(1, 5), (12, 6), (9, 4)])
    assert _against_the_reference(seqs, _drive(eng, seqs)) < TOL


def test_engine_takes_the_module_and_refuses_what_it_does_not_run(params):
    eng = _engine(params)
    assert not eng.latent and eng.v_pool is None and eng.k_scale is None
    # the attention layers' rows in one pool under the K pool's name, the
    # convolution layers' states beside it: a slot a sequence and scratch
    assert eng.k_pool.shape == (CFG.n_attn, 40 * 8, 64)
    assert eng.state_pool.shape == (CFG.n_conv, 4 + 1, 2, 64)
    assert eng.pool_labels == ("kv_pool", "state_pool")
    assert eng.kv_block_bytes() == CFG.n_attn * 8 * 64 * 4
    assert eng.decode_route() == eng._prefill_route() == "xla"
    assert _engine(params, decode_impl="pallas").decode_route() == "pallas"
    assert eng._bucket_widths() == [8]              # one width: the widest
    for kw, word in [(dict(spec_decode=2), "spec_decode"),
                     (dict(kv_dtype="int8"), "kv_dtype int8"),
                     (dict(weight_dtype="int8"), "weight_dtype int8")]:
        with pytest.raises(ValueError, match=f"lfm2_moe: {word}"):
            _engine(params, **kw)


def test_both_pools_are_updated_in_place_in_the_compiled_programs(params):
    """tests/test_serve_pool_inplace.py's contract for the KV pool and the
    state pool: pools that dwarf the program (1,024 blocks of 16: an
    attention layer's slab is 4 MiB; 512 state slots), donated and threaded
    through the layer walk: a compiled program holds no temporary of a slab
    and aliases both pools whole to its outputs."""
    eng = _engine(params, max_batch=2, num_blocks=1024, block_size=16,
                  max_seq_len=64)
    eng.state_pool = jnp.zeros(
        (CFG.n_conv, 4096) + eng.state_pool.shape[2:], CFG.dtype)
    w = eng._bucket_widths()[0]
    programs = {"decode": (eng._decode_fn(2, w), 2),
                "prefill": (eng._prefill_fn(8, w), 8)}
    slab = min(eng.k_pool[0].nbytes, eng.state_pool[0].nbytes)
    pools = eng.k_pool.nbytes + eng.state_pool.nbytes
    for family, (fn, n) in programs.items():
        mem = fn.lower(eng.params, *eng._pools(),
                       *eng.bucket_tail(family, n, w)).compile(
            ).memory_analysis()
        assert mem.temp_size_in_bytes < slab, family
        assert mem.alias_size_in_bytes >= pools, family


def test_servelint_audits_both_pools_donation(params):
    """analysis/serve_trace.py's walker on a hybrid engine's programs: both
    pools are donated (and nothing else), params are not, and the grid it
    enumerates from the `EngineConfig` is the grid `warmup()` builds."""
    from distributed_neural_network_tpu.analysis import serve_trace as st

    eng = _engine(params)
    grid = st.enumerate_grid(eng.ecfg, latent=True)
    for family, key in (("decode", (2, 8)), ("prefill", (8, 8))):
        program = st.bucket_program(eng, family, key)
        assert program.donate == (1, 2)
        assert program.donate_labels == ("params", "kv_pool", "state_pool")
        analysis = st.analyze_serve_program(program)
        assert not analysis.errors, analysis.errors
        assert sum(analysis.facts.donated_invars) == 2
    assert eng.warmup() == st.grid_total(grid) == 3 + 4


def test_the_tick_publishes_its_counters(params):
    eng = _engine(params, decode_impl="pallas")
    n = eng.warmup()
    registry = MetricsRegistry()
    scheduler = ServeScheduler(eng, SchedulerConfig(), registry=registry)
    try:
        seqs = _mixed([(20, 6), (20, 6), (20, 6)], seed=0)
        for s in seqs:
            eng.add(s)
        live = read = held = pairs = experts_read = experts_held = 0
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
                moe = stats["moe"]
                held += moe["held"]
                pairs += moe["held"] + moe["absent"]
                experts_read += moe["experts_read"]
                experts_held += moe["experts_held"]
                assert moe["load"].shape == (CFG.n_moe, 8)
                assert moe["absent"] == 0           # every expert is held
        assert eng.compiled_programs()["total"] == n  # nothing new compiled
        text = registry.render()
    finally:
        scheduler.close()
    # every token of every program chose top_k experts in 4 expert layers
    tokens = sum(s.prompt_len - 1 + len(s.out) for s in seqs)
    assert held == pairs == tokens * CFG.top_k * CFG.n_moe
    assert 0 < experts_read < experts_held and experts_held % (4 * 8) == 0
    assert peak_slots == 3 and read >= live > 0
    for line in (
        f'serve_moe_experts_total{{kind="read"}} {experts_read}',
        f'serve_moe_experts_total{{kind="held"}} {experts_held}',
        f'serve_moe_pairs_total{{where="held"}} {held}',
        f'serve_decode_positions_total{{kind="live"}} {live}',
        f'serve_decode_positions_total{{kind="read"}} {read}',
        f'serve_attn_kernel_positions_total{{path="decode"}} {live}',
        'serve_state_slots_in_use 0',
    ):
        assert line in text, line
    assert 'serve_attn_kernel_pairs_total{path="prefill"} 0' in text


def test_the_server_takes_the_configuration_file(params):
    """`python -m distributed_neural_network_tpu.serve --model-config`'s
    own assembly of the model: the family's module by name, built from the
    file, served by the engine."""
    from distributed_neural_network_tpu import models

    family = models.family_module(MODEL["family"])
    assert family is lm and family.CACHE == "hybrid"
    cfg = family.from_published(MODEL, dtype=jnp.float32)
    tree = family.init_params(jax.random.key(0), cfg)
    want = jax.tree.map(lambda s: s, lm.param_shapes(cfg),
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
    logits = np.asarray(lm.apply(
        tree, jnp.asarray(seq.prompt + seq.out, jnp.int32), cfg))
    assert list(logits[9:13].argmax(-1)) == seq.out
