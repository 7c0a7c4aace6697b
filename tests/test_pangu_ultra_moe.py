"""The `pangu_ultra_moe` module and its serving path against the family's
plain reference (`benchmark/families/pangu_ultra_moe/reference.py`: float32,
the expanded form of latent attention only, nothing of the program imported),
at a small size on the CPU, seeded random weights, float32.

Tolerances. The program and the reference compute the same function in
another order (the absorbed form's `W_uk q` against the expanded `c W_kvb`,
an online softmax over key blocks against one softmax, experts over sorted
tiles against every expert under a mask), so logits of magnitude 0.5 agree to
float32 reassociation: read 2.4e-7 (whole forward) and under 2e-6 (through
the latent pool); the limit is `TOL` = 2e-5. A planted departure (a dropped
sandwich norm, a dropped `k_rope`, a router without its 2.5) moves the logits
by 3e-3 to 0.7, a hundred times `TOL` and more.
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
from distributed_neural_network_tpu.models import pangu_ultra_moe as pm
from distributed_neural_network_tpu.ops.decode_pallas import (
    mla_decode_attention,
    mla_decode_ok,
    mla_prefill_ok,
    paged_read_positions,
)
from distributed_neural_network_tpu.serve.engine import (
    EngineConfig,
    Sequence,
    ServeEngine,
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

    FAMILY = harness.load_family("pangu_ultra_moe", "serve")
finally:
    del sys.path[:1]
# the reference's blocks are sized for 16k rows on the chip; they change no
# value, and a test's sequences are a few dozen tokens
FAMILY.reference.ROW_BLOCK, FAMILY.reference.QUERY_BLOCK = 64, 32

TOL = 2e-5
SEED = 5
MODEL = harness.load_json("families", "pangu_ultra_moe", "tiny.json")
CFG = FAMILY.program.config(MODEL, {}, jnp.float32)
CONFIG_FILE = os.path.join(
    ROOT, "benchmark", "configs", "openpangu-ultra-moe-718b.json")


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


# ------------------------------------------------------------- the module

def test_whole_forward_matches_the_reference(params):
    tok = some_tokens(48)
    rows = np.arange(48)
    got = np.asarray(jax.jit(lambda p, t: pm.apply(p, t, CFG))(params, tok))
    assert np.abs(got - reference_logits(tok, rows)).max() < TOL


@pytest.mark.parametrize("fault", ["no_post_attn_norm", "no_k_rope",
                                   "no_routed_scale"])
def test_a_departure_from_the_equations_is_seen(params, fault):
    """The reference with a sandwich norm, the shared rotary key or the
    routed scaling factor left out lies a hundred `TOL` and more from the
    program: the comparison that passes above would not pass a program that
    dropped one."""
    tok = some_tokens(48)
    rows = np.arange(48)
    got = np.asarray(jax.jit(lambda p, t: pm.apply(p, t, CFG))(params, tok))
    assert np.abs(got - reference_logits(tok, rows, fault)).max() > 100 * TOL


def test_absorbed_attention_is_the_expanded_on_the_same_weights(params):
    """One layer's attention, both forms, every query over its causal
    prefix: `q_lat . row` against `q_nope . k_nope + q_rope . k_rope`, and
    `W_uv^T sum p c` against `sum p v`."""
    lp = pm.layer_params(params, "moe", 1)
    s = 40
    x = jax.random.normal(jax.random.key(2), (s, CFG.d_model))
    pos = jnp.arange(s)
    q_nope, q_rope, rows = pm.block_in(x, lp, CFG, pos)
    expanded = pm.prefill_attention(q_nope, q_rope, pos, lambda j: rows, s,
                                    lp, CFG, key_block=s)
    live = pos[None, :] <= pos[:, None]                 # query i: keys <= i
    o_lat = pm.absorbed_attention(
        pm.absorb_q(q_nope, q_rope, lp, CFG),
        jnp.broadcast_to(rows, (s,) + rows.shape), live, CFG)
    absorbed = pm.unabsorb_o(o_lat, lp, CFG)
    assert np.abs(np.asarray(expanded - absorbed)).max() < TOL


def test_a_chunk_that_starts_past_zero_carries_its_absolute_positions(params):
    """`block_in` on rows 24..39 at positions 24..39 gives the rotary parts
    (of the queries and of the cache rows) the whole sequence gives there,
    and at positions 0..15 it does not."""
    lp = pm.layer_params(params, "dense", 0)
    x = jax.random.normal(jax.random.key(3), (40, CFG.d_model))
    whole = pm.block_in(x, lp, CFG, jnp.arange(40))
    chunk = pm.block_in(x[24:], lp, CFG, 24 + jnp.arange(16))
    wrong = pm.block_in(x[24:], lp, CFG, jnp.arange(16))
    for w, c in zip(whole, chunk):
        assert np.abs(np.asarray(w[24:] - c)).max() < 1e-6
    assert np.abs(np.asarray(whole[1][24:] - wrong[1])).max() > 1e-2
    assert np.abs(np.asarray(whole[2][24:] - wrong[2])).max() > 1e-2


def test_prefill_attention_blocked_over_keys_is_the_unblocked(params):
    lp = pm.layer_params(params, "dense", 0)
    x = jax.random.normal(jax.random.key(4), (64, CFG.d_model))
    pos = jnp.arange(64)
    q_nope, q_rope, rows = pm.block_in(x, lp, CFG, pos)
    one = pm.prefill_attention(q_nope, q_rope, pos, lambda j: rows, 64, lp,
                               CFG, key_block=64)
    blocked = pm.prefill_attention(
        q_nope, q_rope, pos,
        lambda j: jax.lax.dynamic_slice_in_dim(rows, j * 16, 16), 64, lp,
        CFG, key_block=16)
    assert np.abs(np.asarray(one - blocked)).max() < 1e-6


# ---------------------------------------------------------- the expert layer

def test_the_shares_add_up_to_the_uncut_expert_layer(params):
    """Four chips that hold two of the eight routed experts each: their
    routed parts plus the shared expert counted once are the uncut
    reference's expert layer (all eight held). Each share is the program's
    `moe_held_gated_serve`, told which experts it holds."""
    from distributed_neural_network_tpu.parallel.moe import (
        moe_held_gated_serve,
        swiglu,
    )
    z = FAMILY.weights.sizes(MODEL)
    assert z["routed"] == 8
    uncut = dict(MODEL, n_routed_experts=8, published={})
    lp = jax.jit(lambda k: FAMILY.weights.draw_layer(k, uncut, "moe", 0))(
        seed_key(SEED, 1))
    u = jax.random.normal(jax.random.key(6), (40, z["d"]))
    whole = FAMILY.reference.expert_layer(u, lp, uncut, "f32")
    shared = swiglu(u, lp["s_gate"], lp["s_up"], lp["s_down"])
    total, held = shared, 0
    for first in range(0, 8, 2):
        y, stats = moe_held_gated_serve(
            u, lp["router"], lp["e_gate"][first:first + 2],
            lp["e_up"][first:first + 2], lp["e_down"][first:first + 2],
            (lp["s_gate"], lp["s_up"], lp["s_down"]), first=first,
            top_k=z["top_k"], scale=MODEL["routed_scaling_factor"], tile=8)
        total = total + (y - shared)
        held += int(stats["held"])
        assert int(stats["held"] + stats["absent"]) == 40 * z["top_k"]
    assert held == 40 * z["top_k"]          # every pair lands on one share
    assert np.abs(np.asarray(total - whole)).max() < TOL


def test_serving_layout_multiplies_the_tiles_that_own_a_pair(params):
    """Rows multiplied = whole tiles of the experts that were hit, not the
    worst-case buffer; spare rows (`valid` false) route nowhere."""
    from distributed_neural_network_tpu.parallel.moe import (
        moe_held_gated_serve,
    )
    lp = pm.layer_params(params, "moe", 0)
    u = jax.random.normal(jax.random.key(7), (12, CFG.d_model))
    valid = jnp.arange(12) < 9
    args = (lp["router"], lp["e_gate"], lp["e_up"], lp["e_down"],
            (lp["s_gate"], lp["s_up"], lp["s_down"]))
    kw = dict(first=0, top_k=CFG.top_k, scale=CFG.routed_scale, tile=4)
    y, stats = moe_held_gated_serve(u, *args, valid=valid, **kw)
    y9, stats9 = moe_held_gated_serve(u[:9], *args, **kw)
    assert int(stats["held"]) == int(stats9["held"])
    assert int(stats["held"] + stats["absent"]) == 9 * CFG.top_k
    load = np.asarray(stats["load"])
    assert int(stats["multiplied"]) == int((-(-load // 4) * 4).sum())
    assert np.abs(np.asarray(y[:9] - y9)).max() < 1e-6
    # stacked over layers, with the layer's index: the same
    ys, _ = moe_held_gated_serve(
        u, lp["router"], *(params["moe"][k] for k in pm.EXPERT_LEAVES),
        args[-1], valid=valid, layer=jnp.int32(0), **kw)
    assert np.abs(np.asarray(ys - y)).max() == 0.0


def test_the_count_of_the_file_is_the_sum_of_its_shapes():
    """`param_count` of the configuration's file = the sum of
    `weights.shapes` = 4,919,139,840, and with what `published` states put
    back the whole model: 718 to 720 B."""
    with open(CONFIG_FILE) as f:
        model = json.load(f)
    shapes = jax.tree.leaves(FAMILY.weights.shapes(model), is_leaf=is_shape)
    assert (FAMILY.arith.param_count(model) == sum(map(math.prod, shapes))
            == 4_919_139_840)
    pub = model["published"]
    whole = dict(model, published={}, **{k: pub[k] for k in (
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size")})
    assert 718e9 < FAMILY.arith.param_count(whole) < 720e9
    assert set(model["reduced"]) == set(model["reduced_how"])
    # the kernels' work, as the issue reckons it
    assert FAMILY.arith.decode_attn_flops(model, 1) == 5 * 278_528
    assert FAMILY.arith.decode_attn_bytes(model, 1) == 5 * 576 * 2
    assert FAMILY.arith.kv_bytes_per_token(model) == 5_760
    cfg = FAMILY.program.config(model, {}, jnp.bfloat16)
    assert (cfg.n_dense, cfg.n_moe, cfg.experts_held, cfg.n_routed) == (
        1, 4, (0, 16), 256)
    assert cfg.cache_row_width == 576


# ------------------------------------------------------------- the kernel

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 3e-2)])
def test_kernel_interpreted_matches_the_xla_oracle(dtype, tol):
    """Pages through the table, a traced layer, sequences that end inside a
    page, a fetch step's worth and more (8 pages of 8 rows a step, one
    sequence over two steps), a spare row on the scratch block. bfloat16:
    the probabilities are rounded to the pool's type before they weigh the
    values, 2^-9 a term."""
    L, nb, bs, w, rank, h = 2, 40, 8, 128, 64, 4
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(L, nb * bs, w)), dtype)
    q = jnp.asarray(rng.normal(size=(4, h, w)), dtype)
    table = np.zeros((4, 16), np.int32)
    table[0, :12] = np.arange(3, 15)
    table[1, :2] = [1, 2]
    table[2, :1] = [20]
    pos = jnp.asarray([91, 9, 0, 0], jnp.int32)
    table = jnp.asarray(table)

    @jax.jit
    def both(layer):
        o = mla_decode_attention(q, pool, layer, table, pos, block_size=bs,
                                 rank=rank, scale=0.1, interpret=True)
        idx = (table[:, :, None] * bs + jnp.arange(bs)).reshape(4, -1)
        rows = pool[layer][idx].astype(jnp.float32)
        s = jnp.einsum("bhw,bsw->bhs", q.astype(jnp.float32), rows) * 0.1
        s = jnp.where((jnp.arange(16 * bs)[None] <= pos[:, None])[:, None],
                      s, -1e30)
        return o, jnp.einsum("bhs,bsr->bhr", jax.nn.softmax(s, -1),
                             rows[..., :rank])

    for layer in (0, 1):
        o, ref = both(jnp.int32(layer))
        assert o.dtype == dtype
        assert np.abs(np.asarray(o, np.float32) - np.asarray(ref)).max() < tol


def test_kernel_reads_no_page_past_pos():
    """Pages past `pos` hold NaN: a kernel that fetched one would carry it
    into the output (0 x NaN). What it fetches is `paged_read_positions`,
    the engine's `serve_decode_positions_total{kind="read"}`."""
    bs, w, rank = 8, 128, 64
    pool = np.full((1, 12 * bs, w), np.nan, np.float32)
    rng = np.random.default_rng(1)
    pool[0, 3 * bs: 5 * bs] = rng.normal(size=(2 * bs, w))   # blocks 3, 4
    table = jnp.asarray([[3, 4, 7, 9]], jnp.int32)           # 7, 9: unread
    pos = np.asarray([11], np.int32)                         # ends in block 4
    o = mla_decode_attention(
        jnp.asarray(rng.normal(size=(1, 4, w)), jnp.float32),
        jnp.asarray(pool), 0, table, jnp.asarray(pos), block_size=bs,
        rank=rank, scale=0.1, interpret=True)
    assert np.isfinite(np.asarray(o)).all()
    assert paged_read_positions(pos, bs) == 16


def test_prefill_kernel_interpreted_matches_the_expanded_form(monkeypatch):
    """The chunk's queries of every head over pages through the table, two
    fetch steps and a chunk that ends inside a page; pages wholly past the
    last key hold NaN and are not read."""
    import distributed_neural_network_tpu.ops.decode_pallas as dp

    monkeypatch.setattr(dp, "_MLA_PREFILL_KEYS", 64)     # 8 pages a step
    nb, bs, w, rank, nope, v, h, c = 40, 8, 256, 128, 128, 128, 2, 16
    rng = np.random.default_rng(0)
    pool = np.zeros((2, nb * bs, w), np.float32)
    pool[..., :rank + 64] = rng.normal(size=(2, nb * bs, rank + 64))
    wkv = jnp.asarray(0.1 * rng.normal(size=(rank, h * (nope + v))),
                      jnp.float32)
    qn = jnp.asarray(rng.normal(size=(h, c, nope)), jnp.float32)
    qr = np.zeros((h, c, 128), np.float32)
    qr[..., :64] = rng.normal(size=(h, c, 64))
    table_np = np.r_[np.arange(3, 30), np.zeros(5)].astype(np.int32)
    table = jnp.asarray(table_np)
    for pos0, n_valid in ((0, 16), (0, 9), (37, 16), (150, 11), (112, 16)):
        n_keys = pos0 + n_valid
        holed = pool.copy()
        for page in range((n_keys - 1) // bs + 1, 27):
            holed[1, table_np[page] * bs:(table_np[page] + 1) * bs] = np.nan
        o = dp.mla_prefill_attention(
            qn, jnp.asarray(qr), wkv, jnp.asarray(holed), 1, table, pos0,
            n_keys, block_size=bs, rank=rank, scale=0.07, interpret=True)
        rows = jnp.asarray(pool)[1][
            (table[:, None] * bs + jnp.arange(bs)).reshape(-1)]
        kv = (rows[:, :rank] @ wkv).reshape(-1, h, nope + v)
        s = jnp.einsum("hcn,shn->hcs", qn, kv[..., :nope]) + jnp.einsum(
            "hcr,sr->hcs", jnp.asarray(qr), rows[:, rank:rank + 128])
        seen = jnp.arange(rows.shape[0])[None, None, :] <= (
            pos0 + jnp.arange(c))[None, :, None]
        ref = jnp.einsum("hcs,shv->hcv", jax.nn.softmax(
            jnp.where(seen, 0.07 * s, -1e30), -1), kv[..., nope:])
        assert np.isfinite(np.asarray(o)).all()
        assert np.abs(np.asarray(o - ref))[:, :n_valid].max() < 1e-5


def test_kernel_gate():
    assert mla_prefill_ok(64, 640, 512, 128, 128, 128, jnp.bfloat16)
    assert not mla_prefill_ok(64, 640, 512, 128, 128, 64, jnp.bfloat16)
    assert not mla_prefill_ok(64, 512, 512, 128, 128, 128, jnp.bfloat16)
    assert mla_decode_ok(64, 640, 512, jnp.bfloat16)
    assert mla_decode_ok(8, 128, 128, jnp.float32)
    assert not mla_decode_ok(8, 640, 512, jnp.bfloat16)    # half a tile
    assert not mla_decode_ok(64, 576, 512, jnp.bfloat16)   # a row ends in one
    assert not mla_decode_ok(64, 640, 512, jnp.int8)


# ------------------------------------------------------------- the engine

def _engine(params, **kw):
    base = dict(max_batch=4, num_blocks=40, block_size=8, max_seq_len=64,
                prefill_chunk=8, decode_impl="xla")
    return ServeEngine(params, CFG, EngineConfig(**dict(base, **kw)))


def _drive(eng, seqs):
    """Run the engine dry, re-admitting what it preempts; returns {(seq id,
    position): the decode program's logits there}, a replayed position's
    last reading."""
    seen = {}
    run = eng._run_writer

    def recording(fn, *tail):
        out = run(fn, *tail)
        if len(tail) == 5:                    # a decode dispatch
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
    ticks = 0
    while (eng.has_work() or eng.preempted) and ticks < 2000:
        eng.step()
        ticks += 1
        if eng.preempted and eng.kv.can_fit(4):
            eng.add(eng.preempted.popleft())
    assert ticks < 2000
    return seen


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefill_then_decode_through_the_latent_pool_matches_the_reference(
        params, impl):
    """Chunked prefill (chunks of 8, blocks of 8) then decode through the
    latent pool, absorbed form, a batch of mixed lengths that crosses block
    and chunk boundaries, in a pool too small for all of it, so that a
    sequence is preempted and replayed: every logit the decode programs
    gave, at every position of every sequence, against the reference's
    expanded full forward of that sequence's tokens."""
    lens = [(19, 12), (8, 20), (33, 9), (26, 14)]
    seqs = [Sequence(seq_id=i, prompt=list(map(int, some_tokens(n, 10 + i))),
                     max_new_tokens=m) for i, (n, m) in enumerate(lens)]
    eng = _engine(params, num_blocks=10, decode_impl=impl)
    seen = _drive(eng, seqs)
    assert sum(s.preemptions for s in seqs) > 0, "pool was never tight"
    for s in seqs:
        assert len(s.out) == s.max_new_tokens
        full = np.asarray(s.prompt + s.out, np.int32)
        rows = np.arange(s.prompt_len - 1, len(full) - 1)
        ref = reference_logits(full, rows)
        got = np.stack([seen[(s.seq_id, int(r))] for r in rows])
        assert np.abs(got - ref).max() < TOL, s.seq_id
        assert list(ref.argmax(-1)) == s.out        # greedy, token for token


def test_engine_takes_the_module_and_refuses_what_it_does_not_run(params):
    eng = _engine(params)
    assert eng.latent and eng.v_pool is None and eng.k_scale is None
    # one pool of latent rows under the K pool's name; a row is the latent
    # and, in a lane tile of its own, the rotary key (32 + 8 values -> 256)
    assert eng.k_pool.shape == (CFG.n_layers, 40 * 8, 256)
    assert eng.kv_block_bytes() == CFG.n_layers * 8 * 256 * 4
    assert eng.decode_route() == eng._prefill_route() == "xla"
    assert _engine(params, decode_impl="pallas")._prefill_route() == "pallas"
    assert eng._bucket_widths() == [8]              # one width: the widest
    for kw, word in [(dict(spec_decode=2), "spec_decode"),
                     (dict(kv_dtype="int8"), "kv_dtype int8"),
                     (dict(weight_dtype="int8"), "weight_dtype int8")]:
        with pytest.raises(ValueError, match=f"pangu_ultra_moe: {word}"):
            _engine(params, **kw)
    with pytest.raises(ValueError, match="multi-token prediction"):
        pm.from_published(dict(MODEL, num_nextn_predict_layers=1))


def test_latent_pool_is_updated_in_place_in_the_compiled_programs(params):
    """tests/test_serve_pool_inplace.py's contract for the one latent pool:
    a pool that dwarfs the program (1,024 blocks of 16: a layer's slab is 8
    MiB), donated and on the layer scans' carry: a compiled program holds
    no temporary of a slab and aliases the whole pool to its output."""
    eng = _engine(params, max_batch=2, num_blocks=1024, block_size=16,
                  max_seq_len=64)
    i32 = jnp.int32
    w = eng._bucket_widths()[0]
    programs = {
        "decode": (eng._decode_fn(2, w), (
            jnp.zeros((2,), i32), jnp.zeros((2,), i32),
            jnp.zeros((2, w), i32), jnp.zeros((2,), jnp.float32),
            jnp.zeros((2, 2), jnp.uint32))),
        "prefill": (eng._prefill_fn(8, w), (
            jnp.zeros((8,), i32), i32(0), jnp.zeros((w,), i32), i32(0))),
    }
    slab = eng.k_pool[0].nbytes
    for family, (fn, tail) in programs.items():
        mem = fn.lower(eng.params, eng.k_pool, *tail).compile(
            ).memory_analysis()
        assert mem.temp_size_in_bytes < slab, family
        assert mem.alias_size_in_bytes >= eng.k_pool.nbytes, family


def test_servelint_audits_the_one_pools_donation(params):
    """analysis/serve_trace.py's walker on a latent engine's programs: the
    one pool is donated (and nothing else), params are not, and the grid it
    enumerates from the `EngineConfig` is the grid `warmup()` builds."""
    from distributed_neural_network_tpu.analysis import serve_trace as st

    eng = _engine(params)
    grid = st.enumerate_grid(eng.ecfg, latent=True)
    assert grid == {"decode": [(b, 8) for b in (1, 2, 4)],
                    "prefill": [(c, 8) for c in (1, 2, 4, 8)]}
    for family, key in (("decode", (2, 8)), ("prefill", (8, 8))):
        program = st.bucket_program(eng, family, key)
        assert program.donate == (1,)
        assert program.donate_labels == ("params", "latent_pool")
        analysis = st.analyze_serve_program(program)
        assert not analysis.errors, analysis.errors
        donated = analysis.facts.donated_invars
        assert sum(donated) == 1
    assert eng.warmup() == st.grid_total(grid)


def test_warmup_builds_one_width_and_the_tick_publishes_its_counters(params):
    eng = _engine(params, decode_impl="pallas")
    n = eng.warmup()
    # batch 1, 2, 4 at the one width; chunks 1, 2, 4, 8
    assert n == eng.compiled_programs()["total"] == 3 + 4
    registry = MetricsRegistry()
    scheduler = ServeScheduler(eng, SchedulerConfig(), registry=registry)
    try:
        seqs = [Sequence(seq_id=i, prompt=list(map(int, some_tokens(20, i))),
                         max_new_tokens=6) for i in range(3)]
        for s in seqs:
            eng.add(s)
        live = held = multiplied = pairs = 0
        while eng.has_work():
            stats = eng.step()
            scheduler._publish_tick(stats["phase_s"], stats)
            # both kernels run (interpreted): every live position and pair
            # of the tick is handed to a Mosaic call
            pairs += sum(call[2] for call in stats["prefill_calls"])
            if stats["decode_call"]:
                live += stats["decode_call"][2]
            if "moe" in stats:
                held += stats["moe"]["held"]
                multiplied += stats["moe"]["multiplied"]
                assert stats["moe"]["load"].shape == (CFG.n_moe, 4)
        assert eng.compiled_programs()["total"] == n  # nothing new compiled
        text = registry.render()
    finally:
        scheduler.close()
    assert held > 0 and multiplied >= held and pairs > 0
    for line in (
        f'serve_attn_kernel_positions_total{{path="decode"}} {live}',
        f'serve_moe_pairs_total{{where="held"}} {held}',
        f'serve_moe_rows_total{{kind="owned"}} {held}',
        f'serve_moe_rows_total{{kind="multiplied"}} {multiplied}',
        f'serve_attn_kernel_pairs_total{{path="prefill"}} {pairs}',
    ):
        assert line in text, line
    assert 'serve_moe_expert_load_max_over_mean{layer="0"}' in text
    assert 'serve_moe_pairs_total{where="absent"}' in text


def test_routing_counts_reach_the_tick_that_dispatched_them(params):
    """With a tick in flight (`step` dispatches tick n + 1 before it fetches
    tick n) the expert layers' counts that a tick's programs return are
    fetched with that tick's tokens and land in its own stats: every token a
    tick's prefill chunk and decode batch carried chose `top_k` experts in
    each expert layer, held here or absent - also in a tick that only
    prefilled. And the tokens are the reference's."""
    eng = _engine(params)
    seqs = [Sequence(seq_id=i, prompt=list(map(int, some_tokens(n, i))),
                     max_new_tokens=5)
            for i, n in enumerate((20, 9, 3))]
    for s in seqs:
        eng.add(s)
    done = []
    while eng.has_work():
        done.append(eng.step())
        tick = eng._inflight
        if tick is not None:
            # what it dispatched is still on the device, its own to fetch
            assert len(tick.counts) == len(
                tick.stats["prefill_calls"]) + bool(tick.rows)
            assert "moe" not in tick.stats
    assert [st["dispatch"] for st in done] == ["drained"] + ["ahead"] * (
        len(done) - 1)
    assert any(st["decode_call"] is None and st["prefill_calls"]
               for st in done), "no tick that only prefilled"
    for st in done:
        tokens = st["prefill_tokens"] + st["decode_tokens"]
        assert tokens > 0
        moe = st["moe"]
        assert moe["held"] + moe["absent"] == tokens * CFG.top_k * CFG.n_moe
        assert moe["load"].sum() == moe["held"]
    for s in seqs:
        full = np.asarray(s.prompt + s.out, np.int32)
        rows = np.arange(s.prompt_len - 1, len(full) - 1)
        assert list(reference_logits(full, rows).argmax(-1)) == s.out
