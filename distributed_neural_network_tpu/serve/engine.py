"""Continuous-batching decode engine over the paged KV cache.

The execution model, in one paragraph: every engine tick runs ONE
jitted decode step in which each active slot consumes exactly one token
- a prompt token while the sequence is still prefilling (its logits
discarded, except at the last prompt position, which yields the first
generated token), a just-generated token afterwards. Because prompt and
generation tokens ride the same step, sequences JOIN the batch at any
step boundary and RETIRE without draining anyone else - continuous
(in-flight) batching is the default behavior, not a special mode. KV
state lives in the shared paged pool (`kv_cache.py`): the step
scatter-writes each slot's new K/V at ``block_table[pos // bs] * bs +
pos % bs`` and gather-reads each slot's whole table, so one compiled
program serves any mix of sequence lengths at a given (batch,
table-width) bucket.

Two static-shape bucket axes bound compile count: batch size and table
width both round up to powers of two, so a server that has seen B=4/W=2
traffic never compiles again for B<=4/W<=2.

**One tick is always in flight** (docs/SERVING.md "The tick"): `step()`
dispatches tick n + 1's programs BEFORE it fetches tick n's tokens, so
the host's share of a tick (the fetch's return, the clients' tokens, the
scheduler's books and admission, the next tick's arrays) runs while the
device works. Everything tick n + 1's shape depends on is on the host
already - who decodes, at which ``pos``, the bucket, the block table, the
temperatures, the seeds, and who ends by count (`Sequence.dispatched_all`).
Only the VALUE of the input token of a row that decoded in tick n is not:
it is tick n's ``nxt[row]``, on the device, and `_feed_tokens` (a small
program of its own, one a batch bucket) moves it into tick n + 1's ``tok``
there. The pipeline is one tick deep and drains - fetch first, then build -
where the engine sees that it must: a speculative engine's ticks, a tick
with nothing to dispatch, `flush()`.

**Prefill/decode separation** (``prefill_chunk > 1``): long prompts pay
one model call per token on the default path - correct, and bitwise
identical to `models/transformer.py generate` (the parity pin), but a
1000-token prompt would occupy 1000 ticks. The chunked prefill path
processes up to ``prefill_chunk`` prompt tokens of one sequence per
call (causal within the chunk + attention to its cached history),
bounded per tick by ``prefill_token_budget`` so a burst of long prompts
cannot starve the decode batch - decode latency stays one decode step
per tick regardless of prefill backlog. Chunked prefill changes matmul
shapes, so its logits can differ from the token-at-a-time path by float
ulps; greedy token streams are pinned equal in tests at serving shapes.

**Backpressure**: a sequence whose next position needs a block the pool
cannot give is PARKED for the tick (a ``kv_alloc_stall`` ledger
second). If nothing at all could run, the youngest parked sequence is
preempted - blocks freed, position reset - and re-admitted later;
greedy decoding (and the per-position sampling keys) make the replay
deterministic, and already-streamed tokens are not re-emitted.

**Speculative decoding** (``spec_decode = k > 0``): greedy slots break
the one-token-per-tick ceiling (docs/SERVING.md "Speculative decoding").
A drafter - the SAME model early-exited after its first
``spec_draft_layers`` blocks (models/transformer.py early_exit_params) -
proposes k tokens per slot in one call that READS the pool and writes
nothing (`_draft_fn`); one VERIFY step (`_verify_fn`) consumes ``[t0,
d1..dk]`` at ``pos..pos+k``, writes all k+1 KV entries optimistically and
returns the greedy prediction at every position. The host accepts the
longest matching draft prefix, emits ``a+1`` tokens and REWINDS the
write cursor past the rest (`kv_cache.py rewind`, preemption replay's own
bookkeeping, so greedy streams stay token-exact vs offline `generate()`).
Sampled slots take the plain decode path untouched; preemption replay
feeds already-known tokens back as drafts, k+1 positions a tick.

**Who owns what**: the block's arithmetic is the model module's
(models/transformer.py `block_qkv`, `block_out`, `masked_attention`,
`final_logits`); a bucket family here owns its cache step alone, between
q/k/v and the attention output (decode: one row written, the bucket
gathered, the kernel or `masked_attention`; prefill and verify: the chunk
written, the span gathered; draft: a local buffer beside the pre-gathered
history, nothing written).

**A latent cache** (a module whose ``CACHE`` is ``"latent"``,
models/pangu_ultra_moe.py): a position keeps ONE row for all heads in ONE
pool ``(L, slots, row)``, under the K pool's name. The constructor decides
it once, in Python; the two program families of such an engine
(`_latent_decode`, `_latent_prefill`) have one table width, scan the
module's dense stack and then its expert stack (`_latent_layers`), run the
absorbed form on `mla_decode_attention` in decode and the expanded form on
`mla_prefill_attention` in prefill (``decode_impl`` "xla": the module's own
`jax.numpy` forms, the oracles), and hand the expert layers' routing
counts back with the tokens. Speculative decoding and the int8 pool and
weights are refused for it by name. The per-head programs above are
untouched by it.

**Two kinds of state** (a module whose ``CACHE`` is ``"hybrid"``,
models/lfm2_moe.py, models/mimo_v2.py): the module declares which pool each
of its operator kinds keeps (``POOLS``). A kind of ``"rows"`` keeps a row a
position in ONE paged pool ``(such layers, slots, row)`` under the K pool's
name (LFM2's attention layers, K and V of every KV head side by side;
MiMo's full-attention layers); a kind of ``"state"`` keeps a state of fixed
size a SEQUENCE in the state pool ``(such layers, state slots, ...)``, one
slot a sequence, taken and freed with its blocks (`kv_cache.py`): LFM2's
convolution states, MiMo's window layers' rings of their last ``window``
rows. Both pools are donated to every program and rebound from its outputs.
The two program families (`_hybrid_decode`, `_hybrid_prefill`) have one
table width and walk the model's layers in Python, in the published order
(`_hybrid_layers`). A rows kind's step is the engine's: the module's
``<kind>_in``, the row written, the module's kernel (in decode; in prefill
where it declares one) or `jax.numpy` oracle over the pool,
``<kind>_out``. A state kind's step is the module's whole
(``<kind>_decode``, ``<kind>_prefill``), over its slot of the state pool:
whether what a slot holds is the sequence's own - noughts before position 0,
ring rows whose position is not its own - is decided in the program from
positions, since a freed slot is handed on as it was left. Preemption
replays from the tokens, which rebuilds the state: there is no snapshot.

**What kind of engine this is** is asked of the module once, in the
constructor (`_Cache`): the pools, the program families, the table width,
the kernels' gates and what the module refuses. Nothing below asks again.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field, replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..models import transformer as tfm
from ..models.transformer import TransformerConfig, _sinusoid_pe
from ..ops.decode_pallas import (
    decode_paged_attention,
    mla_decode_attention,
    mla_decode_ok,
    mla_prefill_attention,
    mla_prefill_ok,
    paged_decode_ok,
    paged_read_positions,
)
from ..ops.quant import prequantize_weight, quantized_matmul
from ..runtime import on_tpu
from .kv_cache import SCRATCH_BLOCK, KVCacheConfig, OutOfBlocks, PagedKVCache

_INT8_MAX = 127.0
_SCALE_EPS = 1e-30
_LANES = 128
# rows of one tile of the expert products (parallel/moe.py
# `moe_held_gated_serve`): a decode tick's few tokens, a prefill chunk's many
_MOE_TILE_SMALL, _MOE_TILE = 16, 128
# cache positions one step of the blocked prefill attention expands and
# scores (models/pangu_ultra_moe.py `prefill_attention`)
_PREFILL_KEY_BLOCK = 1024

# the phases that partition `ServeEngine.step`, and the parts of its two
# host phases (`prefill_host`, `decode_host`) that build a tick and hand it
# to the device (docs/SERVING.md "Where a tick goes")
STEP_PHASES = ("prefill_host", "decode_host", "fetch", "emit", "spec",
               "release")
HOST_PARTS = ("select", "stage", "dispatch")


class Phases:
    """The time of one thread by phase, on one clock, each phase also a
    ``serve.<name>`` `TraceAnnotation` (inert while no profile is taken).
    `to` reads the clock once: the phase under way ends there, its span
    closes, and the next one's opens. So a profile's spans and the seconds
    in ``s`` are the same intervals, and consecutive `to` calls leave no
    instant between their phases out."""

    def __init__(self, names: tuple, clock=time.monotonic):
        self.names, self.clock = names, clock
        self.s = dict.fromkeys(names, 0.0)
        self.open = None        # (name, span) of the phase under way
        self.t = 0.0            # when it began

    def to(self, name: str | None) -> float:
        """End the phase under way now and begin ``name`` (None: none);
        returns now."""
        t = self.clock()
        if self.open is not None:
            self.s[self.open[0]] += t - self.t
            self.open[1].__exit__(None, None, None)
            self.open = None
        self.t = t
        if name is not None:
            span = TraceAnnotation("serve." + name)
            span.__enter__()
            self.open = (name, span)
        return t

    def take(self) -> dict:
        """The seconds counted up to now; the count starts again, the phase
        under way going on in it."""
        fresh = dict.fromkeys(self.names, 0.0)
        if self.open is not None:
            t = self.clock()
            self.s[self.open[0]] += t - self.t
            self.t = t
        s, self.s = self.s, fresh
        return s


# the weight matrices --precision int8-w stores quantized (per-column
# int8 codes + per-column f32 scales, ops/quant.py prequantize_weight);
# embeddings (a lookup), layer norms and biases stay f32
_QUANT_WEIGHT_KEYS = ("wq", "wk", "wv", "wo", "w1", "w2")


def _prequantize_params(params):
    """Quantize every transformer-block matmul weight of the (dense)
    param tree once at engine init: each ``w`` becomes ``{"q": int8
    (n, k), "s": f32 (n,)}`` - exactly the pair `ops/quant.py
    quantized_matmul` consumes as a prequantized right operand. Stacked
    layer weights keep their leading layer axis, so the jitted steps'
    layer scan is unchanged. The head (logit) projection stays full
    precision: it feeds the argmax directly, so quantizing it flips
    top-1 tokens far more than any block weight, for a d_model x vocab
    sliver of the weight bytes."""
    layers = dict(params["layers"])
    for key in _QUANT_WEIGHT_KEYS:
        q, s = prequantize_weight(layers[key])
        layers[key] = {"q": q, "s": s}
    out = dict(params)
    out["layers"] = layers
    return out


def _make_mm(weight_quantized: bool, dt):
    """The one matmul the jitted steps route every weight through:
    plain ``x @ w`` at the model dtype, or - under int8-w - the
    low-precision dot against the prequantized codes (activation rows
    quantized per call, int8 x int8 -> int32, f32 dequant)."""
    if not weight_quantized:
        return tfm.plain_mm(dt)

    def mm(x, w):
        shp = x.shape
        y = quantized_matmul(x.reshape(-1, shp[-1]), (w["q"], w["s"]),
                             weight_only=True)
        return y.astype(dt).reshape(*shp[:-1], y.shape[-1])
    return mm


@dataclass(frozen=True)
class EngineConfig:
    """Serving-side knobs (model geometry lives in TransformerConfig)."""

    max_batch: int = 8          # decode-slot cap = largest batch bucket
    num_blocks: int = 64        # shared pool size (incl. scratch block)
    block_size: int = 16        # tokens per KV block
    max_seq_len: int = 512      # prompt + generation hard cap
    prefill_chunk: int = 1      # 1 = exact token-at-a-time prefill
    prefill_token_budget: int = 0   # 0 = one chunk call per tick
    eos_token: int | None = None    # retire on this token id
    # "bf16" = pool in the model dtype; "int8" = quantized pool with
    # per-(block, head) f32 scales - ~2x the concurrent-sequence
    # capacity per HBM byte (the exact multiplier:
    # analysis/cost.py kv_block_bytes), quantize-on-append +
    # dequantize-in-step, accuracy gated vs the bf16 oracle
    # (docs/SERVING.md "int8 KV cache")
    kv_dtype: str = "bf16"
    # the decode step's attention: "xla" = gather the table's span out of
    # the pool, then the einsum/softmax/einsum chain (`masked_attention`);
    # "pallas" = the paged decode kernel (ops/decode_pallas.py
    # `decode_paged_attention`), which reads each sequence's live pages
    # from the pool through the block table; "auto" = pallas on a TPU
    # where a pool page is a tile the kernel compiles for
    # (`paged_decode_ok`; an int8 pool is not), xla otherwise (off-TPU
    # the kernel only runs interpreted - a test vehicle, not a fast path)
    decode_impl: str = "auto"
    # speculative decoding: k > 0 lets each GREEDY slot emit up to k+1
    # tokens per tick (draft k with the early-exit drafter, verify all
    # of them in one multi-position target step, rewind the rejected
    # suffix). 0 = off (every slot is one token per tick).
    # docs/SERVING.md "Speculative decoding"
    spec_decode: int = 0
    # early-exit depth of the drafter (first E blocks of the same
    # model); 0 = auto: max(1, n_layers // 8) - the measured
    # sweet spot where draft agreement stays useful while the drafter's
    # weight traffic stays a small fraction of the target step's
    spec_draft_layers: int = 0
    # "bf16" = params at the model dtype; "int8" = every matmul weight
    # stored int8 + per-column f32 scales (ops/quant.py
    # prequantize_weight), consumed by quantized_matmul in every jitted
    # step - the --precision int8-w path, accuracy gated >= 99% top-1
    # vs the bf16 oracle like int8-kv (composes with it)
    weight_dtype: str = "bf16"

    def __post_init__(self):
        for name in ("kv_dtype", "weight_dtype"):
            if getattr(self, name) not in ("bf16", "int8"):
                raise ValueError(
                    f"{name} must be 'bf16' or 'int8', got "
                    f"{getattr(self, name)!r}"
                )
        if self.decode_impl not in ("auto", "xla", "pallas"):
            raise ValueError(
                f"decode_impl must be auto/xla/pallas, got "
                f"{self.decode_impl!r}"
            )
        if self.spec_decode < 0:
            raise ValueError(
                f"spec_decode must be >= 0, got {self.spec_decode}"
            )
        if self.spec_draft_layers < 0:
            raise ValueError(
                f"spec_draft_layers must be >= 0 (0 = auto), got "
                f"{self.spec_draft_layers}"
            )

    def kv(self) -> KVCacheConfig:
        return KVCacheConfig(
            num_blocks=self.num_blocks,
            block_size=self.block_size,
            max_seq_len=self.max_seq_len,
        )


@dataclass
class Sequence:
    """One in-flight request's decode state (engine-internal; the
    scheduler owns queueing/streaming around it).

    ``pos`` counts positions DISPATCHED (handed to a program, whether or
    not it has finished); ``out`` and ``emitted`` count tokens FETCHED. A
    tick's tokens are fetched after the next tick is dispatched
    (`ServeEngine.step`), so between the two at most one generated token
    a sequence is on the device alone: the one at index ``len(out)``."""

    seq_id: int
    prompt: list
    max_new_tokens: int
    temperature: float = 0.0
    seed: int = 0
    on_token: object = None  # callable(seq, token_id, done) or None

    pos: int = 0               # positions dispatched (= KV entries written
    #                            once the programs in flight have run)
    out: list = field(default_factory=list)
    emitted: int = 0           # tokens already streamed (preempt replay)
    finished: bool = False
    preemptions: int = 0
    t_first_token: float | None = None

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)

    @property
    def in_prefill(self) -> bool:
        return self.pos < self.prompt_len

    @property
    def dispatched_all(self) -> bool:
        """Every position this request can consume is dispatched: it ends
        by count (``max_new_tokens``) when the tick in flight is fetched."""
        return self.pos >= self.prompt_len - 1 + self.max_new_tokens

    @property
    def input_in_flight(self) -> bool:
        """The token at ``pos`` is the output of a program whose result
        the host has not fetched yet."""
        return self.pos - self.prompt_len >= len(self.out)

    def next_input(self) -> int:
        """The token this sequence consumes at its current position."""
        if self.pos < self.prompt_len:
            return int(self.prompt[self.pos])
        return int(self.out[self.pos - self.prompt_len])

    def total_len(self) -> int:
        return self.prompt_len + self.max_new_tokens


def export_descriptor(seq: Sequence) -> dict:
    """A live sequence as a migration descriptor: everything a PEER
    replica needs to re-derive the exact remaining stream by
    deterministic re-prefill replay (serve/fleet.py drain/failover).

    The contract is the same one preemption replay rests on: the seeded
    model is identical on every replica, greedy decode is a pure
    function of the token history, and sampled slots key on the
    ABSOLUTE position (`_row_keys` folds ``pos`` into the request
    seed's key) - so prefilling ``prompt + already-emitted tokens`` on
    any replica reconstructs the byte-identical KV state (and, for a
    model that keeps one, each convolution layer's state in the state
    pool: no pool's contents are exported) and the next sampling key,
    and the
    continuation matches the stream a single never-failing replica
    would have produced. ``emitted`` holds only tokens the client has
    already seen (the dedup rule: they become prompt on resume, never
    re-streamed)."""
    emitted = [int(t) for t in seq.out[: seq.emitted]]
    return {
        "seq_id": int(seq.seq_id),
        "prompt": [int(t) for t in seq.prompt],
        "emitted": emitted,
        "max_new_tokens": int(seq.max_new_tokens),
        "remaining_tokens": int(seq.max_new_tokens) - len(emitted),
        "temperature": float(seq.temperature),
        "seed": int(seq.seed),
        "preemptions": int(seq.preemptions),
    }


def resume_request(desc: dict) -> dict:
    """The re-dispatch request body for a migrated descriptor: emitted
    tokens are folded into the prompt (re-prefill replay) and the token
    budget shrinks by the tokens already streamed. Raises ValueError
    when nothing remains to generate (the caller should synthesize the
    done frame itself - it already holds the full stream)."""
    emitted = [int(t) for t in desc.get("emitted") or ()]
    remaining = int(desc["max_new_tokens"]) - len(emitted)
    if remaining < 1:
        raise ValueError(
            f"descriptor for seq {desc.get('seq_id')} has no tokens "
            f"left to generate ({len(emitted)} already emitted)"
        )
    return {
        "prompt": [int(t) for t in desc["prompt"]] + emitted,
        "max_new_tokens": remaining,
        "temperature": float(desc.get("temperature", 0.0)),
        "seed": int(desc.get("seed", 0)),
    }


def resume_sequence(desc: dict, *, seq_id: int | None = None,
                    on_token=None) -> Sequence:
    """Import a migration descriptor as a fresh `Sequence` on this
    engine (the direct, HTTP-less form of `resume_request`). The
    emitted tokens ride as prompt, so the engine prefills them and the
    first token it EMITS is the first one the client has not seen."""
    body = resume_request(desc)
    return Sequence(
        seq_id=int(desc["seq_id"]) if seq_id is None else int(seq_id),
        prompt=body["prompt"],
        max_new_tokens=body["max_new_tokens"],
        temperature=body["temperature"],
        seed=body["seed"],
        on_token=on_token,
    )


def _read_rows(pool, l, idx):
    """Rows ``idx`` of layer ``l`` of a stacked ``(L, n, ...)`` pool (a
    KV pool, or its ``(L, num_blocks, H)`` int8 scales): ONE gather from
    the flat ``(L * n, ...)`` view at ``l * n + idx``, so ``pool[l]`` is
    never a value of the program. ``l`` may be an array that broadcasts
    against ``idx`` (the drafter reads its E layers at once)."""
    n = pool.shape[1]
    return pool.reshape((-1,) + pool.shape[2:])[l * n + idx]


def _write_rows(pool, l, idx, val, op: str = "set"):
    """`_read_rows`' counterpart: ONE scatter (``op`` = "set" or "max")
    of ``val`` into rows ``idx`` of layer ``l``, returned at the pool's
    own shape. On a pool that is donated AND loop-carried the scatter
    updates in place and moves ``val``'s bytes alone
    (tests/test_serve_pool_inplace.py)."""
    n = pool.shape[1]
    flat = pool.reshape((-1,) + pool.shape[2:])
    return getattr(flat.at[l * n + idx], op)(val).reshape(pool.shape)


def _span_idx(table, bs: int):
    """The pool rows of a block table's whole span, in order: block ids
    (..., W) -> slots (..., W * bs)."""
    return (
        (table * bs)[..., None] + jnp.arange(bs)
    ).reshape(*table.shape[:-1], -1)


def _read_span(pool, scales, l, table, idx, bs: int, dt):
    """The table's span at layer ``l`` as (..., S, H, Dh) values at
    ``dt``: the rows as they are, or - under an int8 pool, ``scales`` not
    None - dequantized by their blocks' (block, head) scales, which ride
    the same block-table addressing: one repeat a block, (..., W, H) ->
    (..., W * bs, H)."""
    rows = _read_rows(pool, l, idx)
    if scales is None:
        return rows
    slot = jnp.repeat(_read_rows(scales, l, table), bs, axis=-2)
    return (rows.astype(jnp.float32) * slot[..., None]).astype(dt)


def _round_q8(x):
    return jnp.clip(jnp.round(x), -_INT8_MAX, _INT8_MAX).astype(jnp.int8)


def _shrink(s_old, s_new):
    """What a block's stored codes are multiplied by when its scale grows
    from s_old to s_new (1 where the block is still empty)."""
    return jnp.where(
        s_new > 0.0, s_old / jnp.maximum(s_new, _SCALE_EPS), 1.0
    )


def _append_block(pool, scales, l, val, *, blk, rows, flat):
    """Decode's write: one new row ``val`` (B, H, Dh) a slot, at pool row
    ``flat`` (B,) of block ``blk`` (B,), whose bs rows are ``rows`` (B,
    bs). Returns (pool, scales).

    A bf16 pool (``scales`` None) takes one scatter. An int8 pool
    quantizes on append with a per-(block, head) running scale: a token
    whose amax outgrows the block's scale RE-QUANTIZES the block's
    existing slab under the new scale (one (B, bs) gather/scatter - the
    block is already hot), so every stored code is always ``value /
    scales[block]``. Scale growth is monotone per block and both it and
    the re-rounding depend only on this sequence's own writes -
    preemption replay is bitwise (tested)."""
    if scales is None:
        return _write_rows(pool, l, flat, val), None
    a = jnp.max(jnp.abs(val.astype(jnp.float32)), -1)      # (B, H)
    s_old = _read_rows(scales, l, blk)                     # (B, H)
    s_new = jnp.maximum(s_old, a / _INT8_MAX)
    slab = _read_rows(pool, l, rows).astype(jnp.float32)   # (B, bs, H, Dh)
    pool = _write_rows(
        pool, l, rows,
        _round_q8(slab * _shrink(s_old, s_new)[:, None, :, None]),
    )
    pool = _write_rows(pool, l, flat, _round_q8(
        val.astype(jnp.float32) / jnp.maximum(s_new[..., None], _SCALE_EPS)
    ))
    return pool, _write_rows(scales, l, blk, s_new)


def _append_span(pool, scales, l, val, *, table, idx, blkv, flat,
                 valid=None):
    """Prefill's and verify's write: new rows ``val`` (..., n, H, Dh) at
    pool rows ``flat`` (..., n) of blocks ``blkv`` (..., n) of the span
    ``idx`` (..., S) of ``table`` (..., W); verify has a batch axis in
    front, prefill's one sequence has none and masks its chunk's dead
    tail with ``valid`` (n,). Returns (pool, scales).

    The span form of `_append_block` under an int8 pool: the new rows'
    per-block amax arrives by scatter-max (commutative -> deterministic
    under duplicate block ids), then the whole span is re-quantized under
    the grown scales (it is being gathered for attention anyway) and the
    new rows written at their final scales."""
    if scales is None:
        return _write_rows(pool, l, flat, val), None
    bs = idx.shape[-1] // table.shape[-1]
    a = jnp.max(jnp.abs(val.astype(jnp.float32)), -1)      # (..., n, H)
    if valid is not None:
        a = jnp.where(valid[:, None], a, 0.0)
    s_old = _read_rows(scales, l, table)                   # (..., W, H)
    scales = _write_rows(scales, l, blkv, a / _INT8_MAX, "max")
    s_new = _read_rows(scales, l, table)
    shrink = jnp.repeat(_shrink(s_old, s_new), bs, axis=-2)
    slab = _read_rows(pool, l, idx).astype(jnp.float32)    # (..., S, H, Dh)
    pool = _write_rows(pool, l, idx, _round_q8(slab * shrink[..., None]))
    s_tok = _read_rows(scales, l, blkv)                    # (..., n, H)
    pool = _write_rows(pool, l, flat, _round_q8(
        val.astype(jnp.float32) / jnp.maximum(s_tok[..., None], _SCALE_EPS)
    ))
    return pool, scales


def _scan_layers(cfg, mm, params, x, pools, cache_step):
    """The layers of a pool-writing bucket program (decode, prefill,
    verify): each is the model's block (models/transformer.py `block_qkv`,
    `block_out`) around the family's own ``cache_step(q, k, v, l, pools)
    -> (o, pools)``, which writes the new rows at layer ``l`` and attends
    over what it reads back. Returns (x, pools).

    ``pools`` = (k_pool, v_pool, k_scale, v_scale), the scales None under
    a bf16 pool (an empty pytree: nothing carried). Donated pools are
    updated in place only if they also ride the CARRY, the layer index in
    xs: one buffer from the argument to the output, rows addressed at
    layer l, no layer's slab ever a value (as scan xs/ys they are two
    buffers and the compiler copies the pool to alias them: the parent of
    PR 25 moved 4.8 GB pools several times a program;
    tests/test_serve_pool_inplace.py pins it on the compiled programs).
    The scan is not unrolled: a body of one layer feeds the matmuls from
    the stacked weights, a body of eight slices their weights out into a
    temporary first (17 ms of a 45 ms decode program at 1.3 B; PERF.md
    section 6)."""
    def layer_step(carry, layer):
        x, pools = carry
        lp, l = layer
        q, k, v = tfm.block_qkv(x, lp, cfg, mm)
        o, pools = cache_step(q, k, v, l, pools)
        x, _ = tfm.block_out(x, o, lp, cfg, mm)
        return (x, pools), None

    (x, pools), _ = jax.lax.scan(
        layer_step, (x, pools),
        (params["layers"], jnp.arange(cfg.n_layers)),
    )
    return x, pools


def _write_then_attend(q, k, v, l, pools, *, at, live, dt):
    """Prefill's and verify's cache step: write the new rows (`at`:
    `_append_span`'s addressing), read the table's span back - the rows
    just written included - and attend under ``live``. Verify's q/k/v are
    (B, K, H, Dh) under a (B, W) table; prefill's one sequence has a (W,)
    table, and the leading axis of 1 of its (1, C, H, Dh) comes off for
    the write and goes back on for the attention."""
    k_pool, v_pool, k_scale, v_scale = pools
    table, idx = at["table"], at["idx"]
    bs = idx.shape[-1] // table.shape[-1]
    one_seq = table.ndim == 1
    if one_seq:
        k, v = k[0], v[0]
    k_pool, k_scale = _append_span(k_pool, k_scale, l, k, **at)
    v_pool, v_scale = _append_span(v_pool, v_scale, l, v, **at)
    ks = _read_span(k_pool, k_scale, l, table, idx, bs, dt)
    vs = _read_span(v_pool, v_scale, l, table, idx, bs, dt)
    if one_seq:
        ks, vs = ks[None], vs[None]
    o = tfm.masked_attention(
        q, ks.transpose(0, 2, 1, 3), vs.transpose(0, 2, 1, 3), live, dt
    )
    return o, (k_pool, v_pool, k_scale, v_scale)


def _next_tokens(logits, temps, keys):
    """A decode program's last step: each row's next token from its logits
    (B, vocab) - the argmax where its temperature is 0, a draw from
    ``logits / temperature`` under its key otherwise. (B,) int32."""
    greedy = jnp.argmax(logits, axis=-1)
    sampled = jax.vmap(
        lambda k_, lg, t: jax.random.categorical(
            k_, lg / jnp.maximum(t, 1e-6)
        )
    )(keys, logits, temps)
    return jnp.where(temps > 0.0, sampled, greedy).astype(jnp.int32)


def _pad_last(x, width: int):
    """x (..., n) -> (..., width), noughts behind."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def _latent_layers(cfg, params, x, pool, cache_step, valid):
    """The layers of a latent program: the dense stack, then the expert
    stack, each a scan of the module's block (`block_in` inside
    ``cache_step(x, lp, l, pool) -> (o, pool)``, `block_out` here) with
    the pool on the CARRY and the model's layer index in xs, as
    `_scan_layers` has them and for its reasons. Returns (x, pool,
    counts): counts the expert layers' routing, packed as int32 - pairs
    held, pairs absent, rows multiplied, then each expert layer's load
    over the held experts. (A function of the configuration and not a
    method: a compiled program that held the engine would keep its weights
    and pool alive with it.)"""
    mod = cfg.module
    tile = _MOE_TILE_SMALL if x.shape[0] <= 4 * _MOE_TILE_SMALL else _MOE_TILE
    routing = None
    for kind, n, l0 in mod.layer_stacks(cfg):
        # the held experts' matrices stay out of the scan's xs: a tile of
        # the expert products reads the ones it needs from the stack
        held = {k: v for k, v in params[kind].items()
                if k in mod.EXPERT_LEAVES}
        rest = {k: v for k, v in params[kind].items() if k not in held}

        def layer_step(carry, layer, kind=kind, held=held, l0=l0):
            x, pool = carry
            lp, l = layer
            o, pool = cache_step(x, lp, l, pool)
            x, stats = mod.block_out(
                x, o, lp, cfg, kind, tile=tile, valid=valid,
                experts=(held, l - l0) if held else None)
            return (x, pool), stats

        (x, pool), stats = jax.lax.scan(
            layer_step, (x, pool), (rest, l0 + jnp.arange(n)))
        if stats is not None:
            routing = stats
    return x, pool, _pack_counts(routing)


def _pack_counts(routing):
    """The expert layers' routing counts of one program (each leaf stacked
    over the expert layers; None: no expert layer, nothing routed) as one
    int32 vector: pairs held, pairs absent, rows multiplied, then each
    expert layer's load over the held experts (`ServeEngine._moe_stats`)."""
    if routing is None:
        return jnp.zeros((3,), jnp.int32)
    return jnp.concatenate([
        jnp.stack([routing["held"].sum(), routing["absent"].sum(),
                   routing["multiplied"].sum()]),
        routing["load"].reshape(-1),
    ]).astype(jnp.int32)


def _hybrid_layers(cfg, params, x, pools, op_step, valid):
    """The layers of a hybrid program, walked in Python in the model's own
    order (`layer_plan`: an interleaved pattern of two operator kinds and
    two feed-forward kinds is no stack to scan): the operator is the
    program's own ``op_step[kind](x, lp, i, pools) -> (x, pools)`` at index
    ``i`` of that kind's pool (`ServeEngine._hybrid_steps`: the engine's
    paged step around the module's `*_in` / `*_out`, or the module's own
    state step); the feed-forward is the module's. ``pools`` = (KV
    pool, state pool), donated and threaded through every layer: each step
    reads and writes its rows at a static layer index, so the update is in
    place (tests/test_lfm2_moe.py pins it on the compiled programs). A
    layer's matrices are a static slice of their stack; the held experts'
    stay whole and a tile reads `w[layer, expert]` where it lies. Returns
    (x, pools, counts) as `_latent_layers` does."""
    mod = cfg.module
    tile = mod.expert_tile(cfg, x.shape[0])
    held = {k: v for k, v in params.get("moe", {}).items()
            if k in mod.EXPERT_LEAVES}
    routing = []
    for op, oi, ff, fi in mod.layer_plan(cfg):
        x, pools = op_step[op](x, mod.layer_params(params, op, oi), oi, pools)
        fp = {k: v[fi] for k, v in params[ff].items() if k not in held}
        x, stats = mod.feed_forward(
            x, fp, cfg, ff, tile=tile, valid=valid,
            experts=(held, fi) if ff == "moe" else None)
        if stats is not None:
            routing.append(stats)
    return x, pools, _pack_counts(
        jax.tree.map(lambda *xs: jnp.stack(xs), *routing) if routing
        else None)


@jax.jit
def _row_keys(seeds, pos):
    """Each decode row's sampling key from its request's seed (low 32
    bits) and absolute position: (B,) uint32, (B,) int32 -> (B, 2)
    uint32, the bits of ``fold_in(PRNGKey(seq.seed), seq.pos)``, so
    preemption replay and the fleet's re-submission sample the same
    tokens. The result goes into the decode program from the device:
    the host fetches nothing between a tick's prefill and decode
    dispatches. A program of its own, one a batch size, and not part of
    the decode programs: for the TPU the generator is lowered unrolled,
    and one more of it in each of the grid's 40 decode programs made a
    warm server start 10-21 s later (PERF.md section 6, PR 28)."""
    return jax.vmap(
        lambda s, p: jax.random.fold_in(jax.random.PRNGKey(s), p)
    )(seeds, pos)


@jax.jit
def _feed_tokens(board, src, tok):
    """A decode batch's input tokens where some are still on the device:
    row i takes ``board[src[i]]``, the token the tick in flight produced
    for its sequence, where ``src[i] >= 0``, and the host's ``tok[i]``
    otherwise (a prompt's last token, a replayed token). ``board`` is the
    unfetched output of the decode program in flight at the largest
    bucket's length (`_widen`; not donated: the host fetches it
    afterwards), ``src`` and ``tok`` (B,) int32: one program a batch
    bucket, as `_row_keys` has, whatever the bucket in flight. A program
    of its own and not part of the decode programs, for that one's
    reason: the bucket programs keep their text, and a warm start loads
    them from the compile cache."""
    return jnp.where(src >= 0, board[jnp.maximum(src, 0)], tok)


@partial(jax.jit, static_argnums=1)
def _widen(nxt, n: int):
    """A smaller bucket's tokens (B,) at the largest bucket's length (n,),
    noughts behind, so that `_feed_tokens` has one shape to read from and
    a batch that changes its bucket keeps its tick in flight. One program
    a bucket below the largest; the largest needs none."""
    return jnp.pad(nxt, (0, n - nxt.shape[0]))


@dataclass(frozen=True)
class _Cache:
    """What the model's module keeps between a sequence's programs and which
    programs serve it: asked of the module once, in `ServeEngine.__init__`,
    and read everywhere else."""

    # the engine's attributes that hold the donated operands of a bucket
    # program, in the order it takes and returns them
    pools: tuple
    # what servelint's donation audit calls them (analysis/serve_trace.py)
    labels: tuple
    # the one width of a program's block table, in blocks (0: per-head K
    # and V, whose programs come in power-of-two width buckets)
    width: int = 0
    # (B, W) -> the decode program, (C, W) -> the prefill program, of a
    # module that brings its own block (None: the GPT-2 programs)
    decode: object = None
    prefill: object = None
    # the programs hand the expert layers' routing counts back
    routed: bool = False
    # the programs take each sequence's slot of the state pool
    state: bool = False
    # whether the decode kernel compiles for this pool, and what to say
    # where it was asked for and does not
    kernel_ok: bool = False
    kernel_refusal: str = ""
    # whether a prefill attention kernel compiles (None: the module brings
    # none, its chunked prefill is `jax.numpy`)
    prefill_kernel_ok: bool | None = None
    # the (query, key) pairs a layer's prefill kernel scores for a chunk, of
    # a hybrid module that declares one (`prefill_kernel_scored`)
    prefill_scored: object = None
    # (operator kind, "rows" or "state") of a module with two kinds of
    # state, in the order of its `POOLS`: which pool each kind keeps
    kinds: tuple = ()
    # the module's count of a prefill program's attention pairs by layer
    # kind (`attn_pairs`), None where it keeps none
    attn_pairs: object = None


@dataclass
class _Tick:
    """One tick from its dispatch to its landing: what was handed to the
    device and has not been fetched. ``stats`` is the tick's own
    dictionary, begun by `ServeEngine._dispatch` and finished by
    `ServeEngine._land`."""

    stats: dict
    # (sequence, the position it consumed) a row of the decode batch
    rows: list = field(default_factory=list)
    # every sequence these programs write: seq_id -> its row of the decode
    # batch, None for a prefill chunk alone. What it holds is not freed
    # before they have landed
    touched: dict = field(default_factory=dict)
    nxt: object = None          # the decode program's tokens, on the device
    counts: list = field(default_factory=list)   # latent programs' routing
    spec_batch: list = field(default_factory=list)

    @property
    def in_flight(self) -> bool:
        """It dispatched a program (or holds a speculative phase)."""
        return bool(self.rows or self.spec_batch
                    or self.stats["prefill_calls"])


def _seqstat(stats: dict, s: Sequence) -> dict:
    """The tick's entry for sequence ``s`` in ``stats["per_seq"]``."""
    d = stats["per_seq"].get(s.seq_id)
    if d is None:
        d = stats["per_seq"][s.seq_id] = {
            "prefill": 0, "decode": 0, "replayed": 0, "parked": False,
            # speculative sub-attribution (zero when spec off)
            "proposed": 0, "accepted": 0, "draft_s": 0.0, "verify_s": 0.0,
        }
    return d


def _bucket(n: int, lo: int = 1) -> int:
    """Smallest power of two >= n (>= lo)."""
    b = lo
    while b < n:
        b *= 2
    return b


def batch_buckets(max_batch: int) -> list:
    """The decode batch buckets of an engine: the powers of two under
    ``max_batch`` and ``max_batch`` itself (a batch is padded to the
    smallest that holds it, `_dispatch`)."""
    out = [1 << i for i in range(max_batch.bit_length())
           if 1 << i < max_batch]
    return out + [max_batch]


class ServeEngine:
    """The model executor: owns device params + KV pools and advances
    all active sequences one tick at a time, with one tick in flight:
    `step()` dispatches the next tick's programs, then fetches the tick
    before's tokens and hands them out (`_dispatch`, `_land`).
    Single-threaded by contract - exactly one caller (the scheduler
    loop) drives `step()` / `flush()`; admission/cancel mutate the
    active set under `lock` between ticks."""

    def __init__(self, params, cfg, ecfg: EngineConfig, *,
                 clock=time.monotonic):
        # what the module keeps decides which pools and programs are built,
        # here and never inside one: per-head K and V (`TransformerConfig`),
        # one latent row (`CACHE = "latent"`), or a KV row in the attention
        # layers and a state in the others (`CACHE = "hybrid"`)
        mod = cfg.module
        kind = getattr(mod, "CACHE", "")
        if not kind and not isinstance(cfg, TransformerConfig):
            raise ValueError(
                f"{mod.NAME}: the serving "
                "engine does not run this model - its module declares no "
                "cache (`CACHE`) for its Mamba-2 layers' recurrent state, "
                "and the engine's programs know the blocks of the modules "
                "that do"
            )
        self.latent = kind == "latent"
        asked = {"spec_decode": ecfg.spec_decode,
                 "kv_dtype int8": ecfg.kv_dtype == "int8",
                 "weight_dtype int8": ecfg.weight_dtype == "int8"}
        for what, why in getattr(mod, "REFUSED", {}).items():
            if asked[what]:
                raise ValueError(
                    f"{mod.NAME}: {what} is not supported for this "
                    f"module - {why}")
        if not kind and cfg.n_experts:
            raise ValueError(
                "the serving engine supports dense models; MoE decode "
                "routes through models/transformer.py generate()"
            )
        self.cfg = cfg
        self.ecfg = ecfg
        kv_cfg = ecfg.kv()
        if kind == "hybrid":   # a state slot a sequence, and the scratch
            kv_cfg = replace(kv_cfg, state_slots=ecfg.max_batch + 1)
        self.kv = PagedKVCache(kv_cfg)
        self.weight_quantized = ecfg.weight_dtype == "int8"
        if self.weight_quantized:
            params = _prequantize_params(params)
        self.params = jax.device_put(params)
        self._mm = _make_mm(self.weight_quantized, cfg.dtype)
        self.spec_k = ecfg.spec_decode
        self.draft_layers = 0
        self.draft_params = None
        if self.spec_k:
            self.draft_layers = (
                ecfg.spec_draft_layers or max(1, cfg.n_layers // 8)
            )
            if self.spec_k + 1 >= ecfg.max_seq_len:
                raise ValueError(
                    f"spec_decode {self.spec_k} leaves no room under "
                    f"max_seq_len {ecfg.max_seq_len}"
                )
            # the drafter IS the target model early-exited: the stacked
            # layer axis sliced once (embed / final LN / head shared; a
            # prequantized int8-w tree slices the same way); refuses a
            # depth the model does not have
            self.draft_params = tfm.early_exit_params(
                self.params, self.draft_layers)
        slots = self.kv.cfg.pool_slots
        bs = ecfg.block_size
        self.quantized = ecfg.kv_dtype == "int8"
        pool_dt = jnp.int8 if self.quantized else cfg.dtype
        self.v_pool = self.state_pool = None
        self.k_scale = self.v_scale = None
        one_width = _bucket(self.kv.cfg.max_blocks_per_seq)
        if self.latent:
            # ONE pool of latent rows, under the name the K pool has (what
            # holds the engine's cache is asked for by it); a row is padded
            # to whole 128-lane tiles, which is how the device stores the
            # pool's minor axis whatever its length
            # (the rotary key with its padding is a tile of its own, which
            # the prefill kernel scores as it lies: 512 + 64 -> 640)
            self._rope_width = -(-cfg.qk_rope // _LANES) * _LANES
            self.row_width = -(-(cfg.kv_rank + self._rope_width)
                               // _LANES) * _LANES
            self.k_pool = jnp.zeros(
                (cfg.n_layers, slots, self.row_width), pool_dt)
            self._cache = _Cache(
                pools=("k_pool",), labels=("latent_pool",), width=one_width,
                decode=self._latent_decode, prefill=self._latent_prefill,
                routed=True,
                kernel_ok=mla_decode_ok(
                    bs, self.row_width, cfg.kv_rank, pool_dt),
                prefill_kernel_ok=mla_prefill_ok(
                    bs, self.row_width, cfg.kv_rank, cfg.qk_nope,
                    cfg.v_head, self._rope_width, pool_dt),
                kernel_refusal=(
                    f"the latent decode kernel does not compile for pages "
                    f"of {bs} {jnp.dtype(pool_dt)} rows "
                    "(ops/decode_pallas.py mla_decode_ok)"))
        elif kind == "hybrid":
            # the rows of the layers that keep a row a position in ONE pool
            # under the K pool's name, every KV head's keys and values side
            # by side (per-head (H, Dh) minor axes of (8, 64) would be
            # stored in (16, 128) tiles, four times the bytes); the states
            # of the layers that keep one a sequence in the state pool, a
            # slot a sequence
            shapes = mod.cache_shapes(cfg)
            n_kv, self.row_width = shapes["kv"]
            self.k_pool = jnp.zeros((n_kv, slots, self.row_width), pool_dt)
            self.state_pool = jnp.zeros(
                (shapes["state"][0], self.kv.cfg.state_slots)
                + shapes["state"][1:], pool_dt)
            kernel_ok, refusal = mod.kernel_gate(cfg, bs, pool_dt)
            prefill_gate = getattr(mod, "prefill_kernel_gate", None)
            self._cache = _Cache(
                pools=("k_pool", "state_pool"),
                labels=("kv_pool", "state_pool"), width=one_width,
                decode=self._hybrid_decode, prefill=self._hybrid_prefill,
                routed=True, state=True, kernel_ok=kernel_ok,
                kernel_refusal=refusal,
                prefill_kernel_ok=(None if prefill_gate is None
                                   else prefill_gate(cfg, bs, pool_dt)),
                prefill_scored=getattr(mod, "prefill_kernel_scored", None),
                kinds=tuple(mod.POOLS.items()),
                attn_pairs=getattr(mod, "attn_pairs", None))
        else:
            L, H, Dh = cfg.n_layers, cfg.n_heads, cfg.head_dim
            self.k_pool = jnp.zeros((L, slots, H, Dh), pool_dt)
            self.v_pool = jnp.zeros((L, slots, H, Dh), pool_dt)
            if self.quantized:
                # int8 pool + per-(block, head) f32 scales: the one extra
                # small array rides the SAME block-table addressing (scale
                # of slot s = scales[table[s // bs]]), so every gather/
                # scatter index the bf16 path computes is reused verbatim
                self.k_scale = jnp.zeros(
                    (L, ecfg.num_blocks, H), jnp.float32)
                self.v_scale = jnp.zeros(
                    (L, ecfg.num_blocks, H), jnp.float32)
            names = ("k_pool", "v_pool") + (
                ("k_scale", "v_scale") if self.quantized else ())
            self._cache = _Cache(
                pools=names, labels=names,
                kernel_ok=paged_decode_ok(bs, H, Dh, pool_dt),
                kernel_refusal=(
                    f"the paged decode "
                    f"kernel does not read a {self.kv_dtype_name()} pool "
                    f"whose pages are (block_size {bs}, "
                    f"H {H}, Dh {Dh}): it takes a "
                    "float32 or bfloat16 pool with H 2, 4 or a multiple of "
                    "8 and Dh a multiple of 128"))
        self.lock = threading.Lock()
        self.active: list[Sequence] = []
        self._step_fns: dict = {}
        self._prefill_fns: dict = {}
        self._draft_fns: dict = {}
        self._verify_fns: dict = {}
        # family -> largest compiled temp_size_in_bytes, set by warmup()
        self.program_temp_bytes: dict = {}
        # the tick dispatched and not yet fetched (`step`)
        self._inflight: _Tick | None = None
        # a call's seconds by phase, and its host phases' by part, on the
        # clock the scheduler's phases and ledger read too
        self.clock = clock
        self.phase = Phases(STEP_PHASES, clock)
        self.part = Phases(HOST_PARTS, clock)
        self.ticks = 0
        self.decode_tokens = 0
        self.prefill_tokens = 0
        self.stall_events = 0
        # cumulative speculative-decoding counters (the
        # serve_spec_*_tokens_total metrics + /v1/status)
        self.spec_proposed_tokens = 0
        self.spec_accepted_tokens = 0
        self.spec_steps = 0
        # drained from the FRONT by the scheduler (popleft), re-parked at
        # the back on eviction - a deque so both ends are O(1)
        self.preempted: deque[Sequence] = deque()

    # --------------------------------------------------------- lifecycle

    def add(self, seq: Sequence) -> None:
        """Join the batch at the next step boundary. Raises ValueError
        on an over-long request (an admission-time check, not a crash
        mid-flight) - block availability is the scheduler's gate."""
        if seq.total_len() > self.ecfg.max_seq_len:
            raise ValueError(
                f"request needs {seq.total_len()} positions "
                f"(prompt {seq.prompt_len} + {seq.max_new_tokens} new) "
                f"> max_seq_len {self.ecfg.max_seq_len}"
            )
        if not seq.prompt:
            raise ValueError("empty prompt")
        if len(self.active) >= self.ecfg.max_batch:
            raise ValueError(
                f"engine full ({self.ecfg.max_batch} slots) - the "
                "scheduler should hold admission"
            )
        with self.lock:
            self.active.append(seq)

    def cancel(self, seq_id: int) -> bool:
        """Drop a sequence mid-flight (client disconnect); frees its
        blocks. True when it was active. Where the tick in flight holds
        a row or a chunk of it, its programs still write its blocks: the
        sequence only ends here, the token in flight is dropped when that
        tick lands, and its blocks are freed then (`_retire_finished`)."""
        with self.lock:
            for i, s in enumerate(self.active):
                if s.seq_id == seq_id:
                    s.finished = True
                    if not self._touched(seq_id):
                        self.active.pop(i)
                        self._free_seq(seq_id)
                    return True
        return False

    def has_work(self) -> bool:
        """A sequence is active, or a tick is dispatched and unfetched."""
        with self.lock:
            return bool(self.active) or self._inflight is not None

    def _touched(self, seq_id: int) -> bool:
        """The tick in flight holds a row or a chunk of this sequence."""
        return (self._inflight is not None
                and seq_id in self._inflight.touched)

    # ------------------------------------------------- bytes + kv dtype

    def kv_dtype_name(self) -> str:
        """The /metrics ``serve_kv_dtype`` label value."""
        if self.quantized:
            return "int8"
        return "bf16" if self.cfg.dtype == jnp.bfloat16 else "f32"

    def weight_dtype_name(self) -> str:
        """The /metrics ``serve_weight_dtype`` label value."""
        if self.weight_quantized:
            return "int8"
        return "bf16" if self.cfg.dtype == jnp.bfloat16 else "f32"

    def kv_block_bytes(self) -> int:
        """Device bytes of one paged block at this engine's kv dtype
        (K + V + any per-(block, head) scales) - analysis/cost.py's
        table, so the serving occupancy gauges and the autoshard HBM
        gate can never disagree on a byte."""
        from ..analysis.cost import kv_block_bytes, latent_block_bytes

        cfg = self.cfg
        if self.v_pool is None:   # one pool of rows, over its own layers
            return latent_block_bytes(
                self.k_pool.shape[0], self.row_width, self.ecfg.block_size,
                self.kv_dtype_name(),
            )
        return kv_block_bytes(
            cfg.n_layers, cfg.n_heads, cfg.head_dim,
            self.ecfg.block_size, self.kv_dtype_name(),
        )

    def compiled_programs(self) -> dict:
        """Per-bucket-family compiled-program counts (plus ``total``) -
        the live figure ``GET /v1/status`` reports so a deployment can
        be reconciled against the servelint grid manifest
        (analysis/serve_trace.py enumerate_grid): after ``warmup()``
        the counts match the manifest and must never grow while
        serving (a growth is an un-warmed bucket paying its XLA
        compile on a live request)."""
        fams = {
            "decode": len(self._step_fns),
            "prefill": len(self._prefill_fns),
            "draft": len(self._draft_fns),
            "verify": len(self._verify_fns),
        }
        fams["total"] = sum(fams.values())
        return fams

    def _zero_scales(self, blocks) -> None:
        """Under int8 KV a block that goes back to the pool starts again
        from scale 0, or the previous owner's scale would leak into the
        new sequence's quantization (breaking both accuracy and the
        deterministic preemption replay)."""
        if self.quantized and blocks:
            idx = jnp.asarray(blocks, jnp.int32)
            self.k_scale = self.k_scale.at[:, idx, :].set(0.0)
            self.v_scale = self.v_scale.at[:, idx, :].set(0.0)

    def _free_seq(self, seq_id: int) -> int:
        """Free a sequence's blocks (and zero their int8 scales)."""
        blocks = self.kv.seq_block_ids(seq_id) if self.quantized else ()
        n = self.kv.free(seq_id)
        self._zero_scales(blocks)
        return n

    def _attn_route(self) -> str:
        """The decode step's attention: the paged kernel where it is
        asked for or pays, the XLA chain over the gathered span
        otherwise. The kernel compiles where a pool page is whole tiles
        of the pool's dtype (ops/decode_pallas.py `paged_decode_ok`: a
        constraint on block_size, H, Dh and the dtype, none on a
        bucket's width) and never reads an int8 pool."""
        impl = self.ecfg.decode_impl
        if impl == "xla":
            return "xla"
        legal = self._cache.kernel_ok
        if impl == "pallas":
            # off the TPU the kernel runs interpreted and tiles nothing
            if self.quantized or (on_tpu() and not legal):
                raise ValueError(
                    f"decode_impl 'pallas' requested but "
                    f"{self._cache.kernel_refusal} - use decode_impl 'auto'"
                )
            return "pallas"
        # auto: the kernel only pays on TPU (off-TPU it would run the
        # Pallas interpreter - a test vehicle, not a fast path)
        return "pallas" if legal and on_tpu() else "xla"

    # the CLI's ``decode -> ...`` line and ``GET /v1/status``
    decode_route = _attn_route

    def _prefill_route(self) -> str:
        """A module's chunked-prefill attention over its paged pool: a
        Mosaic kernel (a latent module's `mla_prefill_attention`, which
        expands a fetch step's latent rows a head at a time in VMEM; the
        `prefill_kernel` a hybrid module declares with its gate) where
        `decode_impl` asks for kernels and it compiles, the blocked
        `jax.numpy` loop (the module's `prefill_attention`, the oracle)
        otherwise, and always for a module that declares no kernel."""
        impl = self.ecfg.decode_impl
        if impl == "xla" or self._cache.prefill_kernel_ok is None:
            return "xla"
        legal = self._cache.prefill_kernel_ok
        if on_tpu():
            return "pallas" if legal else "xla"
        return "pallas" if impl == "pallas" else "xla"   # interpreted

    def _bucket_widths(self, max_width_blocks: int | None = None) -> list:
        """The power-of-two width buckets (in blocks) up to the cap."""
        if self._cache.width:
            # the kernel and the blocked prefill walk a table's live part
            # on traced bounds: a narrower table buys further programs only
            return [self._cache.width]
        max_w = _bucket(max_width_blocks or self.kv.cfg.max_blocks_per_seq)
        widths = []
        w = 1
        while w <= max_w:
            widths.append(w)
            w *= 2
        return widths

    # ------------------------------------------------------ jitted steps

    def _jit_bucket(self, program, *, writes_pools: bool = True):
        """jit one bucket program, written as ``program(params, k_pool,
        v_pool, k_scale, v_scale, *tail)``. A bf16 pool has no scales:
        its callable is ``(params, k_pool, v_pool, *tail)``, hands the
        program None for both (an empty pytree, so no operand) and drops
        them from what a pool-writing program returns.

        The pools (and under int8 their scales) are donated: every call
        site threads them through and rebinds the outputs, and an
        un-donated pool double-buffers the engine's largest allocation
        for the life of the step (that the update is also in place is
        `_scan_layers`' doing). Params are NEVER donated (they are not
        returned - donating them would free the weights after the first
        call). The drafter READS the pools and returns only draft tokens,
        so it has nothing to alias and donates nothing. servelint audits
        the donation contract per bucket (analysis/serve_trace.py)."""
        if self._cache.decode is not None:
            # a module's own: ``program(params, *pools, *tail)``
            return jax.jit(program, donate_argnums=tuple(
                range(1, 1 + len(self._cache.pools))))
        if self.quantized:
            return jax.jit(
                program, donate_argnums=(1, 2, 3, 4) if writes_pools else ())

        def bucket(params, k_pool, v_pool, *tail):
            out = program(params, k_pool, v_pool, None, None, *tail)
            return out[:2] + out[4:] if writes_pools else out

        bucket.__name__ = program.__name__ + "_bf16"
        return jax.jit(bucket, donate_argnums=(1, 2) if writes_pools else ())

    def _decode_fn(self, B: int, W: int):
        fn = self._step_fns.get((B, W))
        if fn is not None:
            return fn
        if self._cache.decode is not None:
            fn = self._step_fns[(B, W)] = self._jit_bucket(
                self._cache.decode(B, W))
            return fn
        cfg, dt = self.cfg, self.cfg.dtype
        H, Dh = cfg.n_heads, cfg.head_dim
        bs = self.kv.cfg.block_size
        S = W * bs
        use_kernel = self._attn_route() == "pallas"

        def step(params, k_pool, v_pool, k_scale, v_scale,
                 tok, pos, table, temps, keys):
            # tok/pos (B,), table (B, W), temps (B,), keys (B, 2);
            # k_scale/v_scale (L, num_blocks, H) f32, or None
            x = params["embed"][tok].astype(dt)[:, None, :]
            x = x + _sinusoid_pe(pos, cfg.d_model, dt)[:, None, :]
            blk = table[jnp.arange(B), pos // bs]
            at = {
                "blk": blk,
                "rows": blk[:, None] * bs + jnp.arange(bs)[None, :],
                "flat": blk * bs + pos % bs,
            }
            if not use_kernel:
                idx = _span_idx(table, bs)                    # (B, S)
                live = (
                    jnp.arange(S)[None, :] <= pos[:, None]
                )[:, None, None, :]

            def cache_step(q, k, v, l, pools):
                # write this position's row, then attend over the cache
                k_pool, v_pool, k_scale, v_scale = pools
                k_pool, k_scale = _append_block(
                    k_pool, k_scale, l, k.reshape(B, H, Dh), **at)
                v_pool, v_scale = _append_block(
                    v_pool, v_scale, l, v.reshape(B, H, Dh), **at)
                pools = (k_pool, v_pool, k_scale, v_scale)
                if use_kernel:
                    # the kernel fetches each sequence's live pages from
                    # the pools as `_append_block` returned them (the new
                    # row is in them) and masks on `pos` itself
                    o = decode_paged_attention(
                        q.reshape(B, H, Dh), k_pool, v_pool, l, table, pos,
                        block_size=bs, interpret=not on_tpu(),
                    )
                    return o[:, None], pools
                # the oracle: gather the table's span, attend under `live`
                ks = _read_span(k_pool, k_scale, l, table, idx, bs, dt)
                vs = _read_span(v_pool, v_scale, l, table, idx, bs, dt)
                return tfm.masked_attention(
                    q, ks.transpose(0, 2, 1, 3),
                    vs.transpose(0, 2, 1, 3), live, dt,
                ), pools

            x, pools = _scan_layers(
                cfg, self._mm, params, x, (k_pool, v_pool, k_scale, v_scale),
                cache_step,
            )
            logits = tfm.final_logits(params, x[:, 0], dt)
            return *pools, _next_tokens(logits, temps, keys), logits

        fn = self._step_fns[(B, W)] = self._jit_bucket(step)
        return fn

    def _prefill_fn(self, C: int, W: int):
        fn = self._prefill_fns.get((C, W))
        if fn is not None:
            return fn
        if self._cache.prefill is not None:
            fn = self._prefill_fns[(C, W)] = self._jit_bucket(
                self._cache.prefill(C, W))
            return fn
        cfg, dt = self.cfg, self.cfg.dtype
        bs = self.kv.cfg.block_size
        S = W * bs

        def prefill(params, k_pool, v_pool, k_scale, v_scale,
                    toks, pos0, table, n_valid):
            # toks (C,), pos0 scalar, table (W,), n_valid scalar
            pv = pos0 + jnp.arange(C)
            valid = jnp.arange(C) < n_valid
            x = params["embed"][toks].astype(dt)[None]  # (1, C, d)
            x = x + _sinusoid_pe(pv, cfg.d_model, dt)[None]
            flat = table[pv // bs] * bs + pv % bs
            at = {
                "table": table,
                "idx": _span_idx(table, bs),                  # (S,)
                "blkv": jnp.where(valid, table[pv // bs], 0),
                "flat": jnp.where(valid, flat, 0),  # dead tail -> scratch
                "valid": valid,
            }
            # query at chunk offset q attends to positions <= pos0 + q
            live = (
                jnp.arange(S)[None, :] <= pv[:, None]
            )[None, None, :, :]  # (1, 1, C, S)

            x, pools = _scan_layers(
                cfg, self._mm, params, x, (k_pool, v_pool, k_scale, v_scale),
                partial(_write_then_attend, at=at, live=live, dt=dt),
            )
            return *pools, tfm.final_logits(params, x[0], dt)  # (C, vocab)

        fn = self._prefill_fns[(C, W)] = self._jit_bucket(prefill)
        return fn

    # --------------------------------------- latent cache (MLA) programs

    def _latent_decode(self, B: int, W: int):
        cfg, mod = self.cfg, self.cfg.module
        bs, row_width = self.kv.cfg.block_size, self.row_width
        S = W * bs
        use_kernel = self._attn_route() == "pallas"

        def step(params, pool, tok, pos, table, temps, keys):
            # tok/pos (B,), table (B, W), temps (B,), keys (B, 2)
            x = mod.embed_tokens(params, tok, cfg)               # (B, d)
            flat = table[jnp.arange(B), pos // bs] * bs + pos % bs
            valid = table[:, 0] != SCRATCH_BLOCK    # a spare row: no token
            if not use_kernel:
                idx = _span_idx(table, bs)                       # (B, S)
                live = jnp.arange(S)[None, :] <= pos[:, None]

            def cache_step(x, lp, l, pool):
                # write this position's row, then attend over the cache
                # in the absorbed form: the rows as they lie are K and V
                q_nope, q_rope, row = mod.block_in(x, lp, cfg, pos)
                pool = _write_rows(pool, l, flat, _pad_last(row, row_width))
                q_lat = _pad_last(
                    mod.absorb_q(q_nope, q_rope, lp, cfg), row_width)
                if use_kernel:
                    with jax.named_scope("lm.mla.attn"):
                        o_lat = mla_decode_attention(
                            q_lat, pool, l, table, pos, block_size=bs,
                            rank=cfg.kv_rank, scale=cfg.softmax_scale,
                            interpret=not on_tpu(),
                        )
                else:   # the oracle: gather the table's span
                    o_lat = mod.absorbed_attention(
                        q_lat, _read_rows(pool, l, idx), live, cfg)
                return mod.unabsorb_o(o_lat, lp, cfg), pool

            x, pool, counts = _latent_layers(
                cfg, params, x, pool, cache_step, valid)
            logits = mod.final_logits(params, x, cfg)
            return pool, _next_tokens(logits, temps, keys), logits, counts

        step.__name__ = "latent_decode"
        return step

    def _latent_prefill(self, C: int, W: int):
        cfg, mod = self.cfg, self.cfg.module
        bs, row_width = self.kv.cfg.block_size, self.row_width
        rope_width = self._rope_width
        key_block = min(_PREFILL_KEY_BLOCK, W * bs)
        pages = key_block // bs
        use_kernel = self._prefill_route() == "pallas"

        def prefill(params, pool, toks, pos0, table, n_valid):
            # toks (C,), pos0 scalar, table (W,), n_valid scalar
            pv = pos0 + jnp.arange(C)
            valid = jnp.arange(C) < n_valid
            x = mod.embed_tokens(params, toks, cfg)              # (C, d)
            # the chunk's dead tail -> the scratch block
            flat = jnp.where(valid, table[pv // bs] * bs + pv % bs, 0)

            def cache_step(x, lp, l, pool):
                # write the chunk's rows, then the expanded form over the
                # table's live span, the rows just written included, a key
                # block at a time
                q_nope, q_rope, rows = mod.block_in(x, lp, cfg, pv)
                pool = _write_rows(pool, l, flat, _pad_last(rows, row_width))
                if use_kernel:
                    # heads first; the rotary part padded like the rows'
                    with jax.named_scope("lm.mla.attn"):
                        o = mla_prefill_attention(
                            q_nope.transpose(1, 0, 2),
                            _pad_last(q_rope.transpose(1, 0, 2), rope_width),
                            lp["kv_b"].astype(cfg.dtype), pool, l, table,
                            pos0, pos0 + n_valid, block_size=bs,
                            rank=cfg.kv_rank, scale=cfg.softmax_scale,
                            interpret=not on_tpu(),
                        )
                    return o.transpose(1, 0, 2), pool

                def read_rows(j):
                    blk = jax.lax.dynamic_slice_in_dim(
                        table, j * pages, pages)
                    return _read_rows(pool, l, _span_idx(blk, bs))

                o = mod.prefill_attention(
                    q_nope, q_rope, pv, read_rows, pos0 + n_valid, lp, cfg,
                    key_block=key_block)
                return o, pool

            _, pool, counts = _latent_layers(
                cfg, params, x, pool, cache_step, valid)
            # no logits: the last prompt token is the decode batch's
            return pool, counts

        prefill.__name__ = "latent_prefill"
        return prefill

    # ------------- rows a position and states a sequence (hybrid) programs

    def _hybrid_steps(self, rows_step, state_step) -> dict:
        """The operator step of each of the module's kinds, from which pool
        it keeps (`_Cache.kinds`): ``rows_step(kind)`` or
        ``state_step(kind)``, each ``(x, lp, i, pools) -> (x, pools)``."""
        return {kind: (rows_step if where == "rows" else state_step)(kind)
                for kind, where in self._cache.kinds}

    def _hybrid_decode(self, B: int, W: int):
        cfg, mod = self.cfg, self.cfg.module
        bs = self.kv.cfg.block_size
        S = W * bs
        use_kernel = self._attn_route() == "pallas"

        def step(params, kv_pool, state_pool, tok, pos, table, slots,
                 temps, keys):
            # tok/pos (B,), table (B, W), slots (B,) the rows' state slots,
            # temps (B,), keys (B, 2)
            x = mod.embed_tokens(params, tok, cfg)               # (B, d)
            flat = table[jnp.arange(B), pos // bs] * bs + pos % bs
            valid = table[:, 0] != SCRATCH_BLOCK    # a spare row: no token
            if not use_kernel:
                idx = _span_idx(table, bs)                       # (B, S)
                live = jnp.arange(S)[None, :] <= pos[:, None]

            def state_step(kind):
                # the module's own step over each row's slot, whole
                advance = getattr(mod, kind + "_decode")

                def one(x, lp, i, pools):
                    kv_pool, state_pool = pools
                    x, state_pool = advance(x, lp, i, cfg, state_pool, slots,
                                            pos)
                    return x, (kv_pool, state_pool)
                return one

            def rows_step(kind):
                into, out = (getattr(mod, kind + "_in"),
                             getattr(mod, kind + "_out"))

                def one(x, lp, i, pools):
                    # write this position's row, then attend over the cache
                    kv_pool, state_pool = pools
                    q, row = into(x, lp, cfg, pos)
                    kv_pool = _write_rows(kv_pool, i, flat, row)
                    if use_kernel:
                        o = mod.decode_kernel(
                            q, kv_pool, i, table, pos, cfg, block_size=bs,
                            interpret=not on_tpu())
                    else:   # the oracle: gather the table's span
                        o = mod.decode_attention(
                            q, _read_rows(kv_pool, i, idx), live, cfg)
                    return out(x, o, lp, cfg), (kv_pool, state_pool)
                return one

            x, pools, counts = _hybrid_layers(
                cfg, params, x, (kv_pool, state_pool),
                self._hybrid_steps(rows_step, state_step), valid)
            logits = mod.final_logits(params, x, cfg)
            return *pools, _next_tokens(logits, temps, keys), logits, counts

        step.__name__ = "hybrid_decode"
        return step

    def _hybrid_prefill(self, C: int, W: int):
        cfg, mod = self.cfg, self.cfg.module
        bs = self.kv.cfg.block_size
        key_block = min(_PREFILL_KEY_BLOCK, W * bs)
        pages = key_block // bs
        use_kernel = self._prefill_route() == "pallas"

        def prefill(params, kv_pool, state_pool, toks, pos0, table, slot,
                    n_valid):
            # toks (C,), pos0 scalar, table (W,), slot the sequence's state
            # slot, n_valid scalar
            pv = pos0 + jnp.arange(C)
            valid = jnp.arange(C) < n_valid
            x = mod.embed_tokens(params, toks, cfg)              # (C, d)
            # the chunk's dead tail -> the scratch block
            flat = jnp.where(valid, table[pv // bs] * bs + pv % bs, 0)

            def state_step(kind):
                # the module's own step over the sequence's slot, whole: it
                # starts from what the slot holds of the sequence (decided
                # from positions: the slot is as its last owner left it)
                # and leaves the state behind the last VALID position
                advance = getattr(mod, kind + "_prefill")

                def one(x, lp, i, pools):
                    kv_pool, state_pool = pools
                    x, state_pool = advance(x, lp, i, cfg, state_pool, slot,
                                            pos0, n_valid)
                    return x, (kv_pool, state_pool)
                return one

            def rows_step(kind):
                into, out = (getattr(mod, kind + "_in"),
                             getattr(mod, kind + "_out"))

                def one(x, lp, i, pools):
                    # write the chunk's rows, then attend over the table's
                    # live span, the rows just written included: the
                    # module's kernel over the pool where it lies, or a key
                    # block at a time
                    kv_pool, state_pool = pools
                    q, rows = into(x, lp, cfg, pv)
                    kv_pool = _write_rows(kv_pool, i, flat, rows)
                    if use_kernel:
                        o = mod.prefill_kernel(
                            q, kv_pool, i, table, pos0, pos0 + n_valid, cfg,
                            block_size=bs, interpret=not on_tpu())
                        return out(x, o, lp, cfg), (kv_pool, state_pool)

                    def read_rows(j):
                        blk = jax.lax.dynamic_slice_in_dim(
                            table, j * pages, pages)
                        return _read_rows(kv_pool, i, _span_idx(blk, bs))

                    o = mod.prefill_attention(
                        q, pv, read_rows, pos0 + n_valid, cfg,
                        key_block=key_block)
                    return out(x, o, lp, cfg), (kv_pool, state_pool)
                return one

            _, pools, counts = _hybrid_layers(
                cfg, params, x, (kv_pool, state_pool),
                self._hybrid_steps(rows_step, state_step), valid)
            # no logits: the last prompt token is the decode batch's
            return *pools, counts

        prefill.__name__ = "hybrid_prefill"
        return prefill

    def _draft_fn(self, B: int, W: int):
        """k greedy early-exit steps in ONE jitted call: reads the paged
        pool (history < pos), keeps the in-flight draft K/V in a local
        per-call buffer, writes NOTHING back - the pool (and under int8
        its running scales) never sees a draft, so rejected speculation
        cannot pollute live state."""
        fn = self._draft_fns.get((B, W))
        if fn is not None:
            return fn
        cfg, dt = self.cfg, self.cfg.dtype
        E, K = self.draft_layers, self.spec_k
        H, Dh = cfg.n_heads, cfg.head_dim
        bs = self.kv.cfg.block_size
        S = W * bs

        def draft(params, k_pool, v_pool, k_scale, v_scale,
                  tok, pos, table):
            # tok/pos (B,), table (B, W) -> (B, K) greedy draft tokens.
            # Gather + (int8) dequantize the E layers of pool history
            # ONCE - it is invariant across the K draft steps.
            idx = _span_idx(table, bs)                        # (B, S)
            layers = jnp.arange(E)[:, None, None]
            hk = _read_span(                         # (E, B, H, S, Dh)
                k_pool, k_scale, layers, table, idx, bs, dt
            ).transpose(0, 1, 3, 2, 4)
            hv = _read_span(
                v_pool, v_scale, layers, table, idx, bs, dt
            ).transpose(0, 1, 3, 2, 4)
            hist_live = (jnp.arange(S)[None, :] < pos[:, None])  # (B, S)
            bufk = jnp.zeros((E, B, H, K, Dh), dt)
            bufv = jnp.zeros((E, B, H, K, Dh), dt)
            drafts = []
            for i in range(K):
                x = params["embed"][tok].astype(dt)[:, None, :]
                x = x + _sinusoid_pe(pos + i, cfg.d_model, dt)[:, None, :]
                loc = jnp.broadcast_to(jnp.arange(K) <= i, (B, K))
                live = jnp.concatenate(
                    [hist_live, loc], axis=1
                )[:, None, None, :]             # (B, 1, 1, S + K)

                def layer_step(x, lc, i=i):
                    # the model's block around the local buffer beside
                    # the pre-gathered history
                    lp, lhk, lhv, bk, bv = lc
                    q, k, v = tfm.block_qkv(x, lp, cfg, self._mm)
                    bk = jax.lax.dynamic_update_slice_in_dim(
                        bk, k.transpose(0, 2, 1, 3), i, axis=2
                    )
                    bv = jax.lax.dynamic_update_slice_in_dim(
                        bv, v.transpose(0, 2, 1, 3), i, axis=2
                    )
                    o = tfm.masked_attention(
                        q, jnp.concatenate([lhk, bk], axis=2),
                        jnp.concatenate([lhv, bv], axis=2), live, dt,
                    )
                    x, _ = tfm.block_out(x, o, lp, cfg, self._mm)
                    return x, (bk, bv)

                x, (bufk, bufv) = jax.lax.scan(
                    layer_step, x, (params["layers"], hk, hv, bufk, bufv),
                    unroll=min(E, 8),
                )
                logits = tfm.final_logits(params, x[:, 0], dt)
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                drafts.append(tok)
            return jnp.stack(drafts, axis=1)    # (B, K)

        fn = self._draft_fns[(B, W)] = self._jit_bucket(
            draft, writes_pools=False)
        return fn

    def _verify_fn(self, B: int, W: int):
        """One target-model step over K = spec_k + 1 positions per slot
        (inputs ``[t0, d1..dk]`` at ``pos..pos+k``): write-then-gather
        over the paged pool with the chunked-prefill causal mask
        generalized to a batch axis, greedy prediction returned at
        EVERY position - the host accepts the longest matching draft
        prefix and rewinds the rest."""
        fn = self._verify_fns.get((B, W))
        if fn is not None:
            return fn
        cfg, dt = self.cfg, self.cfg.dtype
        K = self.spec_k + 1
        bs = self.kv.cfg.block_size
        S = W * bs

        def verify(params, k_pool, v_pool, k_scale, v_scale,
                   toks, pos0, table):
            # toks (B, K), pos0 (B,), table (B, W)
            pv = pos0[:, None] + jnp.arange(K)[None, :]      # (B, K)
            x = params["embed"][toks].astype(dt)             # (B, K, d)
            x = x + _sinusoid_pe(
                pv.reshape(-1), cfg.d_model, dt
            ).reshape(B, K, cfg.d_model)
            blkv = jnp.take_along_axis(table, pv // bs, axis=1)  # (B, K)
            at = {
                "table": table,
                "idx": _span_idx(table, bs),                  # (B, S)
                "blkv": blkv,
                "flat": blkv * bs + pv % bs,
            }
            # query (b, i) attends pool slots <= pos0[b] + i (its own
            # just-written position included)
            live = (
                jnp.arange(S)[None, None, :] <= pv[:, :, None]
            )[:, None]                                       # (B,1,K,S)

            x, pools = _scan_layers(
                cfg, self._mm, params, x, (k_pool, v_pool, k_scale, v_scale),
                partial(_write_then_attend, at=at, live=live, dt=dt),
            )
            logits = tfm.final_logits(params, x, dt)         # (B, K, v)
            return *pools, jnp.argmax(logits, axis=-1).astype(jnp.int32)

        fn = self._verify_fns[(B, W)] = self._jit_bucket(verify)
        return fn

    def _pools(self) -> tuple:
        """The donated operands of a bucket program, in its order."""
        return tuple(getattr(self, name) for name in self._cache.pools)

    @property
    def pool_labels(self) -> tuple:
        """`_pools`' names, as the donation audit reports them."""
        return self._cache.labels

    def _board(self, nxt):
        """A decode program's tokens as `_feed_tokens` reads them."""
        n = self.ecfg.max_batch
        return nxt if nxt.shape[0] == n else _widen(nxt, n)

    def _table(self, seqs: list, B: int, W: int):
        """The (B, W) block table of a batch bucket: a row a sequence, the
        bucket's spare rows on the scratch block."""
        return self.kv.table(
            [s.seq_id for s in seqs] + [-1] * (B - len(seqs)), W)

    def _run_writer(self, fn, *tail) -> tuple:
        """Dispatch one pool-writing bucket program: the pools (and int8
        scales) go in donated and are rebound from its leading outputs;
        returns the outputs after them."""
        names = self._cache.pools
        out = fn(self.params, *self._pools(), *tail)
        for name, pool in zip(names, out):
            setattr(self, name, pool)
        return out[len(names):]

    def _state_slots(self, seq_ids: list, *, scalar: bool = False) -> tuple:
        """What a program of an engine with a state pool takes behind its
        table: the state slot of each of ``seq_ids`` (the scratch slot for
        -1, a bucket's spare row), ``(len(seq_ids),)`` int32 - a scalar for
        a prefill chunk's one sequence. Nothing for the other engines."""
        if not self._cache.state:
            return ()
        rows = self.kv.state_rows(seq_ids)
        return (jnp.asarray(rows[0] if scalar else rows),)

    # ----------------------------------------------------------- warmup

    def bucket_tail(self, family: str, n: int, W: int) -> tuple:
        """The operands behind the pools of a ``decode`` (batch ``n``) or
        ``prefill`` (chunk ``n``) program of table width ``W``, all nought:
        what `warmup` calls a bucket with (every write lands in the scratch
        block and the scratch state slot) and what servelint traces it at
        (analysis/serve_trace.py), so that neither repeats the other."""
        i32 = jnp.int32
        if family == "decode":
            return (jnp.zeros((n,), i32), jnp.zeros((n,), i32),
                    jnp.zeros((n, W), i32), *self._state_slots([-1] * n),
                    jnp.zeros((n,), jnp.float32),
                    jnp.zeros((n, 2), jnp.uint32))
        return (jnp.zeros((n,), i32), i32(0), jnp.zeros((W,), i32),
                *self._state_slots([-1], scalar=True), i32(0))

    def warmup(self, *, max_width_blocks: int | None = None) -> int:
        """Pre-compile the (batch, width) bucket grid with dummy calls
        (all writes land in the scratch block, so live state is
        untouched). Without warmup each new bucket pays its XLA compile
        on the first request that needs it - a TTFT spike production
        serving cannot afford. Returns the number of programs built,
        and leaves in ``program_temp_bytes`` each family's largest
        compiled ``temp_size_in_bytes`` (the scheduler's
        ``serve_program_temp_bytes{family}``): a program that moved a
        pool or a layer's slab instead of its rows would show there."""
        bs = self.kv.cfg.block_size
        widths = self._bucket_widths(max_width_blocks)
        n = 0

        def pow2(cap):
            return [1 << i for i in range(cap.bit_length())]

        def warm(family, fn, *tail):
            nonlocal n
            args = (
                self.draft_params if family == "draft" else self.params,
                *self._pools(), *tail,
            )
            # the executable the call below runs (jit builds it once),
            # asked for its temporaries: no second compile, no tick cost
            mem = fn.lower(*args).compile().memory_analysis()
            if mem is not None:
                self.program_temp_bytes[family] = max(
                    self.program_temp_bytes.get(family, 0),
                    int(mem.temp_size_in_bytes),
                )
            if family == "draft":
                out = fn(*args)  # read-only: no pool state to rebind
            else:
                out = self._run_writer(fn, *tail)
                # warmup writes land in the scratch block; its scale is
                # garbage by contract, but reset anyway so a fresh engine
                # stays bitwise clean
                self._zero_scales([0])
            n += 1
            return out

        def zeros(*shape):
            return jnp.zeros(shape, jnp.int32)

        for B in batch_buckets(self.ecfg.max_batch):
            # as `step` calls it: host arrays in, the keys left on the device
            _row_keys(np.zeros((B,), np.uint32), np.zeros((B,), np.int32))
            for W in widths:
                nxt = warm("decode", self._decode_fn(B, W),
                           *self.bucket_tail("decode", B, W))[0]
            # a decode program's tokens into the next tick's batch, as
            # `_dispatch` calls it (not counted: no bucket programs)
            _feed_tokens(self._board(nxt), np.zeros((B,), np.int32),
                         np.zeros((B,), np.int32))
        if self.ecfg.prefill_chunk > 1:
            for C in pow2(self.ecfg.prefill_chunk):
                for W in widths:
                    if C <= W * bs:
                        warm("prefill", self._prefill_fn(C, W),
                             *self.bucket_tail("prefill", C, W))
        if self.spec_k:
            # the speculative bucket families: drafter + K-position
            # verify per (batch, width)
            for B in batch_buckets(self.ecfg.max_batch):
                for W in widths:
                    warm("draft", self._draft_fn(B, W), zeros(B), zeros(B),
                         zeros(B, W))
                    warm("verify", self._verify_fn(B, W),
                         zeros(B, self.spec_k + 1), zeros(B), zeros(B, W))
        return n

    # ------------------------------------------------------------ the tick

    def _moe_stats(self, counts: list) -> dict:
        """The packed counts of a tick's latent programs (`_latent_layers`)
        summed: pairs ``held`` and ``absent``, rows ``multiplied`` (the rows
        a pair owns are the held pairs), each expert layer's ``load``
        (layers, held experts), and ``experts_read`` of ``experts_held``."""
        total = np.sum(counts, axis=0)
        n_held = self.cfg.experts_held[1]
        return {
            "held": int(total[0]), "absent": int(total[1]),
            "multiplied": int(total[2]),
            "load": total[3:].reshape(self.cfg.n_moe, n_held),
            # over every expert layer of every program: the held experts
            # that own a row (whose matrices the tile loop read), and the
            # held experts
            "experts_read": int(sum((c[3:] > 0).sum() for c in counts)),
            "experts_held": len(counts) * self.cfg.n_moe * n_held,
        }

    def _emit(self, seq: Sequence, tok: int) -> None:
        """One NEW generated token: record, maybe retire, stream."""
        seq.out.append(tok)
        done = (
            len(seq.out) >= seq.max_new_tokens
            or (self.ecfg.eos_token is not None
                and tok == self.ecfg.eos_token)
        )
        if done:
            seq.finished = True
        seq.emitted = len(seq.out)
        if seq.on_token is not None:
            seq.on_token(seq, tok, done)

    def _retire_finished(self) -> list:
        """Take what has finished out of the batch and free its blocks,
        but for a sequence the tick in flight still writes (it ended by
        its token, or by a cancel, after that tick was dispatched): that
        one goes when that tick has landed."""
        done = [s for s in self.active
                if s.finished and not self._touched(s.seq_id)]
        if done:
            gone = {s.seq_id for s in done}
            with self.lock:
                self.active = [
                    s for s in self.active if s.seq_id not in gone]
            for s in done:
                self._free_seq(s.seq_id)
        return done

    def _preempt_youngest(self, parked: list) -> Sequence:
        """Nothing could run: evict the youngest parked sequence so the
        others' next allocation can succeed. Blocks freed, position
        reset; generated tokens are kept for replay dedup (greedy /
        per-position keys make the regeneration identical). Returns the
        victim so the caller can record provenance."""
        victim = parked[-1]
        with self.lock:
            self.active = [
                s for s in self.active if s.seq_id != victim.seq_id
            ]
        self._free_seq(victim.seq_id)
        victim.pos = 0
        victim.preemptions += 1
        self.preempted.append(victim)
        self.stall_events += 1
        return victim

    def _spec_eligible(self, s: Sequence) -> bool:
        """Slots speculation applies to: GREEDY (sampled slots keep the
        plain path so their per-(seed, position) keys never change),
        past prefill (positions pos+1..pos+k must all be generation
        positions, i.e. pos >= prompt_len - 1), and with room for k+1
        optimistic writes under max_seq_len."""
        return (
            s.temperature == 0.0
            and s.pos >= s.prompt_len - 1
            and s.pos + self.spec_k + 1 <= self.ecfg.max_seq_len
        )

    def _rewind_seq(self, seq_id: int, n_tokens: int) -> None:
        """Rewind the KV write cursor past a rejected speculative
        suffix; freed blocks get their int8 scales zeroed."""
        self._zero_scales(self.kv.rewind(seq_id, n_tokens))

    def _spec_step(self, batch: list, stats: dict, seqstat) -> None:
        """The speculative phase of one tick: draft k tokens per slot
        (skipped for slots whose future is already known from
        preemption replay - their own `out` tokens are the drafts,
        guaranteed acceptance under greedy determinism), verify all
        k+1 positions in one target step, accept the longest matching
        prefix, emit, rewind the rest."""
        k = self.spec_k
        K = k + 1
        bs = self.kv.cfg.block_size
        n = len(batch)
        W = _bucket(max((s.pos + k) // bs + 1 for s in batch))
        drafts = np.zeros((n, k), np.int32)
        need_draft = []
        for idx, s in enumerate(batch):
            j0 = s.pos + 1 - s.prompt_len
            if 0 <= j0 and j0 + k <= len(s.out):
                drafts[idx] = s.out[j0: j0 + k]   # replay: known future
            else:
                need_draft.append(idx)
        draft_s = 0.0
        if need_draft:
            Bd = min(_bucket(len(need_draft)), self.ecfg.max_batch)
            dtok = np.zeros((Bd,), np.int32)
            dpos = np.zeros((Bd,), np.int32)
            for row, idx in enumerate(need_draft):
                dtok[row] = batch[idx].next_input()
                dpos[row] = batch[idx].pos
            dtable = self._table([batch[i] for i in need_draft], Bd, W)
            fn = self._draft_fn(Bd, W)
            t0 = self.clock()
            out_d = np.asarray(fn(       # asarray = device sync
                self.draft_params, *self._pools(),
                jnp.asarray(dtok), jnp.asarray(dpos), jnp.asarray(dtable),
            ))
            draft_s = self.clock() - t0
            for row, idx in enumerate(need_draft):
                drafts[idx] = out_d[row]

        B = min(_bucket(n), self.ecfg.max_batch)
        toks = np.zeros((B, K), np.int32)
        pos0 = np.zeros((B,), np.int32)
        for i, s in enumerate(batch):
            toks[i, 0] = s.next_input()
            toks[i, 1:] = drafts[i]
            pos0[i] = s.pos
        table = self._table(batch, B, W)
        fn = self._verify_fn(B, W)
        tail = (jnp.asarray(toks), jnp.asarray(pos0), jnp.asarray(table))
        t0 = self.clock()
        (nxt,) = self._run_writer(fn, *tail)
        nxt = np.asarray(nxt)
        verify_s = self.clock() - t0

        sp = stats["spec"] = {
            "proposed": 0, "accepted": 0, "steps": 1,
            "draft_s": draft_s, "verify_s": verify_s, "per_slot": [],
        }
        self.spec_steps += 1
        for i, s in enumerate(batch):
            tgt = nxt[i]          # greedy prediction at pos..pos+k
            a = 0
            while a < k and drafts[i, a] == tgt[a]:
                a += 1
            d = seqstat(s)
            d["proposed"] += k
            d["accepted"] += a
            d["verify_s"] += verify_s / n
            if i in need_draft:
                d["draft_s"] += draft_s / len(need_draft)
            sp["proposed"] += k
            sp["accepted"] += a
            sp["per_slot"].append(a)
            self.spec_proposed_tokens += k
            self.spec_accepted_tokens += a
            # emit tgt[0..a] (a+1 tokens; the all-rejected step emits
            # exactly 1 - the token plain decode would have) through the
            # SAME per-consumed-position accounting as the plain path,
            # so decode_ticks == tokens_emitted + replayed_ticks holds
            # by construction
            start = s.pos
            for t in range(a + 1):
                consumed_at = start + t
                s.pos = consumed_at + 1
                j = consumed_at + 1 - s.prompt_len
                if j == len(s.out):
                    self._emit(s, int(tgt[t]))
                else:
                    d["replayed"] += 1
                self.decode_tokens += 1
                stats["decode_tokens"] += 1
                d["decode"] += 1
                if s.finished:
                    break
            # the verify step wrote K entries optimistically; keep only
            # the consumed prefix (retirement frees everything anyway)
            if not s.finished:
                self._rewind_seq(s.seq_id, s.pos)

    def step(self) -> dict:
        """One call of the serve loop: dispatch the NEXT tick's programs,
        then fetch the tick in flight's tokens, hand them out and return
        that tick's stats. With nothing in flight (the first call, after a
        drain) it dispatches a tick from the host's state first, as ever.

        **The tick in flight.** Tick n + 1 is built while tick n's tokens
        are still on the device, from what the host can count: positions
        (``seq.pos`` advances at dispatch), block needs, the table,
        temperatures, seeds (`_row_keys` of seeds and positions), and who
        has ended by count (`Sequence.dispatched_all`: left out of tick
        n + 1). The input token of a row that decoded in tick n goes from
        tick n's ``nxt`` into tick n + 1's ``tok`` on the device
        (`_feed_tokens`). An end token is the one end the host cannot
        count: a sequence whose token in flight turns out to be
        ``eos_token``, or that is cancelled meanwhile, has one position
        decoded past its end in tick n + 1; that token is dropped when
        tick n + 1 lands, never emitted, and the sequence's blocks are
        freed then and not before (`_retire_finished`). A token reaches
        its client one host lap after its program finished.

        **The pipeline drains** (fetch first, then build) where the
        engine sees that it must: with speculative slots (``spec_k > 0``:
        `_spec_step` needs its tokens on the host; every tick lands in
        the call that dispatched it); where nothing can be dispatched
        (all that is left ends by count, or every candidate is parked on
        blocks: the youngest is only preempted with nothing in flight, in
        the next call); in `flush()`.

        Returns the landed tick's stats for the scheduler's ledger/
        metrics: ``{"decode_tokens", "prefill_tokens", "finished",
        "parked", "batch", "phase_s", "host_s", "decode_call",
        "prefill_calls", "dispatch", "found"}``. ``dispatch`` is "ahead"
        for a tick whose programs were dispatched before the tick before's
        tokens were fetched, "drained" for one dispatched with nothing in
        flight, None for one that dispatched nothing. ``found`` lists
        ``(program, device)`` for each of the tick's bucket programs
        (`_call`): "prefill" or "decode", and "idle" where the device had
        finished all it was handed when the host called the program, so
        that it waited on the host, "busy" where it had not.

        ``phase_s`` partitions THIS CALL, one key per `STEP_PHASES`
        entry, each a `Phases` interval (a ``serve.<phase>`` span and its
        seconds) and every instant from entry to return in one of them:
        ``prefill_host`` (the chunked-prefill loop) and ``decode_host``
        (the decode batch), both of the tick dispatched here; ``fetch``
        (``np.asarray(nxt)`` of the tick landed here: the host blocked
        until the PREVIOUS dispatch's programs have finished, which have
        had a whole host lap to do so), ``emit`` (the per-sequence loop
        after the fetch with its `on_token` callbacks, and retiring),
        ``spec`` (`_spec_step` whole) and ``release`` (the landed tick's
        arrays on the device let go, the call's last phase). ``host_s`` splits the two host
        phases by `HOST_PARTS`, spans nested in theirs: ``select`` (the
        batch and the chunks, their blocks), ``stage`` (host arrays, the
        table, the transfers, `_state_slots`, `_row_keys`, `_feed_tokens`)
        and ``dispatch`` (each bucket program's call up to its return).
        Dispatch is asynchronous, so the host can time the wait for the
        prefill and the decode program together (``fetch``) and not each
        apart: that is why the scheduler's split of the step between the
        ledger's "prefill" and "decode" by token counts stays an
        apportioning.

        ``decode_call`` is ``(B, W, live, read)`` for the tick's decode
        dispatch, None without one: the batch and width-in-blocks
        bucket the program is shaped for (``B * W * block_size`` padded
        positions), the cache positions its queries attend to,
        ``pos + 1`` summed over the batch, and the positions the program
        reads from the pool for them: the pages the paged kernel fetches
        (``live`` rounded up to whole pages a row of the bucket), or on
        the ``xla`` route the whole padded span. ``prefill_calls`` lists
        ``(C, W, live)`` per prefill dispatch: the chunk bucket, the
        width, and ``n * pos0 + n * (n + 1) / 2`` for ``n`` tokens from
        ``pos0`` (each attends to all before it and itself). The
        speculative path's programs are not counted.

        For per-request attribution (serve/reqtrace.py) the dict also
        carries ``per_seq`` - ``{seq_id: {"prefill", "decode",
        "replayed", "parked", "proposed", "accepted", "draft_s",
        "verify_s"}}``, this tick's token counts and park flag
        for every sequence the tick touched - and ``preempted``, the
        provenance of evictions performed this tick (``seq_id``,
        ``tokens_held`` for replay accounting, cumulative
        ``preemptions``). Ticks with a speculative phase additionally
        carry ``spec`` - ``{"proposed", "accepted", "steps",
        "draft_s", "verify_s", "per_slot"}`` (``per_slot`` = accepted
        drafts per slot, the acceptance-histogram input); a latent
        engine's carry ``moe``, the routing counts of the programs the
        tick itself dispatched (`_moe_stats`)."""
        tick, self._inflight = self._inflight, None
        if tick is None:
            tick = self._dispatch(None)
        if tick.in_flight and not self.spec_k:
            ahead = self._dispatch(tick)
            if ahead.in_flight:
                self._inflight = ahead
        return self._land(tick)

    def flush(self) -> dict | None:
        """Land the tick in flight and dispatch none: its tokens reach
        their clients, nothing is left on the device, and every sequence's
        ``pos`` and ``out`` agree again - what a drain for migration asks
        before it exports them (`export_descriptor`). Returns that tick's
        stats (`step`), None with nothing in flight."""
        tick, self._inflight = self._inflight, None
        return None if tick is None else self._land(tick)

    def _select(self, todo: list, held: list) -> tuple:
        """This tick's decode rows among ``todo``: (plain batch,
        speculative batch, parked), ``held`` the sequences the prefill
        phase parked. A row's block is allocated here."""
        ecfg = self.ecfg
        batch: list[Sequence] = []
        spec_batch: list[Sequence] = []
        parked = list(held)
        for seq in todo:
            # (one that ends by count may have its last token in flight)
            if seq.finished or seq.dispatched_all or seq in held:
                continue
            if ecfg.prefill_chunk > 1 and seq.in_prefill and (
                seq.pos < seq.prompt_len - 1
            ):
                continue  # still mid-chunked-prefill; next tick
            if self.spec_k and self._spec_eligible(seq):
                try:
                    self.kv.ensure_range(
                        seq.seq_id, seq.pos + self.spec_k
                    )
                    spec_batch.append(seq)
                    continue
                except OutOfBlocks:
                    pass  # degrade to the one-block plain path
            try:
                self.kv.ensure(seq.seq_id, seq.pos)
            except OutOfBlocks:
                parked.append(seq)
                continue
            batch.append(seq)
        return batch[:ecfg.max_batch], spec_batch, parked

    def _call(self, program: str, fn, tail: tuple, stats: dict) -> tuple:
        """`_run_writer` of one of a tick's bucket programs, as the host
        part ``dispatch``. Just before the call it asks, without blocking,
        whether the pool the program takes, the output of the bucket
        program dispatched before it, is ready: then that program had
        finished and the device waited on the host for this one (but for
        the tick's small programs and transfers, just handed to it). Noted
        in ``stats["found"]`` as ``(program, "idle" or "busy")``."""
        self.part.to("dispatch")
        idle = getattr(self, self._cache.pools[0]).is_ready()
        out = self._run_writer(fn, *tail)
        stats["found"].append((program, "idle" if idle else "busy"))
        return out

    def _dispatch(self, prev: _Tick | None) -> _Tick:
        """Build one tick and hand its programs to the device, fetching
        nothing: the chunked-prefill phase, then the decode batch, each in
        the host parts ``select``, ``stage`` and ``dispatch``. ``prev`` is
        the tick in flight, None where there is none: a row whose input
        token is ``prev``'s to give takes it on the device
        (`_feed_tokens`). Advances ``seq.pos`` of every row and chunk.
        Leaves ``decode_host`` under way."""
        phase, part = self.phase, self.part
        phase.to("prefill_host")
        part.to("select")
        ecfg = self.ecfg
        bs = self.kv.cfg.block_size
        with self.lock:
            todo = list(self.active)
        held: list[Sequence] = []
        tick = _Tick({"decode_tokens": 0, "prefill_tokens": 0,
                      "finished": 0, "parked": 0, "batch": 0, "per_seq": {},
                      "preempted": [], "decode_call": None,
                      "prefill_calls": [], "dispatch": None, "found": []})
        stats = tick.stats
        seqstat = partial(_seqstat, stats)

        # ---- chunked prefill phase (prefill_chunk > 1 only)
        if ecfg.prefill_chunk > 1:
            budget = ecfg.prefill_token_budget or ecfg.prefill_chunk
            for seq in todo:
                if budget <= 0:
                    break
                if not seq.in_prefill or seq.finished:
                    continue
                # leave the LAST prompt token to the decode batch: its
                # logits produce the first generated token there, so
                # first-token sampling/argmax runs on the same path for
                # every sequence
                remaining = seq.prompt_len - 1 - seq.pos
                if remaining <= 0:
                    continue
                n = min(remaining, ecfg.prefill_chunk, budget)
                try:
                    self.kv.ensure_range(seq.seq_id, seq.pos + n - 1)
                except OutOfBlocks:
                    held.append(seq)
                    continue
                part.to("stage")
                C = _bucket(n)
                W = self._cache.width or _bucket((seq.pos + n - 1) // bs + 1)
                toks = np.zeros((C,), np.int32)
                toks[:n] = seq.prompt[seq.pos: seq.pos + n]
                table = self.kv.table([seq.seq_id], W)[0]
                fn = self._prefill_fn(C, W)
                live = n * seq.pos + n * (n + 1) // 2
                tail = (jnp.asarray(toks), jnp.int32(seq.pos),
                        jnp.asarray(table),
                        *self._state_slots([seq.seq_id], scalar=True),
                        jnp.int32(n))
                out = self._call("prefill", fn, tail, stats)
                part.to("select")
                kernel = self._prefill_route() == "pallas"
                table_width = W
                if self._cache.width:
                    # its width: the key blocks the program walks
                    kb = min(_PREFILL_KEY_BLOCK, W * bs)
                    W = -(-(seq.pos + n) // kb) * kb // bs
                if self._cache.attn_pairs is not None:
                    # what a layer with paged rows scores: the kernel's
                    # blocks of query positions against their fetch steps,
                    # or the chunk against the key blocks walked
                    scored = (self._cache.prefill_scored(
                        self.cfg, seq.pos, n, C, block_size=bs,
                        width=table_width) if kernel else C * W * bs)
                    pairs = self._cache.attn_pairs(
                        self.cfg, seq.pos, n, C, scored)
                    stats["attn_pairs"] = {
                        k: v + stats.get("attn_pairs", {}).get(k, 0)
                        for k, v in pairs.items()}
                if self._cache.routed:
                    tick.counts.append(out[0])
                    if kernel:
                        stats["prefill_kernel_pairs"] = live + stats.get(
                            "prefill_kernel_pairs", 0)
                stats["prefill_calls"].append((C, W, live))
                tick.touched[seq.seq_id] = None
                seq.pos += n
                budget -= n
                self.prefill_tokens += n
                stats["prefill_tokens"] += n
                seqstat(seq)["prefill"] += n
        part.to(None)

        # ---- decode batch: plain slots (one token each) + speculative
        # slots (k drafts verified in one multi-position step)
        phase.to("decode_host")
        part.to("select")
        batch, tick.spec_batch, parked = self._select(todo, held)
        for s in parked:
            seqstat(s)["parked"] = True
        stats["parked"] = len(parked)
        if parked:
            self.stall_events += 1
        if batch or tick.spec_batch or stats["prefill_calls"]:
            stats["dispatch"] = "drained" if prev is None else "ahead"
        elif parked and prev is None:
            # every active sequence is parked on blocks: preempt the
            # youngest so the others' next allocation can succeed (never
            # under a tick in flight, whose rows hold theirs)
            victim = self._preempt_youngest(parked)
            stats["preempted"].append({
                "seq_id": victim.seq_id,
                "tokens_held": len(victim.out),
                "preemptions": victim.preemptions,
            })
        if batch:
            part.to("stage")
            B = min(_bucket(len(batch)), ecfg.max_batch)
            W = self._cache.width or _bucket(
                max(s.pos // bs + 1 for s in batch))
            tok = np.zeros((B,), np.int32)
            src = np.full((B,), -1, np.int32)
            pos = np.zeros((B,), np.int32)
            temps = np.zeros((B,), np.float32)
            seeds = np.zeros((B,), np.uint32)
            for i, s in enumerate(batch):
                if s.input_in_flight:
                    src[i] = prev.touched[s.seq_id]
                else:
                    tok[i] = s.next_input()
                pos[i] = s.pos
                temps[i] = s.temperature
                seeds[i] = s.seed & 0xFFFFFFFF
            table = self._table(batch, B, W)
            fn = self._decode_fn(B, W)
            kernel = self._attn_route() == "pallas"
            stats["decode_call"] = (
                B, W, int(pos.sum()) + len(batch),
                paged_read_positions(pos, bs) if kernel
                else B * W * bs,
            )
            stats["decode_kernel"] = kernel
            tail = (
                _feed_tokens(self._board(prev.nxt), src, tok)
                if (src >= 0).any() else jnp.asarray(tok),
                jnp.asarray(pos), jnp.asarray(table),
                *self._state_slots(
                    [s.seq_id for s in batch] + [-1] * (B - len(batch))),
                jnp.asarray(temps), _row_keys(seeds, pos),
            )
            for i, s in enumerate(batch):
                tick.rows.append((s, s.pos))
                tick.touched[s.seq_id] = i
                s.pos += 1
            tick.nxt, *rest = self._call("decode", fn, tail, stats)
            if self._cache.routed:
                tick.counts.append(rest[1])
        part.to(None)
        return tick

    def _land(self, tick: _Tick) -> dict:
        """Fetch what a tick's programs produced (the one blocking read),
        hand each new token out (`_emit`: record, maybe retire, stream),
        run its speculative phase if it has one, retire what finished, let
        go of the tick's arrays on the device (``release``). Ends the
        call's phases; returns the tick's stats with the call's seconds."""
        stats = tick.stats
        seqstat = partial(_seqstat, stats)
        phase = self.phase
        if tick.rows or tick.counts:
            phase.to("fetch")
            if self._cache.routed:
                # the expert layers' counts come with the tokens
                nxt, counts = jax.device_get((tick.nxt, tick.counts))
                stats["moe"] = self._moe_stats(counts)
            else:
                nxt = np.asarray(tick.nxt)
        if tick.rows:
            phase.to("emit")
            for i, (s, consumed_at) in enumerate(tick.rows):
                if s.finished:
                    # it ended (its token, a cancel) while this row was in
                    # flight: decoded past the end, dropped
                    continue
                if consumed_at >= s.prompt_len - 1:
                    # prediction for generated-token index j; after a
                    # preemption the replay re-derives tokens the sequence
                    # already holds (j < len(out)) - deterministic by
                    # construction (greedy, or the per-position sampling
                    # key), so they are dropped, not re-appended/re-streamed
                    j = consumed_at + 1 - s.prompt_len
                    if j == len(s.out):
                        self._emit(s, int(nxt[i]))
                    else:
                        seqstat(s)["replayed"] += 1
                    self.decode_tokens += 1
                    stats["decode_tokens"] += 1
                    seqstat(s)["decode"] += 1
                else:
                    self.prefill_tokens += 1
                    stats["prefill_tokens"] += 1
                    seqstat(s)["prefill"] += 1
        if tick.spec_batch:
            phase.to("spec")
            self._spec_step(tick.spec_batch, stats, seqstat)
        if tick.rows or tick.spec_batch:
            self.ticks += 1
            stats["batch"] = len(tick.rows) + len(tick.spec_batch)
        if tick.in_flight:
            # (a prefill chunk's sequence may have been cancelled under it)
            phase.to("emit")
            stats["finished"] = len(self._retire_finished())
        # the caller keeps the husk to its return, not the arrays; the
        # seconds' dictionaries are made before the last reading, so that
        # no collection they set off falls outside every phase
        phase.to("release")
        tick.nxt = tick.counts = None
        stats["host_s"] = self.part.take()
        stats["phase_s"] = phase.take()
        phase.to(None)
        return stats
