"""Request scheduling: admission control, per-tenant fairness, the
serve loop, and the serving goodput ledger.

The scheduler is the single writer of the engine: one daemon loop
thread admits requests, drives `ServeEngine.step`, and streams tokens
back through per-request queues. Everything user-facing rides three
policies:

- **Admission control**: a bounded global queue - overflow is an
  `AdmissionError` the HTTP layer turns into 429 (the load-balancer
  backoff signal), never an unbounded memory ramp. Requests that could
  never run (prompt + max_new > max_seq_len) are rejected up front
  (400), not admitted to die later.
- **Per-tenant fairness**: each API key gets its own FIFO and a token
  bucket (``tenant_rate`` requests/s, ``tenant_burst`` size - 429 when
  empty); admission drains the per-key FIFOs round-robin, so one
  chatty tenant queues behind itself, not in front of everyone else.
- **KV backpressure**: a request is only admitted when the paged pool
  has blocks for its prompt (plus ``block_headroom``); mid-flight
  exhaustion parks sequences and may preempt the youngest
  (`engine.py`) - preempted sequences re-enter at the FRONT of the
  admission order (they hold streamed state a client is watching).

**Serving ledger** (`utils/goodput.py` taxonomy "serve"): every
wall-clock second of the loop lands in exactly one bucket -

- ``decode``  (goodput)       - step time apportioned to generated
                                tokens;
- ``prefill``                 - step + chunked-prefill time apportioned
                                to prompt tokens;
- ``kv_alloc_stall``          - ticks where block exhaustion blocked
                                every runnable sequence (incl.
                                preemption work);
- ``batch_formation_idle``    - loop time spent assembling batches /
                                admitting while work existed;
- ``queue_wait``              - each request's arrival->admission
                                window, low-priority in the sweep so it
                                claims only otherwise-idle seconds
                                (capacity pressure, not double-counted
                                compute);
- ``idle_other``              - the residual (an empty server).

Conservation is asserted at `close()` (ledger.finalize), the record is
written through to ``run_record`` when configured, and
``goodput_ratio`` / ``badput_seconds_total{cause}`` export live on the
metrics registry next to the QPS/TTFT/KV-occupancy series.
"""

from __future__ import annotations

import itertools
import queue as queue_mod
import threading
import time
from collections import deque
from dataclasses import dataclass, field

from jax.profiler import TraceAnnotation

from ..utils.goodput import GoodputLedger
from ..utils.obs import NULL_REGISTRY
from .engine import (
    HOST_PARTS, STEP_PHASES, Phases, ServeEngine, Sequence, export_descriptor,
)
from .reqtrace import RequestTraceRecorder

# histogram buckets for TTFT / inter-token latency: 1 ms .. 60 s
LATENCY_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)


# the phases that partition the loop thread's time
# (`serve_loop_seconds_total{phase}`): the scheduler's own and the
# engine's, each a `Phases` interval (a `serve.<phase>` span)
SCHED_PHASES = ("admit", "books", "wait")
LOOP_PHASES = SCHED_PHASES + STEP_PHASES


class AdmissionError(Exception):
    """Rejection with an HTTP status: 429 (queue full / rate limited)
    or 400 (a request that could never run)."""

    def __init__(self, status: int, reason: str, message: str):
        self.status = status
        self.reason = reason
        super().__init__(message)


@dataclass
class ServeRequest:
    """One client request + its streaming channel. The HTTP layer (or a
    test) reads ``events`` - a queue of ``("token", id)``,
    ``("done", summary)``, ``("error", message)`` tuples - and sets
    ``cancelled`` on client disconnect."""

    prompt: list
    max_new_tokens: int
    api_key: str = "anonymous"
    temperature: float = 0.0
    seed: int = 0
    # fleet-router failover provenance (X-Router-Retries headers):
    # re-dispatch episode count + client-visible seconds lost before
    # this replica saw the request (serve/reqtrace.py router_retry)
    router_retries: int = 0
    router_retry_s: float = 0.0
    req_id: int = 0
    t_arrival: float = 0.0
    t_admitted: float | None = None
    t_first_token: float | None = None
    t_done: float | None = None
    status: str = "new"
    tokens: list = field(default_factory=list)
    events: object = None       # queue.Queue, created by submit()
    cancelled: threading.Event = field(default_factory=threading.Event)
    # True when a streaming channel (the HTTP layer) owns the tail of
    # the request's lifecycle: the per-request trace record then stays
    # open in ``stream_write`` until `finish_stream` acks the flush
    stream_owner: bool = False
    _seq: object = None
    _t_arrival_ledger: float = 0.0
    _t_prev_token: float | None = None

    def summary(self) -> dict:
        return {
            "req_id": self.req_id,
            "status": self.status,
            "prompt_len": len(self.prompt),
            "tokens": list(self.tokens),
            "n_tokens": len(self.tokens),
            "ttft_s": (
                round(self.t_first_token - self.t_arrival, 6)
                if self.t_first_token is not None else None
            ),
            "total_s": (
                round(self.t_done - self.t_arrival, 6)
                if self.t_done is not None else None
            ),
        }


@dataclass(frozen=True)
class SchedulerConfig:
    max_queue: int = 64          # global bound -> 429 on overflow
    tenant_rate: float = 0.0     # requests/s per API key (0 = unlimited)
    tenant_burst: int = 8        # token-bucket size per API key
    block_headroom: int = 0      # extra free blocks required to admit
    idle_poll_s: float = 0.02    # loop wakeup when completely idle
    run_record: str | None = None  # serving goodput record path
    request_ring: int = 256      # finalized per-request records kept


class _TokenBucket:
    """Per-tenant request-rate limiter (refill-on-read)."""

    def __init__(self, rate: float, burst: int):
        self.rate = float(rate)
        self.burst = max(int(burst), 1)
        self.level = float(self.burst)
        self.t_last = time.monotonic()

    def try_take(self) -> bool:
        now = time.monotonic()
        self.level = min(
            self.burst, self.level + (now - self.t_last) * self.rate
        )
        self.t_last = now
        if self.level >= 1.0:
            self.level -= 1.0
            return True
        return False


class ServeScheduler:
    """Owns the engine + queues; `start()` spawns the loop thread."""

    def __init__(
        self,
        engine: ServeEngine,
        cfg: SchedulerConfig | None = None,
        *,
        registry=NULL_REGISTRY,
        tracer=None,
    ):
        self.engine = engine
        self.cfg = cfg or SchedulerConfig()
        self.registry = registry
        self.tracer = tracer
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._tenants: dict[str, deque] = {}
        self._tenant_order: deque = deque()
        self._buckets: dict[str, _TokenBucket] = {}
        self._queued = 0
        self._by_seq: dict[int, ServeRequest] = {}
        self._ids = itertools.count(1)
        self._running = False
        self._thread: threading.Thread | None = None
        # graceful drain (serve/fleet.py): once set, admission 503s and
        # the loop migrates every live sequence out as deterministic
        # replay descriptors (engine.export_descriptor)
        self._draining = False
        self._drained = threading.Event()
        self._drain_out: list = []
        # the loop thread's phases, the engine's and the ledger's
        # intervals on one clock, the engine's
        self.ledger = GoodputLedger(taxonomy="serve", clock=engine.clock)
        self.ledger.start()
        self._phase = Phases(SCHED_PHASES, engine.clock)
        # per-request lifecycle records on the ledger's clock, so the
        # two accountings reconcile (tools/request_trace.py --ledger)
        self.reqtrace = RequestTraceRecorder(
            ring=self.cfg.request_ring, clock=self.ledger.now,
            tracer=tracer,
        )
        if self.cfg.run_record:
            self.ledger.arm(self.cfg.run_record)
        self.ledger.describe(
            config={
                "engine": {
                    "max_batch": engine.ecfg.max_batch,
                    "num_blocks": engine.ecfg.num_blocks,
                    "block_size": engine.ecfg.block_size,
                    "max_seq_len": engine.ecfg.max_seq_len,
                    "prefill_chunk": engine.ecfg.prefill_chunk,
                    "kv_dtype": engine.ecfg.kv_dtype,
                    "weight_dtype": engine.ecfg.weight_dtype,
                    "spec_decode": engine.ecfg.spec_decode,
                    "spec_draft_layers": (
                        engine.draft_layers if engine.spec_k else 0
                    ),
                },
                "scheduler": {
                    "max_queue": self.cfg.max_queue,
                    "tenant_rate": self.cfg.tenant_rate,
                    "tenant_burst": self.cfg.tenant_burst,
                },
            },
        )
        # ---- metrics (resolved once; the publish path is lock-free)
        r = registry
        self._m_requests = r.counter(
            "serve_requests_total",
            "Requests by terminal status (serve/scheduler.py)",
        )
        self._m_rejected = r.counter(
            "serve_rejected_total", "Admission rejections by reason"
        )
        self._m_tokens = r.counter(
            "serve_tokens_total", "Tokens processed, by kind"
        )
        self._m_queue = r.gauge("serve_queue_depth", "Queued requests")
        self._m_draining = r.gauge(
            "serve_draining", "1 while the replica is draining"
        )
        self._m_active = r.gauge(
            "serve_active_sequences", "Sequences in the decode batch"
        )
        self._m_kv_used = r.gauge(
            "serve_kv_blocks_in_use", "Paged-KV blocks allocated"
        )
        self._m_kv_total = r.gauge(
            "serve_kv_blocks_total", "Paged-KV usable block count"
        )
        self._m_kv_total.set(engine.kv.cfg.usable_blocks)
        # occupancy in the bytes the pool ACTUALLY allocates (int8 KV
        # halves them; analysis/cost.py kv_block_bytes incl. scales) +
        # the effective concurrent-sequence capacity at max_seq_len -
        # the number an operator can compare across kv dtypes, unlike a
        # raw block count whose byte value silently changed
        from ..analysis.cost import kv_capacity_sequences

        self._kv_block_bytes = engine.kv_block_bytes()
        self._m_kv_dtype = r.gauge(
            "serve_kv_dtype",
            "KV-pool storage dtype (1 at the active label)",
        )
        self._m_kv_dtype.labels(dtype=engine.kv_dtype_name()).set(1)
        self._m_kv_bytes_used = r.gauge(
            "serve_kv_bytes_in_use",
            "Allocated paged-KV bytes at the pool dtype (incl. scales)",
        )
        self._m_kv_bytes_total = r.gauge(
            "serve_kv_bytes_total",
            "Usable paged-KV pool bytes at the pool dtype (incl. scales)",
        )
        self._m_kv_bytes_total.set(
            engine.kv.cfg.usable_blocks * self._kv_block_bytes
        )
        # beside the pool's bytes, what the bucket programs hold beyond
        # their operands: a family whose figure nears a pool's size
        # copies the pool instead of addressing its rows (empty until
        # the engine has run warmup())
        m_temp = r.gauge(
            "serve_program_temp_bytes",
            "Largest compiled temp_size_in_bytes of a bucket family",
        )
        for family, nbytes in engine.program_temp_bytes.items():
            m_temp.labels(family=family).set(nbytes)
        self._m_kv_capacity = r.gauge(
            "serve_kv_capacity_sequences",
            "Concurrent max_seq_len sequences the pool holds",
        )
        self._m_kv_capacity.set(kv_capacity_sequences(
            engine.kv.cfg.usable_blocks, engine.ecfg.block_size,
            engine.ecfg.max_seq_len,
        ))
        self._m_ttft = r.histogram(
            "serve_ttft_seconds", "Time to first token",
            buckets=LATENCY_BUCKETS,
        )
        self._m_intertoken = r.histogram(
            "serve_intertoken_seconds", "Gap between streamed tokens",
            buckets=LATENCY_BUCKETS,
        )
        self._m_preempt = r.counter(
            "serve_preemptions_total", "Sequences preempted on KV pressure"
        )
        self._m_steps = r.counter(
            "serve_engine_steps_total", "Engine decode steps executed"
        )
        # speculative decoding: proposed/accepted draft tokens plus a
        # per-slot-step acceptance histogram (integer buckets 0..k -
        # "how many of this step's k drafts survived verification")
        self._m_spec_proposed = r.counter(
            "serve_spec_proposed_tokens_total",
            "Draft tokens proposed by the speculative drafter",
        )
        self._m_spec_accepted = r.counter(
            "serve_spec_accepted_tokens_total",
            "Draft tokens accepted by target-model verification",
        )
        spec_k = max(int(getattr(engine, "spec_k", 0)), 1)
        self._m_spec_accept_hist = r.histogram(
            "serve_spec_accepted_per_step",
            "Accepted draft tokens per speculative slot-step",
            buckets=tuple(float(i) for i in range(spec_k)),
        )
        # where the loop thread's time goes and what the bucket programs
        # are shaped for against what they hold (docs/SERVING.md
        # "Metrics"); published a whole tick at a time (_publish_tick)
        loop_s = r.counter(
            "serve_loop_seconds_total",
            "Seconds of the serve loop thread, by phase",
        )
        self._m_loop_s = {p: loop_s.labels(phase=p) for p in LOOP_PHASES}
        self._m_decode_calls = r.counter(
            "serve_decode_calls_total",
            "Decode dispatches by bucket (batch, width in blocks)",
        )
        self._m_prefill_calls = r.counter(
            "serve_prefill_calls_total",
            "Prefill dispatches by bucket (chunk, width in blocks)",
        )
        decode_pos = r.counter(
            "serve_decode_positions_total",
            "Cache positions of decode dispatches: live (attended to), "
            "read (fetched from the pool) and padded (the bucket's shape)",
        )
        prefill_pos = r.counter(
            "serve_prefill_positions_total",
            "Cache positions of prefill dispatches: live and padded",
        )
        # ticks by how they were dispatched (serve/engine.py `step`):
        # before the tick before's tokens were fetched, or with nothing
        # in flight
        ahead = r.counter(
            "serve_dispatch_ahead_total",
            "Ticks dispatched ahead of the last tick's fetch, or drained",
        )
        self._m_dispatch = {o: ahead.labels(outcome=o)
                            for o in ("ahead", "drained")}
        # the engine's host phases by part, and its bucket programs by
        # whether the device had finished all it was handed when each was
        # called (serve/engine.py `_call`): "idle", it waited on the host
        host_s = r.counter(
            "serve_host_seconds_total",
            "Seconds of the engine's host phases, by part",
        )
        self._m_host_s = {p: host_s.labels(part=p) for p in HOST_PARTS}
        found = r.counter(
            "serve_dispatch_found_total",
            "Bucket-program dispatches by whether the device was idle",
        )
        self._m_found = {(p, d): found.labels(program=p, device=d)
                         for p in ("prefill", "decode")
                         for d in ("idle", "busy")}
        self._m_decode_live = decode_pos.labels(kind="live")
        self._m_decode_read = decode_pos.labels(kind="read")
        self._m_decode_padded = decode_pos.labels(kind="padded")
        self._m_prefill_live = prefill_pos.labels(kind="live")
        self._m_prefill_padded = prefill_pos.labels(kind="padded")
        # the Mosaic attention calls' work, and the expert layers' routing
        # of a module that has them (serve/engine.py `_latent_layers`)
        self._m_kernel_decode = r.counter(
            "serve_attn_kernel_positions_total",
            "Live cached positions handed to Mosaic decode attention calls",
        ).labels(path="decode")
        self._m_kernel_prefill = r.counter(
            "serve_attn_kernel_pairs_total",
            "Live query-key pairs handed to Mosaic prefill attention calls",
        ).labels(path="prefill")
        # a prefill program's attention pairs by layer kind, of a module
        # that counts them (models/mimo_v2.py `attn_pairs`): scored, and
        # kept by the masks
        self._m_attn_pairs = r.counter(
            "serve_attn_pairs_total",
            "Query-key pairs of prefill attention, by layer kind: scored "
            "and live (kept by the causal and window masks)",
        )
        moe_pairs = r.counter(
            "serve_moe_pairs_total",
            "Routed (token, expert) pairs by where their expert lies",
        )
        moe_rows = r.counter(
            "serve_moe_rows_total",
            "Rows of the expert products: owned by a held pair, multiplied",
        )
        self._m_moe = {
            "held": moe_pairs.labels(where="held"),
            "absent": moe_pairs.labels(where="absent"),
            "owned": moe_rows.labels(kind="owned"),
            "multiplied": moe_rows.labels(kind="multiplied"),
        }
        moe_experts = r.counter(
            "serve_moe_experts_total",
            "Held experts over every expert layer of every program "
            "dispatched: read (own a routed row) and held",
        )
        self._m_moe_experts = {k: moe_experts.labels(kind=k)
                               for k in ("read", "held")}
        self._m_state_slots = r.gauge(
            "serve_state_slots_in_use",
            "Slots of the state pool held by sequences (a model whose "
            "layers keep a state a sequence beside the KV cache)",
        )
        self._m_moe_load = r.gauge(
            "serve_moe_expert_load_max_over_mean",
            "Busiest held expert's pairs over the mean, last tick, by "
            "expert layer",
        )
        if r is not NULL_REGISTRY:
            self.ledger.publish(r)

    # --------------------------------------------------------- admission

    def submit(self, req: ServeRequest) -> ServeRequest:
        """Admit a request to the queue (any thread). Raises
        `AdmissionError` (429/400/503); on success the request will
        stream through ``req.events``."""
        if self._draining:
            self._m_rejected.labels(reason="draining").inc()
            self.reqtrace.note_rejected("draining")
            raise AdmissionError(
                503, "draining",
                "replica is draining; retry on another replica",
            )
        ecfg = self.engine.ecfg
        if not req.prompt:
            raise AdmissionError(400, "empty_prompt", "empty prompt")
        total = len(req.prompt) + req.max_new_tokens
        if req.max_new_tokens < 1:
            raise AdmissionError(
                400, "bad_max_new_tokens",
                f"max_new_tokens must be >= 1, got {req.max_new_tokens}",
            )
        if total > ecfg.max_seq_len:
            raise AdmissionError(
                400, "too_long",
                f"prompt {len(req.prompt)} + max_new_tokens "
                f"{req.max_new_tokens} = {total} exceeds max_seq_len "
                f"{ecfg.max_seq_len}",
            )
        vmax = self.engine.cfg.vocab_size
        if any(not (0 <= int(t) < vmax) for t in req.prompt):
            raise AdmissionError(
                400, "bad_token",
                f"prompt token out of range [0, {vmax})",
            )
        if self.cfg.tenant_rate > 0:
            with self._lock:
                bucket = self._buckets.get(req.api_key)
                if bucket is None:
                    bucket = self._buckets[req.api_key] = _TokenBucket(
                        self.cfg.tenant_rate, self.cfg.tenant_burst
                    )
            if not bucket.try_take():
                self._m_rejected.labels(reason="rate_limited").inc()
                self.reqtrace.note_rejected("rate_limited")
                raise AdmissionError(
                    429, "rate_limited",
                    f"tenant {req.api_key!r} over "
                    f"{self.cfg.tenant_rate:g} req/s "
                    f"(burst {self.cfg.tenant_burst})",
                )
        with self._work:
            if self._queued >= self.cfg.max_queue:
                self._m_rejected.labels(reason="queue_full").inc()
                self.reqtrace.note_rejected("queue_full")
                raise AdmissionError(
                    429, "queue_full",
                    f"admission queue full ({self.cfg.max_queue})",
                )
            req.req_id = next(self._ids)
            req.t_arrival = time.monotonic()
            req._t_arrival_ledger = self.ledger.now()
            req.events = queue_mod.Queue()
            req.status = "queued"
            self.reqtrace.arrive(
                req.req_id, req.api_key, len(req.prompt),
                req.max_new_tokens,
            )
            if req.router_retries:
                self.reqtrace.note_router_retry(
                    req.req_id, req.router_retries, req.router_retry_s
                )
            fifo = self._tenants.get(req.api_key)
            if fifo is None:
                fifo = self._tenants[req.api_key] = deque()
                self._tenant_order.append(req.api_key)
            fifo.append(req)
            self._queued += 1
            self._m_queue.set(self._queued)
            self._m_requests.labels(status="accepted").inc()
            self._work.notify()
        return req

    def cancel(self, req: ServeRequest) -> None:
        """Client-side cancel (disconnect): flagged here, enacted by the
        loop at the next step boundary."""
        req.cancelled.set()
        with self._work:
            self._work.notify()

    def finish_stream(self, req: ServeRequest) -> None:
        """Streaming-channel ack (any thread): the owner finished
        writing the request's tail, so its trace record's
        ``stream_write`` span closes and the record seals. Only acts on
        a request already at a terminal status - a mid-flight stream
        error stays with the loop (cancel / shutdown paths)."""
        if req.req_id and req.status in (
            "done", "cancelled", "error", "migrated"
        ):
            self.reqtrace.finalize(
                req.req_id, req.status  # idempotent vs the loop's seal
            )

    # ------------------------------------------------------------- loop

    def start(self) -> "ServeScheduler":
        if self._thread is not None:
            return self
        self._running = True
        self._thread = threading.Thread(
            target=self._loop, name="serve-scheduler", daemon=True
        )
        self._thread.start()
        return self

    def close(self, *, finalize: bool = True) -> dict | None:
        """Stop the loop, fail queued/active requests, finalize the
        serving ledger (conservation asserted) and return the record."""
        self._running = False
        with self._work:
            self._work.notify()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        # drain every remaining request with a shutdown error
        with self._work:
            pending = [r for f in self._tenants.values() for r in f]
            for f in self._tenants.values():
                f.clear()
            self._queued = 0
            self._m_queue.set(0)
        for req in pending + list(self._by_seq.values()):
            if req.status not in ("done", "cancelled", "error", "migrated"):
                req.status = "error"
                if req.events is not None:
                    req.events.put(("error", "server shutting down"))
        self.reqtrace.finalize_all()
        if finalize:
            return self.ledger.finalize()
        return None

    # ------------------------------------------------------------ drain

    @property
    def draining(self) -> bool:
        return self._draining

    def drain(self, timeout: float = 30.0) -> dict:
        """Stop admission and migrate every live sequence out as a
        deterministic replay descriptor (any thread). Returns
        ``{"draining", "completed", "migrated"}`` where ``migrated`` is
        the descriptor list a peer replica (or the fleet router) can
        resubmit via `engine.resume_request` for a byte-identical
        continuation. Idempotent; an empty replica completes
        immediately."""
        with self._work:
            first = not self._draining
            self._draining = True
            self._m_draining.set(1)
            self._work.notify()
        if self._thread is None:
            # no loop thread (tests / synchronous drivers): sweep inline
            self._drain_sweep()
        ok = self._drained.wait(timeout=timeout)
        with self._work:
            descs = list(self._drain_out)
            if first:
                self._drain_out = []
        return {"draining": True, "completed": ok, "migrated": descs}

    def _migrate_one(self, req: ServeRequest) -> None:
        """Seal one request as migrated and emit its replay descriptor
        (loop thread). Queued requests (no engine sequence yet) migrate
        with an empty emitted list — a plain re-dispatch."""
        if req._seq is not None:
            desc = export_descriptor(req._seq)
        else:
            desc = {
                "seq_id": int(req.req_id),
                "prompt": [int(t) for t in req.prompt],
                "emitted": [],
                "max_new_tokens": int(req.max_new_tokens),
                "remaining_tokens": int(req.max_new_tokens),
                "temperature": float(req.temperature),
                "seed": int(req.seed),
                "preemptions": 0,
            }
        desc["api_key"] = req.api_key
        req.status = "migrated"
        req.t_done = time.monotonic()
        self._m_requests.labels(status="migrated").inc()
        self.reqtrace.finalize(req.req_id, "migrated")
        if req.events is not None:
            req.events.put(("migrate", desc))
        self._drain_out.append(desc)

    def _drain_sweep(self) -> None:
        """Evict every live request as a migration descriptor (loop
        thread, or inline when the loop never started). Cancels are
        enacted FIRST so a client cancel racing the drain wins — its
        request ends cancelled, not migrated. The tick in flight lands
        before anything is exported: its tokens reach their clients, and
        no row is left on the device under the blocks freed here."""
        t0 = self.ledger.now()
        landed = self.engine.flush()
        if landed is not None:
            # (its seconds are the sweep's: the loop's `admit` phase)
            self._books({}, landed, t0, self.ledger.now(),
                        len(self.engine.preempted))
        self._enact_cancels()
        # active (running AND parked-on-kv) sequences: both live in
        # engine.active; cancel() frees their blocks
        for sid, req in list(self._by_seq.items()):
            self.engine.cancel(sid)
            self._by_seq.pop(sid, None)
            self._migrate_one(req)
        # preempted sequences' requests were in _by_seq too (their
        # blocks are already freed); clear the replay deque
        self.engine.preempted.clear()
        with self._work:
            pending = [r for f in self._tenants.values() for r in f]
            for f in self._tenants.values():
                f.clear()
            self._queued = 0
            self._m_queue.set(0)
        for req in pending:
            if req.cancelled.is_set():
                req.status = "cancelled"
                req.t_done = time.monotonic()
                self._m_requests.labels(status="cancelled").inc()
                self.reqtrace.finalize(req.req_id, "cancelled")
                if req.events is not None:
                    req.events.put(("done", req.summary()))
            else:
                self._migrate_one(req)
        self._m_active.set(len(self.engine.active))
        self._m_kv_used.set(self.engine.kv.blocks_in_use)
        self._m_kv_bytes_used.set(
            self.engine.kv.blocks_in_use * self._kv_block_bytes
        )
        self._drained.set()

    def _next_request(self):
        """Round-robin over tenant FIFOs (caller holds the lock)."""
        for _ in range(len(self._tenant_order)):
            key = self._tenant_order[0]
            self._tenant_order.rotate(-1)
            fifo = self._tenants.get(key)
            if fifo:
                self._queued -= 1
                return fifo.popleft()
        return None

    def _admit_one(self, req: ServeRequest) -> None:
        """Wire a queued request into the engine (loop thread)."""
        if req.cancelled.is_set():
            req.status = "cancelled"
            req.t_done = time.monotonic()
            self._m_requests.labels(status="cancelled").inc()
            self.reqtrace.finalize(req.req_id, "cancelled")
            if req.events is not None:
                req.events.put(("done", req.summary()))
            return
        self.reqtrace.mark(req.req_id, "admission")
        seq = Sequence(
            seq_id=req.req_id,
            prompt=[int(t) for t in req.prompt],
            max_new_tokens=int(req.max_new_tokens),
            temperature=float(req.temperature),
            seed=int(req.seed),
            on_token=self._on_token,
        )
        req._seq = seq
        self._by_seq[seq.seq_id] = req
        self.engine.add(seq)
        req.t_admitted = time.monotonic()
        req.status = "active"
        self.reqtrace.mark(req.req_id, "prefill")
        # the request's whole queued window, attributed once the sweep
        # resolves overlaps (it only claims otherwise-idle seconds)
        self.ledger.add(
            "queue_wait", req._t_arrival_ledger, self.ledger.now()
        )

    def _on_token(self, seq: Sequence, tok: int, done: bool) -> None:
        """Engine callback (loop thread): stream + latency metrics."""
        req = self._by_seq.get(seq.seq_id)
        if req is None:
            return
        now = time.monotonic()
        req.tokens.append(int(tok))
        self.reqtrace.note_token(seq.seq_id)
        if req.t_first_token is None:
            req.t_first_token = now
            self._m_ttft.observe(now - req.t_arrival)
        elif req._t_prev_token is not None:
            self._m_intertoken.observe(now - req._t_prev_token)
        req._t_prev_token = now
        if req.events is not None:
            req.events.put(("token", int(tok)))
        if done:
            req.status = "done"
            req.t_done = now
            self._m_requests.labels(status="completed").inc()
            self._by_seq.pop(seq.seq_id, None)
            # the stream_write window opens BEFORE the done event is
            # visible to the streaming thread; with no stream owner the
            # record seals immediately (zero-length flush)
            self.reqtrace.mark(seq.seq_id, "stream_write")
            if not req.stream_owner:
                self.reqtrace.finalize(seq.seq_id, "done")
            if req.events is not None:
                req.events.put(("done", req.summary()))

    def _enact_cancels(self) -> None:
        for sid, req in list(self._by_seq.items()):
            if req.cancelled.is_set() and req.status == "active":
                self.engine.cancel(sid)
                self._by_seq.pop(sid, None)
                req.status = "cancelled"
                req.t_done = time.monotonic()
                self._m_requests.labels(status="cancelled").inc()
                self.reqtrace.finalize(sid, "cancelled")
                if req.events is not None:
                    req.events.put(("done", req.summary()))
        # preempted sequences whose request was cancelled while parked
        self.engine.preempted = deque(
            s for s in self.engine.preempted
            if self._by_seq.get(s.seq_id) is not None
        )

    def _loop(self) -> None:
        eng = self.engine
        cfg = self.cfg
        phase = self._phase
        while self._running:
            # every instant in one phase but for the spans' own enter and
            # exit and the engine's call and return; the loop's checks for
            # work are its waits'
            phase.to("wait")
            if self._draining:
                phase.to("admit")
                self._drain_sweep()
                phase.to("wait")
                with self._work:
                    self._work.wait(timeout=cfg.idle_poll_s)
                continue
            with self._work:
                have_queued = self._queued > 0
            if not (have_queued or eng.has_work() or eng.preempted):
                with self._work:
                    self._work.wait(timeout=cfg.idle_poll_s)
                continue
            phase.to(None)

            with TraceAnnotation("serve.tick", tick=eng.ticks):
                t_form0 = phase.to("admit")
                self._admit_ready()
                work = eng.has_work()
                preempted_before = len(eng.preempted)
                t0 = phase.to(None)
                if work:
                    with TraceAnnotation("serve.step"):
                        stats = eng.step()
                # (the next tick's programs are on the device meanwhile)
                t1 = phase.to("books")
                # the phases' readings of the clock serve the ledger too
                if t0 > t_form0:
                    self.ledger.add("batch_formation_idle", t_form0, t0)
                if work:
                    self._books(phase.take() | stats["phase_s"], stats, t0,
                                t1, preempted_before)
                phase.to(None)
        phase.to(None)

    def _admit_ready(self) -> None:
        """The admission pass of one loop iteration (loop thread):
        enact cancels, re-admit preempted sequences, admit queued
        requests while capacity lasts."""
        eng = self.engine
        kv = eng.kv
        self._enact_cancels()
        # re-admit preempted sequences first (streamed state)
        while eng.preempted and len(eng.active) < eng.ecfg.max_batch:
            s = eng.preempted[0]
            if not kv.can_fit(s.prompt_len + 1):
                break
            eng.preempted.popleft()
            eng.add(s)
            # replay starts at pos 0: back to prefill until the
            # engine re-derives the held tokens
            self.reqtrace.mark(s.seq_id, "prefill")
        # admit new requests round-robin while capacity lasts
        while len(eng.active) < eng.ecfg.max_batch:
            with self._work:
                nxt = self._next_request() if self._queued > 0 else None
                if nxt is not None:
                    self._m_queue.set(self._queued)
            if nxt is None:
                break
            need = kv.cfg.blocks_for_tokens(len(nxt.prompt) + 1)
            if need + self.cfg.block_headroom > kv.free_blocks:
                # no room for this prompt yet: back to the head of
                # its tenant FIFO (it keeps its place; 429 pressure
                # builds behind the queue bound), stop admitting
                with self._work:
                    self._tenants[nxt.api_key].appendleft(nxt)
                    self._queued += 1
                    self._m_queue.set(self._queued)
                break
            self._admit_one(nxt)

    def _books(self, phase_s: dict, stats: dict, t0: float, t1: float,
               preempted_before: int) -> None:
        """One landed tick into the registry, the traces and the ledger
        (loop thread): counted when its tokens have reached the clients."""
        self._m_steps.inc()
        self._publish_tick(phase_s, stats)
        self._account_step(stats, t0, t1, preempted_before)

    def _publish_tick(self, phase_s: dict, stats: dict) -> None:
        """Everything the registry learns of one tick's time and
        shapes, in one place and after `serve_engine_steps_total` has
        counted it: a scrape between two ticks sees whole ticks (one
        step's end to the next, as that counter beats), so
        delta(seconds) / delta(steps) is a mean per tick. ``phase_s``:
        the loop thread's seconds by phase since the last tick."""
        for phase, dt in phase_s.items():
            self._m_loop_s[phase].inc(dt)
        for part, dt in stats.get("host_s", {}).items():
            self._m_host_s[part].inc(dt)
        for program, device in stats.get("found", ()):
            self._m_found[program, device].inc()
        if stats.get("dispatch") is not None:
            self._m_dispatch[stats["dispatch"]].inc()
        bs = self.engine.ecfg.block_size
        call = stats["decode_call"]
        if call is not None:
            B, W, live, read = call
            self._m_decode_calls.labels(
                batch=str(B), width_blocks=str(W)
            ).inc()
            self._m_decode_live.inc(live)
            self._m_decode_read.inc(read)
            self._m_decode_padded.inc(B * W * bs)
            if stats.get("decode_kernel"):
                self._m_kernel_decode.inc(live)
        for C, W, live in stats["prefill_calls"]:
            self._m_prefill_calls.labels(
                chunk=str(C), width_blocks=str(W)
            ).inc()
            self._m_prefill_live.inc(live)
            self._m_prefill_padded.inc(C * W * bs)
        # (only the prefill programs of a module with a prefill attention
        # kernel call one: other ticks do not bring the key)
        self._m_kernel_prefill.inc(stats.get("prefill_kernel_pairs", 0))
        for (layers, kind), n in stats.get("attn_pairs", {}).items():
            self._m_attn_pairs.labels(layers=layers, kind=kind).inc(n)
        moe = stats.get("moe")
        if moe is not None:
            self._m_moe["held"].inc(moe["held"])
            self._m_moe["absent"].inc(moe["absent"])
            self._m_moe["owned"].inc(moe["held"])
            self._m_moe["multiplied"].inc(moe["multiplied"])
            self._m_moe_experts["read"].inc(moe["experts_read"])
            self._m_moe_experts["held"].inc(moe["experts_held"])
            for layer, load in enumerate(moe["load"]):
                if load.sum():
                    self._m_moe_load.labels(layer=str(layer)).set(
                        float(load.max() / load.mean()))

    def _account_step(self, stats: dict, t0: float, t1: float,
                      preempted_before: int) -> None:
        """The books of one tick (loop thread): per-request traces, the
        ledger's share of the step ``t0..t1``, token counters, gauges,
        the heartbeat."""
        eng = self.engine
        kv = eng.kv
        self.reqtrace.observe_step(stats, t0, t1)
        if len(eng.preempted) > preempted_before:
            self._m_preempt.inc(len(eng.preempted) - preempted_before)
        spec = stats.get("spec")
        if spec:
            if spec["proposed"]:
                self._m_spec_proposed.inc(spec["proposed"])
            if spec["accepted"]:
                self._m_spec_accepted.inc(spec["accepted"])
            for a in spec.get("per_slot", ()):
                self._m_spec_accept_hist.observe(float(a))
        dec, pre = stats["decode_tokens"], stats["prefill_tokens"]
        span = t1 - t0
        if dec + pre > 0 and span > 0:
            # one fenced step span, apportioned to the two phases by
            # token counts - prefill and decode genuinely share the
            # batch (token-level continuous batching), so the split
            # is the honest per-phase cost
            t_split = t0 + span * (pre / (dec + pre))
            if pre > 0:
                self.ledger.add("prefill", t0, t_split)
            if dec > 0:
                self.ledger.add("decode", t_split, t1)
            self._m_tokens.labels(kind="prefill").inc(pre)
            self._m_tokens.labels(kind="decode").inc(dec)
            self.ledger.note_steps(1, tokens=float(dec))
        elif span > 0:
            # a tick that moved nothing: block exhaustion (possibly
            # including preemption work)
            self.ledger.add("kv_alloc_stall", t0, t1)
        self._m_active.set(len(eng.active))
        self._m_kv_used.set(kv.blocks_in_use)
        self._m_state_slots.set(kv.state_slots_in_use)
        self._m_kv_bytes_used.set(
            kv.blocks_in_use * self._kv_block_bytes
        )
        self.ledger.maybe_publish()
        self.ledger.maybe_write()
        self.registry.beat(eng.ticks)
        if not self.registry.ready and eng.ticks > 0:
            self.registry.mark_ready()
