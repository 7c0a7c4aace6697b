"""HTTP face of the serving stack + the `python -m
distributed_neural_network_tpu.serve` CLI.

One `utils/obs.py ObsServer` carries everything: the observability
endpoints every load balancer / scraper already knows (``/metrics``
Prometheus text with the full serve_* series, ``/healthz`` liveness ->
status-code mapping) plus the serving routes mounted through the
pluggable route table:

- ``POST /v1/generate`` - body ``{"prompt": [int, ...] | "text": str,
  "max_new_tokens": N, "temperature": t, "seed": s, "stream": bool,
  "api_key": k}`` (the key may also ride the ``X-API-Key`` header).
  With ``stream`` (default true) the response is server-sent events:
  one ``data: {"token": id}`` frame per generated token as it leaves
  the decode step, then ``data: {"done": true, ...summary}``. A client
  disconnect mid-stream cancels the request at the next step boundary
  (blocks freed - a closed tab never holds KV memory). Without
  ``stream``, one JSON body after completion. Admission rejections map
  to HTTP status: 429 (queue full / tenant over rate, with
  ``Retry-After``) and 400 (malformed / over-length), so standard
  client backoff just works.
- ``GET /v1/status`` - one JSON snapshot (active/queued/KV occupancy,
  in-flight request summaries).
- ``GET /v1/requests`` - the per-request lifecycle records
  (serve/reqtrace.py): in-flight summaries + the bounded ring of
  finalized records. ``?full=1`` includes every ringed record's span
  sequence (the `tools/request_trace.py` input); ``?id=N`` returns one
  request's full detail (404 when it fell off the ring).

``"text"`` prompts are byte-tokenized (the `data/tokens.py` .txt
convention; needs vocab >= 256); responses for text prompts include the
decoded completion.

The CLI builds a seeded-random model (the same ``init_params(key(seed),
cfg)`` any offline process can rebuild - `tools/loadgen.py
--check-oracle` exploits exactly this to verify streamed completions
bitwise against `models/transformer.py generate`), prints the bound URL
for port-0 discovery, and on SIGTERM/SIGINT finalizes the serving
goodput ledger (conservation asserted) before printing a
``SERVE_SUMMARY`` JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading
from urllib.parse import parse_qs, urlsplit

from ..utils.obs import MetricsRegistry, ObsServer
from .engine import EngineConfig, ServeEngine
from .scheduler import (
    AdmissionError,
    SchedulerConfig,
    ServeRequest,
    ServeScheduler,
)

# how long a streaming reader waits on the next token before declaring
# the stream wedged (a generous multiple of any sane step time)
STREAM_TIMEOUT_S = 300.0


def _json_response(handler, code: int, doc: dict,
                   extra_headers=()) -> None:
    body = (json.dumps(doc) + "\n").encode()
    handler.send_response(code)
    handler.send_header("Content-Type", "application/json")
    for k, v in extra_headers:
        handler.send_header(k, v)
    handler.send_header("Content-Length", str(len(body)))
    handler.end_headers()
    handler.wfile.write(body)


class ServeServer:
    """The scheduler behind an ObsServer with /v1/* routes mounted."""

    def __init__(
        self,
        scheduler: ServeScheduler,
        registry: MetricsRegistry,
        *,
        port: int = 0,
        host: str = "127.0.0.1",
        replica_id: str | None = None,
    ):
        self.scheduler = scheduler
        self.registry = registry
        self.replica_id = replica_id
        self.obs = ObsServer(
            registry,
            port=port,
            host=host,
            routes={
                ("POST", "/v1/generate"): self._generate,
                ("GET", "/v1/status"): self._status,
                ("GET", "/v1/requests"): self._requests,
                ("POST", "/v1/drain"): self._drain_route,
            },
        )
        self.port = self.obs.port
        self.url = self.obs.url

    def close(self) -> None:
        self.obs.close()

    # ------------------------------------------------------------ routes

    def _status(self, handler) -> None:
        eng = self.scheduler.engine
        blk_bytes = eng.kv_block_bytes()
        _json_response(handler, 200, {
            "replica": self.replica_id,
            "draining": self.scheduler.draining,
            "active_sequences": len(eng.active),
            "queued": self.scheduler._queued,
            "kv_blocks_in_use": eng.kv.blocks_in_use,
            "kv_blocks_total": eng.kv.cfg.usable_blocks,
            "kv_utilization": round(eng.kv.utilization(), 4),
            "kv_dtype": eng.kv_dtype_name(),
            "kv_bytes_in_use": eng.kv.blocks_in_use * blk_bytes,
            "kv_bytes_total": eng.kv.cfg.usable_blocks * blk_bytes,
            "engine_ticks": eng.ticks,
            "decode_tokens": eng.decode_tokens,
            "prefill_tokens": eng.prefill_tokens,
            # per-bucket-family compiled-program counts: reconcile a
            # live deployment against its servelint grid manifest
            # (after warmup() the counts match the manifest and must
            # never grow - analysis/serve_trace.py)
            "compiled_programs": eng.compiled_programs(),
            "decode_route": eng.decode_route(),
            "weight_dtype": eng.weight_dtype_name(),
            "spec_decode": eng.spec_k,
            "spec_draft_layers": eng.draft_layers if eng.spec_k else 0,
            "spec_proposed_tokens": eng.spec_proposed_tokens,
            "spec_accepted_tokens": eng.spec_accepted_tokens,
            "spec_steps": eng.spec_steps,
            "spec_acceptance_rate": (
                round(eng.spec_accepted_tokens
                      / eng.spec_proposed_tokens, 4)
                if eng.spec_proposed_tokens else None
            ),
            "requests": self.scheduler.reqtrace.in_flight(),
            "requests_finalized":
                self.scheduler.reqtrace.finalized_total,
        })

    def _requests(self, handler) -> None:
        # the route table keys on the query-stripped path; the raw
        # request line still carries ?id= / ?full=
        qs = parse_qs(urlsplit(handler.path).query)
        rid = qs.get("id", [None])[0]
        if rid is not None:
            try:
                rid = int(rid)
            except ValueError:
                _json_response(
                    handler, 400, {"error": "id must be an integer"}
                )
                return
            doc = self.scheduler.reqtrace.get(rid)
            if doc is None:
                _json_response(handler, 404, {
                    "error": f"request {rid} not found "
                    "(never seen, or evicted from the ring)",
                })
            else:
                _json_response(handler, 200, {"request": doc})
            return
        full = qs.get("full", ["0"])[0] not in ("0", "", "false")
        _json_response(
            handler, 200, self.scheduler.reqtrace.snapshot(full=full)
        )

    def _drain_route(self, handler) -> None:
        """Graceful drain: stop admission, migrate live sequences out as
        deterministic replay descriptors (engine.export_descriptor), and
        report them so the fleet router can re-dispatch to peers. The
        process itself is released by the caller (SIGTERM after drain -
        the CLI exits 0)."""
        out = self.scheduler.drain()
        _json_response(handler, 200, {
            "replica": self.replica_id,
            "draining": True,
            "completed": bool(out.get("completed")),
            "migrated": out.get("migrated", []),
        })

    def _parse_request(self, handler):
        try:
            n = int(handler.headers.get("Content-Length") or 0)
        except ValueError:
            n = 0
        try:
            body = json.loads(handler.rfile.read(n) or b"{}")
            if not isinstance(body, dict):
                raise ValueError("body must be a JSON object")
        except (ValueError, UnicodeDecodeError) as e:
            raise AdmissionError(400, "bad_json", f"invalid JSON body: {e}")
        is_text = False
        prompt = body.get("prompt")
        if prompt is None and isinstance(body.get("text"), str):
            vocab = self.scheduler.engine.cfg.vocab_size
            if vocab < 256:
                raise AdmissionError(
                    400, "no_text_tokens",
                    f"text prompts are byte-tokenized and need "
                    f"vocab_size >= 256 (model has {vocab}); send "
                    "integer 'prompt' tokens instead",
                )
            prompt = list(body["text"].encode())
            is_text = True
        if not isinstance(prompt, list) or not all(
            isinstance(t, int) for t in prompt
        ):
            raise AdmissionError(
                400, "bad_prompt",
                "body needs 'prompt': [int token ids] or 'text': str",
            )
        api_key = (
            handler.headers.get("X-API-Key")
            or body.get("api_key")
            or "anonymous"
        )
        # fleet-router failover provenance (serve/fleet.py re-dispatch)
        try:
            retries = int(handler.headers.get("X-Router-Retries") or 0)
            retry_s = float(
                handler.headers.get("X-Router-Retry-Seconds") or 0.0
            )
        except ValueError:
            retries, retry_s = 0, 0.0
        req = ServeRequest(
            prompt=prompt,
            max_new_tokens=int(body.get("max_new_tokens", 16)),
            temperature=float(body.get("temperature", 0.0)),
            seed=int(body.get("seed", 0)),
            api_key=str(api_key),
            router_retries=retries,
            router_retry_s=retry_s,
            stream_owner=True,  # this handler acks the stream tail
        )
        return req, bool(body.get("stream", True)), is_text

    def _generate(self, handler) -> None:
        try:
            req, stream, is_text = self._parse_request(handler)
            self.scheduler.submit(req)
        except AdmissionError as e:
            extra = (
                (("Retry-After", "1"),) if e.status == 429 else ()
            )
            _json_response(handler, e.status, {
                "error": str(e), "reason": e.reason,
            }, extra)
            return
        if stream:
            self._stream_response(handler, req, is_text)
        else:
            self._block_response(handler, req, is_text)

    def _drain(self, req):
        """Yield events until done/error/timeout (generator)."""
        import queue as queue_mod

        while True:
            try:
                kind, payload = req.events.get(timeout=STREAM_TIMEOUT_S)
            except queue_mod.Empty:
                yield "error", "stream timeout"
                return
            yield kind, payload
            if kind in ("done", "error", "migrate"):
                return

    def _summary_doc(self, req, is_text) -> dict:
        doc = req.summary()
        if self.replica_id is not None:
            doc["replica"] = self.replica_id
        if is_text:
            doc["text"] = bytes(
                t for t in req.tokens if 0 <= t < 256
            ).decode("utf-8", "replace")
        return doc

    def _stream_response(self, handler, req, is_text) -> None:
        handler.send_response(200)
        handler.send_header("Content-Type", "text/event-stream")
        handler.send_header("Cache-Control", "no-store")
        handler.send_header("Connection", "close")
        handler.end_headers()
        try:
            for kind, payload in self._drain(req):
                if kind == "token":
                    frame = {"token": payload}
                elif kind == "done":
                    frame = dict(self._summary_doc(req, is_text))
                    frame["done"] = True
                elif kind == "migrate":
                    # drain migration: the fleet router re-dispatches
                    # with already-streamed tokens as prompt suffix
                    frame = {
                        "migrated": True,
                        "req_id": req.req_id,
                        "n_tokens": len(req.tokens),
                        "replica": self.replica_id,
                    }
                else:
                    frame = {"error": payload}
                handler.wfile.write(
                    f"data: {json.dumps(frame)}\n\n".encode()
                )
                handler.wfile.flush()
        except (BrokenPipeError, ConnectionResetError, OSError):
            # client went away mid-stream: free its slot + KV blocks
            self.scheduler.cancel(req)
        finally:
            # seals the trace record's stream_write span (no-op unless
            # the request already reached a terminal status - a wedged
            # stream stays with the loop's cancel/shutdown paths)
            self.scheduler.finish_stream(req)

    def _block_response(self, handler, req, is_text) -> None:
        last_err = None
        for kind, payload in self._drain(req):
            if kind == "error":
                last_err = payload
        try:
            if last_err is not None and req.status != "done":
                _json_response(handler, 500, {"error": last_err})
                return
            _json_response(handler, 200, self._summary_doc(req, is_text))
        finally:
            self.scheduler.finish_stream(req)


# ----------------------------------------------------------------- CLI


def build_model(args):
    """Seeded-random model from CLI geometry (rebuildable offline for
    the oracle check)."""
    import jax
    import jax.numpy as jnp

    from ..models.transformer import TransformerConfig, init_params

    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    if getattr(args, "model_config", ""):
        # a published configuration's file names its family's module
        # (models/__init__.py FAMILIES); the geometry flags stand unused
        import json

        from .. import models

        with open(args.model_config) as f:
            published = json.load(f)
        try:
            family = models.family_module(published.get("family"))
        except KeyError:
            raise SystemExit(
                f"--model-config {args.model_config}: family "
                f"{published.get('family')!r} has no model module here "
                f"(there is {sorted(models.FAMILIES)})"
            ) from None
        if not hasattr(family, "CACHE"):
            raise SystemExit(
                f"--model-config {args.model_config}: family "
                f"{published['family']!r} is not served (its module has no "
                "cache row for the engine)"
            )
        cfg = family.from_published(published, dtype=dtype)
        params = jax.tree.map(
            lambda x: x.astype(dtype),
            family.init_params(jax.random.key(args.seed), cfg))
        return params, cfg
    cfg = TransformerConfig(
        vocab_size=args.vocab,
        d_model=args.d_model,
        n_heads=args.n_heads,
        n_layers=args.n_layers,
        d_ff=args.d_ff,
        dtype=dtype,
    )
    params = init_params(jax.random.key(args.seed), cfg)
    return params, cfg


def add_model_args(p: argparse.ArgumentParser) -> None:
    """The model-geometry flags, shared verbatim by `tools/loadgen.py
    --check-oracle` so both sides always rebuild the same model."""
    p.add_argument("--d-model", type=int, default=64)
    p.add_argument("--n-heads", type=int, default=4)
    p.add_argument("--n-layers", type=int, default=2)
    p.add_argument("--d-ff", type=int, default=128)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--dtype", choices=("float32", "bfloat16"),
                   default="float32")
    p.add_argument("--seed", type=int, default=0,
                   help="init_params seed (the oracle contract)")
    p.add_argument("--model-config", default="", metavar="FILE",
                   help="serve the model of a published configuration's "
                   "file (its \"family\" names the module, "
                   "models/__init__.py FAMILIES: a latent-attention "
                   "expert model, e.g. benchmark/families/pangu_ultra_moe/"
                   "tiny.json, or a short-convolution and grouped-query "
                   "expert model, benchmark/families/lfm2_moe/tiny.json, "
                   "or a window- and full-attention expert model, "
                   "benchmark/configs/mimo-v2.5.json) "
                   "in place of the GPT-2 block of the geometry "
                   "flags; seeded weights at --dtype")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m distributed_neural_network_tpu.serve",
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    p.add_argument("--port", type=int, default=8000,
                   help="0 = ephemeral (the bound URL is printed)")
    p.add_argument("--host", default="127.0.0.1")
    add_model_args(p)
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--num-blocks", type=int, default=128)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--max-seq-len", type=int, default=512)
    p.add_argument("--prefill-chunk", type=int, default=1,
                   help="prompt tokens per chunked-prefill call (1 = "
                   "exact token-at-a-time prefill)")
    p.add_argument("--precision", default="bf16",
                   help="comma-separated set from {bf16, int8-kv, "
                   "int8-w}. 'int8-kv' stores the paged KV pool "
                   "quantized (int8 + per-(block, head) f32 scales): "
                   "~2x the concurrent-sequence capacity per HBM byte; "
                   "'int8-w' stores the weights quantized (int8 codes "
                   "+ per-output-column f32 scales) and routes every "
                   "weight matmul through the int8 dot path; they "
                   "compose ('int8-kv,int8-w'). Per-token top-1 "
                   "agreement vs the bf16 oracle gated >= 99%% in the "
                   "bench/CI parity rows (docs/SERVING.md). "
                   "'bf16' = neither quantization")
    p.add_argument("--spec-decode", type=int, default=0, metavar="K",
                   help="speculative decoding: an early-exit drafter "
                   "(the first --spec-draft-layers layers of the same "
                   "model) proposes K tokens per greedy slot each tick "
                   "and ONE verify step checks all K+1 positions at "
                   "once; rejected suffixes rewind the block-table "
                   "write cursor. Greedy streams stay token-exact vs "
                   "offline generate(). 0 = off")
    p.add_argument("--spec-draft-layers", type=int, default=0,
                   metavar="E",
                   help="drafter depth (early-exit layer count); "
                   "0 = auto (max(1, n_layers // 8))")
    p.add_argument("--decode-impl", choices=("auto", "xla", "pallas"),
                   default="auto",
                   help="the decode step's attention: the paged Pallas "
                   "kernel, which reads the KV pool through the block "
                   "table ('pallas'; float pools with heads of 128), vs "
                   "gathering the bucket for the XLA chain ('xla'); "
                   "'auto' routes to the kernel on TPU where a pool "
                   "page is a tile it compiles for, XLA otherwise")
    p.add_argument("--eos-token", type=int, default=None)
    p.add_argument("--max-queue", type=int, default=64)
    p.add_argument("--tenant-rate", type=float, default=0.0,
                   help="per-API-key token-bucket rate (req/s; 0 = off)")
    p.add_argument("--tenant-burst", type=int, default=8)
    p.add_argument("--run-record",
                   default=os.environ.get("DNN_TPU_RUN_RECORD"),
                   help="write the serving goodput record here "
                   "(utils/goodput.py taxonomy 'serve'; default "
                   "$DNN_TPU_RUN_RECORD - the fleet supervisor sets it "
                   "so serve/fleet.py aggregate_serve_records can fold "
                   "per-replica records into the fleet view)")
    p.add_argument("--trace-out", default=None,
                   help="export a Chrome trace of per-request lifecycle "
                   "lanes (one slot lane per concurrent request, spans "
                   "by cause + preempt instants) at shutdown - merges "
                   "with training shards via tools/trace_merge.py")
    p.add_argument("--request-ring", type=int, default=256,
                   help="finalized per-request records kept for "
                   "GET /v1/requests / tools/request_trace.py")
    p.add_argument("--warmup", action="store_true",
                   help="pre-compile the (batch, width) bucket grid "
                   "before binding the port (no first-request compile "
                   "TTFT spike)")
    p.add_argument("--replica-id",
                   default=os.environ.get("DNN_TPU_REPLICA_ID"),
                   help="fleet replica identity (stamped on summaries "
                   "and /v1/status; default $DNN_TPU_REPLICA_ID)")
    p.add_argument("--heartbeat-file",
                   default=os.environ.get("DNN_TPU_HEARTBEAT_FILE"),
                   help="write a liveness heartbeat JSON here "
                   "(advertises the /metrics URL for serve/fleet.py "
                   "router discovery; default $DNN_TPU_HEARTBEAT_FILE)")
    args = p.parse_args(argv)

    precision = {s.strip() for s in args.precision.split(",") if s.strip()}
    bad = precision - {"bf16", "int8-kv", "int8-w"}
    if bad:
        p.error(f"--precision: unknown mode(s) {sorted(bad)} "
                "(choose from bf16, int8-kv, int8-w)")

    from ..runtime import enable_compile_cache

    print(f"(compile cache: {enable_compile_cache()})", flush=True)
    params, cfg = build_model(args)
    engine = ServeEngine(params, cfg, EngineConfig(
        max_batch=args.max_batch,
        num_blocks=args.num_blocks,
        block_size=args.block_size,
        max_seq_len=args.max_seq_len,
        prefill_chunk=args.prefill_chunk,
        eos_token=args.eos_token,
        kv_dtype="int8" if "int8-kv" in precision else "bf16",
        weight_dtype="int8" if "int8-w" in precision else "bf16",
        decode_impl=args.decode_impl,
        spec_decode=args.spec_decode,
        spec_draft_layers=args.spec_draft_layers,
    ))
    if args.warmup:
        n = engine.warmup()
        print(f"(warmup: {n} bucket programs compiled)", flush=True)
    registry = MetricsRegistry()
    tracer = None
    if args.trace_out:
        import socket

        from ..utils.tracing import Tracer

        tracer = Tracer().set_process(
            hostname=socket.gethostname(),
            label=f"serve:{args.port}",
        )
    scheduler = ServeScheduler(
        engine,
        SchedulerConfig(
            max_queue=args.max_queue,
            tenant_rate=args.tenant_rate,
            tenant_burst=args.tenant_burst,
            run_record=args.run_record,
            request_ring=args.request_ring,
        ),
        registry=registry,
        tracer=tracer,
    ).start()
    server = ServeServer(
        scheduler, registry, port=args.port, host=args.host,
        replica_id=args.replica_id,
    )
    heartbeat = None
    if args.heartbeat_file:
        from ..utils.obs import HeartbeatFileWriter

        heartbeat = HeartbeatFileWriter(
            registry, args.heartbeat_file,
            metrics_url=server.url, role="serve",
        )
    print(
        f"serving on {server.url} "
        f"(model d{cfg.d_model}/L{cfg.n_layers}/H{cfg.n_heads} "
        f"vocab {cfg.vocab_size} seed {args.seed}; "
        f"{engine.kv.cfg.usable_blocks} KV blocks x "
        f"{args.block_size} tokens [{engine.kv_dtype_name()}, "
        f"{engine.kv_block_bytes():,} B/block]; "
        f"weights {engine.weight_dtype_name()}; "
        + (f"spec-decode k={engine.spec_k} "
           f"E={engine.draft_layers}; " if engine.spec_k else "")
        + f"decode -> {engine.decode_route()}; endpoints: "
        "POST /v1/generate, GET /v1/status, GET /v1/requests, "
        "/metrics, /healthz)",
        flush=True,
    )

    stop = threading.Event()

    def _stop(signum, frame):
        stop.set()

    signal.signal(signal.SIGTERM, _stop)
    signal.signal(signal.SIGINT, _stop)
    while not stop.wait(0.2):
        pass
    if heartbeat is not None:
        heartbeat.close()
    record = scheduler.close()
    server.close()
    if tracer is not None:
        tracer.export(args.trace_out, goodput=record)
        print(f"(request trace lanes -> {args.trace_out})", flush=True)
    print("SERVE_SUMMARY " + json.dumps({
        "requests_completed": int(
            registry.counter("serve_requests_total")
            .labels(status="completed").value
        ),
        "decode_tokens": engine.decode_tokens,
        "prefill_tokens": engine.prefill_tokens,
        "spec_proposed_tokens": engine.spec_proposed_tokens,
        "spec_accepted_tokens": engine.spec_accepted_tokens,
        "spec_steps": engine.spec_steps,
        "goodput_ratio": record.get("goodput_ratio") if record else None,
        "run_record": args.run_record,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
