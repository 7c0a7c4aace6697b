"""A serving cell: the server as `serve/http.py main` assembles it
(`ServeEngine`, `ServeScheduler`, `ServeServer` over HTTP/SSE) in this
process, which holds the chip; the load generator is a child that never
loads JAX."""

from __future__ import annotations

import json
import os
import random
import shutil
import subprocess
import sys
import threading
import time

from . import compare, harness, traffic as tgen, xtrace


def counters(registry) -> dict:
    """The registry's Prometheus text as {"name{labels}": value}."""
    out = {}
    for line in registry.render().splitlines():
        if line and not line.startswith("#"):
            name, _, value = line.rpartition(" ")
            try:
                out[name] = float(value)
            except ValueError:
                pass
    return out


class TickWatch:
    """The engine's beat, read as a scraper of `/metrics` reads it: a thread
    polls `serve_engine_steps_total` and, when it has moved, notes the time
    and the token counters and the gauge of active sequences beside it. One
    row a tick, its time late by `POLL_S` at most. Nothing of the engine is
    touched or wrapped."""

    POLL_S = 0.002

    def __init__(self, registry):
        self._steps = registry.counter("serve_engine_steps_total")
        tokens = registry.counter("serve_tokens_total")
        self._decode = tokens.labels(kind="decode")
        self._prefill = tokens.labels(kind="prefill")
        self._active = registry.gauge("serve_active_sequences")
        self.rows = []  # (time, steps, decode tokens, prefill tokens, active)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        seen = self._steps.value
        while not self._stop.wait(self.POLL_S):
            now, steps = time.monotonic(), self._steps.value
            if steps != seen:
                seen = steps
                # the scheduler counts the tick first and its tokens next
                self._stop.wait(self.POLL_S)
                self.rows.append((now, steps, self._decode.value,
                                  self._prefill.value, self._active.value))

    def close(self) -> None:
        self._stop.set()
        self._thread.join()

    def ticks(self) -> list:
        """(end of the tick before, end, decode tokens, prefill tokens,
        active sequences) of every tick whose predecessor's end was seen."""
        return [(a[0], b[0], b[2] - a[2], b[3] - a[3], b[4])
                for a, b in zip(self.rows, self.rows[1:]) if b[1] - a[1] == 1]


def window_edges(tick_ends: list, t_open: float, seconds: float) -> tuple:
    """The measured window on the engine's own beat. A tick hands all its
    tokens to the clients at its end, a dozen and more at once, so an edge on
    the clock alone would count a tick's tokens in or out by a few
    milliseconds' chance; half way between two ticks' ends none is under way.
    So the window opens at the first such middle at or after `t_open` and
    closes at the first that lies `seconds` or more later: `seconds` long and
    at most a tick longer. Where fewer than two ticks ended, the edges are
    the clock's."""
    mids = [(a + b) / 2 for a, b in zip(tick_ends, tick_ends[1:])]
    later = [m for m in mids if m >= t_open]
    if not later:
        return t_open, t_open + seconds
    closing = [m for m in later if m >= later[0] + seconds]
    return later[0], (closing[0] if closing else later[0] + seconds)


def latency_numbers(records: list, t_open: float, seconds: float,
                    edges: tuple | None = None) -> dict:
    """What the clients saw. Requests due in the nominal window are judged;
    tokens that arrived inside `edges` count towards the rate, whoever
    asked."""
    t_close = t_open + seconds
    lo, hi = edges or (t_open, t_close)
    due = [r for r in records if t_open <= r["due"] < t_close]
    ok = [r for r in due if r["status"] == "completed"
          and len(r["tokens"]) == r["max_new_tokens"]]
    ttft = [1e3 * (r["token_t"][0] - r["due"]) for r in due if r["token_t"]]
    itl = [1e3 * (b - a) for r in due
           for a, b in zip(r["token_t"], r["token_t"][1:])]
    lag = [1e3 * (r["sent"] - r["due"]) for r in due if r["sent"] is not None]
    in_window = sum(1 for r in records for t in r["token_t"] if lo <= t < hi)
    return {"due": due, "ok": ok, "ttft_ms": ttft, "itl_ms": itl,
            "lag_ms": lag, "tokens_in_window": in_window,
            "window_s": hi - lo}


def pick_sample(finished: list, seed: int, n: int) -> list:
    """A sample drawn from the seed, the longest request in it."""
    if not finished:
        return []
    longest = max(finished,
                  key=lambda r: r["prompt_len"] + len(r["tokens"]))
    rest = [r for r in finished if r is not longest]
    random.Random(seed).shuffle(rest)
    return [longest] + rest[: n - 1]


def reference_gaps(sample, prompts, *, seed, family, model, max_len,
                   precision="f32") -> dict:
    """The family's reference once over each sampled prompt with its served
    tokens: by how much each served token's logit lies below the reference's
    best. With a lower `precision` also, under "control", the same numbers
    for the token that this precision puts first at each position."""
    import numpy as np

    max_rows = max(len(r["tokens"]) for r in sample)
    seqs = np.zeros((len(sample), max_len), np.int32)
    rows = np.zeros((len(sample), max_rows), np.int32)
    for i, r in enumerate(sample):
        prompt, toks = prompts[r["idx"]], r["tokens"]
        full = (prompt + toks)[:max_len]
        seqs[i, :len(full)] = full
        rows[i, :len(toks)] = np.arange(len(prompt) - 1,
                                        len(prompt) - 1 + len(toks))
    served_logits = family.reference.served_logits
    logits = served_logits(seed, model, seqs, rows, "f32")
    low = (served_logits(seed, model, seqs, rows, precision)
           if precision != "f32" else None)
    served, control = [], []
    for i, r in enumerate(sample):
        n = len(r["tokens"])
        served.append(compare.served_gap(logits[i, :n], r["tokens"]))
        if low is not None:
            control.append(compare.served_gap(
                logits[i, :n], low[i, :n].argmax(axis=-1)))

    def widest_and_mean(gaps):
        return {"served_logit_gap": float(max(g.max() for g in gaps)),
                "served_logit_gap_mean": float(np.concatenate(gaps).mean())}

    out = dict(widest_and_mean(served),
               tokens_compared=sum(len(r["tokens"]) for r in sample))
    if control:
        # under the program's names: the control stands in its place
        out["control"] = widest_and_mean(control)
    return out


class Server:
    """The system under test, assembled as `serve/http.py main --warmup`
    assembles it, through its public entries alone: the engine warms its own
    grid of bucket programs (`ServeEngine.warmup`), and the ticks are read
    from the registry's counters."""

    def __init__(self, spec, seed, stages, wrap_engine=None):
        import jax.numpy as jnp

        from distributed_neural_network_tpu.serve.engine import (
            EngineConfig,
            ServeEngine,
        )
        from distributed_neural_network_tpu.serve.http import ServeServer
        from distributed_neural_network_tpu.serve.scheduler import (
            SchedulerConfig,
            ServeScheduler,
        )
        from distributed_neural_network_tpu.utils.obs import MetricsRegistry

        model, tr, family = spec["config"], spec["traffic"], spec["family"]
        eng = tr["engine"]
        cfg = family.program.config(model, tr, jnp.bfloat16)
        # in the type they are served in: `cfg.dtype`. (`serve/http.py main`
        # hands the engine float32 weights and every step casts them; at
        # these widths that does not fit beside a pool worth having -
        # PERF.md section 7.)
        params = family.weights.make(seed, model, dtype=jnp.bfloat16)
        engine = ServeEngine(params, cfg, EngineConfig(
            max_batch=eng["max_batch"], num_blocks=eng["num_blocks"],
            block_size=eng["block_size"], max_seq_len=eng["max_seq_len"],
            prefill_chunk=eng["prefill_chunk"],
            decode_impl=eng["decode_impl"]))
        del params
        stages.mark("build")
        self.n_programs = engine.warmup()
        engine.k_pool.block_until_ready()
        stages.mark("compile_or_cache_load")
        if wrap_engine is not None:  # a test plants its fault here
            wrap_engine(engine)
        self.registry = MetricsRegistry()
        self.scheduler = ServeScheduler(
            engine, SchedulerConfig(max_queue=eng["max_queue"]),
            registry=self.registry).start()
        self.watch = TickWatch(self.registry)
        self.http = ServeServer(self.scheduler, self.registry, port=0,
                                host="127.0.0.1")
        self.url = self.http.url

    def close(self) -> None:
        self.watch.close()
        self.scheduler.close()
        self.http.close()


def drive(server, traffic_file, *, seed, vocab, seconds, out_file, grace_s,
          on_open=None, in_window=None) -> dict:
    """Start the load generator against the server, wait for the window to
    open (`on_open(t_open)`), let `in_window()` work inside it, wait for its
    close and for the generator's file. Returns the generator's document
    with the registry's counters at the window's edges."""
    child = subprocess.Popen(
        [sys.executable, os.path.join(harness.BENCH_DIR, "lib", "loadgen.py"),
         "--url", server.url, "--traffic", traffic_file, "--seed", str(seed),
         "--vocab", str(vocab), "--seconds", str(seconds), "--out", out_file],
        stdout=subprocess.PIPE, text=True,
        env={k: v for k, v in os.environ.items() if k != "BENCH_RUN"})
    try:
        first = child.stdout.readline().split()
        if len(first) != 2 or first[0] != "OPEN":
            raise SystemExit(f"the load generator said {first!r}")
        t_open = float(first[1])
        time.sleep(max(t_open - time.monotonic(), 0))
        if on_open:
            on_open(t_open)
        c_open = counters(server.registry)
        if in_window:
            in_window()
        time.sleep(max(t_open + seconds - time.monotonic(), 0))
        c_close = counters(server.registry)
        child.wait(timeout=60 + grace_s)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    with open(out_file) as f:
        doc = json.load(f)
    doc["counters_window"] = (c_open, c_close)
    return doc


def run(spec, *, seed, seconds, trace, device, t_start, wrap_engine=None,
        precision="f32"):
    import jax
    from jax.profiler import TraceAnnotation

    from distributed_neural_network_tpu.runtime import enable_compile_cache

    stages = harness.Stages(t_start)
    compiles = harness.CompileCounter()
    enable_compile_cache()
    model, tr, family = spec["config"], spec["traffic"], spec["family"]
    vocab = family.weights.vocab(model)
    workload = spec["cell"]["name"]
    stages.mark("import_and_device")
    server = Server(spec, seed, stages, wrap_engine)
    traffic_file = spec.get("traffic_file") or os.path.join(
        harness.BENCH_DIR, "traffic", spec["cell"]["traffic"] + ".json")
    state = {}

    def on_open(t_open):
        stages.mark("preroll")
        compiles.armed = True

    def traced():
        tdir = harness.out_path(workload, seed, trace, "xplane")
        shutil.rmtree(tdir, ignore_errors=True)
        state["tdir"] = tdir
        jax.profiler.start_trace(tdir)
        try:
            c0 = counters(server.registry)
            with TraceAnnotation("bench.traced_window"):
                time.sleep(tr["trace_seconds"])
            state["counters_traced"] = (c0, counters(server.registry))
        finally:
            jax.profiler.stop_trace()

    doc = drive(server, traffic_file, seed=seed, vocab=vocab,
                seconds=seconds, grace_s=tr["grace_s"],
                out_file=harness.out_path(workload, seed, trace,
                                          "requests.json"),
                on_open=on_open, in_window=traced if trace else None)
    compiles.armed = False
    records, t_open = doc["records"], doc["t_open"]
    peak = harness.memory_peak_bytes(jax.devices()[:1])
    server.close()
    ticks, tick_ends = server.watch.ticks(), [r[0] for r in server.watch.rows]
    n_programs = server.n_programs
    summary = None
    if trace:
        tr_all = xtrace.read_trace(
            state["tdir"], harness.out_path(workload, seed, trace, "layout.txt"))
        win = [e for e in tr_all.host if e[0] == "bench.traced_window"]
        summary = xtrace.summarize(tr_all, win[0][1], win[0][1] + win[0][2])
        shutil.rmtree(state["tdir"], ignore_errors=True)

    # what the clients saw
    edges = window_edges(tick_ends, t_open, seconds)
    lat = latency_numbers(records, t_open, seconds, edges)
    tick_rows = [t for t in ticks if edges[0] <= t[1] < edges[1]]
    with open(harness.out_path(workload, seed, trace, "ticks.json"), "w") as f:
        json.dump({"t_open": t_open, "edges": [e - t_open for e in edges],
                   "ticks_before": sum(1 for t in tick_ends if t < edges[0]),
                   "setup_parts_s": stages.parts,
                   "programs_warmed": n_programs,
                   "ticks": [[t[0] - t_open, t[1] - t[0]] + list(t[2:])
                             for t in tick_rows]}, f)

    # free the program's state, then the reference over a sample
    del server, ticks, tick_ends
    pool = tgen.request_pool(tr, seed, vocab)
    prompts = {r["idx"]: pool[r["idx"] % len(pool)]["prompt"] for r in records}
    finished = [r for r in records if r["status"] == "completed"
                and r["tokens"]]
    sample = pick_sample(finished, seed, tr["check_requests"])
    numbers = {"requests_short": (len(lat["due"]) - len(lat["ok"]), "")}
    control = None
    if sample:
        gaps = reference_gaps(
            sample, prompts, seed=seed, family=family, model=model,
            max_len=tr["engine"]["max_seq_len"], precision=precision)
        in_place = gaps.pop("control", None)
        numbers.update({k: (v, "") for k, v in gaps.items()})
        if in_place is not None:
            # the control in the program's place, judged as the program is
            held = compare.with_limits(
                {k: (v, "") for k, v in in_place.items()}, spec["limits"])
            control = {"precision": precision, "compared": held,
                       "correct": harness.judge(held)}
            for name, c in held.items():
                print(f"control {precision} {name}: value {c['value']} "
                      f"limit {c['limit']}", file=sys.stderr)
            print(f"control {precision} correct: {control['correct']}",
                  file=sys.stderr)
    else:
        numbers["served_logit_gap"] = (float("nan"), "nothing finished")
    compared = compare.with_limits(numbers, spec["limits"])

    def pct(xs, q):
        return harness.quantile(xs, q) if xs else None

    values = {
        "serve_tokens_per_s": lat["tokens_in_window"] / lat["window_s"],
        "serve_ttft_p95_ms": pct(lat["ttft_ms"], 0.95),
        "serve_itl_p95_ms": pct(lat["itl_ms"], 0.95),
        "setup_s": edges[0] - t_start,
    }
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in spec["end_to_end"] if values.get(m["name"]) is not None}
    dev = dict(device, memory_peak_bytes=int(peak))
    breakdown = None
    if trace:
        dev["busy_s"], dev["window_s"] = summary["busy_s"], summary["window_s"]
        breakdown = {"device_ops": summary["device_ops"],
                     "idle_gaps": summary["idle_gaps"]}
        obs = {"trace": summary, "ticks": tick_rows, "lat": lat,
               "counters_window": doc["counters_window"],
               "counters_traced": state["counters_traced"],
               "model": model, "family": family, "traffic": tr,
               "chips": 1, "seconds": seconds, "device_kind": device["kind"],
               "memory_peak_bytes": peak,
               "compiles_in_window": compiles.count}
        metrics = harness.read_per_layer(spec, obs)
    return harness.emit(
        correct=harness.judge(compared) and compiles.count == 0,
        attempted=len(lat["due"]), failed=len(lat["due"]) - len(lat["ok"]),
        metrics=metrics, device=dev, compared=compared, breakdown=breakdown,
        extra={"setup_parts_s": stages.parts, "programs_warmed": n_programs,
               "requests_sent": len(records), "ticks": len(tick_rows),
               "tokens_in_window": lat["tokens_in_window"],
               "window_s": lat["window_s"],
               "compiles_in_window": compiles.count,
               "ttft_p50_ms": pct(lat["ttft_ms"], 0.5),
               "itl_p50_ms": pct(lat["itl_ms"], 0.5),
               "lag_p95_ms": pct(lat["lag_ms"], 0.95), "control": control,
               "numbers": {k: [v, d] for k, (v, d) in numbers.items()}})
