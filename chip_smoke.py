#!/usr/bin/env python
"""Prove on the chip that the trainer and the server still start and are right.

One process holds the chip and drives the repo's main paths once through the
entry points a user calls, at the widest configuration the repo supports:

  device    the backend must be a TPU whose kind the peak table lists
  fence     `block_until_ready` against a value fetch on ten chained
            8192^3 bf16 matmuls (what lets `hard_block` be the former)
  cnn       the source paper's regime: `train.cli.run_training` (what
            `data_parallelism_train.py` calls), full CNN, bs 16, 2 epochs
  lm_train  `lm_train.py main()` at d1024 / L16 / H16 / d_ff 4096 / seq 2048,
            global batch 8, bf16, `--attn flash`, dots_saveable remat
  serve     `serve/http.py main()` at d512 / L8 / H4 / d_ff 2048 bf16 over
            HTTP/SSE (heads of 128: the paged decode kernel's tile), every
            stream compared with offline `generate()`; then the xla route,
            `--precision int8-kv` and `--spec-decode 4`

`--chips 4` runs only the cross-chip path and what it is compared with:
`lm_train.py --dp 2 --tp 2` against one chip, and `run_training --nb-proc 4`.

Any failed check exits non-zero at once. These prints are a smoke, not a
benchmark: the numbers say the path ran, not how fast the system is. The last
line of stdout is the JSON verdict the driver reads.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import re
import signal
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "chiprun_out", "chip_smoke")
SEED = 0

# the widest LM the repo's records name (bench.py's d1024 dots_saveable row)
LM_ARGS = [
    "--d-model", "1024", "--n-layers", "16", "--n-heads", "16",
    "--d-ff", "4096", "--seq-len", "2048", "--batch-size", "8",
    "--vocab", "32768", "--dtype", "bfloat16", "--lr", "0.01",
    "--attn", "flash", "--remat", "--remat-policy", "dots_saveable",
    "--log-every", "1", "--step-stats", "--seed", str(SEED),
]
# the serving geometry of train/measure.py measure_serving, with its 8
# heads of 64 made 4 of 128: `--decode-impl auto` takes the paged decode
# kernel where a pool row is whole 128-lane tiles (paged_decode_ok)
SERVE_MODEL = {"d_model": 512, "n_layers": 8, "n_heads": 4, "d_ff": 2048,
               "vocab": 256, "dtype": "bfloat16", "seed": SEED}
SERVE_ARGS = [
    "--port", "0", "--max-batch", "8", "--num-blocks", "129",
    "--block-size", "16", "--max-seq-len", "256", "--prefill-chunk", "16",
]
PROMPT_LENS = [8, 32, 128, 16, 64]
MAX_NEW = 32
MIN_AGREEMENT = 0.99  # the int8-KV gate's threshold (measure_serving)
# a served bf16 token may sit this far below the float32 reference's best
# logit: four bf16 ulps at the logits' magnitude (unit variance, |x| < 4)
LOGIT_TOL = 4 * 2.0 ** -6


class SmokeFailure(SystemExit):
    """A failed check: message to stderr, exit code 1, no verdict line."""

    def __init__(self, msg: str):
        super().__init__(f"chip_smoke FAILED: {msg}")


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


class _Tee:
    """stdout that passes text through and keeps the lines (the entry
    points report through prints; the checks read what they printed)."""

    def __init__(self, stream):
        self._stream = stream
        self._buf = ""
        self.lines: list[str] = []
        self._lock = threading.Lock()

    def write(self, text):
        with self._lock:
            self._buf += text
            *done, self._buf = self._buf.split("\n")
            self.lines.extend(done)
        return self._stream.write(text)

    def flush(self):
        self._stream.flush()

    def findall(self, pattern: str) -> list:
        with self._lock:
            return [m for line in self.lines
                    if (m := re.search(pattern, line))]

    def json_after(self, prefix: str) -> dict:
        found = self.findall("^" + re.escape(prefix) + "(.*)$")
        check(found, f"no {prefix!r} line was printed")
        return json.loads(found[-1].group(1))


@contextlib.contextmanager
def tee_stdout():
    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        yield tee


# ------------------------------------------------------------------ device


def phase_device(want_chips: int) -> dict:
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SmokeFailure(
            f"needs a TPU, JAX found platform {d0.platform!r} "
            f"({d0.device_kind}); no CPU run under this script's name"
        )
    import jaxlib

    from distributed_neural_network_tpu import native
    from distributed_neural_network_tpu.runtime import enable_compile_cache
    from distributed_neural_network_tpu.train.measure import peak_flops

    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "unknown"
    cache_dir = enable_compile_cache()
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say("device", f"platform {d0.platform}, kind {d0.device_kind!r}, "
        f"count {len(devs)}; jax {jax.__version__}, jaxlib "
        f"{jaxlib.__version__}, libtpu {libtpu_version}")
    say("device", f"compile cache: {cache_dir} ({n_cached} entries at start)")
    # raises on a kind the peak table does not list: no null MFU on a chip
    say("device", f"peak bf16: {peak_flops(d0.device_kind) / 1e12:.0f} "
        "TFLOP/s (train/measure.py PEAK_TFLOPS_BF16)")
    say("device", "native batcher: "
        + ("built and loaded" if native.available() else "numpy fallback"))
    check(len(devs) >= want_chips,
          f"--chips {want_chips} needs {want_chips} devices, "
          f"JAX reports {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


# ------------------------------------------------------------------- fence


def phase_fence(device_kind: str) -> None:
    """Ten chained 8192^3 bf16 matmuls: seconds until `block_until_ready`
    returns against seconds until a value fetch returns. A fence that
    returned early would beat the chip's peak."""
    import jax
    import jax.numpy as jnp

    from distributed_neural_network_tpu.train.measure import peak_flops

    n, chain = 8192, 10
    x = jax.random.normal(jax.random.key(SEED), (n, n), jnp.bfloat16)
    w = (jax.random.normal(jax.random.key(SEED + 1), (n, n), jnp.float32)
         / math.sqrt(n)).astype(jnp.bfloat16)

    @jax.jit
    def chained(x, w):
        for _ in range(chain):
            x = x @ w
        return x

    jax.block_until_ready(chained(x, w))  # compile
    t_block, t_fetch = [], []
    for _ in range(5):
        t0 = time.perf_counter()
        y = chained(x, w)
        t_dispatch = time.perf_counter() - t0
        jax.block_until_ready(y)
        t_block.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        y = chained(x, w)
        float(y[0, 0])
        t_fetch.append(time.perf_counter() - t0)
    block, fetch = sorted(t_block)[2], sorted(t_fetch)[2]
    floor = chain * 2 * n**3 / peak_flops(device_kind)
    say("fence", f"{chain} chained {n}^3 bf16 matmuls: dispatch returned "
        f"in {t_dispatch * 1e3:.2f} ms, block_until_ready in "
        f"{block * 1e3:.2f} ms, value fetch in {fetch * 1e3:.2f} ms "
        f"(medians of 5; peak-FLOP/s floor {floor * 1e3:.2f} ms)")
    check(block >= floor,
          f"block_until_ready returned in {block * 1e3:.2f} ms, under the "
          f"{floor * 1e3:.2f} ms the chip needs at peak: it did not wait")
    check(abs(fetch - block) <= max(0.1 * fetch, 0.005),
          f"block_until_ready ({block * 1e3:.2f} ms) and a value fetch "
          f"({fetch * 1e3:.2f} ms) disagree: hard_block may not be "
          "block_until_ready here")


# --------------------------------------------------------------------- cnn


def run_cnn(nb_proc: int, epochs: int):
    """`data_parallelism_train.py`'s body with its reference defaults."""
    from distributed_neural_network_tpu.train.cli import (
        add_common_flags,
        add_distributed_flags,
        run_training,
    )

    parser = argparse.ArgumentParser()
    add_common_flags(parser, epochs=25, batch_size=16)
    add_distributed_flags(parser)
    args = parser.parse_args([
        "--nb-proc", str(nb_proc), "--data", "synthetic",
        "--batch-size", "16", "--epochs", str(epochs),
        "--seed", str(SEED), "--log-dir", os.path.join(OUT, "log"),
    ])
    with tee_stdout() as out:
        engine = run_training(args, "data_parallel")
    return engine, out.json_after("SUMMARY ")


def phase_cnn() -> None:
    engine, summary = run_cnn(nb_proc=1, epochs=2)
    losses = [m.train_loss for m in engine.history]
    say("cnn", f"train loss by epoch {losses}, val acc "
        f"{summary['final_val_acc']}, {summary['wall_clock_s']} s wall")
    check(len(losses) == 2 and all(math.isfinite(x) for x in losses),
          f"expected 2 finite epoch losses, got {losses}")
    check(losses[-1] < losses[0], f"CNN loss did not fall: {losses}")
    check(summary["data_source"] == "synthetic" and summary["epochs"] == 2,
          f"unexpected SUMMARY {summary}")


# ---------------------------------------------------------------- lm_train


def run_lm(extra: list) -> dict:
    """`lm_train.py main()`; returns its SUMMARY plus the per-step losses."""
    import lm_train

    with tee_stdout() as out:
        rc = lm_train.main(LM_ARGS + extra)
    check(rc == 0, f"lm_train.main returned {rc}")
    summary = out.json_after("SUMMARY ")
    summary["losses"] = [
        float(m.group(1)) for m in out.findall(r"^step +\d+ +loss +(\S+)")
    ]
    return summary


def phase_lm_train() -> None:
    import jax

    steps = 5
    s = run_lm(["--steps", str(steps)])
    losses = s["losses"]
    check(len(losses) == steps and all(math.isfinite(x) for x in losses),
          f"expected {steps} finite losses, got {losses}")
    check(losses[-1] < losses[0], f"LM loss did not fall: {losses}")
    check(s["attn_route"] == "pallas",
          f"--attn flash took route {s['attn_route']!r}, not the kernel")
    check((s["mosaic_custom_calls"] or 0) > 0,
          "the compiled step holds no Mosaic custom call: --attn flash "
          "ran the plain attention")
    mem = jax.devices()[0].memory_stats() or {}
    say("lm_train", f"losses {losses}")
    say("lm_train", f"attn=flash -> {s['attn_route']}, "
        f"{s['mosaic_custom_calls']} Mosaic custom calls in the step; first "
        f"step incl. compile {s['first_step_s']} s; steady step "
        f"{s['wall_s_post_compile'] / (steps - 1) * 1e3:.1f} ms (fenced "
        f"with block_until_ready), {s['tokens_per_s']} tokens/s, MFU "
        f"{s['mfu_pct']}%; peak_bytes_in_use "
        f"{mem.get('peak_bytes_in_use', 'not reported')}")
    check(s["mfu_pct"] is not None, "MFU is null on a chip run")


# ------------------------------------------------------------------- serve


def _loadgen():
    tools = os.path.join(REPO, "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    import loadgen

    return loadgen


class Oracle:
    """The two offline references on the same chip, from the same seeded
    model the server builds.

    `generate()` decodes free-running in bf16, as the server does, and is
    what the CPU tests hold the server to token for token. On the chip two
    bf16 paths round differently (the batch bucket a step ran in changes a
    matmul's rounding), so at a near-tie they pick different tokens and
    every later token then follows a different history. What decides a
    stream is therefore `margins()`: the model's full forward in float32
    at the highest matmul precision, teacher-forced over the stream the
    server produced - for each served token, how far below the reference's
    best logit it sits (0 = the reference's own choice)."""

    def __init__(self):
        import jax
        import jax.numpy as jnp

        from distributed_neural_network_tpu.models import transformer as tfm

        m = SERVE_MODEL
        geometry = dict(
            vocab_size=m["vocab"], d_model=m["d_model"],
            n_heads=m["n_heads"], n_layers=m["n_layers"], d_ff=m["d_ff"],
        )
        self.cfg = tfm.TransformerConfig(dtype=jnp.bfloat16, **geometry)
        self.params = tfm.init_params(jax.random.key(m["seed"]), self.cfg)
        self._generate = tfm.generate
        self._free: dict = {}
        cfg32 = tfm.TransformerConfig(dtype=jnp.float32, **geometry)
        self._forward = jax.jit(lambda p, t: tfm.apply(p, t, cfg32))

    def free_running(self, prompt) -> list:
        import jax.numpy as jnp

        key = tuple(prompt)
        if key not in self._free:
            out = self._generate(
                self.params, jnp.asarray([prompt], jnp.int32), self.cfg,
                max_new_tokens=MAX_NEW,
            )
            self._free[key] = [int(t) for t in out[0, len(prompt):]]
        return self._free[key]

    def margins(self, prompt, tokens):
        """Reference logit of the best token minus that of the served
        token, per served position. The stream is padded to the full
        length (one compile per prompt length; the model is causal, so
        the padding changes nothing before it)."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        n = len(prompt)
        seq = list(prompt) + list(tokens) + [0] * (MAX_NEW - len(tokens))
        with jax.default_matmul_precision("highest"):
            logits = self._forward(self.params, jnp.asarray([seq], jnp.int32))
        rows = np.asarray(logits)[0, n - 1:n - 1 + len(tokens)]
        return rows.max(-1) - rows[np.arange(len(tokens)), list(tokens)]


def serve_once(name: str, extra: list, n_requests: int, cancel_one: bool):
    """The real CLI `main()` on this thread (it owns the chip and the
    signal handlers); the HTTP client on a second thread, which ends the
    server the way an operator does - SIGTERM."""
    import urllib.request

    from distributed_neural_network_tpu.serve import http as serve_http

    loadgen = _loadgen()
    m = SERVE_MODEL
    argv = SERVE_ARGS + [
        "--d-model", str(m["d_model"]), "--n-layers", str(m["n_layers"]),
        "--n-heads", str(m["n_heads"]), "--d-ff", str(m["d_ff"]),
        "--vocab", str(m["vocab"]), "--dtype", m["dtype"],
        "--seed", str(m["seed"]),
    ] + extra
    got: dict = {}

    def client(out: _Tee):
        try:
            deadline = time.monotonic() + 900
            while not (hit := out.findall(r"serving on (http://\S+)")):
                if time.monotonic() > deadline:
                    raise SmokeFailure(f"{name}: server never came up")
                time.sleep(0.2)
            url = hit[0].group(1)
            got["load"] = loadgen.run_load(
                url, rate=4.0, n_requests=n_requests, duration=None,
                prompt_lens=PROMPT_LENS, max_new=MAX_NEW,
                vocab=m["vocab"], seed=SEED, api_keys=["smoke"],
                temperature=0.0, burst=0, cancel_one=cancel_one,
                timeout=300.0, poisson=False,
            )
            with urllib.request.urlopen(url + "/v1/status", timeout=30) as r:
                got["status"] = json.loads(r.read())
        except BaseException as e:  # handed to the main thread below
            got["error"] = e
        finally:
            os.kill(os.getpid(), signal.SIGTERM)

    handlers = {s: signal.getsignal(s) for s in (signal.SIGTERM, signal.SIGINT)}
    with tee_stdout() as out:
        th = threading.Thread(target=client, args=(out,), daemon=True)
        th.start()
        try:
            rc = serve_http.main(argv)
        finally:
            for sig, h in handlers.items():
                signal.signal(sig, h)
        th.join(timeout=60)
    if "error" in got:
        raise got["error"]
    check(rc == 0 and not th.is_alive(), f"{name}: server main returned {rc}")
    got["summary"] = out.json_after("SERVE_SUMMARY ")
    return got


def judge_streams(name: str, got: dict, oracle: Oracle, *,
                  n_cancelled: int, min_within: float = 1.0) -> dict:
    """Every stream (the cancelled one up to where the client left)
    against the float32 reference: a served token counts when it is the
    reference's choice or within LOGIT_TOL of it. Agreement with
    free-running `generate()` is printed beside it."""
    results = got["load"]["results"]
    by_status: dict = {}
    for r in results:
        by_status[r.status] = by_status.get(r.status, 0) + 1
    check(by_status.get("client_cancelled", 0) == n_cancelled
          and by_status.get("completed", 0) == len(results) - n_cancelled,
          f"{name}: request outcomes {by_status} "
          f"({[r.error for r in results if r.error]})")
    total = top1 = within = free_same = exact_streams = 0
    worst = 0.0
    streams = {}
    for r in results:
        check(r.tokens and (r.status != "completed"
                            or len(r.tokens) == MAX_NEW),
              f"{name}: request {r.idx} streamed {len(r.tokens)} tokens")
        margin = oracle.margins(r.prompt, r.tokens)
        free = oracle.free_running(r.prompt)[: len(r.tokens)]
        same = sum(int(a == b) for a, b in zip(r.tokens, free))
        if same != len(free):
            first = next(i for i, (a, b) in enumerate(zip(r.tokens, free))
                         if a != b)
            say(name, f"request {r.idx} (prompt {len(r.prompt)}) leaves "
                f"free-running generate() at token {first}, where the "
                f"float32 reference puts the served token "
                f"{margin[first]:.4f} below its best")
        total += len(r.tokens)
        top1 += int((margin == 0).sum())
        within += int((margin <= LOGIT_TOL).sum())
        worst = max(worst, float(margin.max()))
        free_same += same
        exact_streams += int(same == len(free))
        streams[tuple(r.prompt)] = list(r.tokens)
    st = got["status"]
    say(name, f"route decode -> {st['decode_route']}; {by_status}; against "
        f"the float32 reference over {total} served tokens: "
        f"{top1 / total:.4f} are its top-1, {within / total:.4f} within "
        f"{LOGIT_TOL} of it (worst {worst:.4f}); token-exact with "
        f"free-running generate(): {exact_streams}/{len(results)} streams, "
        f"{free_same / total:.4f} of tokens; compiled_programs "
        f"{st['compiled_programs']}; ttft p50 "
        f"{got['load']['ttft_p50_s']:.3f} s")
    check(within / total >= min_within,
          f"{name}: only {within / total:.4f} of served tokens are within "
          f"{LOGIT_TOL} logits of the float32 reference's choice (worst "
          f"{worst:.4f}); {min_within} required")
    return streams


def phase_serve() -> None:
    oracle = Oracle()
    # the main run: warmed bucket grid, 8 mixed-length requests, one of
    # them closed by the client after two tokens
    got = serve_once("serve", ["--decode-impl", "auto", "--warmup"], 8, True)
    check(got["status"]["decode_route"].startswith("pallas"),
          f"--decode-impl auto took {got['status']['decode_route']!r} on a "
          "TPU, not the decode kernel")
    judge_streams("serve", got, oracle, n_cancelled=1)
    check(got["summary"]["requests_completed"] == 7,
          f"SERVE_SUMMARY {got['summary']}")

    got = serve_once("serve-xla", ["--decode-impl", "xla"], 4, False)
    check(got["status"]["decode_route"] == "xla", str(got["status"]))
    plain = judge_streams("serve-xla", got, oracle, n_cancelled=0)

    # a quantized cache moves logits by more than bf16 rounding does: held
    # to the int8-KV gate's share of tokens, not to every token
    got = serve_once("serve-int8kv", ["--precision", "int8-kv"], 4, False)
    check(got["status"]["kv_dtype"] == "int8", str(got["status"]))
    judge_streams("serve-int8kv", got, oracle, n_cancelled=0,
                  min_within=MIN_AGREEMENT)

    # greedy slots under speculation go through the drafter and the
    # multi-position verify step, which attend through XLA: the same
    # route is --decode-impl xla
    got = serve_once("serve-spec4",
                     ["--spec-decode", "4", "--decode-impl", "xla"], 4, False)
    st = got["status"]
    check(st["spec_decode"] == 4 and st["spec_proposed_tokens"] > 0,
          f"spec decode did not speculate: {st}")
    spec = judge_streams("serve-spec4", got, oracle, n_cancelled=0)
    same = sum(int(toks == plain[p]) for p, toks in spec.items())
    say("serve-spec4", f"acceptance {st['spec_accepted_tokens']}/"
        f"{st['spec_proposed_tokens']} over {st['spec_steps']} steps; "
        f"streams byte-identical to plain greedy on the same route: "
        f"{same}/{len(spec)}")


# -------------------------------------------------------------- four chips


def phase_four_chips() -> None:
    """Only what exists across chips, and what it is compared with."""
    import jax
    import numpy as np

    steps = 3
    mesh = run_lm(["--steps", str(steps), "--dp", "2", "--tp", "2"])
    one = run_lm(["--steps", str(steps)])
    say("dp2xtp2", f"mesh {mesh['mesh']}: losses {mesh['losses']}, params on "
        f"{mesh['param_devices']} devices, batch on {mesh['batch_devices']}; "
        f"{mesh['mosaic_custom_calls']} Mosaic custom calls; "
        f"{mesh['tokens_per_s']} tokens/s")
    say("dp2xtp2", f"one chip, same seed and global batch: losses "
        f"{one['losses']}; {one['tokens_per_s']} tokens/s")
    check(mesh["param_devices"] == 4 and mesh["batch_devices"] == 4,
          "parameters or batch are not on four distinct devices: "
          f"{mesh['param_devices']}, {mesh['batch_devices']}")
    check(one["param_devices"] == 1, str(one))
    check(mesh["attn_route"] == "pallas"
          and (mesh["mosaic_custom_calls"] or 0) > 0,
          "the dp x tp step did not run the flash kernel")
    # bf16 activations carry ~3 significant digits; the step-0 loss sees
    # only a different reduction order, later ones also a different update
    check(abs(mesh["losses"][0] - one["losses"][0])
          <= 2e-3 * abs(one["losses"][0]),
          f"step-0 loss {mesh['losses'][0]} vs {one['losses'][0]} on one "
          "chip: more than bf16 reduction order apart")
    for a, b in zip(mesh["losses"][1:], one["losses"][1:]):
        check(abs(a - b) <= 2e-2 * abs(b),
              f"losses drift apart: {mesh['losses']} vs {one['losses']}")
    check(mesh["losses"][-1] < mesh["losses"][0], str(mesh["losses"]))

    engine, summary = run_cnn(nb_proc=4, epochs=1)
    devices = set()
    for leaf in jax.tree.leaves(engine.params):
        shards = leaf.addressable_shards
        devices |= {s.device for s in shards}
        first = np.asarray(shards[0].data)
        check(len(shards) == 4 and all(
            np.array_equal(first, np.asarray(s.data)) for s in shards[1:]),
            "CNN parameters differ across devices after the epoch-edge "
            "pmean")
    check(len(devices) == 4, f"CNN parameters live on {len(devices)} devices")
    say("cnn-dp4", f"--nb-proc 4, 1 epoch: train loss "
        f"{summary['final_train_loss']}, parameters bit-identical on "
        f"{len(devices)} devices after the epoch-edge pmean")
    check(math.isfinite(summary["final_train_loss"]), str(summary))


# -------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the cross-chip path and its comparison")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    os.makedirs(OUT, exist_ok=True)
    device = phase_device(args.chips)
    if args.chips == 4:
        phase_four_chips()
    else:
        phase_fence(device["kind"])
        phase_cnn()
        phase_lm_train()
        phase_serve()
    say("done", f"{time.perf_counter() - t0:.0f} s wall, compile included")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
