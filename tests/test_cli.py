"""CLI integration tests: one run per entry point on tiny synthetic data.

The analog of the reference's only verification path - actually running the
scripts (SURVEY.md sec. 4) - but automated: each script runs in a subprocess
on the 8-fake-device CPU platform, and we assert on its summary line, metric
series, and phase-log artifacts.
"""

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_script(tmp_path, script, *extra):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    args = [
        sys.executable,
        os.path.join(REPO, script),
        "--data",
        "synthetic",
        "--synthetic-size",
        "400",
        "--epochs",
        "2",
        "--batch-size",
        "16",
        "--log-dir",
        str(tmp_path / "log"),
        "--metrics-jsonl",
        str(tmp_path / "metrics.jsonl"),
        *extra,
    ]
    proc = subprocess.run(
        args, capture_output=True, text=True, cwd=REPO, env=env, timeout=600
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    summary = next(
        line for line in proc.stdout.splitlines() if line.startswith("SUMMARY ")
    )
    return json.loads(summary[len("SUMMARY ") :]), proc.stdout, tmp_path


@pytest.mark.parametrize(
    "script,regime,extra",
    [
        ("single_proc_train.py", "single", ()),
        ("model_replication_train.py", "replication", ("--nb-proc", "4")),
        ("data_parallelism_train.py", "data_parallel", ("--nb-proc", "4")),
    ],
)
def test_entry_point_runs(tmp_path, script, regime, extra):
    summary, stdout, _ = _run_script(tmp_path, script, *extra)
    assert summary["regime"] == regime
    assert summary["epochs"] == 2
    assert summary["final_val_acc"] is not None
    assert summary["data_source"] == "synthetic"
    # metrics series present with reference names
    series = [
        json.loads(line)["series"]
        for line in open(tmp_path / "metrics.jsonl")
    ]
    for s in ("train/loss", "val/loss", "val/acc"):
        assert series.count(s) == 2, (s, series)


def test_dp_writes_reference_named_phase_logs(tmp_path):
    _, _, path = _run_script(
        tmp_path, "data_parallelism_train.py", "--nb-proc", "4"
    )
    parent = path / "log" / "bs16_log_epochs2_proc4_parent.txt"
    children = path / "log" / "bs16_log_epochs2_proc4_children.txt"
    assert parent.exists() and children.exists()
    lines = parent.read_text().splitlines()
    assert lines[0].startswith("Eval data loading time: ")
    assert lines[1].startswith("Time spent on evaluation: ")
    assert lines[2].startswith("Time spent on parent communication and param sync: ")
    clines = children.read_text().splitlines()
    assert clines[0].startswith("Train data loading time: ")
    assert clines[1].startswith("Time spent on training: ")
    assert clines[2].startswith("Time spent on children communication: ")


def test_dp_fault_flags(tmp_path):
    summary, stdout, _ = _run_script(
        tmp_path,
        "data_parallelism_train.py",
        "--nb-proc",
        "8",
        "--failure-probability",
        "0.9",
        "--seed",
        "5",
    )
    assert summary["final_val_acc"] is not None  # survived heavy failures


def test_dp_checkpoint_resume_and_profile(tmp_path):
    ckdir = tmp_path / "ckpt"
    profdir = tmp_path / "prof"
    # interrupted run: 2 of 4 epochs, checkpointing each epoch + profiling
    _run_script(
        tmp_path,
        "data_parallelism_train.py",
        "--nb-proc",
        "4",
        "--checkpoint-dir",
        str(ckdir),
        "--profile-dir",
        str(profdir),
    )
    assert any(ckdir.rglob("*")), "no checkpoint written"
    assert any(profdir.rglob("*.pb")) or any(profdir.rglob("*trace*")), (
        "no profiler trace under " + str(profdir)
    )
    # resumed run to 4 epochs picks up at epoch 2
    summary, stdout, _ = _run_script(
        tmp_path,
        "data_parallelism_train.py",
        "--nb-proc",
        "4",
        "--checkpoint-dir",
        str(ckdir),
        "--resume",
        "--epochs",
        "4",
    )
    assert "(Resumed from checkpoint: next epoch 2)" in stdout
    assert summary["epochs"] == 4


def _strict_loads(text):
    def reject(tok):
        raise ValueError(f"non-strict token {tok}")

    return json.loads(text, parse_constant=reject)


def test_dp_trace_out_and_step_stats(tmp_path):
    """--trace-out writes strict Chrome trace JSON with train_step spans
    carrying step metadata; --step-stats emits step/* series and the
    summary block (the PR's acceptance path)."""
    trace = tmp_path / "trace.json"
    summary, stdout, path = _run_script(
        tmp_path, "data_parallelism_train.py", "--nb-proc", "4",
        "--trace-out", str(trace), "--step-stats",
    )
    doc = _strict_loads(trace.read_text())  # STRICT json parse
    events = doc["traceEvents"]
    steps = [
        e for e in events
        if e.get("name") == "train_step" and e.get("ph") == "X"
    ]
    assert len(steps) == 2, "one fenced train_step span per epoch"
    for ev in steps:
        assert {"ts", "dur", "pid", "tid"} <= set(ev)
        assert "step" in ev.get("args", {})
    assert [e["args"]["step"] for e in steps] == [0, 1]
    for phase in ("data_loading", "sync", "eval"):
        assert any(e.get("name") == phase for e in events), phase
    assert isinstance(doc.get("stepStats"), dict)
    # step/* series landed in the metrics JSONL next to the classic ones
    series = [
        _strict_loads(line)["series"]
        for line in open(path / "metrics.jsonl")
    ]
    assert series.count("step/wall_s") == 2
    assert "step/images_per_s" in series
    assert "Step stats (" in stdout
    assert "MFU" in stdout
    # the analysis tool round-trips the artifact without error
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_summary.py"),
         str(trace), str(path / "metrics.jsonl")],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert "train_step" in proc.stdout
    assert "steady-state" in proc.stdout


def test_module_cli_trace_smoke(tmp_path):
    """`python -m distributed_neural_network_tpu.train.cli` is the tiny
    telemetry harness: one epoch with --trace-out/--step-stats produces a
    strict trace + step series (mirrors the acceptance command)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    trace = tmp_path / "trace.json"
    proc = subprocess.run(
        [sys.executable, "-m", "distributed_neural_network_tpu.train.cli",
         "--epochs", "1", "--trace-out", str(trace), "--step-stats",
         "--metrics-jsonl", str(tmp_path / "m.jsonl"),
         "--log-dir", str(tmp_path / "log")],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    doc = _strict_loads(trace.read_text())
    steps = [
        e for e in doc["traceEvents"]
        if e.get("name") == "train_step" and e.get("ph") == "X"
    ]
    assert steps and all("step" in e.get("args", {}) for e in steps)
    series = [
        _strict_loads(line)["series"] for line in open(tmp_path / "m.jsonl")
    ]
    assert "step/wall_s" in series
    assert "SUMMARY " in proc.stdout
    proc2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_summary.py"),
         str(trace)],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    assert proc2.returncode == 0, proc2.stderr
    assert "MFU" in proc2.stdout  # an estimate or the explicit fallback


@pytest.mark.parametrize(
    "extra,mesh",
    [
        (("--dp", "2", "--sp", "2", "--tp", "2"), "data2xseq2xmodel2"),
        (("--pp", "2", "--dp", "2", "--tp", "2", "--n-layers", "2"),
         "data2xpipe2xmodel2"),
        (("--dp", "4", "--experts", "4", "--optimizer", "sgd"), "data4"),
        (("--dp", "8", "--optimizer", "zero"), "data8"),
    ],
)
def test_lm_train_entry_point(tmp_path, extra, mesh):
    """lm_train.py exposes every parallel axis from the CLI and learns."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    args = [
        sys.executable, os.path.join(REPO, "lm_train.py"),
        "--steps", "25", "--batch-size", "16", "--seq-len", "16",
        "--d-model", "32", "--n-heads", "4", "--d-ff", "64",
        "--vocab", "32", "--lr", "0.3", *extra,
    ]
    proc = subprocess.run(
        args, capture_output=True, text=True, cwd=REPO, env=env, timeout=600
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    summary = json.loads(next(
        line for line in proc.stdout.splitlines() if line.startswith("SUMMARY ")
    )[len("SUMMARY "):])
    assert summary["mesh"] == mesh
    assert summary["final_loss"] < summary["first_loss"] - 1.0, summary


def test_lm_train_trace_out_and_step_stats(tmp_path):
    """lm_train.py --trace-out records one fenced train_step span per step
    and the StepStats summary separates the compile step."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    trace = tmp_path / "lm_trace.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "lm_train.py"),
         "--dp", "2", "--steps", "6", "--batch-size", "8", "--seq-len", "16",
         "--d-model", "32", "--n-heads", "4", "--d-ff", "64", "--vocab", "32",
         "--trace-out", str(trace), "--step-stats",
         "--metrics-jsonl", str(tmp_path / "m.jsonl")],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    doc = _strict_loads(trace.read_text())
    steps = [
        e for e in doc["traceEvents"]
        if e.get("name") == "train_step" and e.get("ph") == "X"
    ]
    assert [e["args"]["step"] for e in steps] == list(range(6))
    assert all(e["args"]["fenced"] for e in steps)
    stats = doc["stepStats"]
    assert stats["steps"] == 6
    assert stats["compile_steps"] == 1
    assert stats["steady_steps"] == 5
    assert stats["item_label"] == "tokens"
    assert stats["flops_source"] in ("cost_analysis", "analytic")
    assert "Step stats (" in proc.stdout
    series = [
        _strict_loads(line)["series"] for line in open(tmp_path / "m.jsonl")
    ]
    assert series.count("step/wall_s") == 6
    assert series.count("step/tokens_per_s") == 5  # compile step excluded


def test_lm_train_rejects_pp_with_sp(tmp_path):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "lm_train.py"),
         "--pp", "2", "--sp", "2", "--steps", "1"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=120,
    )
    assert proc.returncode != 0
    assert "--pp composes with" in proc.stderr


def test_lm_train_pp_eval_and_accum(tmp_path):
    """--eval-every and --accum-steps work under --pp (r3 ADVICE/VERDICT):
    held-out eval runs through the microbatch schedule and the SUMMARY
    carries it; accumulation runs k schedule passes per step."""
    corpus = tmp_path / "corpus.txt"
    corpus.write_text("the quick brown fox jumps over the lazy dog. " * 400)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "lm_train.py"),
         "--pp", "2", "--dp", "2", "--microbatches", "2",
         "--accum-steps", "2", "--optimizer", "zero-adam",
         "--steps", "10", "--batch-size", "16", "--seq-len", "16",
         "--d-model", "32", "--n-heads", "4", "--n-layers", "2",
         "--d-ff", "64", "--vocab", "256", "--lr", "0.01",
         "--data-path", str(corpus), "--eval-every", "5",
         "--eval-batches", "2"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "eval_loss" in proc.stdout, proc.stdout[-2000:]
    summary = json.loads(next(
        line for line in proc.stdout.splitlines() if line.startswith("SUMMARY ")
    )[len("SUMMARY "):])
    assert summary["mesh"] == "data2xpipe2"
    assert summary["eval"] is not None and "eval_loss" in summary["eval"]
    assert summary["final_loss"] < summary["first_loss"], summary


def test_dp_stream_input_mode(tmp_path):
    """--input-mode stream trains from host RAM via the native kernel."""
    summary, stdout, _ = _run_script(
        tmp_path, "data_parallelism_train.py",
        "--nb-proc", "4", "--input-mode", "stream",
    )
    assert summary["regime"] == "data_parallel"
    assert summary["final_val_acc"] is not None
    assert summary["data_source"] == "synthetic"


def test_lm_train_checkpoint_resume(tmp_path):
    """Checkpointed LM run resumes at the next step with continuous loss."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    base = [
        sys.executable, os.path.join(REPO, "lm_train.py"),
        "--dp", "4", "--batch-size", "16", "--seq-len", "16",
        "--d-model", "32", "--n-heads", "4", "--d-ff", "64",
        "--vocab", "32", "--lr", "0.3",
        "--checkpoint-dir", str(tmp_path / "ck"),
    ]

    def run(*extra):
        proc = subprocess.run(
            [*base, *extra], capture_output=True, text=True, cwd=REPO,
            env=env, timeout=600,
        )
        assert proc.returncode == 0, proc.stderr[-3000:]
        return json.loads(next(
            l for l in proc.stdout.splitlines() if l.startswith("SUMMARY ")
        )[len("SUMMARY "):])

    first = run("--steps", "20")
    second = run("--steps", "10", "--resume")
    assert second["start_step"] == 20
    # resumed loss continues from the trained state, not from scratch
    assert second["first_loss"] < first["first_loss"] / 2
    assert second["final_loss"] <= second["first_loss"] + 1e-3


@pytest.mark.slow
def test_lm_train_pp_interleave_resume_guard(tmp_path):
    """A pipeline checkpoint written at one --pp-interleave holds a
    permuted layer layout; resuming at a different v must be rejected
    with the clear meta-guard message, not an opaque restore error."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    base = [
        sys.executable, os.path.join(REPO, "lm_train.py"),
        "--pp", "4", "--n-layers", "8", "--microbatches", "4",
        "--batch-size", "8", "--seq-len", "16",
        "--d-model", "32", "--n-heads", "4", "--d-ff", "64",
        "--vocab", "32", "--lr", "0.3",
        "--checkpoint-dir", str(tmp_path / "ck"),
    ]
    proc = subprocess.run(
        [*base, "--steps", "4", "--pp-interleave", "2"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    proc = subprocess.run(
        [*base, "--steps", "2", "--resume", "--pp-interleave", "1"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=600,
    )
    assert proc.returncode != 0
    assert "pp_interleave" in (proc.stderr + proc.stdout)
    # matching layout resumes fine
    proc = subprocess.run(
        [*base, "--steps", "2", "--resume", "--pp-interleave", "2"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]


def test_lm_train_rejects_orphan_sampling_flags(tmp_path):
    """--gen-* flags without --generate error instead of silently doing
    nothing (the r3-ADVICE class of silently-ignored flag combos)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "lm_train.py"),
         "--steps", "1", "--gen-temperature", "0.8"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=120,
    )
    assert proc.returncode != 0
    assert "--generate" in proc.stderr


def test_lm_train_rejects_orphan_or_unknown_remat_policy(tmp_path):
    """--remat-policy without --remat is a parse error; with --remat but
    an unknown jax.checkpoint_policies name it fails after startup with
    the name in the message (r5 feature)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    base = [sys.executable, os.path.join(REPO, "lm_train.py"), "--steps", "1"]
    orphan = subprocess.run(
        base + ["--remat-policy", "dots_saveable"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=120,
    )
    assert orphan.returncode != 0
    assert "--remat-policy only applies with --remat" in orphan.stderr
    unknown = subprocess.run(
        base + ["--remat", "--remat-policy", "not_a_policy"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=120,
    )
    assert unknown.returncode != 0
    assert "not_a_policy" in unknown.stderr


def test_lm_train_overlap_grad_sync_and_compilation_cache(tmp_path):
    """lm_train.py --grad-sync overlap: the run learns, the SUMMARY
    carries the schedule, the trace holds one grad_bucket event per
    bucket, StepStats attributes per-bucket collective bytes, and a
    second run against the same --compilation-cache-dir records a
    (cache-hit) compile step no slower than the cold one. The flag
    places the cache only while JAX_COMPILATION_CACHE_DIR is unset."""
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    trace = tmp_path / "ov_trace.json"
    cache = tmp_path / "xla_cache"
    args = [
        sys.executable, os.path.join(REPO, "lm_train.py"),
        "--dp", "2", "--optimizer", "zero", "--accum-steps", "2",
        "--grad-sync", "overlap", "--bucket-mb", "0.001",
        "--steps", "12", "--batch-size", "16", "--seq-len", "16",
        "--d-model", "32", "--n-heads", "4", "--d-ff", "64",
        "--vocab", "32", "--lr", "0.3",
        "--compilation-cache-dir", str(cache),
    ]
    proc = subprocess.run(
        [*args, "--trace-out", str(trace), "--step-stats"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    summary = json.loads(next(
        line for line in proc.stdout.splitlines()
        if line.startswith("SUMMARY ")
    )[len("SUMMARY "):])
    assert summary["grad_sync"] == "overlap"
    assert summary["final_loss"] < summary["first_loss"] - 1.0, summary
    doc = _strict_loads(trace.read_text())
    buckets = [
        e for e in doc["traceEvents"] if e.get("name") == "grad_bucket"
    ]
    assert buckets, "overlap run must record its bucket plan in the trace"
    assert all(e["args"]["schedule"] == "overlap" for e in buckets)
    assert all(e["args"]["op"] == "reduce_scatter" for e in buckets)
    stats = doc["stepStats"]
    assert stats["grad_sync"] == "overlap"
    assert stats["comm_buckets"]["count"] == len(buckets)
    assert stats["compilation_cache_dir"] == str(cache)
    assert sum(stats["comm_buckets"]["bytes_per_bucket"]) > 0
    assert "(persistent compilation cache" in proc.stdout
    # second run, same cache dir: the recorded compile step is the
    # cache-hit time (whether the backend wrote entries is up to the jax
    # version/platform - the provenance field is the contract here)
    proc2 = subprocess.run(
        [*args, "--trace-out", str(tmp_path / "t2.json"), "--step-stats"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=600,
    )
    assert proc2.returncode == 0, proc2.stderr[-3000:]
    doc2 = _strict_loads((tmp_path / "t2.json").read_text())
    assert doc2["stepStats"]["compilation_cache_dir"] == str(cache)
    assert doc2["stepStats"]["compile_s"] is not None


# ---------------------------------------------------- live observability


def _popen_env():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _await_metrics_url(proc, deadline_s=240):
    """Read the child's stdout until attach_monitor prints the server URL."""
    import re
    import time as _time

    t0 = _time.time()
    lines = []
    while _time.time() - t0 < deadline_s:
        line = proc.stdout.readline()
        if not line:
            break
        lines.append(line)
        m = re.search(r"metrics server: (http://[0-9.:]+)/metrics", line)
        if m:
            return m.group(1), lines
    raise AssertionError(
        "metrics server URL never printed:\n" + "".join(lines)
    )


def _scrape(url, path="/metrics"):
    import urllib.request

    with urllib.request.urlopen(url + path, timeout=5) as r:
        return r.read().decode()


def _metric(body, name):
    for line in body.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    return None


@pytest.mark.skipif(
    not hasattr(__import__("jax"), "shard_map"),
    reason="engine execution needs jax.shard_map with vma typing",
)
def test_cli_smoke_serves_live_metrics_and_healthz(tmp_path):
    """The CI acceptance path: `python -m ...train.cli smoke
    --metrics-port 0` serves valid Prometheus text with an advancing
    `train_steps_total`, and /healthz flips ready after compile."""
    import json as _json

    proc = subprocess.Popen(
        [sys.executable, "-m", "distributed_neural_network_tpu.train.cli",
         "smoke", "--metrics-port", "0", "--metrics-linger", "20",
         "--data", "synthetic", "--synthetic-size", "128",
         "--epochs", "3", "--batch-size", "16",
         "--log-dir", str(tmp_path / "log")],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=REPO, env=_popen_env(),
    )
    try:
        url, _ = _await_metrics_url(proc)
        h = _json.loads(_scrape(url, "/healthz"))
        assert h["alive"] is True  # liveness from process start
        # poll until the first epoch compiled + completed
        import time as _time

        t0 = _time.time()
        steps = 0.0
        while _time.time() - t0 < 240:
            body = _scrape(url)
            steps = _metric(body, "train_steps_total") or 0.0
            if steps >= 3:
                break
            _time.sleep(0.5)
        assert steps >= 3, body
        h = _json.loads(_scrape(url, "/healthz"))
        assert h["ready"] is True and h["step"] is not None
        assert _metric(body, "train_ready") == 1
        assert _metric(body, "train_loss") is not None
        # the reference's phase accumulators are published on exit; the
        # linger window keeps the server up for this final scrape
        deadline = _time.time() + 60
        while _time.time() < deadline:
            if "phase_seconds_total" in _scrape(url):
                break
            _time.sleep(0.5)
        assert "phase_seconds_total" in _scrape(url)
    finally:
        proc.stdout.close()
        proc.stderr.close()
        proc.terminate()
        proc.wait(timeout=30)


@pytest.mark.skipif(
    not hasattr(__import__("jax"), "shard_map"),
    reason="LM step execution needs jax.shard_map with vma typing",
)
def test_lm_train_chaos_stall_is_flagged_by_watchdog(tmp_path):
    """`--chaos-stall-step` wedges the host loop; with --metrics-port the
    watchdog must count a watchdog_stall_total episode and the trace must
    carry the watchdog/stall instant."""
    trace = str(tmp_path / "t.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "lm_train.py"),
         "--steps", "30", "--batch-size", "8", "--seq-len", "16",
         "--d-model", "32", "--n-heads", "4", "--d-ff", "64",
         "--vocab", "32", "--dp", "1",
         "--metrics-port", "0",
         "--chaos-stall-step", "20", "--chaos-stall-seconds", "8",
         "--trace-out", trace],
        capture_output=True, text=True, cwd=REPO, env=_popen_env(),
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "(chaos: stalling the step loop" in proc.stdout
    doc = json.load(open(trace))
    names = [e.get("name") for e in doc["traceEvents"]]
    assert "straggler" in names  # the injected stall span (fault track)
    # the watchdog's detection window is adaptive (10 x steady p95,
    # floored at 5 s); an 8 s stall over ~ms steps must be flagged
    assert "watchdog/stall" in names, sorted(set(names))
