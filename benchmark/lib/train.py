"""A training cell: the trainer's own compiled step (`train/lm.py`
`make_lm_shardings` + `make_lm_train_step`), driven as `lm_train.py`'s bare
loop drives it, with one step in flight."""

from __future__ import annotations

import json
import shutil
import time

from . import compare, harness, reference, weights, xtrace


def _steady(intervals, n: int, tol: float) -> bool:
    if len(intervals) < n:
        return False
    last = intervals[-n:]
    mid = harness.median(last)
    return all(abs(x - mid) <= tol * mid for x in last)


def _scaled_bf16(tree, scale: float):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda t: jax.tree.map(
        lambda x: (x * scale).astype(jnp.bfloat16), t))(tree)


class Loop:
    """Holds the state and dispatches steps with one in flight: after
    dispatching step i it waits for step i-1, as the trainer's bare loop
    does through its one-step-lagged pipe."""

    def __init__(self, step, batch_fn, params, mom):
        self.step, self.batch_fn = step, batch_fn
        self.params, self.mom = params, mom
        self.index = 0
        self.loss = None

    def one(self):
        """Dispatch one step and return its loss without waiting."""
        tok, tgt = self.batch_fn(self.index)
        out = self.step(self.params, self.mom, tok, tgt)
        self.params, self.mom, self.loss = out[0], out[1], out[2]
        self.index += 1
        return self.loss

    def run(self, seconds: float, stop=None):
        """Steps until `seconds` have passed at a step's completion (or
        `stop(intervals)` says so). Returns (t_open, t_close, completion
        times): fenced at both ends, every dispatched step completed."""
        import jax
        from jax.profiler import TraceAnnotation

        jax.block_until_ready((self.params, self.mom))
        done = []
        t_open = time.monotonic()
        prev = None
        while True:
            with TraceAnnotation("bench.dispatch_step"):
                loss = self.one()
            if prev is not None:
                with TraceAnnotation("bench.wait_step"):
                    prev.block_until_ready()
                done.append(time.monotonic())
                gaps = [b - a for a, b in zip([t_open] + done, done)]
                if done[-1] - t_open >= seconds or (stop and stop(gaps)):
                    break
            prev = loss
        with TraceAnnotation("bench.wait_step"):
            loss.block_until_ready()
        done.append(time.monotonic())
        jax.block_until_ready((self.params, self.mom))
        return t_open, time.monotonic(), done


def run(spec, *, seed, seconds, trace, device, t_start, wrap_step=None):
    """`wrap_step` lets a test break the timed path underneath."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_neural_network_tpu.runtime import enable_compile_cache
    from distributed_neural_network_tpu.train import lm as lmtrain

    stages = harness.Stages(t_start)
    compiles = harness.CompileCounter()
    enable_compile_cache()
    model, tr, chips = spec["config"], spec["traffic"], spec["cell"]["chips"]
    family = spec["family"]
    workload = spec["cell"]["name"]
    devices = jax.devices()[:chips]
    stages.mark("import_and_device")

    cfg = family.program.config(model, tr, jnp.bfloat16)
    mesh = lmtrain.create_lm_mesh(tr["dp"], 1, tr["tp"])
    _, p_shard, _ = lmtrain.make_lm_shardings(cfg, mesh, tr["optimizer"])
    params = family.weights.make(seed, model, shardings=p_shard)
    mom = lmtrain.init_lm_momentum(params, mesh, tr["optimizer"])
    step = lmtrain.make_lm_train_step(
        cfg, mesh, lr=tr["lr"], momentum=tr["b1"], attn_impl=tr["attn"],
        optimizer=tr["optimizer"])
    if wrap_step is not None:
        step = wrap_step(step)
    batch_fn = weights.make_batch_fn(
        seed, batch=tr["batch"], seq=tr["seq"],
        vocab=family.weights.vocab(model),
        sharding=NamedSharding(mesh, P(lmtrain.DATA_AXIS, lmtrain.SEQ_AXIS)))
    loop = Loop(step, batch_fn, params, mom)
    del params, mom
    stages.mark("build")

    # the first steps of the very object the window drives: their losses,
    # the first gradient as the optimizer got it (Adam's m after one step is
    # (1 - b1) g, SGD's momentum buffer is g), and the parameters' change
    n_check = tr["check_steps"]
    prog = {"losses": []}
    adam = tr["optimizer"] == "adam"
    g_scale = 1.0 / (1.0 - tr["b1"]) if adam else 1.0
    for i in range(n_check):
        loss = loop.one()
        if i == 0:
            m1 = loop.mom["m"] if adam else loop.mom
            m1_norms = reference.leaf_norms(m1)
            loss.block_until_ready()
            stages.mark("compile_or_cache_load")
            # a copy on the host, for the norm of its difference from the
            # reference's once the window has closed (step 2 donates m1);
            # in bfloat16, whose rounding (2^-9) is a twentieth of the gap
            # that the copy is there to measure, to halve the transfer
            prog["first_grad"] = jax.device_get(_scaled_bf16(m1, g_scale))
            del m1
        prog["losses"].append(loss)
    p0 = family.weights.make(seed, model, shardings=p_shard)
    change = reference.diff_norms(loop.params, p0)
    del p0
    prog["losses"] = [float(x) for x in prog["losses"]]
    prog["grad"] = {k: v * g_scale for k, v in
                    compare.flat_norms(jax.device_get(m1_norms)).items()}
    prog["change"] = compare.flat_norms(jax.device_get(change))
    stages.mark("first_steps")

    # warm-up ends on evidence: the last few step times agree
    wu = tr["warmup"]
    _, _, wdone = loop.run(
        wu["max_seconds"],
        stop=lambda gaps: _steady(gaps[1:], wu["agree_steps"], wu["tolerance"]))
    stages.mark("warmup")

    setup_s = time.monotonic() - t_start
    compiles.armed = True
    t_open, t_close, done = loop.run(seconds)
    window_s = t_close - t_open
    gaps_ms = [1e3 * (b - a) for a, b in zip([t_open] + done, done)]
    tokens_per_step = tr["batch"] * tr["seq"]
    rate = len(done) * tokens_per_step / window_s
    steps_file = harness.out_path(workload, seed, trace, "steps.json")
    with open(steps_file, "w") as f:
        json.dump({"window_s": window_s, "warmup_steps": len(wdone),
                   "setup_parts_s": stages.parts,
                   "step_done_s": [t - t_open for t in done]}, f)

    summary, traced_rate = None, None
    if trace:
        tdir = harness.out_path(workload, seed, trace, "xplane")
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(tdir)
        try:
            a, b, tdone = loop.run(tr["trace_seconds"])
        finally:
            jax.profiler.stop_trace()
        traced_rate = len(tdone) * tokens_per_step / (b - a)
        tr_all = xtrace.read_trace(
            tdir, harness.out_path(workload, seed, trace, "layout.txt"))
        # the traced window on the trace's clock: from the first dispatch
        # annotation to the end of the last wait
        lo = min(s for n, s, d in tr_all.host)
        hi = max(s + d for n, s, d in tr_all.host)
        summary = xtrace.summarize(tr_all, lo, hi)
        xtrace.record_slice(tr_all, lo, lo + 30_000_000, harness.out_path(
            workload, seed, trace, "trace_slice.json"))
        shutil.rmtree(tdir, ignore_errors=True)
    compiles.armed = False

    peak = harness.memory_peak_bytes(devices)
    mem = {"peak_bytes_in_use": peak}
    if hasattr(step, "lower"):  # a test's wrapped step has none
        tok, tgt = batch_fn(0)
        ma = step.lower(loop.params, loop.mom, tok, tgt).compile(
            ).memory_analysis()
        mem["program_bytes"] = int(ma.peak_memory_in_bytes)
        peak = max(peak, mem["program_bytes"])

    # free the program's state, then the reference follows the same steps
    del loop, step
    ref = reference_steps(seed, family, model, tr, batch_fn, devices=devices,
                          against=prog.pop("first_grad"))
    prog["grad_diff"] = ref["grad_diff"]
    numbers = compare.train_numbers(prog, ref)
    compared = compare.with_limits(numbers, spec["limits"])
    dev = dict(device, memory_peak_bytes=int(peak))
    metrics = {
        "train_tokens_per_s": {"value": rate, "unit": "tokens/s"},
        "setup_s": {"value": setup_s, "unit": "s"},
    }
    breakdown = None
    if trace:
        dev["busy_s"], dev["window_s"] = summary["busy_s"], summary["window_s"]
        breakdown = {"device_ops": summary["device_ops"],
                     "idle_gaps": summary["idle_gaps"]}
        obs = {"gaps_ms": gaps_ms, "trace": summary, "traced_rate": traced_rate,
               "traced_steps": len(tdone),
               "model": model, "family": family, "traffic": tr,
               "chips": chips,
               "device_kind": device["kind"], "memory_peak_bytes": peak,
               "compiles_in_window": compiles.count}
        metrics = harness.read_per_layer(spec, obs)
    return harness.emit(
        correct=harness.judge(compared) and compiles.count == 0,
        attempted=len(done), failed=0, metrics=metrics, device=dev,
        compared=compared, breakdown=breakdown,
        extra={"steps": len(done), "window_s": window_s,
               "setup_parts_s": stages.parts, "memory": mem,
               "compiles_in_window": compiles.count,
               "numbers": {k: [v, d] for k, (v, d) in numbers.items()}})


def reference_steps(seed, family, model, tr, batch_fn, precision="f32",
                    devices=None, fault="", against=None,
                    keep_first_grad=False) -> dict:
    """The family's plain reference through the same first steps: losses, the
    first gradient's norms, the change's norms. On one device, or with its leaves
    spread over the cell's chips where one cannot hold them. `against` is
    another side's first gradient (a tree on the host): the per-leaf norms of
    this side's difference from it come back as "grad_diff";
    `keep_first_grad` hands this side's back on the host as "first_grad"."""
    import jax
    import jax.numpy as jnp

    shard = None
    if devices is not None and len(devices) > 1:
        shard = reference.spread_over(devices, family.weights.shapes(model))
    adam = tr["optimizer"] == "adam"
    p, fn = family.reference.loss_and_grads(seed, model, tr, precision, fault,
                                            shardings=shard)
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p) if adam else None
    out = {"losses": []}
    for t in range(1, tr["check_steps"] + 1):
        tok, tgt = jax.device_get(batch_fn(t - 1))
        loss, g = fn(p, tok, tgt)
        if t == 1:
            out["grad"] = compare.flat_norms(
                jax.device_get(reference.leaf_norms(g)))
            if against is not None:
                other = jax.device_put(against, jax.tree.map(
                    lambda x: x.sharding, g))
                out["grad_diff"] = compare.flat_norms(
                    jax.device_get(reference.diff_norms(g, other)))
                del other
            if keep_first_grad:
                out["first_grad"] = jax.device_get(g)
        if fault == "state_unchanged":
            pass
        elif adam:
            p, m, v = reference.adam_update(
                p, g, m, v, t, lr=tr["lr"], b1=tr["b1"], b2=0.999, eps=1e-8)
        else:
            p, m = reference.sgd_update(p, g, m, lr=tr["lr"],
                                        momentum=tr["b1"])
        out["losses"].append(float(loss))
        del g
    del m, v
    p0 = family.weights.make(seed, model, shardings=shard)
    out["change"] = compare.flat_norms(
        jax.device_get(reference.diff_norms(p, p0)))
    return out
