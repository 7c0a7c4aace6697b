"""The five readers of the serving tick's untraced tail (ISSUE 38):
`serve_dispatch_found_idle_pct.tput`, `serve_host_{select,stage,dispatch}
_ms.tput` and `serve_loop_release_ms.tput`, through `benchmark/lib/
untraced.py`. Each reads the growth of the program's counters from the
traced window's end (`counters_traced[1]`) to the measured window's end
(`counters_window[1]`), and None where the program publishes no such
counter (the parent of ISSUE 38), where the run was not traced, or where no
tick lies in the tail. `BENCHMARK.json` lists them for the docqa cell
alone: in the other two serving cells the profile's export, at the tail's
start, stretches it by 11-46 % (PERF.md section 3). `benchmark/` is on
`sys.path` only while a reader is imported."""
import importlib.util
import json
import os
import sys

import jax
import numpy as np
import pytest

from conftest import BENCH
from distributed_neural_network_tpu.models import transformer as tfm
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

STEPS = "serve_engine_steps_total"
FOUND = 'serve_dispatch_found_total{device="%s",program="%s"}'
HOST = 'serve_host_seconds_total{part="%s"}'
RELEASE = 'serve_loop_seconds_total{phase="release"}'
SECONDS = {  # reader -> the seconds counter it divides by the ticks
    "serve_host_select_ms.tput": HOST % "select",
    "serve_host_stage_ms.tput": HOST % "stage",
    "serve_host_dispatch_ms.tput": HOST % "dispatch",
    "serve_loop_release_ms.tput": RELEASE,
}
IDLE = "serve_dispatch_found_idle_pct.tput"
NAMES = [IDLE, *SECONDS]
# the serving cell whose tail the export leaves within 10 % of a wholly
# untraced window
CELLS = ["openpangu-ultra-moe-718b.serve-docqa-6k"]


def _load(name):
    sys.path.insert(0, BENCH)
    try:
        spec = importlib.util.spec_from_file_location(
            "untraced_reader_" + name.replace(".", "_"),
            os.path.join(BENCH, "metrics", name + ".py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    finally:
        sys.path.remove(BENCH)
    return module


@pytest.fixture(scope="module")
def readers():
    return {name: _load(name) for name in NAMES}


def _obs(opened, traced_end, closed):
    """A traced run's observation: the registry when the measured window
    opened, when the traced window ended and when the measured one
    closed."""
    return {"counters_window": (opened, closed),
            "counters_traced": (opened, traced_end)}


def _found(idle, busy):
    return {FOUND % ("idle", "prefill"): idle[0],
            FOUND % ("idle", "decode"): idle[1],
            FOUND % ("busy", "prefill"): busy[0],
            FOUND % ("busy", "decode"): busy[1]}


def _every(steps, seconds=0.0, idle=(0.0, 0.0), busy=(0.0, 0.0)):
    return {STEPS: steps, **{k: seconds for k in SECONDS.values()},
            **_found(idle, busy)}


PARENT = {STEPS: 100.0, 'serve_loop_seconds_total{phase="fetch"}': 1.0,
          'serve_dispatch_ahead_total{outcome="ahead"}': 99.0}


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("obs", [
    {},                                                     # untraced
    {"counters_window": (_every(0.0), _every(900.0, 9.0))},  # the same
    _obs({}, {}, {}),                                       # no counters
    _obs(PARENT, dict(PARENT, **{STEPS: 180.0}),
         dict(PARENT, **{STEPS: 1300.0})),                  # the parent's
    _obs(_every(0.0), _every(180.0, 1.0, (5, 5), (80, 90)),
         _every(180.0, 1.0, (5, 5), (80, 90))),             # no tick after
], ids=["untraced", "untraced-window", "empty", "parent", "no-tail"])
def test_reader_finds_nothing_to_read(readers, name, obs):
    assert readers[name].read(obs) is None


@pytest.mark.parametrize("name", list(SECONDS))
def test_ms_a_tick_of_the_tail(readers, name):
    # the traced window's ticks are four times as long: the reader does not
    # see them
    obs = _obs(_every(1000.0, 10.0), _every(1200.0, 18.0),
               _every(2500.0, 31.0))
    assert readers[name].read(obs) == pytest.approx(1e3 * 13.0 / 1300,
                                                    rel=1e-12)


@pytest.mark.parametrize("idle,busy,want", [
    ((3.0, 7.0), (217.0, 1073.0), 100.0 * 10 / 1300),
    ((0.0, 0.0), (220.0, 1080.0), 0.0),
    ((220.0, 1080.0), (0.0, 0.0), 100.0),
])
def test_found_idle_share_of_the_tail(readers, idle, busy, want):
    opened = _every(1000.0, idle=(50.0, 60.0), busy=(70.0, 80.0))
    traced = _every(1200.0, idle=(150.0, 160.0), busy=(170.0, 180.0))
    closed = _every(2500.0, idle=(150.0 + idle[0], 160.0 + idle[1]),
                    busy=(170.0 + busy[0], 180.0 + busy[1]))
    assert readers[IDLE].read(_obs(opened, traced, closed)) == pytest.approx(
        want, rel=1e-12)


@pytest.mark.parametrize("name", NAMES)
def test_the_benchmark_names_the_reader_for_the_docqa_cell(name):
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == name)
    assert set(CELLS) <= set(entry["workloads"])
    assert (entry["better"], entry["layer"], entry["moves"]) == (
        "lower", "engine", "serve_tokens_per_s")
    assert (entry["unit"], entry["source"]) == (
        ("%", "program_counter") if name == IDLE else ("ms", "program_span"))


def test_readers_read_what_the_scheduler_publishes(readers, n_devices):
    """A scheduler's own registry, scraped as the benchmark scrapes it
    (`lib/serve.py counters`): every reader finds its counter, and the
    three parts and the release are the published seconds a tick."""
    sys.path.insert(0, BENCH)
    try:
        from lib.serve import counters
    finally:
        sys.path.remove(BENCH)
    cfg = tfm.TransformerConfig(vocab_size=32, d_model=32, n_heads=4,
                                n_layers=2, d_ff=64)
    registry = MetricsRegistry()
    engine = ServeEngine(tfm.init_params(jax.random.key(0), cfg), cfg,
                         EngineConfig(max_batch=4, num_blocks=32,
                                      block_size=4, max_seq_len=64,
                                      prefill_chunk=4))
    sched = ServeScheduler(engine, SchedulerConfig(), registry=registry)
    snaps, published = [counters(registry)], []
    for i, n in enumerate((9, 5, 7)):
        engine.add(Sequence(i, [int(t) for t in np.asarray(jax.random.randint(
            jax.random.key(90 + i), (n,), 2, 32))], 4))
    while engine.has_work():
        stats = engine.step()
        sched._publish_tick(stats["phase_s"], stats)
        sched._m_steps.inc()
        published.append(stats)
        if len(published) == 2:
            snaps.append(counters(registry))
    snaps.append(counters(registry))
    sched.close(finalize=False)
    obs = _obs(*snaps)
    tail = published[2:]
    for name, key in SECONDS.items():
        part = key.split('"')[1]
        got = sum(st["host_s"][part] if "part" in key
                  else st["phase_s"][part] for st in tail)
        assert readers[name].read(obs) == pytest.approx(
            1e3 * got / len(tail), rel=1e-9), name
    found = [f for st in tail for f in st["found"]]
    assert readers[IDLE].read(obs) == pytest.approx(
        100.0 * sum(d == "idle" for _, d in found) / len(found), rel=1e-12)
