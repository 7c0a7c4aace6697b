"""What every cell shares: finding its files by name (configuration, family,
traffic, limits, readers), the look for the chip, the compile counter and the
result line."""

from __future__ import annotations

import importlib.util
import json
import os
import statistics
import sys
import time
import types

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
OUT_DIR = os.path.join(ROOT, "benchmark_out")


def load_json(*parts) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


# A family is a directory `families/<name>/` of four modules. What the
# harness itself calls of each, by the kind of cell; what a per-layer reader
# calls of `arith` is between the reader and the families of its cells.
FAMILY_PARTS = ("weights", "reference", "program", "arith")
FAMILY_ENTRIES = {
    "train": {"weights": ("shapes", "make", "vocab"),
              "reference": ("loss_and_grads",), "program": ("config",)},
    "serve": {"weights": ("make", "vocab"),
              "reference": ("served_logits",), "program": ("config",)},
}


def families_present() -> list:
    d = os.path.join(BENCH_DIR, "families")
    return sorted(n for n in os.listdir(d)
                  if os.path.isdir(os.path.join(d, n)) and n[:1].isalnum())


def load_family(name: str, kind: str | None = None):
    """The family's four parts, imported by name from `families/<name>/` as
    a package of their own (so `from . import weights` works inside it): an
    object with `.name`, `.weights`, `.reference`, `.program`, `.arith`. A
    part that is missing, or lacks an entry a cell of `kind` calls, ends the
    run."""
    d = os.path.join(BENCH_DIR, "families", name)
    if not os.path.isdir(d):
        raise SystemExit(f"no family {name!r} under benchmark/families; "
                         f"there is {families_present()}")
    lacks = [p + ".py" for p in FAMILY_PARTS
             if not os.path.isfile(os.path.join(d, p + ".py"))]
    if lacks:
        raise SystemExit(f"family {name!r} lacks {lacks}: a family is "
                         f"{', '.join(p + '.py' for p in FAMILY_PARTS)} "
                         f"under benchmark/families/{name}/")
    pkg_name = "bench_family_" + "".join(
        c if c.isalnum() else "_" for c in name)
    for stale in [m for m in sys.modules
                  if m == pkg_name or m.startswith(pkg_name + ".")]:
        del sys.modules[stale]
    pkg = types.ModuleType(pkg_name)
    pkg.__path__ = [d]
    sys.modules[pkg_name] = pkg
    family = types.SimpleNamespace(name=name)
    for part in FAMILY_PARTS:
        setattr(family, part,
                importlib.import_module(f"{pkg_name}.{part}"))
    for part, entries in FAMILY_ENTRIES.get(kind, {}).items():
        lacks = [e for e in entries
                 if not callable(getattr(getattr(family, part), e, None))]
        if lacks:
            raise SystemExit(f"family {name!r}: {part}.py lacks {lacks}, "
                             f"which a {kind!r} cell calls")
    return family


def load_spec(workload: str) -> dict:
    """The cell's entry in BENCHMARK.json with its configuration, that
    configuration's family, its traffic and its limits, each found by the
    name the entry gives."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    if "family" not in config:
        raise SystemExit(f"{cfg_entry['file']} names no \"family\"; "
                         f"benchmark/families has {families_present()}")
    traffic = load_json("traffic", cell["traffic"] + ".json")

    def reports(metric):
        return workload in metric.get("workloads", [workload])

    end_to_end = [m for m in bench["end_to_end"] if reports(m)]
    moved = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if reports(m) and m["moves"] in moved]
    return {
        "cell": cell, "config": config,
        "family": load_family(config["family"], traffic.get("kind")),
        "traffic": traffic,
        "limits": load_json("limits", workload + ".json"),
        "end_to_end": end_to_end, "per_layer": per_layer,
    }


def need_tpu(chips: int) -> dict:
    """The devices as JAX reports them; anything but enough TPU chips of a
    kind the peak table lists ends the run with no result."""
    import jax

    from . import arith

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SystemExit(f"needs a TPU: JAX found platform {d0.platform!r} "
                         f"({d0.device_kind}); no number is printed")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found {len(devs)}")
    arith.peaks(d0.device_kind)
    return {"platform": d0.platform, "kind": d0.device_kind, "count": chips}


def memory_peak_bytes(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileCounter:
    """Counts backend compilations (cache loads included) while armed."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event == self.EVENT:
            self.count += 1


def quantile(xs, q: float):
    """Linear-interpolated quantile of a non-empty list."""
    s = sorted(xs)
    if len(s) == 1:
        return s[0]
    pos = q * (len(s) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


median = statistics.median


def read_per_layer(spec: dict, obs: dict) -> dict:
    """Each per-layer metric of the cell through the reader of its own name
    (`metrics/<name>.py`, `read(obs)`); a reader that finds nothing returns
    None and the metric is left out."""
    out = {}
    for m in spec["per_layer"]:
        path = os.path.join(BENCH_DIR, "metrics", m["name"] + ".py")
        mod_name = "bench_metric_" + m["name"].replace(".", "_")
        loader = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(loader)
        loader.loader.exec_module(mod)
        value = mod.read(obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def judge(compared: dict) -> bool:
    """`compared`: name -> {"value", "limit"}; correct when every value is a
    number at or under its limit."""
    ok = True
    for c in compared.values():
        v = c["value"]
        if v is None or v != v or v > c["limit"]:
            ok = False
    return ok


def emit(*, correct: bool, attempted: int, failed: int, metrics: dict,
         device: dict, compared: dict, breakdown: dict | None = None,
         extra: dict | None = None) -> dict:
    """The numbers compared, each beside its limit, as the last lines on
    standard error; then the result as the last line on standard output,
    with `compared` as its last key."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    if extra:
        line.update(extra)
    line["compared"] = compared
    sys.stdout.flush()
    for name, c in compared.items():
        print(f"compared {name}: value {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(f"correct: {bool(correct)}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return line


def out_path(workload: str, seed: int, trace: int, suffix: str) -> str:
    d = os.path.join(OUT_DIR, workload)
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, f"seed{seed}_trace{trace}.{suffix}")


class Stages:
    """Named parts of set-up, on the process's clock."""

    def __init__(self, t_start: float):
        self.t_start = t_start
        self.t_last = t_start
        self.parts: dict = {}

    def mark(self, name: str) -> None:
        now = time.monotonic()
        self.parts[name] = round(self.parts.get(name, 0.0)
                                 + now - self.t_last, 3)
        self.t_last = now
