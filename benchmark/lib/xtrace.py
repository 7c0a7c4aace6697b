"""From a profiler trace (`.xplane.pb`) to plain lists of events, and from
those to busy time, self times, kernel time and idle gaps.

`read_trace` is the only function that touches `jax.profiler.ProfileData`;
everything after it works on `Trace`, which the tests build from a small
recorded file (`tests/recorded_trace.json`).
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HOST_PREFIX = ("bench.", "serve.")  # the harness's spans and the program's
COLLECTIVE_PREFIXES = ("all-reduce", "all-gather", "reduce-scatter",
                       "collective-permute", "all-to-all", "send", "recv")


@dataclass
class Trace:
    """Events as (name, start_ns, duration_ns). `device[i]` is chip i's
    operation line (serial), `overlapped[i]` its line of asynchronous
    operations (collectives that run beside the compute); `host` holds the
    annotations of the harness and of the program (`HOST_PREFIX`)."""
    device: dict = field(default_factory=dict)
    host: list = field(default_factory=list)
    overlapped: dict = field(default_factory=dict)

    def to_json(self) -> dict:
        return {"device": {str(k): v for k, v in self.device.items()},
                "overlapped": {str(k): v for k, v in self.overlapped.items()},
                "host": self.host}

    @classmethod
    def from_json(cls, doc: dict) -> "Trace":
        def lines(d):
            return {int(k): [tuple(e) for e in v] for k, v in d.items()}
        return cls(lines(doc["device"]), [tuple(e) for e in doc["host"]],
                   lines(doc.get("overlapped", {})))


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_trace(trace_dir: str, layout_out: str | None = None) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(trace_dir))
    trace = Trace()
    layout = []
    for plane in data.planes:
        for line in plane.lines:
            layout.append(f"{plane.name} | {line.name}")
        if plane.name.startswith("/device:TPU:"):
            chip = int(plane.name.rsplit(":", 1)[1].split()[0])
            for line in plane.lines:
                if line.name in (OPS_LINE, ASYNC_LINE):
                    into = (trace.device if line.name == OPS_LINE
                            else trace.overlapped)
                    into[chip] = [
                        (short_name(ev.name), int(ev.start_ns),
                         int(ev.duration_ns)) for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        trace.host.append(
                            (ev.name, int(ev.start_ns), int(ev.duration_ns)))
    if layout_out:
        with open(layout_out, "w") as f:
            f.write("\n".join(layout) + "\n")
    trace.host.sort(key=lambda e: e[1])
    return trace


def short_name(text: str) -> str:
    """An operation's event carries its whole HLO line; keep the name, and
    mark a Pallas kernel (`tpu_custom_call`) so that it is found whatever the
    compiler called it: `checkpoint.18__mosaic_`."""
    name = text.split(" = ", 1)[0].strip().lstrip("%")
    return name + "__mosaic_" if "tpu_custom_call" in text else name


def clip(events, lo_ns: int, hi_ns: int) -> list:
    """The parts of the events that lie inside [lo, hi)."""
    out = []
    for name, start, dur in events:
        s, e = max(start, lo_ns), min(start + dur, hi_ns)
        if e > s:
            out.append((name, s, e - s))
    return out


def union(intervals) -> list:
    """Sorted, merged (start, end) pairs."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def busy_ns(events) -> int:
    return sum(e - s for s, e in union((s, s + d) for _, s, d in events))


def self_times(events) -> list:
    """(name, self_ns) per event: its duration less what the events nested
    in it on the same line cover (a `while` holds its body's operations)."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []  # stack of [name, end, self]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            out.append((name, max(self_ns, 0)))

    for name, start, dur in order:
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return out


def by_name(pairs) -> dict:
    tot: dict = {}
    for name, ns in pairs:
        tot[name] = tot.get(name, 0) + ns
    return tot


def is_mosaic(name: str) -> bool:
    return "mosaic" in name.lower()


def is_collective(name: str) -> bool:
    return name.lower().startswith(COLLECTIVE_PREFIXES)


def innermost(host, lo_ns: int, hi_ns: int) -> list:
    """[lo, hi) cut at every annotation's start and end: sorted (start, end,
    name) pieces, each named after the shortest annotation that covers it
    (the innermost of a nest, whatever thread it is on), `unattributed` where
    none does."""
    starts = sorted((e for e in host if e[1] < hi_ns and e[1] + e[2] > lo_ns),
                    key=lambda e: e[1])
    cuts = sorted({lo_ns, hi_ns}
                  | {max(s, lo_ns) for _, s, _ in starts}
                  | {min(s + d, hi_ns) for _, s, d in starts})
    pieces, active, nxt = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while nxt < len(starts) and starts[nxt][1] <= a:
            active.append(starts[nxt])
            nxt += 1
        active = [e for e in active if e[1] + e[2] > a]
        # an annotation's whole length decides, of two alike the later start
        owner = (min(active, key=lambda e: (e[2], -e[1]))[0] if active
                 else "unattributed")
        if pieces and pieces[-1][2] == owner and pieces[-1][1] == a:
            pieces[-1][1] = b
        else:
            pieces.append([a, b, owner])
    return pieces


def idle_gaps(events, host, lo_ns: int, hi_ns: int) -> dict:
    """Idle nanoseconds of one chip inside [lo, hi), each gap split among
    the annotations by overlap: every piece of it goes to the innermost
    annotation that covers that piece (`unattributed` where none does)."""
    edges = [lo_ns]
    for s, e in union((s, s + d) for _, s, d in clip(events, lo_ns, hi_ns)):
        edges += [s, e]
    edges.append(hi_ns)
    pieces, at = innermost(host, lo_ns, hi_ns), 0
    out: dict = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        while at < len(pieces) and pieces[at][1] <= a:
            at += 1
        i = at
        while i < len(pieces) and pieces[i][0] < b:
            s, e, owner = pieces[i]
            if min(e, b) > max(s, a):
                out[owner] = out.get(owner, 0) + min(e, b) - max(s, a)
            i += 1
    return out


def summarize(trace: Trace, lo_ns: int | None = None,
              hi_ns: int | None = None) -> dict:
    """What the per-layer readers take from a trace. Seconds are averaged
    over the chips; the window is [lo, hi) or, without them, from the first
    device operation to the last."""
    chips = sorted(trace.device)
    if not chips:
        return {"chips": 0}
    if lo_ns is None:
        lo_ns = min(e[1] for c in chips for e in trace.device[c])
    if hi_ns is None:
        hi_ns = max(e[1] + e[2] for c in chips for e in trace.device[c])
    n = len(chips)
    busy = mosaic = coll = coll_exposed = 0.0
    ops: dict = {}
    gaps: dict = {}
    for c in chips:
        ev = clip(trace.device[c], lo_ns, hi_ns)
        busy += busy_ns(ev)
        selfs = self_times(ev)
        for name, ns in by_name(selfs).items():
            ops[name] = ops.get(name, 0) + ns
            if is_mosaic(name):
                mosaic += ns
            if is_collective(name):
                coll += ns
        # a collective is exposed while no compute runs on that chip: all of
        # one on the serial line, and of an asynchronous one the part that
        # the serial line's other operations do not cover
        comp = union((s, s + d) for nm, s, d in ev
                     if not is_collective(nm) and not nm.startswith("while"))
        asyn = [e for e in clip(trace.overlapped.get(c, []), lo_ns, hi_ns)
                if is_collective(e[0])]
        coll += sum(d for _, _, d in asyn)
        for nm, s, d in asyn + [e for e in ev if is_collective(e[0])]:
            covered = sum(min(e, s + d) - max(b, s) for b, e in comp
                          if b < s + d and e > s)
            coll_exposed += max(d - covered, 0)
        for k, v in idle_gaps(ev, trace.host, lo_ns, hi_ns).items():
            gaps[k] = gaps.get(k, 0) + v
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {
        "chips": n,
        "window_s": (hi_ns - lo_ns) / 1e9,
        "busy_s": busy / n / 1e9,
        "mosaic_s": mosaic / n / 1e9,
        "collective_s": coll / n / 1e9,
        "collective_exposed_s": coll_exposed / n / 1e9,
        "device_ops": [[k, v / n / 1e9] for k, v in top],
        "idle_gaps": [[k, v / n / 1e9] for k, v in top_gaps],
    }


def record_slice(trace: Trace, lo_ns: int, hi_ns: int, path: str) -> None:
    """A small piece of a trace with what `summarize` makes of it: the
    recorded trace that the tests hold the reduction to."""
    import json

    piece = Trace(
        {c: clip(ev, lo_ns, hi_ns) for c, ev in trace.device.items()},
        clip(trace.host, lo_ns, hi_ns),
        {c: clip(ev, lo_ns, hi_ns) for c, ev in trace.overlapped.items()})
    s = summarize(piece, lo_ns, hi_ns)
    expect = {k: s[k] for k in ("window_s", "busy_s", "mosaic_s",
                                "collective_s", "collective_exposed_s")}
    with open(path, "w") as f:
        json.dump({"lo_ns": lo_ns, "hi_ns": hi_ns, "expect": expect,
                   "top_op": s["device_ops"][0], "trace": piece.to_json()}, f)
