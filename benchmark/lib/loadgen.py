#!/usr/bin/env python3
"""The load generator: a process of its own that never loads JAX.

It reads a traffic file, makes the requests from the seed, sends them to
`POST /v1/generate` (streamed), and writes every request's times and tokens
to a file. A pre-roll fills the server before the window opens; requests due
in the pre-roll are sent and not judged. Open loop: a dispatcher hands each
request to a fixed pool of sender threads at the time it is due, and every
time is taken from when the request was DUE, so a stall shows in the
latencies of the requests behind it; how late the senders ran is reported.
Closed loop: `callers` threads, each sending its next request when its
answer has come; they start `stagger_s` apart, so that the server meets the
first requests in the pool's order and not in the order a race gives.

Sending goes on for LINGER_S past the window's nominal close, because the
harness opens and closes its window between two ticks' ends, up to a tick
after the clock's edges.

Copied from `tools/loadgen.py` (the streaming client) and repaired: lengths
from a distribution, due-time latencies, lateness, no thread per request,
and no token-exact oracle (the harness compares against its reference).
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import queue
import sys
import threading
import time
import urllib.parse

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import traffic as tgen  # noqa: E402


def send(base, req: dict, rec: dict, timeout: float) -> None:
    """One streamed request; fills `rec` (times on the monotonic clock)."""
    u = urllib.parse.urlsplit(base)
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=timeout)
    rec["sent"] = time.monotonic()
    try:
        conn.request("POST", "/v1/generate", json.dumps({
            "prompt": req["prompt"], "max_new_tokens": req["max_new_tokens"],
            "temperature": req["temperature"], "stream": True,
        }), {"Content-Type": "application/json", "X-API-Key": "bench"})
        r = conn.getresponse()
        rec["http"] = r.status
        if r.status != 200:
            rec["status"] = f"http_{r.status}"
            r.read()
            return
        buf = b""
        while True:
            chunk = r.read1(65536)
            if not chunk:
                break
            buf += chunk
            while b"\n\n" in buf:
                frame, buf = buf.split(b"\n\n", 1)
                line = frame.decode("utf-8", "replace").strip()
                if not line.startswith("data: "):
                    continue
                doc = json.loads(line[6:])
                if "token" in doc:
                    rec["tokens"].append(int(doc["token"]))
                    rec["token_t"].append(time.monotonic())
                elif doc.get("done"):
                    rec["status"] = "completed"
                    rec["done"] = time.monotonic()
                    return
                elif "error" in doc:
                    rec["status"] = "error"
                    rec["error"] = str(doc["error"])
                    return
        rec["status"] = "error"
        rec["error"] = "stream ended without a done frame"
    except OSError as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        conn.close()


def new_record(idx: int, req: dict, due: float) -> dict:
    return {"idx": idx, "due": due, "sent": None, "done": None,
            "status": "not_sent", "prompt_len": len(req["prompt"]),
            "max_new_tokens": req["max_new_tokens"], "tokens": [],
            "token_t": []}


LINGER_S = 2.0


def run(base: str, traffic: dict, seed: int, vocab: int, seconds: float,
        t_open: float) -> list:
    """Send from now until LINGER_S past `t_open + seconds`; wait up to the
    traffic file's `grace_s` more for the answers. Returns the records."""
    pool = tgen.request_pool(traffic, seed, vocab)
    t_close = t_open + seconds + LINGER_S
    grace_s = traffic["grace_s"]
    records, lock = [], threading.Lock()
    timeout = seconds + traffic["preroll_s"] + grace_s

    def take(due):
        with lock:
            idx = len(records)
            req = pool[idx % len(pool)]
            rec = new_record(idx, req, due)
            records.append(rec)
        return req, rec

    threads = []
    if traffic["loop"] == "closed":
        t_begin = time.monotonic()

        def caller(i):
            time.sleep(max(t_begin + i * traffic["stagger_s"]
                           - time.monotonic(), 0))
            while time.monotonic() < t_close:
                req, rec = take(time.monotonic())
                send(base, req, rec, timeout)

        threads = [threading.Thread(target=caller, args=(i,), daemon=True)
                   for i in range(traffic["callers"])]
        for th in threads:
            th.start()
    else:
        t_begin = time.monotonic()
        horizon = t_close - t_begin
        due_times = [t_begin + t for t in
                     tgen.arrival_times(traffic, seed, horizon)]
        work: queue.Queue = queue.Queue()

        def sender():
            while True:
                item = work.get()
                if item is None:
                    return
                send(base, *item, timeout)

        threads = [threading.Thread(target=sender, daemon=True)
                   for _ in range(traffic["senders"])]
        for th in threads:
            th.start()
        for due in due_times:
            delay = due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            work.put(take(due))
        for _ in threads:
            work.put(None)
    deadline = t_close + grace_s
    for th in threads:
        th.join(timeout=max(deadline - time.monotonic(), 0.1))
    return records


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--url", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    with open(args.traffic) as f:
        traffic = json.load(f)
    t_open = time.monotonic() + traffic["preroll_s"]
    print(f"OPEN {t_open!r}", flush=True)
    records = run(args.url, traffic, args.seed, args.vocab, args.seconds,
                  t_open)
    tmp = args.out + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"t_open": t_open, "seconds": args.seconds,
                   "records": records}, f)
    os.replace(tmp, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
