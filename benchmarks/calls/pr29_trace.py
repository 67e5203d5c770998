#!/usr/bin/env python3
"""What the device did in a traced window, by program and by operation.

    python3 benchmarks/calls/pr29_trace.py <saved-trace.json> [program-name-part]

Reads what ``bench/run.py --trace 1 --save-trace`` wrote (the reduced
trace of ``bench/benchlib/tracered.py``: the first chip's operations and
programs) and prints, a request: the programs by name (launches, ms),
the operations by name, and the operations that ran inside the programs
whose name holds ``program-name-part`` (default ``f64_sum_mean``: ISSUE
29's one program of a float64 aggregate).
"""
import collections
import json
import sys


def table(title, rows, n_req, top=14):
    print(f"-- {title} (a request; {len(rows)} names)")
    for name, (count, ns) in sorted(rows.items(), key=lambda kv: -kv[1][1])[:top]:
        print(f"   {ns / 1e6 / n_req:10.2f} ms  {count / n_req:9.1f} x  {name[:110]}")


def main(path, part="f64_sum_mean"):
    t = json.load(open(path))
    w0, w1 = t["window_ns"]
    n_req = max(int(t["requests"]), 1)
    plane, dev = sorted(t["trace"]["devices"].items())[0]
    inside = lambda ev: ev[1] >= w0 and ev[1] + ev[2] <= w1  # noqa: E731
    programs = [p for p in dev["programs"] if inside(p)]
    ops = [o for o in dev["ops"] if inside(o)]
    print(f"{plane}: window {(w1 - w0) / 1e9:.2f} s, {n_req} requests, {len(programs) / n_req:.0f} programs and "
          f"{len(ops) / n_req:.0f} operations a request")
    by = collections.defaultdict(lambda: [0, 0])
    for name, _s, d in programs:
        by[name][0] += 1
        by[name][1] += d
    table("programs", by, n_req)
    by = collections.defaultdict(lambda: [0, 0])
    for name, _s, d in ops:
        by[name][0] += 1
        by[name][1] += d
    table("operations", by, n_req)
    mine = sorted((s, s + d) for name, s, d in programs if part in name)
    by = collections.defaultdict(lambda: [0, 0])
    k = 0
    for name, s, d in sorted(ops, key=lambda o: o[1]):
        while k < len(mine) and mine[k][1] <= s:
            k += 1
        if k < len(mine) and mine[k][0] <= s:
            by[name][0] += 1
            by[name][1] += d
    print(f"programs named *{part}*: {len(mine) / n_req:.1f} a request, "
          f"{sum(e - s for s, e in mine) / 1e6 / n_req:.2f} ms a request")
    table(f"operations inside *{part}*", by, n_req, top=16)


if __name__ == "__main__":
    main(*sys.argv[1:3])
