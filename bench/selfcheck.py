#!/usr/bin/env python3
"""Check the yardstick itself, by hand, on the CPU: no chip, no JAX.

    python3 bench/selfcheck.py

1. The readers reduce the recorded trace and span log under
   bench/fixtures/ to the numbers recorded beside them, every time.
2. ``rows_per_s`` over a synthetic list of request times with a stall in
   it moves with the stall; ``latency_p50_ms`` does not.
3. Every per-layer metric of BENCHMARK.json has its file under
   bench/metrics/, which names a reader that exists, and every cell
   resolves.
"""

from __future__ import annotations

import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

from benchlib import loader, tracered, window  # noqa: E402


def check_fixture(name: str) -> None:
    with open(os.path.join(BENCH_DIR, "fixtures", f"{name}.json")) as f:
        fx = json.load(f)
    with open(os.path.join(BENCH_DIR, "fixtures", f"{name}.expected.json")) as f:
        want = json.load(f)
    tr, spans, (w0, w1), n = fx["trace"], fx["spans"], fx["window_ns"], fx["requests"]
    for _ in range(3):  # the same numbers every time
        busy = tracered.busy_seconds(tr, w0, w1)
        got = {
            "device_busy_ms_per_request": 1e3 * busy / n,
            "device_idle_share": 100.0 * (1.0 - busy / ((w1 - w0) / 1e9)),
            "device_programs_per_request": tracered.programs_in(tr, w0, w1) / n,
            "idle_gaps": tracered.idle_by_span(tr, spans, w0, w1),
            "device_ops": tracered.top_device_ops(tr, w0, w1),
        }
        assert got == want, f"{name}: the reduction moved:\n got {got}\nwant {want}"
    print(f"fixture {name}: busy {got['device_busy_ms_per_request']:.3f} ms/request, "
          f"idle {got['device_idle_share']:.2f}%, top gap {got['idle_gaps'][0] if got['idle_gaps'] else None}")


def check_stall() -> None:
    steady = [window.Request(float(i), i + 1.0, 1000) for i in range(10)]
    stalled = [window.Request(float(i), i + 1.0, 1000) for i in range(5)]
    stalled.append(window.Request(5.0, 9.0, 1000))  # one request stalls for 3 s
    stalled += [window.Request(9.0 + i, 10.0 + i, 1000) for i in range(4)]
    gapped = [window.Request(i * 1.5, i * 1.5 + 1.0, 1000) for i in range(10)]  # time that belongs to no request
    assert window.latency_p50_ms(steady) == window.latency_p50_ms(stalled) == window.latency_p50_ms(gapped) == 1000.0
    assert window.rows_per_s(steady) == 1000.0
    assert abs(window.rows_per_s(stalled) - 10_000 / 13.0) < 1e-9, window.rows_per_s(stalled)
    assert abs(window.rows_per_s(gapped) - 10_000 / 14.5) < 1e-9, window.rows_per_s(gapped)
    print("stall: rows_per_s 1000 -> %.1f with one 3 s stall, -> %.1f with 0.5 s gaps; latency_p50_ms stays 1000"
          % (window.rows_per_s(stalled), window.rows_per_s(gapped)))


def check_files() -> None:
    bm = loader.benchmark()
    cells = {w["name"] for w in bm["workloads"]}
    for m in bm["per_layer"]:
        spec = loader.read_json("metrics", f"{m['name']}.json")
        assert set(spec) == {"reader", "what"}, f"{m['name']}: the metric file holds its reader and what it reads, no more"
        assert set(m.get("workloads", cells)) <= cells
        loader.module("readers", spec["reader"]).read  # noqa: B018  (the reader exists)
    for w in bm["workloads"]:
        c = loader.cell(w["name"])
        loader.module("drivers", c["traffic"]["driver"]).Session  # noqa: B018
        assert c["per_layer"] and len(c["end_to_end"]) >= 2
    print(f"files: {len(bm['per_layer'])} per-layer metrics, {len(cells)} cells resolve")


def main() -> int:
    for name in sorted(f[:-len(".expected.json")] for f in os.listdir(os.path.join(BENCH_DIR, "fixtures"))
                       if f.endswith(".expected.json")):
        check_fixture(name)
    check_stall()
    check_files()
    print("selfcheck ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
