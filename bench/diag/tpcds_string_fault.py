#!/usr/bin/env python3
"""Show the fault that keeps `tpcds-sf1-store.q3-q55` out of BENCHMARK.json
(PERF.md section 7): the program cannot carry a STRING column through a
plan. q3 and q55 group by ``i_brand``; the plan rewriter's column pruning
puts a pass-through Project over the filtered ``item`` (to drop the
filter-only ``i_manufact_id`` / ``i_manager_id``), and a Project of a STRING
column raises in ``ops/expressions.py`` (``Expression.evaluate`` reads
``v.data.dtype`` of a column that has offsets and chars and no data).

    python3 bench/diag/tpcds_string_fault.py <seed> [cpu] [rows]

1. The cell's own files, as they wait under bench/: set-up's first
   warm-up request raises.
2. The same star with ``i_brand`` left out of ``item`` and of both queries:
   the program answers, and the answers equal the pandas reference: the
   string is the only obstacle. Prints each request's seconds.

``cpu`` skips the look for a chip (and then no time printed is a device
time); ``rows`` cuts store_sales for a quick look.
"""
import json
import os
import sys
import time
import traceback

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))  # bench/
sys.path.insert(0, BENCH)
import numpy as np  # noqa: E402

from benchlib import compare, loader, window  # noqa: E402

seed = int(sys.argv[1])
cpu = "cpu" in sys.argv[2:]
rows = [int(a) for a in sys.argv[2:] if a.isdigit()]


def cell():
    # the cell is not in BENCHMARK.json: put it together from its files
    c = {"name": "tpcds-sf1-store.q3-q55", "chips": 1, "config": loader.read_json("configs", "tpcds-sf1-store.json"),
         "traffic": loader.read_json("traffic", "q3-q55.json"), "end_to_end": [], "per_layer": []}
    if rows:
        c["config"]["tables"]["store_sales"]["rows"] = rows[0]
    return c


def drive(c, tag):
    s = loader.open_session(c, seed, False, False, tag=tag)
    if cpu:
        import benchlib.device as device
        device.require = lambda *a, **k: None
    try:
        t0 = time.time()
        s.setup()
        print(tag, "set-up", round(time.time() - t0, 1), {k: round(v, 2) for k, v in s.facts.items()}, flush=True)
        reqs = window.closed_loop(s.issue, 5.0, s.keep)
        print(tag, "request seconds", [round(r.end - r.start, 3) for r in reqs], "device", s.device, flush=True)
        print(tag, "answers", [(t.names, t.num_rows) for t in s.kept[0][1]], flush=True)
        s.release()
        checks = compare.judge(s.check(), c["config"]["limits"])
        print(tag, json.dumps([{k: x[k] for k in ("name", "value", "limit", "ok")} for x in checks]), flush=True)
    finally:
        s.close()


print("== 1. the cell as its files have it", flush=True)
try:
    drive(cell(), "diag-as-is-")
    print("as-is: ran (the fault is mended?)")
except Exception:
    print("as-is: RAISED:\n" + "".join(traceback.format_exc().splitlines(True)[-6:]), flush=True)

print("== 2. i_brand left out", flush=True)
c = cell()
del c["config"]["tables"]["item"]["columns"]["i_brand"]
real_module = loader.module


def module(kind, name):
    m = real_module(kind, name)
    if kind == "data":
        inner = m.host_tables

        def host_tables(config, seed_, n):
            t = inner(config, seed_, n)
            del t["item"]["i_brand"]
            return t
        m.host_tables = host_tables
    if kind == "queries":
        m.READS = {k: tuple(x for x in v if x != "i_brand") for k, v in m.READS.items()}
        m.EXACT = tuple(x for x in m.EXACT if x != "i_brand")
        plan, reference = m.plan, m.reference

        class WithoutBrand:  # the plan module `P`, with i_brand dropped from the group keys
            def __init__(self, P):
                self.P = P

            def __getattr__(self, k):
                return getattr(self.P, k)

            def Aggregate(self, x, keys, aggs):
                return self.P.Aggregate(x, keys=tuple(k for k in keys if k != "i_brand"), aggs=aggs)
        m.plan = lambda P: plan(WithoutBrand(P))
        m.reference = lambda frames, real=np.float64: reference(
            {k: (v.assign(i_brand="") if k == "item" else v) for k, v in frames.items()}, real).drop(columns=["i_brand"])
    return m


loader.module = module
drive(c, "diag-no-brand-")
