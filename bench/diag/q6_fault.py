#!/usr/bin/env python3
"""Reproduce the fault that keeps `tpch-sf1.q6` out of BENCHMARK.json
(PERF.md section 7, first row): on the chip, on some seeds, the fused q6
pipeline's float64 sum is off by what looks like dropped carry bits.

    python3 bench/diag/q6_fault.py <seed> <requests> [bisect]

Prints the program's answer less the float64 reference for each request;
with ``bisect`` and a wrong answer, halves the table (prices of the other
rows set to 0, so shapes stay) down to one row and sums that row alone at
three places. Seed 2200007920 reads -2049.125 on every request (my chip
runs, PR 25); ``DIAG_CPU=1`` runs the same on the CPU, where it reads
under 1.2e-7 (one ulp of the sum), also with ``bitutils.backend_has_f64`` patched to False.
"""
import sys, os, json
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))  # bench/
sys.path.insert(0, BENCH); sys.path.insert(1, os.path.dirname(BENCH))
import numpy as np
from benchlib import loader, device
if os.environ.get("DIAG_CPU"): device.require = lambda *a, **k: None
# the cell is not in BENCHMARK.json: put it together from its files
cell = {"name": "tpch-sf1.q6", "chips": 1, "config": loader.read_json("configs", "tpch-sf1.json"),
        "traffic": loader.read_json("traffic", "q6.json"), "end_to_end": [], "per_layer": []}
seed = int(sys.argv[1]); nreq = int(sys.argv[2]); bisect = len(sys.argv) > 3
s = loader.open_session(cell, seed, False, False, tag="diag-")
s.setup()
from spark_rapids_jni_tpu import plan as P
from spark_rapids_jni_tpu.columnar import Column, Table
q = s.queries[0][1]
li = s.host["lineitem"]
m = ((li["l_shipdate"] >= 731) & (li["l_shipdate"] < 1096) & (li["l_discount"] >= 0.05)
     & (li["l_discount"] <= 0.07) & (li["l_quantity"] < 24))
def want_of(price):
    return float((price[m] * li["l_discount"][m]).sum())
def got_of(cp):
    out = s.sched.submit(cp).result()
    return float(np.asarray(out.columns[0].data).view(np.float64)[0])
want = want_of(li["l_extendedprice"])
vals = [got_of(s.compiled[0]) for _ in range(nreq)]
print(json.dumps({"seed": seed, "selected": int(m.sum()), "want": want, "diffs": [v - want for v in vals]}), flush=True)
bad = abs(vals[0] - want) > 1e-9 * want or bool(os.environ.get("DIAG_CPU"))
if bisect and bad:
    base = s.tables["lineitem"]
    names = list(base.names)
    k = names.index("l_extendedprice")
    lo, hi = 0, len(m)
    idx = np.arange(len(m))
    while hi - lo > 1:
        mid = (lo + hi) // 2
        price = np.where((idx >= lo) & (idx < mid), li["l_extendedprice"], 0.0)
        cols = list(base.columns); cols[k] = Column.from_numpy(price)
        t = Table(cols, names)
        cp = P.compile_ir(q.plan(P), {"lineitem": t}, name="diag")
        d = got_of(cp) - want_of(price)
        print("range", lo, mid, "diff", d, flush=True)
        if abs(d) > 1e-6:
            hi = mid
        else:
            lo = mid
    r = lo
    print(json.dumps({"row": r, "of": len(m), "selected": bool(m[r]),
                      "values": {c: float(li[c][r]) for c in li}, "price_bits": hex(int(li["l_extendedprice"][r:r+1].view(np.uint64)[0])),
                      "contribution": float(li["l_extendedprice"][r] * li["l_discount"][r])}), flush=True)
    # the same row's values alone at position 0 of a table of the same size: value or place?
    for pos in (0, r, len(m) - 1):
        price = np.zeros(len(m)); price[pos] = li["l_extendedprice"][r]
        cols = list(base.columns); cols[k] = Column.from_numpy(price)
        cp = P.compile_ir(q.plan(P), {"lineitem": Table(cols, names)}, name="diag")
        print("price of the row alone at", pos, "selected there", bool(m[pos]), "diff", got_of(cp) - want_of(price), flush=True)
s.release()
