#!/usr/bin/env python3
"""ISSUE 35, the first chip call, before any timing of the cell: q1's two
forms — the Filter hands its mask on (``deferred``) or compacts
(``compacted``) — at full size on the cell's own data.

    python3 benchmarks/calls/pr35_forms.py [--rows N] [--seeds a,b,c] [--shares 0.5,0.25,...]
                                           [--reps 3] [--budget-s 2400]

(a) ``lanes``: for each seed, ``tpch-sf1.q1`` as the cell makes and plans it,
    compiled twice; one plan's Filter stage is told not to defer. Every
    column of the two answers (keys, four exact sums, three exact means, the
    count) is compared lane for lane: 0 wanted — the deferred form's
    ``_f64_sum_mean`` is a jitted caller of the 64-bit chains at a new shape
    (6,001,215 slots, a mask folded into the validity), which PERF.md 7 asks
    to be checked on the chip. A warm request of each form is timed beside it.
(b) ``sweep``: where the threshold belongs. The same plan with the predicate
    ``l_extendedprice <= c`` (a column that is independent of the flags, so
    the four groups stay and only the share kept moves), ``c`` the quantile
    that keeps each share; both forms forced (``_DEFER_MIN_KEEP`` 0 for the
    one, ``deferrable`` False for the other), one warm-up then ``--reps``
    timed requests each, ``block_until_ready`` on every column. Shares run in
    the order given until ``--budget-s`` is spent (every share of the
    compacted form is a new row count: ~100 programs to compile).
(c) how many programs a q1 process asks the backend for is not counted here
    (the second form of a process finds the first's programs compiled):
    ``pr35-call1.sh`` runs ``benchmarks/calls/pr32_run.py`` once in the
    parent's checkout and once in the change's and reads
    ``xla.backend_compiles + xla.cache_hits`` of each process.

One JSON line a reading, also under ``chiprun_out/pr35/``; exit code 1 if a
lane differs. The last line is the device. Off the chip (``JAX_PLATFORMS=cpu``,
a small ``--rows``) it rehearses the control flow, and says so; its times
are then no device times.
"""
import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "bench"))

import numpy as np  # noqa: E402

SEEDS = [3500000011, 3500104729, 3500209441]
SHARES = [0.5, 0.25, 0.75, 0.1, 0.986, 0.01]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=6001215)
    ap.add_argument("--seeds", default=",".join(map(str, SEEDS)))
    ap.add_argument("--shares", default=",".join(map(str, SHARES)))
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--budget-s", type=float, default=2400.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    t_start = time.perf_counter()

    import jax

    import spark_rapids_jni_tpu  # noqa: F401  (x64 and the compile cache before any array)
    from benchlib import loader
    from spark_rapids_jni_tpu import plan as P
    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.columnar import dtype as dt
    from spark_rapids_jni_tpu.plan import compiler
    from spark_rapids_jni_tpu.utils import metrics

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count()}
    out_path = args.out or os.path.join(ROOT, "chiprun_out", "pr35", "forms.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    out_f = open(out_path, "w")
    bad = 0

    def say(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        out_f.write(line + "\n")
        out_f.flush()

    reg = metrics.registry()

    def counters():
        return {k: reg.value(k) for k in ("xla.backend_compiles", "xla.cache_hits", "xla.cache_misses",
                                          "plan.filter.deferred", "plan.filter.compacted")}

    def moved(before):
        return {k.split(".", 1)[1]: round(v - before[k], 1) for k, v in counters().items()}

    def request(cp):
        t0 = time.perf_counter()
        out = cp()
        jax.block_until_ready([x for c in out.columns for x in (c.data, c.validity) if x is not None])
        return out, (time.perf_counter() - t0) * 1e3

    def lineitem(seed):
        config = loader.read_json("configs", "tpch-sf1.json")
        types = {"float64": dt.FLOAT64, "int8": dt.INT8, "timestamp_days": dt.TIMESTAMP_DAYS}
        spec = config["tables"]["lineitem"]["columns"]
        cols = loader.module("data", config["data"]).host_tables(config, seed, args.rows)["lineitem"]
        table = Table([Column.from_numpy(np.ascontiguousarray(a), types[spec[c]]) for c, a in cols.items()], list(cols))
        return cols, table

    def two_forms(plan, table, name):
        """(deferred, compacted): one plan compiled twice, the second's Filter told to compact."""
        forms = {}
        for form in ("deferred", "compacted"):
            cp = P.compile_ir(plan, {"lineitem": table}, name=f"{name}-{form}")
            [flt] = [s for s in cp.stages if type(s).__name__ == "_FilterExec"]
            assert flt.deferrable, "q1's Filter should be deferrable by its shape"
            flt.deferrable = form == "deferred"
            forms[form] = cp
        return forms

    def lanes_differ(a, b):
        n = 0
        for name in a.names:
            ca, cb = a.column(name), b.column(name)
            n += int(np.count_nonzero(np.asarray(ca.data) != np.asarray(cb.data)))
            n += int(np.count_nonzero(np.asarray(ca.valid_mask()) != np.asarray(cb.valid_mask())))
            n += len(ca) * ((ca.validity is None) != (cb.validity is None) or ca.dtype != cb.dtype)
        return n

    q1 = loader.module("queries", "tpch_q1")

    # -- (a) the two forms of q1, lane for lane -------------------------------------------------
    for seed in (int(s) for s in args.seeds.split(",") if s):
        cols, table = lineitem(seed)
        forms = two_forms(q1.plan(P), table, "q1")
        answers, ms = {}, {}
        for form, cp in forms.items():
            request(cp)  # compiles or loads what this form needs
            answers[form], ms[form] = request(cp)
        differ = lanes_differ(answers["deferred"], answers["compacted"])
        bad += bool(differ)
        kept = int((cols["l_shipdate"] <= q1.CUTOFF).sum())
        say({"what": "lanes", "seed": seed, "rows": args.rows, "kept": kept, "groups": answers["deferred"].num_rows,
             "columns": len(answers["deferred"].names), "lanes_differ": differ,
             "counts_add_up": int(np.asarray(answers["deferred"].column("count_order").data).sum()) == kept,
             "deferred_ms": round(ms["deferred"], 2), "compacted_ms": round(ms["compacted"], 2)})
        del forms, answers, table

    # -- (b) the sweep of the share kept ----------------------------------------------------------
    cols, table = lineitem(int(args.seeds.split(",")[0]))
    price = np.sort(cols["l_extendedprice"])
    real_threshold = compiler._DEFER_MIN_KEEP
    for share in (float(s) for s in args.shares.split(",") if s):
        if time.perf_counter() - t_start > args.budget_s:
            say({"what": "sweep", "share": share, "skipped": "budget spent"})
            continue
        cut = float(price[min(int(share * args.rows), args.rows - 1)])
        kept = int((cols["l_extendedprice"] <= cut).sum())
        plan = q1.plan(P)
        agg = plan.input
        flt = P.Filter(P.Scan("lineitem"), P.pcol("l_extendedprice") <= P.plit(cut))
        plan = P.Sort(P.Aggregate(P.Project(flt, agg.input.exprs), keys=agg.keys, aggs=agg.aggs), plan.keys)
        forms = two_forms(plan, table, f"sweep-{share}")
        rec = {"what": "sweep", "share": share, "kept": kept, "kept_share": round(kept / args.rows, 4)}
        answers = {}
        compiler._DEFER_MIN_KEEP = 0.0  # the deferred form whatever it keeps
        try:
            for form, cp in forms.items():
                before = counters()
                _, first_ms = request(cp)
                times = []
                for _ in range(args.reps):
                    answers[form], t = request(cp)
                    times.append(t)
                m = moved(before)
                assert m[f"filter.{form}"] == args.reps + 1, (form, m)
                rec[f"{form}_ms"] = round(statistics.median(times), 2)
                rec[f"{form}_min_ms"] = round(min(times), 2)
                rec[f"{form}_first_ms"] = round(first_ms, 1)
                rec[f"{form}_compiled"] = m["backend_compiles"]
        finally:
            compiler._DEFER_MIN_KEEP = real_threshold
        differ = lanes_differ(answers["deferred"], answers["compacted"])
        bad += bool(differ)
        rec["lanes_differ"] = differ
        rec["deferred_over_compacted"] = round(rec["deferred_ms"] / rec["compacted_ms"], 3)
        say(rec)
        del forms, answers

    say({"what": "done", "comparisons_that_differ": bad, "threshold": real_threshold,
         "rehearsal": device["platform"] != "tpu", "seconds": round(time.perf_counter() - t_start, 1)})
    say({"device": device})
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
