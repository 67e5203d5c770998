#!/usr/bin/env python3
"""ISSUE 37, the first chip call, before any timing of the cell: q1's group-by
in its two forms — the groups numbered from the keys' codes (``dense``) or by
a sort of the rows (``sorted``) — at full size on the cell's own data.

    python3 benchmarks/calls/pr37_dense.py [--rows N] [--seeds a,b,c] [--domains 1,4,6,16,32,64]
                                           [--reps 3] [--budget-s 2400] [--parts lanes,sweep,probe]

(a) ``lanes``: for each seed, ``tpch-sf1.q1`` as the cell makes and plans it,
    run in both forms (the sort path is the same compiled plan with
    ``ops/aggregate._DENSE_MAX_SLOTS`` at 0, which shuts the gate behind the
    probe). Every column of the two answers (keys, four exact sums, three
    exact means, the count) is compared lane for lane: 0 wanted —
    ``_f64_sum_mean`` without its gathers is a new compiled program over the
    64-bit shift-and-add chains (ROADMAP F1). A warm request of each form is
    timed beside it.
(b) ``programs``: the first seed again under the profiler, ``--reps``
    requests a form: the device's milliseconds a request by program and by
    operation, both forms.
(c) ``sweep``: where ``_DENSE_MAX_SLOTS`` belongs. ``groupby_aggregate`` over
    the same 6,001,215 slots, q1's mask and three of its aggregates (a sum
    and a mean of ``l_quantity``, a sum of ``l_extendedprice``, the count),
    grouped by ONE int16 key that takes ``D`` values evenly; both forms forced
    (the bound at ``max(domains)`` for the one, 0 for the other), one warm-up
    then ``--reps`` timed calls each, lanes compared. Domains run in the
    order given until ``--budget-s`` is spent (every domain is four new
    programs a form).
(d) ``probe``: what the probe costs a group-by it refuses. One INT32 key that
    takes 1 << 15 values (far past the bound: GROUP BY an id), q1's mask, a
    sum and the count, at ``--rows`` and at 65,536 rows (a shape the host
    bounds): the sort path as the system runs it (``probed``: the dtype gate
    passes, the probe comes back with the domain, the bound refuses it)
    against the parent's path (``unprobed``: ``_dense_key_dtypes`` patched to
    False, so nothing is launched before the sort); one warm-up then
    ``--reps`` timed calls each, alternating, lanes compared. Beside them the
    probe alone on an idle queue: the program and its round trip.

One JSON line a reading, also under ``chiprun_out/pr37/``; exit code 1 if a
lane differs. The last line is the device. Off the chip (``JAX_PLATFORMS=cpu``,
a small ``--rows``) it rehearses the control flow, and says so; its times
are then no device times.
"""
import argparse
import collections
import glob
import json
import os
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "bench"))

import numpy as np  # noqa: E402

SEEDS = [3700000011, 3700104729, 3700209441]
DOMAINS = [6, 4, 16, 1, 32, 64]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=6001215)
    ap.add_argument("--seeds", default=",".join(map(str, SEEDS)))
    ap.add_argument("--domains", default=",".join(map(str, DOMAINS)))
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--budget-s", type=float, default=2400.0)
    ap.add_argument("--parts", default="lanes,sweep,probe")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    t_start = time.perf_counter()

    import jax
    import jax.numpy as jnp

    import spark_rapids_jni_tpu  # noqa: F401  (x64 and the compile cache before any array)
    from benchlib import loader, tracered
    from spark_rapids_jni_tpu import plan as P
    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.columnar import dtype as dt
    from spark_rapids_jni_tpu.ops import aggregate
    from spark_rapids_jni_tpu.utils import metrics

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count()}
    out_path = args.out or os.path.join(ROOT, "chiprun_out", "pr37", "dense.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    out_f = open(out_path, "w")
    bad = 0
    real_bound = aggregate._DENSE_MAX_SLOTS
    domains = [int(d) for d in args.domains.split(",") if d]
    parts = set(args.parts.split(","))
    seeds = [int(s) for s in args.seeds.split(",") if s]

    def say(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        out_f.write(line + "\n")
        out_f.flush()

    reg = metrics.registry()

    def counters():
        return {k: reg.value(k) for k in ("xla.backend_compiles", "xla.cache_hits", "groupby.dense", "groupby.sorted")}

    def moved(before):
        return {k.split(".", 1)[1]: round(v - before[k], 1) for k, v in counters().items()}

    def request(fn, bound):
        """One call of ``fn`` with the dense form's bound at ``bound``, every lane waited for."""
        aggregate._DENSE_MAX_SLOTS = bound
        try:
            t0 = time.perf_counter()
            out = fn()
            jax.block_until_ready([x for c in out.columns for x in (c.data, c.validity) if x is not None])
            return out, (time.perf_counter() - t0) * 1e3
        finally:
            aggregate._DENSE_MAX_SLOTS = real_bound

    def lineitem(seed):
        config = loader.read_json("configs", "tpch-sf1.json")
        types = {"float64": dt.FLOAT64, "int8": dt.INT8, "timestamp_days": dt.TIMESTAMP_DAYS}
        spec = config["tables"]["lineitem"]["columns"]
        cols = loader.module("data", config["data"]).host_tables(config, seed, args.rows)["lineitem"]
        table = Table([Column.from_numpy(np.ascontiguousarray(a), types[spec[c]]) for c, a in cols.items()], list(cols))
        return cols, table

    def lanes_differ(a, b):
        n = 0
        for name in a.names:
            ca, cb = a.column(name), b.column(name)
            n += int(np.count_nonzero(np.asarray(ca.data) != np.asarray(cb.data)))
            n += int(np.count_nonzero(np.asarray(ca.valid_mask()) != np.asarray(cb.valid_mask())))
            n += len(ca) * ((ca.validity is None) != (cb.validity is None) or ca.dtype != cb.dtype)
        return n + abs(a.num_rows - b.num_rows)

    def device_ms(fn, bound, reps):
        """{program: ms a call}, {operation: ms a call} of ``reps`` calls under the profiler (first device)."""
        with tempfile.TemporaryDirectory() as tmp:
            with jax.profiler.trace(tmp):
                for _ in range(reps):
                    request(fn, bound)
            paths = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))
            if not paths:
                return {}, {}
            data = jax.profiler.ProfileData.from_file(max(paths, key=os.path.getmtime))
            by = {"XLA Modules": collections.Counter(), "XLA Ops": collections.Counter()}
            for plane in data.planes:
                if plane.name.startswith("/device:") and "TPU" in plane.name and "SparseCore" not in plane.name:
                    for line in plane.lines:
                        if line.name in by:
                            for e in line.events:
                                by[line.name][tracered.short(e.name)] += e.duration_ns / 1e6 / reps
                    break
        top = lambda c, n: {k: round(v, 3) for k, v in c.most_common(n)}  # noqa: E731
        return top(by["XLA Modules"], 14), top(by["XLA Ops"], 14)

    q1 = loader.module("queries", "tpch_q1")
    forms = {"dense": real_bound, "sorted": 0}

    # -- (a) the two forms of q1, lane for lane; (b) the device's time by program, first seed ----------
    for i, seed in enumerate(seeds if "lanes" in parts else []):
        cols, table = lineitem(seed)
        cp = P.compile_ir(q1.plan(P), {"lineitem": table}, name="q1")
        answers, ms, took = {}, {}, {}
        for form, bound in forms.items():
            before = counters()
            request(cp, bound)  # compiles or loads what this form needs
            answers[form], ms[form] = request(cp, bound)
            took[form] = moved(before)
        differ = lanes_differ(answers["dense"], answers["sorted"])
        bad += bool(differ)
        kept = int((cols["l_shipdate"] <= q1.CUTOFF).sum())
        say({"what": "lanes", "seed": seed, "rows": args.rows, "kept": kept, "groups": answers["dense"].num_rows,
             "columns": len(answers["dense"].names), "lanes_differ": differ,
             "counts_add_up": int(np.asarray(answers["dense"].column("count_order").data).sum()) == kept,
             "took_its_form": took["dense"]["dense"] == 2 and took["sorted"]["sorted"] == 2,
             "dense_ms": round(ms["dense"], 2), "sorted_ms": round(ms["sorted"], 2),
             "dense_compiled": took["dense"]["backend_compiles"], "sorted_compiled": took["sorted"]["backend_compiles"]})
        if i == 0:
            for form, bound in forms.items():
                programs, ops = device_ms(cp, bound, args.reps)
                say({"what": "programs", "form": form, "seed": seed, "ms_a_request_by_program": programs,
                     "ms_a_request_by_operation": ops})
        del cp, answers, table

    # -- (c) the sweep of the domain -----------------------------------------------------------------
    cols, table = lineitem(seeds[0])
    present = jnp.asarray(cols["l_shipdate"] <= q1.CUTOFF)
    values = table.select(["l_quantity", "l_extendedprice"])
    aggs = [("l_quantity", "sum"), ("l_quantity", "mean"), ("l_extendedprice", "sum"), ("l_quantity", "count_all")]
    forms = {"dense": max(domains + [real_bound]), "sorted": 0}
    for domain in domains if "sweep" in parts else []:
        if time.perf_counter() - t_start > args.budget_s:
            say({"what": "sweep", "domain": domain, "skipped": "budget spent"})
            continue
        key = (np.arange(args.rows, dtype=np.int64) * 2654435761 % (1 << 31) % domain).astype(np.int16)
        keys = Table([Column.from_numpy(key, dt.INT16)], ["k"])
        call = lambda: aggregate.groupby_aggregate(keys, values, aggs, present=present)  # noqa: E731
        rec = {"what": "sweep", "domain": domain, "bound": real_bound}
        answers = {}
        for form, bound in forms.items():
            before = counters()
            _, first_ms = request(call, bound)
            times = []
            for _ in range(args.reps):
                answers[form], t = request(call, bound)
                times.append(t)
            m = moved(before)
            assert m[form] == args.reps + 1, (form, m)
            rec[f"{form}_ms"] = round(statistics.median(times), 2)
            rec[f"{form}_min_ms"] = round(min(times), 2)
            rec[f"{form}_first_ms"] = round(first_ms, 1)
            rec[f"{form}_compiled"] = m["backend_compiles"]
        differ = lanes_differ(answers["dense"], answers["sorted"])
        bad += bool(differ)
        rec["groups"] = answers["dense"].num_rows
        rec["lanes_differ"] = differ
        rec["dense_over_sorted"] = round(rec["dense_ms"] / rec["sorted_ms"], 3)
        say(rec)
        del answers, keys

    # -- (d) the probe's cost where it is refused ------------------------------------------------------
    real_gate = aggregate._dense_key_dtypes
    aggs = [("l_quantity", "sum"), ("l_quantity", "count_all")]
    for rows in [args.rows, 1 << 16] if "probe" in parts else []:
        rows = min(rows, args.rows)
        key = (np.arange(rows, dtype=np.int64) * 2654435761 % (1 << 31) % (1 << 15)).astype(np.int32)
        keys = Table([Column.from_numpy(key, dt.INT32)], ["k"])
        vals = Table([Column.from_numpy(np.ascontiguousarray(cols["l_quantity"][:rows]), dt.FLOAT64)], ["l_quantity"])
        mask = present[:rows]
        call = lambda: aggregate.groupby_aggregate(keys, vals, aggs, present=mask)  # noqa: E731
        gates = {"probed": real_gate, "unprobed": lambda _keys: False}
        rec, answers, times = {"what": "probe", "rows": rows, "domain": 1 << 15, "bound": real_bound}, {}, {}
        try:
            for form, gate in gates.items():  # the warm-up: compiles or loads what the form needs
                aggregate._dense_key_dtypes = gate
                before = counters()
                _, first_ms = request(call, real_bound)
                rec[f"{form}_first_ms"], rec[f"{form}_compiled"] = round(first_ms, 1), moved(before)["backend_compiles"]
            for _ in range(args.reps):
                for form, gate in gates.items():
                    aggregate._dense_key_dtypes = gate
                    before = counters()
                    answers[form], t = request(call, real_bound)
                    assert moved(before)["sorted"] == 1, form
                    times.setdefault(form, []).append(t)
        finally:
            aggregate._dense_key_dtypes = real_gate
        for form, ts in times.items():
            rec[f"{form}_ms"], rec[f"{form}_min_ms"] = round(statistics.median(ts), 2), round(min(ts), 2)
        alone = []
        for _ in range(args.reps + 1):
            t0 = time.perf_counter()
            jax.device_get(aggregate._key_domain((keys.columns[0].data,), (None,), mask))
            alone.append((time.perf_counter() - t0) * 1e3)
        rec["probe_alone_ms"] = round(statistics.median(alone[1:]), 3)
        rec["groups"] = answers["probed"].num_rows
        rec["lanes_differ"] = lanes_differ(answers["probed"], answers["unprobed"])
        bad += bool(rec["lanes_differ"])
        rec["probed_less_unprobed_ms"] = round(rec["probed_ms"] - rec["unprobed_ms"], 2)
        say(rec)
        del answers, keys, vals

    say({"what": "done", "comparisons_that_differ": bad, "bound": real_bound,
         "rehearsal": device["platform"] != "tpu", "seconds": round(time.perf_counter() - t_start, 1)})
    say({"device": device})
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
