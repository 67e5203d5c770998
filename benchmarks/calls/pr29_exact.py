#!/usr/bin/env python3
"""ISSUE 29, the first chip call: the ONE program of a FLOAT64 sum or mean
(``ops/aggregate._f64_sum_mean``) against the un-jitted chain over
``ops/f64acc``, lane for lane, on the chip.

    python3 benchmarks/calls/pr29_exact.py [--rows N] [--seeds a,b,...] [--q6-seeds a,b]

For each seed: ``lineitem`` as the cell ``tpch-sf1.q1`` makes it
(``bench/data/tpch_lineitem.py``), q1's Filter and Project through the
plan path, then each of q1's seven float64 aggregates three ways: the
eager chain (what ``_agg_column`` ran before), the one program, and for
the sums the host's correctly rounded ``math.fsum`` over the very lanes
the device summed. For each of ``--q6-seeds`` (F1's two): q6's
``price x discount`` over all rows with q6's selection as the validity,
one group, as the fused q6 program sums it (ROADMAP F1). Where the one
program differs, and on the first seed anyway, the body cut in two
(accumulate | normalise, divide, round) is compared as well, and on the
q6 data also ``segment_sum_f64bits`` jitted alone over the two segments
the fused q6 program gives it (selected, the rest): F1's bisect.

Prints one JSON line a seed and aggregate: the number of lanes that
differ; exit code 1 if any lane of the one program differs. The last
line is the device. Off the chip (``JAX_PLATFORMS=cpu``) it is a
rehearsal of the control flow, and says so.
"""
import argparse
import functools
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "bench"))

import numpy as np  # noqa: E402

Q1_SEEDS = [2900000000 + 104729 * i for i in range(22)] + [2500142543, 2200007920]
Q6_SEEDS = [2500142543, 2200007920]
Q1_AGGS = [("qty", "sum"), ("price", "sum"), ("disc_price", "sum"), ("charge", "sum"),
           ("qty", "mean"), ("price", "mean"), ("disc", "mean")]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=6001215)
    ap.add_argument("--seeds", default=",".join(map(str, Q1_SEEDS)))
    ap.add_argument("--q6-seeds", default=",".join(map(str, Q6_SEEDS)))
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "pr29_exact.jsonl"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import spark_rapids_jni_tpu  # noqa: F401  (x64 and the compile cache before any array)
    from benchlib import loader
    from spark_rapids_jni_tpu import plan as P
    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.columnar import dtype as dt
    from spark_rapids_jni_tpu.ops import aggregate, f64acc
    from spark_rapids_jni_tpu.ops.sort import sorted_order

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count()}
    config = loader.read_json("configs", "tpch-sf1.json")
    data = loader.module("data", config["data"])
    q1, q6 = loader.module("queries", "tpch_q1"), loader.module("queries", "tpch_q6")
    types = {"float64": dt.FLOAT64, "int8": dt.INT8, "timestamp_days": dt.TIMESTAMP_DAYS}
    spec = config["tables"]["lineitem"]["columns"]
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    out_f = open(args.out, "w")
    bad = 0

    def say(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        out_f.write(line + "\n")
        out_f.flush()

    def lineitem(seed):
        host = data.host_tables(config, seed, args.rows)["lineitem"]
        return Table([Column.from_numpy(np.ascontiguousarray(a), types[spec[c]]) for c, a in host.items()],
                     list(host))

    def eager_chain(col, order, seg, num, how):
        valid = col.valid_mask()[order]
        bits = col.data[order]
        if how == "sum":
            out = f64acc.segment_sum_f64bits(bits, seg, num, valid=valid)
        else:
            out, _ = f64acc.segment_mean_f64bits(bits, seg, num, valid=valid)
        return np.asarray(out), np.asarray(jax.ops.segment_max(valid.astype(jnp.int32), seg, num) > 0)

    # the body cut in two, for the bisect: the limbs come back to the
    # host's hands between the accumulation and what follows it
    @functools.partial(jax.jit, static_argnames=("num", "how"))
    def cut_accumulate(data_, validity, order, seg, *, num, how):
        bits = data_[order]
        valid = jnp.ones(order.shape, bool) if validity is None else validity[order]
        gs = f64acc._accumulate(bits, valid, seg, num)
        cnt = jax.ops.segment_sum(valid.astype(jnp.int64), seg, num)
        return tuple(gs), cnt

    @functools.partial(jax.jit, static_argnames=("how",))
    def cut_finish(gs, cnt, *, how):
        limbs, emax, has_nan, has_pinf, has_ninf, _ = gs
        negative, mag = f64acc._carry_normalize(limbs)
        rem = None
        if how == "mean":
            mag, rem = f64acc._limb_divide(mag, cnt)
        return f64acc._round_to_bits(negative, mag, emax, has_nan, has_pinf, has_ninf, extra_sticky=rem)

    def differing(a, b):
        return int(np.count_nonzero(a[0] != b[0]) + np.count_nonzero(a[1] != b[1]))

    def compare(seed, what, col_name, how, col, order, seg, num, host_groups=None):
        nonlocal bad
        t0 = time.perf_counter()
        want = eager_chain(col, order, seg, num, how)
        t1 = time.perf_counter()
        got_col = aggregate._agg_column(col, order, seg, num, how)
        got = (np.asarray(got_col.data), np.asarray(got_col.validity))
        t2 = time.perf_counter()
        rec = {"seed": seed, "what": what, "col": col_name, "how": how, "groups": num, "rows": int(order.shape[0]),
               "lanes_differ": differing(want, got), "eager_s": round(t1 - t0, 3), "one_program_s": round(t2 - t1, 3)}
        if host_groups is not None:  # the exact sum, correctly rounded, of the very lanes the device summed
            exact = np.array([math.fsum(g) for g in host_groups]).view(np.uint64)
            rec["eager_vs_fsum"] = int(np.count_nonzero(want[0] != exact))
            rec["one_program_vs_fsum"] = int(np.count_nonzero(got[0] != exact))
        if rec["lanes_differ"]:
            bad += 1
            rec["eager_bits"] = [hex(int(x)) for x in want[0]]
            rec["one_program_bits"] = [hex(int(x)) for x in got[0]]
        if rec["lanes_differ"] or seed == first_seed:
            gs, cnt = cut_accumulate(col.data, col.validity, order, seg, num=num, how=how)
            two = np.asarray(cut_finish(gs, cnt, how=how))
            rec["cut_in_two_lanes_differ"] = int(np.count_nonzero(two != want[0]))
        say(rec)

    project = q1.plan(P).input.input  # q1 without its Sort and Aggregate: Filter, Project
    # q6's product over ALL rows, its selection the validity: the shapes are the same for every seed
    rev_plan = P.Project(P.Scan("lineitem"), (("rev", P.pcol("l_extendedprice") * P.pcol("l_discount")),))

    seeds = [int(s) for s in args.seeds.split(",") if s]
    first_seed = seeds[0] if seeds else None
    for seed in seeds:
        li = lineitem(seed)
        t = P.compile_ir(project, {"lineitem": li}, name="q1_inputs")()
        keys = t.select(["l_returnflag", "l_linestatus"])
        order = sorted_order(keys)
        seg, num = aggregate._segment_ids(keys, order)
        seg_h, order_h = np.asarray(seg), np.asarray(order)
        for col_name, how in Q1_AGGS:
            col = t.column(col_name)
            groups = None
            if how == "sum":
                lanes = np.asarray(col.data).view(np.float64)[order_h]
                groups = [lanes[seg_h == g] for g in range(num)]
            compare(seed, "q1", col_name, how, col, order, seg, num, groups)
        del t, li

    for seed in [int(s) for s in args.q6_seeds.split(",") if s]:
        li = lineitem(seed)
        rev = P.compile_ir(rev_plan, {"lineitem": li}, name="q6_rev")().column("rev")
        h = {c: np.asarray(li.column(c).data) for c in ("l_shipdate", "l_discount", "l_quantity")}
        disc, qty = h["l_discount"].view(np.float64), h["l_quantity"].view(np.float64)
        m = ((h["l_shipdate"] >= q6.D_1994_01_01) & (h["l_shipdate"] < q6.D_1995_01_01)
             & (disc >= 0.05) & (disc <= 0.07) & (qty < 24))
        col = Column(dt.FLOAT64, data=rev.data, validity=jnp.asarray(m))
        n = len(m)
        order, seg = jnp.arange(n, dtype=jnp.int32), jnp.zeros((n,), jnp.int32)
        lanes = np.asarray(rev.data).view(np.float64)[m]
        compare(seed, "q6", f"rev[{int(m.sum())} selected]", "sum", col, order, seg, 1, [lanes])
        compare(seed, "q6", "rev", "mean", col, order, seg, 1)
        # as pipeline._grouped_agg hands it over: the rows that are not selected in a second segment
        as_fused = jax.jit(lambda bits, gid: f64acc.segment_sum_f64bits(bits, gid, 2))(
            rev.data, jnp.asarray(np.where(m, 0, 1).astype(np.int32)))
        say({"seed": seed, "what": "q6", "col": "rev", "how": "sum, jitted alone over two segments",
             "lanes_differ_from_fsum": int(np.asarray(as_fused)[0] != np.float64(math.fsum(lanes)).view(np.uint64))})

    if device["platform"] != "tpu":
        say({"rehearsal": True, "note": "not a chip run: says nothing of the chip's bits"})
    say({"aggregates_with_differing_lanes": bad, "device": device})
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
