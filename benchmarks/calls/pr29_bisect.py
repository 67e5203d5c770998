#!/usr/bin/env python3
"""ISSUE 29 / ROADMAP F1, the bisect: where do the bits of a JITTED exact
float64 sum part from the un-jitted chain's on the chip?

    python3 benchmarks/calls/pr29_bisect.py [--rows N] [--seeds a,b,...] [--seconds S]

Chip call 1 (``pr29_exact.py``) found the whole body as one program wrong
on 3 of 168 q1 aggregates (one lane each, off by a nibble product: -5, +1
and -8 units of ONE nibble plane), and ``segment_sum_f64bits`` jitted
alone over q6's two segments wrong on both of F1's seeds. This script
runs several cuts of the body over the same inputs — q1's four summed
columns on the seeds that failed, first as q1 groups them and then under
random regroupings made on the device (the same shapes, so the same
programs; every regrouping is a new sample) — against the eager chain:

  one            the whole body, one program
  cut_in_two     accumulate | normalise, round (two programs)
  barrier_limbs  one program, ``optimization_barrier`` on the limb sums
  barrier_dot    one program, ``optimization_barrier`` behind the contraction
  barrier_operands  one program, ``optimization_barrier`` on the contraction's two operands
  pad_rows       one program, the rows padded with dead rows to a multiple of 1024
  probe          ``one`` that also returns the contraction's output (to place a fault)

and last, every variant and the eager chain itself after int32 ones were
left in the device's freed memory: a fault that reads the lanes that pad
a buffer to its tile then shows on every call.

One JSON line a sample that any variant gets wrong, a tally at the end.

What it found (call 1b, on the tree BEFORE the mend): ``one``, both
contraction barriers, ``pad_rows`` and ``probe`` wrong on the same 16 of
348 samples, the contraction's output right in all 16, ``cut_in_two`` and
``barrier_limbs`` wrong on none. The barrier has since gone to the end of
``f64acc._accumulate_mxu``, so every variant now runs behind it: run on
this tree the script is a regression check (every tally 0), not the bisect.
"""
import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "bench"))

import numpy as np  # noqa: E402

FAILED = [(2901780393, "charge"), (2901885122, "price"), (2902094580, "disc_price")]  # call 1
F1_SEEDS = [2500142543, 2200007920]
SUMMED = ["qty", "price", "disc_price", "charge"]
PAD = 1024


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=6001215)
    ap.add_argument("--seeds", default=",".join(str(s) for s, _ in FAILED))
    ap.add_argument("--q6-seeds", default=",".join(map(str, F1_SEEDS)))
    ap.add_argument("--seconds", type=float, default=540.0, help="of random regroupings, over all seeds")
    ap.add_argument("--poison-bytes", type=int, default=6 << 30)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "pr29_bisect.jsonl"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax

    import spark_rapids_jni_tpu  # noqa: F401
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

    def say(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        out_f.write(line + "\n")
        out_f.flush()

    class Lax:
        """``f64acc``'s view of ``jax.lax`` with a hook behind the contraction."""

        def __init__(self, after=lambda x: x, before=lambda x: x):
            self.after, self.before, self.seen = after, before, []

        def __getattr__(self, name):
            return getattr(lax, name)

        def dot_general(self, lhs, rhs, *a, **k):
            lhs, rhs = self.before((lhs, rhs))
            out = lax.dot_general(lhs, rhs, *a, **k)
            self.seen.append(out)
            return self.after(out)

    def with_lax(proxy, fn, *a, **k):
        f64acc.lax = proxy
        try:
            return fn(*a, **k)
        finally:
            f64acc.lax = lax

    def finish(gs):
        negative, mag = f64acc._carry_normalize(gs.limbs)
        return f64acc._round_to_bits(negative, mag, gs.emax, gs.has_nan, gs.has_pinf, gs.has_ninf)

    def gathered(data_, order):
        return data_[order], jnp.ones(order.shape, bool)

    @functools.partial(jax.jit, static_argnames=("num",))
    def cut_accumulate(data_, order, seg, *, num):
        return f64acc._accumulate(*gathered(data_, order), seg, num)

    cut_finish = jax.jit(finish)

    @functools.partial(jax.jit, static_argnames=("num",))
    def barrier_limbs(data_, order, seg, *, num):
        gs = f64acc._accumulate(*gathered(data_, order), seg, num)
        return finish(f64acc._GroupSum(*lax.optimization_barrier(tuple(gs))))

    @functools.partial(jax.jit, static_argnames=("num",))
    def barrier_dot(data_, order, seg, *, num):
        bits, valid = gathered(data_, order)
        return with_lax(Lax(lax.optimization_barrier), f64acc.segment_sum_f64bits, bits, seg, num, valid=valid)

    @functools.partial(jax.jit, static_argnames=("num",))
    def barrier_operands(data_, order, seg, *, num):
        bits, valid = gathered(data_, order)
        proxy = Lax(before=lax.optimization_barrier)
        return with_lax(proxy, f64acc.segment_sum_f64bits, bits, seg, num, valid=valid)

    @functools.partial(jax.jit, static_argnames=("num",))
    def pad_rows(data_, order, seg, *, num):
        bits, valid = gathered(data_, order)
        pad = -order.shape[0] % PAD
        bits = jnp.concatenate([bits, jnp.zeros((pad,), bits.dtype)])
        valid = jnp.concatenate([valid, jnp.zeros((pad,), bool)])
        seg = jnp.concatenate([seg, jnp.zeros((pad,), seg.dtype)])
        return f64acc.segment_sum_f64bits(bits, seg, num, valid=valid)

    @functools.partial(jax.jit, static_argnames=("num",))
    def probe(data_, order, seg, *, num):
        bits, valid = gathered(data_, order)
        proxy = Lax()
        out = with_lax(proxy, f64acc.segment_sum_f64bits, bits, seg, num, valid=valid)
        return out, proxy.seen[0]

    def eager(data_, order, seg, num):
        bits, valid = gathered(data_, order)
        proxy = Lax()
        out = with_lax(proxy, f64acc.segment_sum_f64bits, bits, seg, num, valid=valid)
        return np.asarray(out), np.asarray(proxy.seen[0])

    variants = {
        "one": lambda d, o, s, num: aggregate._f64_sum_mean(d, None, o, s, num=num, how="sum")[0],
        "cut_in_two": lambda d, o, s, num: cut_finish(cut_accumulate(d, o, s, num=num)),
        "barrier_limbs": lambda d, o, s, num: barrier_limbs(d, o, s, num=num),
        "barrier_dot": lambda d, o, s, num: barrier_dot(d, o, s, num=num),
        "barrier_operands": lambda d, o, s, num: barrier_operands(d, o, s, num=num),
        "pad_rows": lambda d, o, s, num: pad_rows(d, o, s, num=num),
    }
    tally = {name: 0 for name in list(variants) + ["probe"]}
    samples = 0

    def sample(seed, col_name, regroup, col, order, seg, num):
        nonlocal samples
        samples += 1
        want, want_acc = eager(col.data, order, seg, num)
        wrong = {}
        for name, fn in variants.items():
            got = np.asarray(fn(col.data, order, seg, num))
            if not np.array_equal(got, want):
                tally[name] += 1
                wrong[name] = [[int(g), float(got[g:g + 1].view(np.float64)[0] - want[g:g + 1].view(np.float64)[0])]
                               for g in np.nonzero(got != want)[0]]
        got, got_acc = probe(col.data, order, seg, num=num)
        got, got_acc = np.asarray(got), np.asarray(got_acc)
        if not np.array_equal(got, want):
            tally["probe"] += 1
            wrong["probe"] = [int(g) for g in np.nonzero(got != want)[0]]
        if wrong:
            where = np.argwhere(got_acc != want_acc)
            say({"seed": seed, "col": col_name, "regrouping": regroup, "wrong": wrong,
                 "probe_contraction_differs_at_group_plane_by": [
                     [int(g), int(p), int(got_acc[g, p]) - int(want_acc[g, p])] for g, p in where[:12]]})

    def lineitem(seed):
        host = data.host_tables(config, seed, args.rows)["lineitem"]
        return Table([Column.from_numpy(np.ascontiguousarray(a), types[spec[c]]) for c, a in host.items()],
                     list(host))

    project = q1.plan(P).input.input
    seeds = [int(s) for s in args.seeds.split(",") if s]
    tables = []
    for seed in seeds:
        t = P.compile_ir(project, {"lineitem": lineitem(seed)}, name="q1_inputs")()
        keys = t.select(["l_returnflag", "l_linestatus"])
        order = sorted_order(keys)
        seg, num = aggregate._segment_ids(keys, order)
        tables.append((seed, t, order, seg, num))
        for col_name in SUMMED:  # as q1 groups them: call 1's three faults are among these
            sample(seed, col_name, "q1", t.column(col_name), order, seg, num)
    say({"after": "q1's own groupings", "samples": samples, "wrong": dict(tally)})

    t0, r = time.perf_counter(), 0
    while tables and time.perf_counter() - t0 < args.seconds:
        r += 1
        for seed, t, order, seg, num in tables:
            shuffled = jax.random.permutation(jax.random.PRNGKey(r), order)  # rows dealt anew to the groups' sizes
            for col_name in SUMMED:
                sample(seed, col_name, r, t.column(col_name), shuffled, seg, num)
    say({"after": f"{r} random regroupings of each seed and column", "samples": samples, "wrong": dict(tally)})

    # Does the fault read what lies in the lanes that pad a buffer to its tile? Leave int32 ones in the freed
    # memory before each call (as s32 a group's number, as s8 a nonzero sign and nibble in every fourth lane)
    # and see which variants, the eager chain among them, then read them.
    def poison():
        junk = jnp.ones((args.poison_bytes // 4,), jnp.int32)
        junk.block_until_ready()
        del junk

    poisoned = dict(variants, eager=lambda d, o, s, num: eager(d, o, s, num)[0])
    tally_p, n_p = {name: 0 for name in poisoned}, 0
    for seed, t, order, seg, num in tables[:1]:
        for r in range(1, 4):
            shuffled = jax.random.permutation(jax.random.PRNGKey(1000 + r), order)
            for col_name in SUMMED:
                col, n_p = t.column(col_name), n_p + 1
                want = eager(col.data, shuffled, seg, num)[0]
                for name, fn in poisoned.items():
                    poison()
                    got = np.asarray(fn(col.data, shuffled, seg, num))
                    if not np.array_equal(got, want):
                        tally_p[name] += 1
                        if tally_p[name] <= 2:
                            say({"poisoned": name, "seed": seed, "col": col_name, "less_eager_unpoisoned": (
                                got.view(np.float64) - want.view(np.float64)).tolist()})
    say({"after": "int32 ones left in freed memory before each call", "samples": n_p, "wrong": tally_p})
    del tables

    # F1 itself: q6's product over all rows, the rows not selected in a second segment, as pipeline._grouped_agg
    # hands them to segment_sum_f64bits, jitted alone; then the same with the rows padded
    rev_plan = P.Project(P.Scan("lineitem"), (("rev", P.pcol("l_extendedprice") * P.pcol("l_discount")),))
    alone = jax.jit(lambda bits, gid: f64acc.segment_sum_f64bits(bits, gid, 2))

    @jax.jit
    def alone_padded(bits, gid):
        pad = -bits.shape[0] % PAD
        bits = jnp.concatenate([bits, jnp.zeros((pad,), bits.dtype)])
        gid = jnp.concatenate([gid, jnp.ones((pad,), gid.dtype)])
        return f64acc.segment_sum_f64bits(bits, gid, 2)

    for seed in [int(s) for s in args.q6_seeds.split(",") if s]:
        li = lineitem(seed)
        rev = P.compile_ir(rev_plan, {"lineitem": li}, name="q6_rev")().column("rev")
        h = {c: np.asarray(li.column(c).data) for c in ("l_shipdate", "l_discount", "l_quantity")}
        disc, qty = h["l_discount"].view(np.float64), h["l_quantity"].view(np.float64)
        m = ((h["l_shipdate"] >= q6.D_1994_01_01) & (h["l_shipdate"] < q6.D_1995_01_01)
             & (disc >= 0.05) & (disc <= 0.07) & (qty < 24))
        gid = jnp.asarray(np.where(m, 0, 1).astype(np.int32))
        want = np.asarray(f64acc.segment_sum_f64bits(rev.data, gid, 2)).view(np.float64)
        rec = {"seed": seed, "what": "q6 as the fused program sums it, two segments", "eager": want.tolist()}
        for name, fn in (("jitted_alone", alone), ("jitted_alone_padded", alone_padded)):
            got = np.asarray(fn(rev.data, gid)).view(np.float64)
            rec[name + "_less_eager"] = (got - want).tolist()
        say(rec)

    say({"samples": samples, "wrong": tally, "device": device,
         "rehearsal": device["platform"] != "tpu"})
    return 0


if __name__ == "__main__":
    sys.exit(main())
