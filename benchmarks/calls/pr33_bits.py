#!/usr/bin/env python3
"""ISSUE 33, the first chip call, before any timing: a plan stage's ONE
jitted program (``plan/compiler.py::_StageProgram``, ``_normalize_agg_columns``)
against the eager evaluator, lane for lane, at full size on the benchmark's
own data.

    python3 benchmarks/calls/pr33_bits.py [--chips 1|4] [--seeds a,b] [--rows N] [--ws-rows N]

``--chips 1``: for each seed, q1 as the cell ``tpch-sf1.q1`` makes and plans
it: the Filter's mask and the Project's ``disc_price`` and ``charge`` (two
double-float products over 5.9 M rows; five ``dd_from_f64bits``, two
``dd_to_f64bits``) from the stage's program against the same lowered trees
evaluated eagerly. Then q95's ``wh_lo`` / ``wh_hi`` (the per-order min and
max warehouse of ``tpcds-sf10-web``'s ``web_sales``, reckoned on the host)
through the aggregate stage's float64 normalisation and the
``wh_lo <> wh_hi`` Filter, jitted against eager and against numpy.

``--chips 4``: ``ws_wh`` of q95 as the cell ``tpcds-sf10-web.q95-x4`` plans
it, on the mesh: scan, exchange, shard-local group-by, then the stage's
normalisation of ``wh_lo`` / ``wh_hi`` and the mesh Filter's ``present``,
each from its one program over the row-sharded arrays against (a) the
eager evaluator over the same lanes on the first chip and (b) numpy on the
host; and whether the outputs lie as the inputs do.

Prints one JSON line a comparison with the count of differing lanes (0
wanted); exit code 1 if any differs. The last line is the device. Off the
chip (``JAX_PLATFORMS=cpu``, small ``--rows``) it is a rehearsal of the
control flow, and says so.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "bench"))

import numpy as np  # noqa: E402

SEEDS = [3300000007, 3300104729]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seeds", default=",".join(map(str, SEEDS)))
    ap.add_argument("--rows", type=int, default=6001215, help="lineitem rows")
    ap.add_argument("--ws-rows", type=int, default=7197566, help="web_sales rows")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.chips == 4 and "jax" not in sys.modules and os.environ.get("JAX_PLATFORMS") == "cpu":
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4").strip()

    import jax
    import jax.numpy as jnp

    import spark_rapids_jni_tpu  # noqa: F401  (x64 and the compile cache before any array)
    from benchlib import loader
    from spark_rapids_jni_tpu import plan as P
    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.columnar import dtype as dt
    from spark_rapids_jni_tpu.plan import compiler
    from spark_rapids_jni_tpu.utils import metrics

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count()}
    out_path = args.out or os.path.join(ROOT, "chiprun_out", f"pr33_bits-{args.chips}chip.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    out_f = open(out_path, "w")
    bad = 0

    def say(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        out_f.write(line + "\n")
        out_f.flush()

    def host(x):
        return None if x is None else np.asarray(x)

    def lanes_differ(got: Column, want: Column) -> int:
        """Lanes of ``data`` that differ, plus validity lanes that differ
        (a validity on one side only counts every lane)."""
        n = int(np.count_nonzero(host(got.data) != host(want.data)))
        gv, wv = host(got.validity), host(want.validity)
        if (gv is None) != (wv is None):
            return n + len(got)
        return n if gv is None else n + int(np.count_nonzero(gv != wv))

    def compare(seed, what, got, want, **more):
        nonlocal bad
        differ = lanes_differ(got, want) if isinstance(got, Column) else int(np.count_nonzero(host(got) != host(want)))
        bad += bool(differ)
        say({"seed": seed, "what": what, "lanes": int(host(got.data if isinstance(got, Column) else got).shape[0]),
             "lanes_differ": differ, **more})

    def stages_of(cp, kind):
        return [s for s in cp.stages if type(s).__name__ == kind]

    def timed(fn):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(jax.tree_util.tree_leaves(out))
        return out, round(time.perf_counter() - t0, 3)

    def compiles():
        return metrics.registry().value("xla.backend_compiles")

    # -- q1: the Filter's mask, disc_price and charge ---------------------------------------------
    def q1(seed):
        config = loader.read_json("configs", "tpch-sf1.json")
        data, q = loader.module("data", config["data"]), loader.module("queries", "tpch_q1")
        types = {"float64": dt.FLOAT64, "int8": dt.INT8, "timestamp_days": dt.TIMESTAMP_DAYS}
        spec = config["tables"]["lineitem"]["columns"]
        cols = data.host_tables(config, seed, args.rows)["lineitem"]
        lineitem = Table([Column.from_numpy(np.ascontiguousarray(a), types[spec[c]]) for c, a in cols.items()], list(cols))
        cp = P.compile_ir(q.plan(P), {"lineitem": lineitem}, name="q1")
        ctx = compiler._RunContext(cp._tables)
        [flt] = stages_of(cp, "_FilterExec")
        t_in = flt.inputs[0].run(ctx)
        [(low, want)] = flt.program.trees
        c0 = compiles()
        keep, s_jit = timed(lambda: flt.program(t_in, t_in.num_rows))
        keep_eager, s_eager = timed(lambda: compiler._keep(compiler._materialize(low, t_in, want, t_in.num_rows)))
        compare(seed, "q1.filter.keep", keep, keep_eager, jit_s=s_jit, eager_s=s_eager, kept=int(host(keep).sum()))
        [proj] = [s for s in stages_of(cp, "_ProjectExec") if s.program.trees]
        t = proj.inputs[0].run(ctx)
        got, s_jit = timed(lambda: proj.program(t, t.num_rows))
        names = [name for name, _ in proj.exprs]
        for slot, name in zip(proj.program.slots, names):
            if slot[0] == "col":
                assert got[names.index(name)].data is t.column(slot[1]).data, name  # handed on, no launch
                continue
            low, want = proj.program.trees[slot[1]]
            eager, s_eager = timed(lambda: compiler._materialize(low, t, want, t.num_rows))
            compare(seed, f"q1.project.{name}", got[names.index(name)], eager, rows=t.num_rows,
                    jit_s=s_jit, eager_s=s_eager, exprs=len(proj.program.trees))
        say({"seed": seed, "what": "q1.compiles", "backend_compiles": compiles() - c0})

    # -- q95: wh_lo / wh_hi and the wh_lo <> wh_hi Filter ------------------------------------------
    def web(seed):
        config = loader.read_json("configs", "tpcds-sf10-web.json")
        return config, loader.module("data", config["data"]).host_tables(config, seed, args.ws_rows)

    def ws_wh_host(ws):
        """(order numbers, min, max, any warehouse) per order, on the host."""
        order = ws["ws_order_number"]
        wh, valid = ws["ws_warehouse_sk"]
        orders, inv = np.unique(order, return_inverse=True)
        lo = np.full(orders.size, np.iinfo(np.int32).max, np.int32)
        hi = np.full(orders.size, np.iinfo(np.int32).min, np.int32)
        np.minimum.at(lo, inv[valid], wh[valid])
        np.maximum.at(hi, inv[valid], wh[valid])
        seen = np.zeros(orders.size, bool)
        seen[inv[valid]] = True
        return orders, np.where(seen, lo, 0), np.where(seen, hi, 0), seen

    def check_ws_wh(seed, where, raw, jit_cols, keep, eager_of, present):
        """``raw``: the group-by's (wh_lo, wh_hi) columns; ``jit_cols``: what
        the stage's one program made of them; ``keep``: the Filter program's
        rows that pass. Against the eager evaluator (``eager_of`` brings a
        column where it evaluates) and against numpy."""
        eager_cols = []
        for name, r, j in zip(("wh_lo", "wh_hi"), raw, jit_cols):
            e, s_eager = timed(lambda: compiler._to_float64(eager_of(r)))
            eager_cols.append(e)
            compare(seed, f"{where}.{name}", j, e, eager_s=s_eager)
            exact = host(r.data).astype(np.int64).astype(np.float64).view(np.uint64)
            compare(seed, f"{where}.{name}.numpy", host(j.data), exact)
        t = Table(eager_cols, ["wh_lo", "wh_hi"])
        pred = (P.pcol("wh_lo") != P.pcol("wh_hi")).lower()
        here = None if present is None else eager_of(Column(dt.BOOL8, data=present)).data
        want, s_eager = timed(lambda: compiler._keep(compiler._materialize(pred, t, dt.BOOL8, t.num_rows), here))
        compare(seed, f"{where}.filter.keep", keep, want, eager_s=s_eager, kept=int(host(keep).sum()))
        lo, hi = (host(c.data).astype(np.int64) for c in raw)
        valid = np.ones(lo.shape, bool)
        for c in raw:
            if c.validity is not None:
                valid &= host(c.validity)
        exact = (lo != hi) & valid & (True if present is None else host(present))
        compare(seed, f"{where}.filter.keep.numpy", keep, exact)

    def q95_one_chip(seed):
        _, tables = web(seed)
        orders, lo, hi, seen = ws_wh_host(tables["web_sales"])
        ws_wh = Table([Column.from_numpy(orders, dt.INT64), Column.from_numpy(lo, dt.INT32, validity=seen),
                       Column.from_numpy(hi, dt.INT32, validity=seen)], ["ws_order_number", "wh_lo", "wh_hi"])
        raw = [ws_wh.column("wh_lo"), ws_wh.column("wh_hi")]
        c0 = compiles()
        jit_cols, s_jit = timed(lambda: compiler._normalize_agg_columns(raw, ["min", "max"]))
        normal = Table([ws_wh.column("ws_order_number")] + jit_cols, ws_wh.names)
        cp = P.compile_ir(P.Filter(P.Scan("ws_wh"), P.pcol("wh_lo") != P.pcol("wh_hi")), {"ws_wh": normal}, name="ws_wh")
        [flt] = stages_of(cp, "_FilterExec")
        keep, s_keep = timed(lambda: flt.program(normal, normal.num_rows))
        say({"seed": seed, "what": "q95.one_chip.programs", "orders": int(orders.size), "normalise_s": s_jit,
             "filter_s": s_keep, "backend_compiles": compiles() - c0})
        check_ws_wh(seed, "q95.one_chip", raw, jit_cols, keep, lambda c: c, None)

    def q95_mesh(seed):
        from spark_rapids_jni_tpu.parallel.mesh import make_mesh
        from spark_rapids_jni_tpu.parallel.table_ops import groupby_sharded

        config, tables = web(seed)
        ws = tables["web_sales"]
        wh, valid = ws["ws_warehouse_sk"]
        fact = Table([Column.from_numpy(np.ascontiguousarray(ws["ws_order_number"]), dt.INT64),
                      Column.from_numpy(np.ascontiguousarray(wh), dt.INT32, validity=valid)],
                     ["ws_order_number", "ws_warehouse_sk"])
        binding = P.MeshBinding(make_mesh({"data": 4}, devices=jax.devices()[:4]), sharded=("web_sales",), axis="data")
        agg = P.Aggregate(P.Scan("web_sales", columns=("ws_order_number", "ws_warehouse_sk")), keys=("ws_order_number",),
                          aggs=(P.AggSpec("ws_warehouse_sk", "min", "wh_lo"), P.AggSpec("ws_warehouse_sk", "max", "wh_hi")))
        plan = P.Project(P.Filter(agg, P.pcol("wh_lo") != P.pcol("wh_hi")), (("ws_order_number", P.pcol("ws_order_number")),))
        cp = P.compile_ir(P.insert_exchanges(plan, 4, sharded=("web_sales",)), {"web_sales": fact}, name="ws_wh", mesh=binding)
        ctx = compiler._RunContext(cp._tables)
        [agg_stage], [flt] = stages_of(cp, "_MeshAggExec"), stages_of(cp, "_MeshFilterExec")
        c0 = compiles()
        st_in = agg_stage.inputs[0].run(ctx)
        grouped, s_group = timed(lambda: groupby_sharded(st_in, agg_stage.keys, [(a.source, a.how, a.name) for a in agg_stage.aggs]).table)
        raw = list(grouped.columns[1:])
        st, s_stage = timed(lambda: agg_stage.run(ctx).table)  # the stage itself: group-by, then the one program
        jit_cols = [st.column("wh_lo"), st.column("wh_hi")]
        out, s_keep = timed(lambda: flt.run(ctx).present)
        present = agg_stage.run(ctx).present
        first = jax.devices()[0]

        def on_first_chip(c):
            return Column(c.dtype, data=jax.device_put(c.data, first),
                          validity=None if c.validity is None else jax.device_put(c.validity, first))

        laid = {"wh_lo": str(jit_cols[0].data.sharding.spec), "wh_hi": str(jit_cols[1].data.sharding.spec),
                "present": str(out.sharding.spec), "inputs": str(raw[0].data.sharding.spec),
                "devices": len(out.sharding.device_set)}
        same = (jit_cols[0].data.sharding.is_equivalent_to(raw[0].data.sharding, 1)
                and jit_cols[1].data.sharding.is_equivalent_to(raw[1].data.sharding, 1)
                and out.sharding.is_equivalent_to(present.sharding, 1))
        nonlocal bad
        bad += not same
        say({"seed": seed, "what": "q95.mesh.programs", "slots": int(out.shape[0]), "groupby_s": s_group,
             "aggregate_stage_s": s_stage, "filter_stage_s": s_keep, "backend_compiles": compiles() - c0,
             "layout": laid, "outputs_lie_as_inputs": bool(same)})
        check_ws_wh(seed, "q95.mesh", raw, jit_cols, out, on_first_chip, present)

    # -- every expression kind of tests/test_plan.py, and the dd primitives one by one --------------
    def kinds(seed, rows=200_000):
        import importlib.util

        spec_ = importlib.util.spec_from_file_location("test_plan_kinds", os.path.join(ROOT, "tests", "test_plan.py"))
        tp = importlib.util.module_from_spec(spec_)
        spec_.loader.exec_module(tp)
        t = tp._expr_table(np.random.default_rng(seed), rows, True)
        cp = P.compile_ir(P.Project(P.Scan("t"), tuple(tp._KINDS.items())), {"t": t}, name="kinds")
        [stage] = [s for s in stages_of(cp, "_ProjectExec") if s.program.trees]
        got, s_jit = timed(lambda: stage.program(t, t.num_rows))
        schema = {n: c.dtype for n, c in zip(t.names, t.columns)}
        for (name, e), g in zip(tp._KINDS.items(), got):
            low = None if tp.pex.is_null_lit(e) else e.lower()
            want, s_eager = timed(lambda: compiler._materialize(low, t, e.dtype(schema), t.num_rows))
            compare(seed, f"kinds.{name}", g, want, eager_s=s_eager)

    def primitives(seed, rows=1_000_000):
        """jit against eager for each double-float32 primitive, with
        ``f64acc._rounded`` (what keeps a product from being contracted into
        the add behind it) as it is, taken out, and in each of its parts:
        which primitive a compiler changes when it sees the whole chain, and
        what stops it."""
        from spark_rapids_jni_tpu.ops import f64acc as F

        rng = np.random.default_rng(seed)
        a = jnp.asarray(rng.uniform(900, 105000, rows).astype(np.float32))
        b = jnp.asarray(rng.uniform(0, 1, rows).astype(np.float32))
        A, B = F.DD(a, a * np.float32(3e-8)), F.DD(b, b * np.float32(-2e-8))
        bits = jnp.asarray((rng.uniform(900, 105000, rows).round(2)).view(np.uint64))
        cases = {"split": (F._split, (a,)), "two_prod": (F._two_prod, (a, b)), "two_sum": (F._two_sum, (a, b)),
                 "dd_mul": (lambda x, y: tuple(x * y), (A, B)), "dd_div": (lambda x, y: tuple(x / y), (A, B)),
                 "dd_add": (lambda x, y: tuple(x + y), (A, B)), "dd_from_f64bits": (lambda x: tuple(F.dd_from_f64bits(x)), (bits,)),
                 "dd_to_f64bits": (F.dd_to_f64bits, (A,)),
                 "roundtrip_product": (lambda x, y: F.dd_to_f64bits(F.dd_from_f64bits(x) * F.dd_from_f64bits(y)), (bits, bits[::-1]))}
        kept = F._rounded
        ways = {"as_it_is": kept, "nothing": lambda x: x, "barrier": jax.lax.optimization_barrier,
                "select_on_itself": lambda x: jnp.where(x == x, x, jnp.float32(np.nan)),
                "abs_copysign": lambda x: jnp.copysign(jnp.abs(x), x)}
        for way, fn_rounded in ways.items():
            F._rounded = fn_rounded
            try:
                for name, (fn, xs) in cases.items():
                    if way != "as_it_is" and name not in ("split", "two_prod", "dd_mul", "dd_div", "roundtrip_product"):
                        continue  # the others hold no product
                    eager = jax.tree_util.tree_leaves(fn(*xs))
                    jitted = jax.tree_util.tree_leaves(jax.jit(lambda *ys, fn=fn: fn(*ys))(*xs))
                    differ = [int(np.count_nonzero(host(e).view(f"u{host(e).dtype.itemsize}") != host(j).view(f"u{host(j).dtype.itemsize}")))
                              for e, j in zip(eager, jitted)]
                    say({"seed": seed, "what": f"primitive.{name}", "rounded": way, "lanes": rows, "lanes_differ": differ})
                    nonlocal bad
                    bad += bool(way == "as_it_is" and any(differ))
            finally:
                F._rounded = kept

    for seed in [int(s) for s in args.seeds.split(",") if s]:
        if args.chips == 1:
            if seed == int(args.seeds.split(",")[0]):
                primitives(seed)
                kinds(seed)
            q1(seed)
            q95_one_chip(seed)
        else:
            q95_mesh(seed)
    reg = metrics.registry()
    say({"what": "counters", "plan.expr.jitted": reg.value("plan.expr.jitted"), "plan.expr.eager": reg.value("plan.expr.eager"),
         "comparisons_that_differ": bad})
    if device["platform"] == "cpu":
        say({"what": "rehearsal", "note": "no chip: the control flow only"})
    say({"device": device})
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
