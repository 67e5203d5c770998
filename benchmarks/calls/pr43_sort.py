#!/usr/bin/env python3
"""A Sort as one program against the form it replaces, at the two shapes the
cells sort: ``ops/sort.py::sort_by_key`` (key lanes, sort and every
fixed-width column's gather in ``_sort_gather``) against
``gather(values, sorted_order(keys, ...))`` (one launch an operation).

    python3 benchmarks/calls/pr43_sort.py [--reps 200] [--out PATH]

Shapes: ``q1`` (4 result rows: two INT8 keys, seven FLOAT64 aggregates, the
INT64 count), ``q1-decimal`` (the same with seven DECIMAL128 aggregates) and
``store-star`` (816 rows, q55's Sort: a FLOAT64 sum descending then an INT32
brand id, the brand's STRING name riding along). For each shape and form: the
two answers compared byte for byte (exit code 1 if one differs), a warm call
timed ``--reps`` times, the forms alternating, every output waited for; then
five calls under the profiler, and the programs a call ran (the chip's
"XLA Modules" line; off the chip the host's ``PjRtCpuExecutable::Execute``
events). One JSON line a reading, also written to ``--out`` where given; the
last line is the device. Off the chip (``JAX_PLATFORMS=cpu``) the times are the
CPU backend's, no device times.
"""
import argparse
import glob
import json
import os
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=200)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import spark_rapids_jni_tpu  # noqa: F401  (x64 before any array)
    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.columnar import dtype as dt
    from spark_rapids_jni_tpu.ops import sort
    from spark_rapids_jni_tpu.ops.copying import gather

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count()}
    out_f = open(args.out, "w") if args.out else None

    def say(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out_f:
            out_f.write(line + "\n")
            out_f.flush()

    rng = np.random.default_rng(4300000043)

    def q1(decimal):
        def agg():
            if decimal:
                limbs = rng.integers(0, 1 << 32, (4, 4), dtype=np.uint64).astype(np.uint32)
                return Column(dt.decimal128(4, 38), data=jnp.asarray(limbs), validity=jnp.ones((4,), bool))
            return Column.from_numpy(rng.uniform(0, 1e9, 4), dt.FLOAT64, validity=np.ones(4, bool))

        values = Table([Column.from_numpy(np.array([2, 0, 1, 1], np.int8), dt.INT8),
                        Column.from_numpy(np.array([1, 0, 1, 0], np.int8), dt.INT8)]
                       + [agg() for _ in range(7)]
                       + [Column.from_numpy(rng.integers(1, 1 << 21, 4), dt.INT64)],
                       ["l_returnflag", "l_linestatus"] + [f"agg{i}" for i in range(7)] + ["count_order"])
        return values, values.select(["l_returnflag", "l_linestatus"]), [True, True]

    def star():
        n = 816
        brands = [f"brand#{i % 50:02d}{'x' * (i % 17)}" for i in rng.integers(0, 1000, n)]
        values = Table([Column.from_numpy(rng.integers(1_001_001, 10_016_017, n).astype(np.int32), dt.INT32),
                        Column.from_pylist(brands, dt.STRING),
                        Column.from_numpy(rng.uniform(0, 1e6, n).round(2), dt.FLOAT64, validity=np.ones(n, bool))],
                       ["brand_id", "brand", "ext_price"])
        return values, values.select(["ext_price", "brand_id"]), [False, True]

    forms = {
        "one_program": lambda v, k, a: sort.sort_by_key(v, k, ascending=a),
        "old": lambda v, k, a: gather(v, sort.sorted_order(k, a)),
    }

    def waited(t):
        jax.block_until_ready([x for c in t.columns for x in (c.data, c.validity, c.offsets, c.chars)
                               if x is not None])
        return t

    def differ(a, b):
        n = 0
        for ca, cb in zip(a.columns, b.columns):
            for part in ("data", "validity", "offsets", "chars"):
                x, y = getattr(ca, part), getattr(cb, part)
                if (x is None) != (y is None) or (x is not None and np.asarray(x).tobytes() != np.asarray(y).tobytes()):
                    n += 1
        return n

    def programs(fn, calls=5):
        """Programs a call ran, read from the profiler: the first TPU's "XLA Modules", else the CPU's executions."""
        with tempfile.TemporaryDirectory() as tmp:
            with jax.profiler.trace(tmp):
                for _ in range(calls):
                    waited(fn())
            paths = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))
            if not paths:
                return None
            data = jax.profiler.ProfileData.from_file(max(paths, key=os.path.getmtime))
            for plane in data.planes:
                if plane.name.startswith("/device:") and "TPU" in plane.name and "SparseCore" not in plane.name:
                    return sum(len(list(line.events)) for line in plane.lines if line.name == "XLA Modules") / calls
            return sum(e.name == "PjRtCpuExecutable::Execute" for plane in data.planes for line in plane.lines
                       for e in line.events) / calls

    bad = 0
    for shape, make in (("q1", lambda: q1(False)), ("q1-decimal", lambda: q1(True)), ("store-star", star)):
        values, keys, asc = make()
        answers = {form: waited(fn(values, keys, asc)) for form, fn in forms.items()}  # compiles
        diff = differ(answers["one_program"], answers["old"])
        bad += bool(diff)
        ms = {form: [] for form in forms}
        for _ in range(args.reps):
            for form, fn in forms.items():
                t0 = time.perf_counter()
                waited(fn(values, keys, asc))
                ms[form].append((time.perf_counter() - t0) * 1e3)
        rec = {"what": "sort", "shape": shape, "rows": values.num_rows, "columns": values.num_columns,
               "parts_differ": diff, "reps": args.reps}
        for form, fn in forms.items():
            q = statistics.quantiles(ms[form], n=4)
            rec[form] = {"ms_p50": round(statistics.median(ms[form]), 4), "ms_q1": round(q[0], 4),
                         "ms_q3": round(q[2], 4), "programs_a_call": programs(lambda: fn(values, keys, asc))}
        say(rec)
    say({"device": device})
    if out_f:
        out_f.close()
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
