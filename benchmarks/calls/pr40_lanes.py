#!/usr/bin/env python3
"""``tpch-sf1.q1``'s answer, every lane of its ten columns, from two trees:
the exact float64 aggregates of one tree against another's, bit for bit.

    python3 benchmarks/calls/pr40_lanes.py [--root DIR] [--rows N] [--seeds a,b] --save A.npz [--against B.npz]

q1 as the cell makes and plans it (``bench/configs/tpch-sf1.json``, the
plan of ``bench/queries/tpch_q1.py``, ``plan.compile_ir``), run from the
package under ``--root`` (default: the tree this file is in), for each
seed. Every column's data and validity are written to ``--save``; with
``--against`` (an earlier ``--save`` of another tree) the lanes that differ
are counted a seed and column. One JSON line a seed; exit code 1 if a lane
differs. The last line is the device.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=HERE)
    ap.add_argument("--rows", type=int, default=6001215)
    ap.add_argument("--seeds", default="4000000007,4000104729")
    ap.add_argument("--save", required=True)
    ap.add_argument("--against")
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    sys.path.insert(1, os.path.join(root, "bench"))

    import jax
    import numpy as np

    import spark_rapids_jni_tpu  # noqa: F401  (x64 and the compile cache before any array)
    from benchlib import loader
    from spark_rapids_jni_tpu import plan as P
    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.columnar import dtype as dt

    dev = jax.devices()[0]
    config = loader.read_json("configs", "tpch-sf1.json")
    types = {"float64": dt.FLOAT64, "int8": dt.INT8, "timestamp_days": dt.TIMESTAMP_DAYS}
    spec = config["tables"]["lineitem"]["columns"]
    q1 = loader.module("queries", "tpch_q1")
    against = dict(np.load(args.against)) if args.against else None
    saved, bad = {}, 0
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        cols = loader.module("data", config["data"]).host_tables(config, seed, args.rows)["lineitem"]
        table = Table([Column.from_numpy(np.ascontiguousarray(a), types[spec[c]]) for c, a in cols.items()],
                      list(cols))
        out = P.compile_ir(q1.plan(P), {"lineitem": table}, name="q1")()
        rec = {"seed": seed, "package": os.path.dirname(spark_rapids_jni_tpu.__file__), "rows": args.rows,
               "groups": out.num_rows, "columns": len(out.names)}
        differ = {}
        for name in out.names:
            col = out.column(name)
            for part, arr in (("data", np.asarray(col.data)), ("valid", np.asarray(col.valid_mask()))):
                key = f"{seed}/{name}/{part}"
                saved[key] = arr
                if against is not None:
                    other = against.get(key)
                    n = arr.size if other is None or other.shape != arr.shape else int(np.count_nonzero(arr != other))
                    if n:
                        differ[f"{name}/{part}"] = n
        if against is not None:
            rec["lanes_differ"] = sum(differ.values())
            rec["differ_by_column"] = differ
            bad += bool(differ)
        print(json.dumps(rec), flush=True)
    np.savez(args.save, **saved)
    print(json.dumps({"device": {"platform": dev.platform, "kind": dev.device_kind}, "seeds_with_differing_lanes": bad}))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
