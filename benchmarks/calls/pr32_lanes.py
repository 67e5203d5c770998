#!/usr/bin/env python3
"""PR 32: ``ops/sort.py::_string_lanes`` (jitted: two u32 halves joined by
one 64-bit shift a lane) against numpy on the host, lane for lane, and the
order and group boundaries they give against plain Python — on whatever
backend JAX starts on (PERF.md 7 (iv): a new jitted caller of 64-bit
shifts is checked on the chip). dsdgen-shaped brands (17-22 bytes),
200-byte descriptions, empties and NULLs; exit 1 if a lane differs.

    python3 benchmarks/calls/pr32_lanes.py [--rows 200000] [--seeds 3] [--only brand|desc]
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=200_000)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--only", choices=("brand", "desc"), help="one of the two shapes (default both)")
    args = ap.parse_args()
    import jax

    import spark_rapids_jni_tpu  # noqa: F401
    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.columnar import dtype as dt
    from spark_rapids_jni_tpu.ops.aggregate import groupby_aggregate
    from spark_rapids_jni_tpu.ops.sort import sorted_order, string_key_lanes

    d = jax.devices()[0]
    print(f"device {d.platform} {d.device_kind}", flush=True)
    bad = 0
    syll = ["able", "anti", "bar", "cally", "eing", "ese", "ought", "pri", "ation"]
    for seed in range(args.seeds):
        rng = np.random.default_rng(3200 + seed)
        for what in ("brand", "desc"):
            if args.only not in (None, what):
                continue
            n = args.rows if what == "brand" else args.rows // 10
            if what == "brand":
                vals = [f"{syll[a]}{syll[b]}{syll[c]} #{k}" for a, b, c, k in
                        zip(*(rng.integers(0, len(syll), n) for _ in range(3)), rng.integers(1, 18, n))]
            else:
                stem = "x" * 180
                vals = [stem[:int(k)] + str(int(t)) for k, t in zip(rng.integers(0, 181, n), rng.integers(0, 10**6, n))]
            for i in rng.choice(n, n // 50, replace=False):
                vals[i] = ""
            nulls = set(rng.choice(n, n // 50, replace=False).tolist())
            col = Column.from_pylist([None if i in nulls else v for i, v in enumerate(vals)], dt.STRING)
            raw = [b"" if i in nulls else v.encode() for i, v in enumerate(vals)]
            lanes = [np.asarray(k) for k in string_key_lanes(col)]
            width = 8 * (len(lanes) - 1)
            padded = np.zeros((n, max(width, 1)), np.uint8)
            for i, b in enumerate(raw):
                padded[i, :len(b)] = np.frombuffer(b, np.uint8)
            for j, lane in enumerate(lanes[:-1]):
                want = padded[:, 8 * j:8 * j + 8].copy().view(">u8")[:, 0].astype(np.uint64)
                diff = int(np.count_nonzero(lane != want))
                bad += diff
                if diff:
                    print(f"seed {seed} {what} lane {j}: {diff} of {n} rows differ", flush=True)
            bad += int(np.count_nonzero(lanes[-1] != np.array([len(b) for b in raw], np.uint32)))
            order = np.asarray(sorted_order(Table([col], ["k"]))).tolist()
            want_order = sorted(range(n), key=lambda i: (i not in nulls, raw[i]))
            wrong = sum(a != b for a, b in zip(order, want_order))
            bad += wrong
            out = groupby_aggregate(Table([col], ["k"]), Table([Column.from_numpy(np.ones(n, np.int64), dt.INT64)], ["v"]),
                                    [("v", "sum")])
            groups = len({raw[i] for i in range(n) if i not in nulls}) + (1 if nulls else 0)
            bad += int(out.num_rows != groups) + int(int(np.asarray(out.columns[1].data).sum()) != n)
            print(f"seed {seed} {what}: rows {n} lanes {len(lanes)} (longest {max(map(len, raw))}) "
                  f"order mismatches {wrong} groups {out.num_rows} want {groups}", flush=True)
    print("lanes ok" if not bad else f"lanes WRONG: {bad}", flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
