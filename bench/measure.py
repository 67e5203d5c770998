#!/usr/bin/env python3
"""Run a cell several times, one process a run, and print what the
bounds are set from: each metric's median and its spread (the distance
between the quartiles of ``statistics.quantiles(values, n=4)`` as a
share of the median), for each set and over all runs.

    python3 bench/measure.py --workload tpch-sf1.q6 --seconds 51 --sets 2 --runs 6 --out chiprun_out/q6.jsonl

Every set uses the same seeds. This parent never touches JAX; each run
is ``bench/run.py`` in a child, one after the other. ``--trace 1`` makes
traced runs instead; ``--save-trace`` is passed to the first run.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def spread(values) -> float:
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--first-seed", type=int, default=2_300_000_011)
    ap.add_argument("--out", required=True)
    ap.add_argument("--save-trace")
    args = ap.parse_args()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)) or ".", exist_ok=True)
    sets = []
    with open(args.out, "a") as log:
        for s in range(args.sets):
            rows = []
            for k in range(args.runs):
                seed = args.first_seed + 104_729 * k
                cmd = [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload", args.workload,
                       "--seed", str(seed), "--seconds", str(args.seconds), "--trace", str(args.trace)]
                if args.save_trace and s == 0 and k == 0:
                    cmd += ["--save-trace", args.save_trace]
                t0 = time.time()
                p = subprocess.run(cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
                lines = p.stdout.strip().splitlines()
                last = lines[-1] if lines else ""
                try:
                    res = json.loads(last)
                except ValueError:
                    res = None
                rec = {"workload": args.workload, "set": s, "run": k, "seed": seed, "rc": p.returncode,
                       "wall_s": round(time.time() - t0, 1), "result": res,
                       "requests": [ln for ln in lines[:-1] if ln.startswith(("request ", "setup "))]}
                if res is None or not res.get("correct"):
                    rec["stderr_tail"] = p.stderr[-6000:]
                    rec["stdout_tail"] = p.stdout[-2000:]
                log.write(json.dumps(rec) + "\n")
                log.flush()
                m = {k2: v["value"] for k2, v in (res or {}).get("metrics", {}).items()}
                print(f"set {s} run {k} seed {seed} rc {p.returncode} wall {rec['wall_s']}s "
                      f"correct {(res or {}).get('correct')} n {(res or {}).get('attempted')} "
                      + " ".join(f"{k2}={v:.6g}" for k2, v in m.items()), flush=True)
                if res is None or not res.get("correct"):
                    print(p.stderr[-3000:], flush=True)
                rows.append(m)
            sets.append(rows)
    names = sorted({n for rows in sets for r in rows for n in r})
    for n in names:
        per = [[r[n] for r in rows if n in r] for rows in sets]
        every = [v for vs in per for v in vs]
        if not every:
            continue
        line = f"{args.workload} {n}: median {statistics.median(every):.6g}"
        for i, vs in enumerate(per):
            if vs:
                line += f" | set {i} median {statistics.median(vs):.6g} spread {100 * spread(vs):.3f}%"
        line += f" | all runs spread {100 * spread(every):.3f}% min {min(every):.6g} max {max(every):.6g}"
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
