#!/usr/bin/env python3
"""Reduce chiprun_out/pr26/runs.jsonl (written by pr26-call*.sh) to the
tables of PERF.md: untraced medians a side, the traced lines, spans a
request, and the split of a traced request by phase span.

    python3 benchmarks/calls/pr26_summary.py [chiprun_out/pr26/runs.jsonl ...]
"""

from __future__ import annotations

import collections
import glob
import json
import statistics
import sys


def spread(values):
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(paths):
    runs = [json.loads(line) for p in paths for line in open(p) if line.strip()]
    cells = sorted({r["cell"] for r in runs})
    for cell in cells:
        print(f"== {cell}")
        for r in runs:
            if r["cell"] == cell and (r["rc"] != 0 or not (r["result"] or {}).get("correct")):
                print(f"  NOT CORRECT: {r['side']} seed {r['seed']} trace {r['trace']} rc {r['rc']}")
        med = {}
        for side in ("parent", "change"):
            rs = [r for r in runs if r["cell"] == cell and r["side"] == side and not r["trace"] and r["result"]]
            for name in ("latency_p50_ms", "rows_per_s", "setup_s"):
                vals = [r["result"]["metrics"][name]["value"] for r in rs]
                if vals:
                    med[side, name] = statistics.median(vals)
                    print(f"  untraced {side:6s} {name:15s} median {med[side, name]:.6g} spread {100 * spread(vals):.2f}% "
                          f"runs {[round(v, 4) for v in vals]}")
        for name in ("latency_p50_ms", "rows_per_s", "setup_s"):
            if ("parent", name) in med and ("change", name) in med:
                print(f"  change/parent {name}: {100 * (med['change', name] / med['parent', name] - 1):+.2f}%")
        for r in runs:
            if r["cell"] != cell or not r["trace"] or not r["result"]:
                continue
            res, n = r["result"], r.get("requests") or 1
            print(f"  traced {r['side']} seed {r['seed']}: requests {n} window_s {res['device']['window_s']:.2f} "
                  f"busy_s {res['device']['busy_s']:.3f}")
            print("    metrics " + " ".join(f"{k}={v['value']:.6g}" for k, v in sorted(res["metrics"].items())))
            gaps = res["breakdown"]["idle_gaps"]
            total = sum(v for _, v in gaps)
            print("    idle_gaps " + ", ".join(f"{k} {v:.2f}" for k, v in gaps) + f"  (sum {total:.2f})")
            spans = r.get("spans") or []
            by = collections.defaultdict(list)
            for s in spans:
                by[s["name"]].append(s["dur_us"])
            print(f"    spans a request {len(spans) / n:.1f}")
            for name, d in sorted(by.items()):
                print(f"      {name:32s} n/req {len(d) / n:5.1f}  ms/req {sum(d) / 1e3 / n:10.2f}")


if __name__ == "__main__":
    main(sys.argv[1:] or sorted(glob.glob("chiprun_out/pr26/runs-*.jsonl")))
