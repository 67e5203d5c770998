#!/usr/bin/env python3
"""A traced run's program spans by name, ms a request, from what
``bench/run.py --trace 1 --save-trace`` wrote: count and summed duration
of every span name over 0.05 ms a request, and the annotations of the
first ``join.*``, ``groupby.sort`` and ``op.sort_by_key`` spans.

    python3 benchmarks/calls/pr32_spans.py <saved-trace.json>
"""
import collections
import json
import sys


def main(path):
    t = json.load(open(path))
    n = max(int(t["requests"]), 1)
    by = collections.defaultdict(lambda: [0, 0.0])
    seen = {}
    for s in t["spans"]:
        by[s["name"]][0] += 1
        by[s["name"]][1] += s["dur_us"]
        if s["name"].startswith(("join.", "groupby.sort", "op.sort_by_key")) and len(seen.setdefault(s["name"], [])) < 3:
            seen[s["name"]].append(s.get("annotations", {}))
    print(f"spans a request: {len(t['spans']) / n:.1f} over {n} requests")
    for name, (count, us) in sorted(by.items(), key=lambda kv: -kv[1][1]):
        if us / n >= 50:
            print(f"  {us / 1e3 / n:10.2f} ms {count / n:7.1f} x  {name}")
    for name, notes in sorted(seen.items()):
        print(f"  {name}: {notes}")


if __name__ == "__main__":
    main(sys.argv[1])
