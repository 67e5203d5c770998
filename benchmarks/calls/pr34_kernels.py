#!/usr/bin/env python3
"""PR 34: the device time of the Pallas kernels (Mosaic custom calls) in a
traced window, a request, from what `bench/run.py --trace 1 --save-trace`
wrote: every operation whose text holds `custom-call`, grouped by the
shape of its result, their share of the window's operations, and every
operation of 1 ms a request or more by name.

    python3 benchmarks/calls/pr34_kernels.py <saved-trace.json>
"""
import collections
import json
import re
import sys


def main(path):
    t = json.load(open(path))
    w0, w1 = t["window_ns"]
    n = max(int(t["requests"]), 1)
    _, dev = sorted(t["trace"]["devices"].items())[0]
    ops = [o for o in dev["ops"] if o[1] >= w0 and o[1] + o[2] <= w1]
    total = sum(o[2] for o in ops)
    by = collections.defaultdict(lambda: [0, 0])
    for name, _s, d in ops:
        if "custom-call" in name:
            m = re.match(r"([\w.\-]+?)(?:\.\d+)? = (\S+) custom-call", name)
            key = f"{m.group(1)} -> {m.group(2)}" if m else name[:60]
            by[key][0] += 1
            by[key][1] += d
    print(f"custom calls: {sum(v[1] for v in by.values()) / 1e6 / n:.2f} ms of {total / 1e6 / n:.2f} ms of operations a request")
    for key, (count, ns) in sorted(by.items(), key=lambda kv: -kv[1][1])[:12]:
        print(f"   {ns / 1e6 / n:10.2f} ms  {count / n:7.1f} x  {key}")
    every = collections.defaultdict(lambda: [0, 0])
    for name, _s, d in ops:
        every[name][0] += 1
        every[name][1] += d
    print("every operation of 1 ms a request or more (a `while` holds the operations of its body):")
    for name, (count, ns) in sorted(every.items(), key=lambda kv: -kv[1][1]):
        if ns / 1e6 / n >= 1.0:
            print(f"   {ns / 1e6 / n:10.2f} ms  {count / n:7.1f} x  {name}")


if __name__ == "__main__":
    main(sys.argv[1])
