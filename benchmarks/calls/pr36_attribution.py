#!/usr/bin/env python3
"""A traced run's device time by the span that asked for it (PR 36).

    python3 benchmarks/calls/pr36_attribution.py <saved-trace.json>

Reads what ``bench/run.py --trace 1 --save-trace`` wrote and prints, a
request: every ``device.wait`` by site (``what``) and parent span, every
``device.launch`` by program and parent span with the device time matched
to it (``bench/benchlib/attribution.py``: by name and order), the names
whose counts differ, the programs matched to no launch, the closure
(attributed + unattributed against the busy time), and the spans whose
self time is largest (a sync site without a ``device.wait`` shows there).
"""
import collections
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..", "bench"))

from benchlib import attribution, tracered  # noqa: E402


def main(path):
    t = json.load(open(path))
    w0, w1 = t["window_ns"]
    n = max(int(t["requests"]), 1)
    ctx = {"spans": t["spans"], "trace": t["trace"], "w0_ns": w0, "w1_ns": w1, "requests": [None] * n,
           "busy_s": tracered.busy_seconds(t["trace"], w0, w1)}
    by_id = {s["span"]: s for s in t["spans"] if "span" in s}
    busy_ms = 1e3 * ctx["busy_s"] / n
    print(f"{n} requests, window {(w1 - w0) / 1e9:.2f} s, {len(t['trace']['devices'])} device(s), "
          f"busy {busy_ms:.2f} ms a request")

    waits = collections.defaultdict(lambda: [0, 0.0])
    for s in t["spans"]:
        if s["name"] == attribution.WAIT:
            parent = by_id.get(s.get("parent"), {}).get("name", "?")
            key = ((s.get("annotations") or {}).get("what", "?"), parent)
            waits[key][0] += 1
            waits[key][1] += s["dur_us"]
    total = sum(v[1] for v in waits.values()) / 1e3 / n
    print(f"-- device.wait: {total:.2f} ms a request ({100 * total / busy_ms if busy_ms else 0:.1f}% of busy)")
    for (what, parent), (count, us) in sorted(waits.items(), key=lambda kv: -kv[1][1]):
        print(f"   {us / 1e3 / n:10.2f} ms {count / n:7.1f} x  {what:<16} under {parent}")

    m = attribution.match(ctx)
    n_dev = max(len(m["unmatched_ns"]), 1)
    rows = collections.defaultdict(lambda: [0, 0.0])
    for s, ns in zip(m["launches"], m["ns"]):
        parent = by_id.get(s.get("parent"), {}).get("name", "?")
        key = (s["annotations"]["program"], parent)
        rows[key][0] += 1
        rows[key][1] += sum(ns) / n_dev
    matched = sum(v[1] for v in rows.values()) / 1e6 / n
    print(f"-- device.launch: {len(m['launches']) / n:.1f} a request, {matched:.2f} ms of device matched")
    for (program, parent), (count, ns) in sorted(rows.items(), key=lambda kv: -kv[1][1]):
        flag = "  MISMATCH" if program in m["mismatched"] else ""
        print(f"   {ns / 1e6 / n:10.2f} ms {count / n:7.1f} x  {program:<32} under {parent}{flag}")
    for program, (launched, ran) in m["mismatched"].items():
        print(f"   mismatch: {program}: {launched} launches, programs on each device {ran}")
    un = sum(m["unmatched_ns"]) / n_dev / 1e6 / n
    print(f"-- matched to no launch: {un:.2f} ms a request ({100 * un / busy_ms if busy_ms else 0:.2f}% of busy); "
          f"attributed + unattributed = {matched + un:.2f} against busy {busy_ms:.2f}")
    for name, ns in sorted(m["unmatched_names"].items(), key=lambda kv: -kv[1])[:12]:
        print(f"   {ns / n_dev / 1e6 / n:10.2f} ms  {name}")

    self_us = {s["span"]: s["dur_us"] for s in t["spans"] if "span" in s}
    for s in t["spans"]:
        if s.get("parent") in self_us:
            self_us[s["parent"]] -= s["dur_us"]
    by_name = collections.defaultdict(float)
    for sid, us in self_us.items():
        by_name[by_id[sid]["name"]] += us
    print("-- self time (a span less its children), ms a request")
    for name, us in sorted(by_name.items(), key=lambda kv: -kv[1])[:14]:
        print(f"   {us / 1e3 / n:10.2f} ms  {name}")


if __name__ == "__main__":
    main(sys.argv[1])
