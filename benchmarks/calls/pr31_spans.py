#!/usr/bin/env python3
"""The timeline of one traced q95-x4 request, from what pr26-common.sh's
``bench_run`` kept of a traced run (``runs-<call>.jsonl``): every
``exchange.*`` span of the first request in time order with its
annotations (``capacity``, ``max_bucket``, ``fill``, ``rows_in``), the
operator spans over 20 ms beside them, and the ``xla.compile`` spans of the window.

    python3 benchmarks/calls/pr31_spans.py chiprun_out/pr31/runs-call1.jsonl
"""
import json
import sys


def main(paths):
    for path in paths:
        for line in open(path):
            run = json.loads(line)
            spans = run.get("spans")
            if not spans:
                continue
            queries = sorted((s for s in spans if s["name"] == "serve.query"), key=lambda s: s["ts"])
            first = queries[0]
            t0, t1 = first["ts"], first["ts"] + first["dur_us"] / 1e6
            compiles = [s for s in spans if s["name"] == "xla.compile"]
            print(f"== {run['side']} seed {run['seed']}: {len(queries)} requests, the first {first['dur_us'] / 1e3:.1f} ms; "
                  f"xla.compile spans in the window {len(compiles)} "
                  f"({sorted({(s.get('annotations', {}).get('fun'), s.get('annotations', {}).get('cache')) for s in compiles})})")
            for s in sorted(spans, key=lambda s: s["ts"]):
                if not t0 <= s["ts"] <= t1:
                    continue
                if s["name"].startswith("exchange.") or (s["dur_us"] >= 20_000 and s["name"].startswith("op.")):
                    print(f"  {1e3 * (s['ts'] - t0):8.1f} +{s['dur_us'] / 1e3:8.1f} ms  {s['name']:22s} {s.get('annotations', {})}")


if __name__ == "__main__":
    main(sys.argv[1:])
