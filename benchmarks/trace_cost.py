#!/usr/bin/env python3
"""What tracing costs a q1 request when it is on, and whether a compile
inside a traced window is named (ISSUE 26; chip only, by hand).

    python3 benchmarks/trace_cost.py --seed 2600000087 --requests 3 --out chiprun_out/trace_cost.json

One process, the benchmark's own ``tpch-sf1.q1`` session (its data, its
plan, its two warm-up requests), then the same request timed under five
postures, one after the other: nothing on; the profiler on and program
tracing off; program tracing on and the profiler off; both (what a
``--trace 1`` run of the benchmark does); nothing on again (drift).
Each posture reports its requests' milliseconds, the spans a request
made and the ``xla.compile`` spans among them. Last, with program
tracing on, a group-by over a shape the process has not seen: its
compiles must show as ``xla.compile`` spans under the operator that
caused them and in ``xla.backend_compiles`` (skipped where the program
has no such counter, as before ISSUE 26). Works in a checkout of the
parent commit too: both sides of a comparison run this same file.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench"))

from benchlib import device, loader, tracered  # noqa: E402

POSTURES = (("off", False, False), ("profiler", True, False), ("spans", False, True),
            ("both", True, True), ("off_again", False, False))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, default=3)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", action="store_true", help="tiny size on the CPU: control flow only")
    args = ap.parse_args()

    session = loader.open_session(loader.cell("tpch-sf1.q1"), args.seed, args.rehearse, False, tag="cost-")
    session.setup()
    import jax

    from spark_rapids_jni_tpu.utils import metrics, trace_sink, tracing

    result = {"device": session.device, "rehearsal": args.rehearse, "postures": {}}
    for name, profiler, spans in POSTURES:
        base = os.path.join(session.workdir, f"spans-{name}")
        if spans:
            tracing.set_enabled(True)
            trace_sink.set_log_path(base)
        if profiler:
            device.start_profile(os.path.join(session.workdir, f"profile-{name}"))
        ms = []
        for i in range(args.requests):
            t0 = time.perf_counter()
            session.issue(i)
            ms.append(1e3 * (time.perf_counter() - t0))
        if profiler:
            jax.profiler.stop_trace()
        made = []
        if spans:
            tracing.set_enabled(False)
            trace_sink.close_log()
            trace_sink.set_log_path(None)
            made = tracered.read_span_log(base)
        result["postures"][name] = {
            "request_ms": ms, "median_ms": statistics.median(ms),
            "spans_per_request": len(made) / args.requests,
            "xla_compile_spans_per_request": sum(s["name"] == "xla.compile" for s in made) / args.requests,
        }
        print(name, result["postures"][name], flush=True)

    if metrics.registry().peek("xla.backend_compiles") is not None:
        import numpy as np

        from spark_rapids_jni_tpu.columnar import Column, Table
        from spark_rapids_jni_tpu.columnar import dtype as dt
        from spark_rapids_jni_tpu.ops.aggregate import groupby_aggregate

        rng = np.random.default_rng(args.seed)
        n = 12_347  # a shape no request of the cell has
        keys = Table([Column.from_numpy(rng.integers(0, 5, n).astype(np.int32), dt.INT32)], ["k"])
        vals = Table([Column.from_numpy(rng.integers(0, 99, n).astype(np.int64), dt.INT64)], ["v"])
        base = os.path.join(session.workdir, "spans-provoked")
        tracing.set_enabled(True)
        trace_sink.set_log_path(base)
        before = metrics.counters_snapshot()
        out = groupby_aggregate(keys, vals, [("v", "sum")])
        jax.block_until_ready([c.data for c in out.columns])
        after = metrics.counters_snapshot()
        tracing.set_enabled(False)
        trace_sink.close_log()
        trace_sink.set_log_path(None)
        made = tracered.read_span_log(base)
        by_id = {s["span"]: s for s in made}

        def chain(s):
            names = []
            while s is not None:
                names.append(s["name"])
                s = by_id.get(s["parent"])
            return " < ".join(names)

        compiles = [s for s in made if s["name"] == "xla.compile"]
        result["provoked"] = {
            "counters": {k: after[k] - before.get(k, 0) for k in after if k.startswith("xla.")},
            "xla_compile_spans": len(compiles),
            "under": sorted({chain(s) for s in compiles}),
            "compile_ms": sum(s["dur_us"] for s in compiles) / 1e3,
        }
        print("provoked", result["provoked"], flush=True)
    session.release()
    session.close()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
