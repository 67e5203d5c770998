#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, in this process, on this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (data from the seed, plan compile, XLA compile or cache load, two
whole unmeasured requests), then the window (bench/benchlib/window.py),
then, with the window closed, the comparison with the plain reference.
stdout: one line per request, then as the LAST line one JSON object
(correct, attempted, failed, metrics, device, breakdown, checks). With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics from a profiler trace and the
program's spans over a shorter window. Without an accelerator the run
fails and prints no result; ``--rehearse`` walks the same control flow at
a tiny size on the CPU and can print neither a metric nor ``correct``.
"""

from __future__ import annotations

import time

_T_IMPORT = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)

from benchlib import compare, loader, tracered, window  # noqa: E402


def process_start_wall() -> float:
    """Wall-clock time at which this process was started (set-up counts
    the interpreter's own start and the imports above)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        if 0 <= age < 3600:
            return time.time() - age
    except (OSError, ValueError, IndexError):
        pass
    return _T_IMPORT


def note(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)


def per_layer(cell: dict, ctx: dict) -> dict:
    """Each per-layer metric of the cell through its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell["per_layer"]:
        spec = loader.read_json("metrics", f"{m['name']}.json")
        value = loader.module("readers", spec["reader"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(args) -> int:
    t_start = process_start_wall()
    if not os.path.isdir(os.path.join(ROOT, "spark_rapids_jni_tpu")):
        note(f"the program is not in this checkout ({ROOT}): nothing to measure")
        return 2
    cell = loader.cell(args.workload)
    trace = bool(args.trace)
    session = loader.open_session(cell, args.seed, args.rehearse, trace)
    failed = [0]

    def issue(i):
        try:
            return session.issue(i)
        except Exception:  # a failed request is counted, and fails the run's `correct`
            traceback.print_exc()
            failed[0] += 1
            return 0, None

    def keep(i, handle):
        if handle is not None:
            session.keep(i, handle)

    try:
        session.setup()
        note(f"set-up done on {session.device}: {session.facts}")
        seconds = min(args.seconds, float(cell["traffic"].get("trace_seconds", args.seconds))) if trace else args.seconds
        if trace:
            session.start_trace()
        wall0, pc0 = time.time(), time.perf_counter()
        requests = window.closed_loop(issue, seconds, keep)
        setup_s = wall0 + (requests[0].start - pc0) - t_start
        traced = session.stop_trace() if trace else None
        peak = 0 if args.rehearse else session.memory_peak_bytes()
        print("setup " + " ".join(f"{k} {v:.3f}" for k, v in session.facts.items()) + f" total_s {setup_s:.3f}")
        for line in window.request_lines(requests):
            print(line, flush=True)
        request_bytes = session.request_bytes() if session.kept else 0
        facts, client_s = dict(session.facts), list(getattr(session, "client_s", []))
        session.release()  # the program's state goes before the reference runs
        t0 = time.perf_counter()
        checks = compare.judge(session.check(), cell["config"]["limits"])
        note(f"reference and comparison took {time.perf_counter() - t0:.1f}s")
    finally:
        session.close()
        shutil.rmtree(os.path.join(session.workdir, "profile"), ignore_errors=True)

    done = [r for r in requests if r.rows]
    correct = bool(done) and failed[0] == 0 and all(c["ok"] for c in checks)
    result = {"correct": correct, "attempted": len(requests), "failed": failed[0]}
    dev = dict(session.device, memory_peak_bytes=peak)
    if args.rehearse:
        # a rehearsal names no device metric and can not say `correct`
        result = {"rehearsal": True, "attempted": len(requests), "failed": failed[0],
                  "checks_pass": correct, "traced": trace}
    elif not trace:
        values = {"latency_p50_ms": window.latency_p50_ms(done), "rows_per_s": window.rows_per_s(done),
                  "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]} for m in cell["end_to_end"]}
        result["device"] = dev
    if trace:
        tr, spans = traced
        lo, hi = wall0 + (requests[0].start - pc0), wall0 + (requests[-1].end - pc0)
        w0, w1 = tracered.to_trace_ns(tr, lo), tracered.to_trace_ns(tr, hi)
        spans = [s for s in spans if lo - 1e-3 <= s["ts"] <= hi]
        busy_s, window_s = tracered.busy_seconds(tr, w0, w1), (w1 - w0) / 1e9
        ctx = {"config": cell["config"], "traffic": cell["traffic"], "device": session.device,
               "requests": requests, "spans": spans, "trace": tr, "w0_ns": w0, "w1_ns": w1,
               "busy_s": busy_s, "window_s": window_s, "facts": facts, "client_s": client_s,
               "request_bytes": request_bytes, "memory_peak_bytes": peak,
               "rows_per_s": window.rows_per_s(done) if done else None}
        note(f"trace: planes {tr.get('planes_seen')}")
        if args.rehearse:
            result["per_layer_read"] = sorted(per_layer(cell, ctx))
        else:
            result["metrics"] = per_layer(cell, ctx)
            result["device"] = dict(dev, busy_s=busy_s, window_s=window_s)
            result["breakdown"] = {"device_ops": tracered.top_device_ops(tr, w0, w1),
                                   "idle_gaps": tracered.idle_by_span(tr, spans, w0, w1)}
        if args.save_trace:
            os.makedirs(os.path.dirname(os.path.abspath(args.save_trace)), exist_ok=True)
            with open(args.save_trace, "w") as f:
                json.dump({"trace": {k: v for k, v in tr.items() if k != "planes_seen"}, "spans": spans,
                           "window_ns": [w0, w1], "requests": len(requests)}, f)
    result["checks"] = [{k: c[k] for k in ("name", "value", "limit")} for c in checks]
    for c in checks:  # each number compared beside its limit: the last lines on stderr
        print(f"check {c['name']} value {c['value']!r} limit {c['limit']!r} {'ok' if c['ok'] else 'FAIL'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0 if (correct or not args.rehearse) else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on the CPU: control flow only, no metric, no `correct`")
    ap.add_argument("--save-trace", help="with --trace 1: write the reduced trace and spans here as JSON")
    return run(ap.parse_args())


if __name__ == "__main__":
    sys.exit(main())
