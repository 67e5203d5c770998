#!/usr/bin/env python3
"""PR 27: how often the kept request buffer and the gathered reply engage.

Opens the rowconv cell's own session (bench/drivers/sidecar.py: two warm-up
requests), then reads the worker's STATS before and after N requests and
prints how far the four counters moved, and the governor's scratch entry.

    python3 benchmarks/calls/pr27_counters.py [--requests 4] [--seed N] [--rehearse]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "bench"))

COUNTERS = (
    "sidecar.worker.scratch.grows",
    "sidecar.worker.scratch.reuses",
    "sidecar.worker.reply.gathered_bytes",
    "sidecar.worker.reply.joined_bytes",
    "sidecar.worker.requests.CONVERT_TO_ROWS",
    "sidecar.worker.requests.STATS",
    "sidecar.worker.requests.PING",
)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--seed", type=int, default=2710000019)
    ap.add_argument("--rehearse", action="store_true", help="tiny size on the CPU")
    args = ap.parse_args()
    from benchlib import loader

    session = loader.open_session(
        loader.cell("rowconv-212x1m.to-rows"), args.seed, args.rehearse, False, tag="pr27-"
    )
    try:
        session.setup()

        def read():
            stats = session.pool.worker_stats(fold=False)["w0"]
            counters = stats["snapshot"]["counters"]
            # the worker's reply is json.dumps of this dict with the same defaults
            return {c: counters.get(c, 0) for c in COUNTERS}, stats["memgov"], len(json.dumps(stats))

        before, _, first_stats_len = read()
        ms = []
        for i in range(args.requests):
            t0 = time.perf_counter()
            session.issue(i)
            ms.append(round((time.perf_counter() - t0) * 1e3, 1))
        after, memgov, _ = read()
        print(json.dumps({
            "device": session.device,
            "requests": args.requests,
            "request_ms": ms,
            "payload_bytes": len(session.payload),
            "reply_bytes": session.reply_bytes,
            "before": before,
            "moved": {c: after[c] - before[c] for c in COUNTERS},
            # the only one-object reply between the two readings is the first STATS reply itself
            "joined_bytes_less_first_stats_reply": after[COUNTERS[3]] - before[COUNTERS[3]] - first_stats_len,
            "memgov_catalog": memgov.get("catalog"),
        }))
    finally:
        if session.pool is not None:
            session.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
