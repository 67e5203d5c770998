#!/usr/bin/env python3
"""Read the two ends a limit is set between, in one process.

For each seed: the cell's own set-up, a short window at the cell's own
load, then the program's answers against the plain reference (the lower
reading) and the control in the program's place (the upper reading): the
reference computed in float32 where the configuration states float64; for
the transcode, the reference with one stated guarantee broken (nulls not
honoured). Prints one JSON line a seed and a summary; never a result
line of the benchmark.

    python3 bench/control.py --workload tpch-sf1.q1 --seeds 12 --control-seeds 3 --seconds 1
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import numpy as np  # noqa: E402

from benchlib import loader, window  # noqa: E402


def readings(workload: str, seed: int, seconds: float, rehearse: bool, control: bool, prepare=None) -> dict:
    """{"program": {...}, "control": {...}} for one seed. ``prepare(session)``
    runs after set-up (the tests break the timed path there)."""
    session = loader.open_session(loader.cell(workload), seed, rehearse, False, tag="control-")
    try:
        session.setup()
        if prepare is not None:
            prepare(session)
        requests = window.closed_loop(session.issue, seconds, session.keep)
        session.release()
        out = {"seed": seed, "requests": len(requests), "program": session.check()}
        if control:
            out["control"] = session.check(substitute=np.float32)
        return out
    finally:
        session.close()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    lows, highs = {}, {}
    for k in range(args.seeds):
        seed = args.first_seed + 7919 * k
        r = readings(args.workload, seed, args.seconds, args.rehearse, k < args.control_seeds)
        print(json.dumps(r), flush=True)
        for name, v in r["program"].items():
            lows[name] = max(lows.get(name, 0.0), v)
        for name, v in r.get("control", {}).items():
            highs[name] = min(highs.get(name, float("inf")), v)
    print(json.dumps({"workload": args.workload, "lower_largest_program": lows, "upper_smallest_control": highs}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
