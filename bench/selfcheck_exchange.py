#!/usr/bin/env python3
"""Check the exchange layer's three readers, by hand, on the CPU: no chip,
no JAX (``bench/selfcheck.py``'s manner, beside it because that file is
not this PR's to edit).

    python3 bench/selfcheck_exchange.py

1. On the recorded trace and span log of one traced request of
   ``tpcds-sf10-web.q95-x4`` (bench/fixtures/q95-x4.exchange.json, cut
   down from a chip run to the spans and device operations the readers
   look at), ``exchange_ms``, ``exchange_collective_ms`` and
   ``exchange_ici_share`` give the numbers recorded beside it, every time.
2. A trace with no all-to-all in it reads ``None`` for both device
   metrics, never 0; so does a run whose driver counted no exchange.
3. The byte count is the schema's: rows x lane bytes x 3/4 over 4 chips.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import numpy as np  # noqa: E402

from benchlib import exchange_bytes, loader  # noqa: E402

READERS = ("exchange_ms", "exchange_collective_ms", "exchange_ici_share")


def read_all(ctx: dict) -> dict:
    with contextlib.redirect_stderr(io.StringIO()):  # the share prints what it is made of
        return {name: loader.module("readers", name).read(ctx) for name in READERS}


def context(fx: dict) -> dict:
    w0, w1 = fx["window_ns"]
    return {"spans": fx["spans"], "trace": fx["trace"], "w0_ns": w0, "w1_ns": w1,
            "requests": [None] * fx["requests"], "facts": fx["facts"], "device": fx["device"]}


def check_fixture() -> None:
    with open(os.path.join(BENCH_DIR, "fixtures", "q95-x4.exchange.json")) as f:
        fx = json.load(f)
    with open(os.path.join(BENCH_DIR, "fixtures", "q95-x4.exchange.want.json")) as f:
        want = json.load(f)
    for _ in range(3):  # the same numbers every time
        got = read_all(context(fx))
        assert got == want, f"the readers moved:\n got {got}\nwant {want}"
    assert 0 < got["exchange_ici_share"] <= 100 and got["exchange_collective_ms"] > 0
    print("fixture q95-x4.exchange: " + ", ".join(f"{k} {v:.6g}" for k, v in got.items()))


def check_nothing_to_read() -> None:
    with open(os.path.join(BENCH_DIR, "fixtures", "q95-x4.exchange.json")) as f:
        fx = json.load(f)
    bare = json.loads(json.dumps(fx))
    for dev in bare["trace"]["devices"].values():
        dev["ops"] = [op for op in dev["ops"] if "all-to-all" not in op[0] and "all_to_all" not in op[0]]
    got = read_all(context(bare))
    assert got["exchange_collective_ms"] is None and got["exchange_ici_share"] is None, got
    assert got["exchange_ms"] is not None  # the spans are still there
    uncounted = dict(fx, facts={k: v for k, v in fx["facts"].items() if k != "exchange_bytes_off_chip"})
    assert read_all(context(uncounted))["exchange_ici_share"] is None
    spanless = dict(fx, spans=[s for s in fx["spans"] if not s["name"].startswith("exchange.")])
    assert read_all(context(spanless))["exchange_ms"] is None
    print("no all-to-all in the trace, no count from the driver, no exchange span: None, never 0")


def check_bytes() -> None:
    host = {"t": {"k": np.zeros(8, np.int64), "v": (np.zeros(8, np.float64), np.ones(8, bool)),
                  "w": (np.zeros(8, np.int32), np.ones(8, bool))}}
    assert exchange_bytes.row_bytes(host, "t", ("k", "v", "w")) == 8 + 8.125 + 4.125
    # 1,000 rows of an int64: 8,000 bytes, of which 3/4 have their home on another chip, over 4 chips
    assert exchange_bytes.off_chip_per_chip(host, [(1000, "t", ("k",))], 4) == 8000 * 0.75 / 4
    assert exchange_bytes.off_chip_per_chip(host, [(1000, "t", ("k",)), (10, "t", ("w",))], 4) == (8000 + 41.25) * 0.75 / 4
    assert exchange_bytes.ici_peak("TPU v5 lite")["ici_bytes_per_s"] == 200e9
    print("bytes: rows x lane bytes x (chips - 1) / chips / chips; TPU v5 lite ICI 200 GB/s")


def main() -> int:
    check_fixture()
    check_nothing_to_read()
    check_bytes()
    print("selfcheck_exchange ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
