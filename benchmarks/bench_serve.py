"""Serving benchmark: sustained QPS + tail latency for a mixed TPC
q1/q6/q98 workload at fixed offered load, with a chaos-under-load tier
(ISSUE 8).

Two modes, both emitting BENCH rows (JSON lines, the bench.py /
bench_pool.py discipline; ``SRJT_RESULTS`` appends them to a file):

- **steady** (default): N queries submitted at ``--offered-qps``
  across ``--tenants`` tenants into a ``serve.Scheduler``; every
  completed query is verified BIT-IDENTICAL to its sequential oracle
  before it counts. The row carries sustained QPS and p50/p99/p999
  end-to-end latency (queue wait included — that is what a caller
  sees).
- **chaos** (``--chaos``): the same workload while
  ``ci/chaos_serve.json`` storms the runtime — `reject` sheds at the
  serve.admit choke point, retryable + delay + hang faults on the ops
  the queries cross, and `crash` (kill -9 before answering) inside a
  REAL sidecar worker pool of ``--pool-size`` that every query also
  routes one arena op through. Asserts: zero wrong answers, every shed
  surfaced as retryable ``Overloaded`` (never a timeout), bounded
  p999 (<= the per-query deadline), ``serve.shed_total > 0``, and
  ``sidecar.pool.failovers > 0`` (the storm really fired). Exit 1 on
  any violation — this is the premerge serve tier's gate.
- **gray** (``--gray``, ISSUE 9): the same workload while
  ``ci/chaos_gray.json`` ramps ONE worker of the real pool into
  persistent slowness (the per-worker ``@w1`` fault keys — a gray
  failure, not a crash). Asserts the tail-tolerance contract: zero
  wrong answers (every completed query bit-identical), p999 <= the
  deadline, the slow worker QUARANTINED (quarantines >= 1) and later
  REINSTATED after the ramp ends, hedged dispatch WON at least one
  race, and the hedge volume stayed within its configured budget.
  Exit 1 on any violation — the premerge gray tier's gate.

- **cache** (``--cache``, ISSUE 17): a mixed plan-IR workload (q1/q6
  shapes over lineitem + a q98-style star over the store tables) with
  literal values cycling over a few bindings, submitted in duplicate
  bursts through a cache-armed scheduler TWICE — cold (empty caches)
  then warm (same submissions again). Every completed query is
  verified bit-identical to its sequential *uncached* oracle. The
  ``serve_cached_qps`` BENCH row carries warm QPS, the cold/warm
  speedup, warm plan-cache hit rate, in-flight shares, and p50/p99 for
  both passes. Gates (exit 1): zero wrong answers, warm hit rate >=
  0.8, warm QPS >= 3x cold at equal-or-better p99, ``cache.share`` >
  0. With ``--chaos`` the ``ci/chaos_cache.json`` eviction/spill/
  reject storm runs during BOTH passes and only the zero-wrong-answers
  + evictions-landed gates apply (hit economics are meaningless while
  entries are being shot down).

The chaos, gray and pool tiers are CPU functional gates, not device
measurements: this process imports the models (which initialises the
JAX backend at import) and then starts a ``SidecarPool``. A chip has
one owner, so on a TPU the pool refuses to start from here
(``sidecar_pool._refuse_second_chip_owner``); rows from these tiers
name no device and are not speed results.

Usage::

    python benchmarks/bench_serve.py                      # steady BENCH row
    python benchmarks/bench_serve.py --chaos --pool-size 2
    python benchmarks/bench_serve.py --cache              # cold/warm cache row
    python benchmarks/bench_serve.py --cache --chaos      # eviction storm
    SRJT_METRICS_ENABLED=1 SRJT_METRICS_LOG=artifacts/serve_metrics.jsonl \
        python benchmarks/bench_serve.py --chaos
"""

from __future__ import annotations

import argparse
import json
import os
import struct
import sys
import time

os.environ.setdefault("SRJT_METRICS_ENABLED", "1")  # counters feed the rows

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from spark_rapids_jni_tpu import serve
from spark_rapids_jni_tpu.models import tpcds, tpch
from spark_rapids_jni_tpu.utils import faultinj, knobs, metrics, retry, tracing
from spark_rapids_jni_tpu.utils.errors import (
    DeadlineExceeded,
    Overloaded,
)

_CHAOS_PROFILE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "ci", "chaos_serve.json",
)
_GRAY_PROFILE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "ci", "chaos_gray.json",
)
_CACHE_PROFILE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "ci", "chaos_cache.json",
)


def _emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)
    out_path = knobs.get_str("SRJT_RESULTS")
    if out_path:
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")


def _counter(name: str) -> int:
    return metrics.registry().value(name)


def _tables_equal(got, want) -> bool:
    if got.names != want.names or got.num_rows != want.num_rows:
        return False
    for n in want.names:
        if not np.array_equal(
            np.asarray(got.column(n).data), np.asarray(want.column(n).data)
        ):
            return False
    return True


def _groupby_payload(n: int = 400, k: int = 16, seed: int = 3) -> bytes:
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, k, n).astype(np.int64)
    vals = rng.standard_normal(n).astype(np.float32)
    return struct.pack("<IQ", k, n) + keys.tobytes() + vals.tobytes()


class _Workload:
    """The mixed q1/q6/q98 query set: oracles computed once
    sequentially (which also warms every XLA compile cache), then each
    query re-runs the pipeline and verifies bit-identical before
    counting as completed."""

    def __init__(self, rows: int, seed: int, pool=None, pool_payload=None,
                 pool_want=None, pool_ops: int = 1):
        self.lineitem = tpch.gen_lineitem(rows, seed=seed)
        self.store = tpcds.gen_store(max(rows // 2, 1000), seed=seed)
        t0 = time.perf_counter()
        self.want_q1 = tpch.q1(self.lineitem)
        self.want_q6 = tpch.q6(self.lineitem)
        self.want_q98 = tpcds.q98(self.store)
        self.oracle_secs = time.perf_counter() - t0
        self.pool = pool
        self.pool_payload = pool_payload
        self.pool_want = pool_want
        self.pool_ops = int(pool_ops)
        self.wrong: list = []
        self.end_times: dict = {}

    def _pool_leg(self):
        """The device-path leg under chaos: ``pool_ops`` arena ops
        through the REAL worker pool, each answer checked against the
        host oracle — a kill -9 mid-request must surface as a healed
        failover and a gray worker's straggler as a quarantine or a
        lost hedge race, never a wrong answer."""
        if self.pool is None:
            return
        from spark_rapids_jni_tpu import sidecar

        for _ in range(self.pool_ops):
            got = self.pool.call_arena(
                sidecar.OP_GROUPBY_SUM_F32, self.pool_payload
            )
            if got != self.pool_want:
                self.wrong.append("pool groupby diverged from host oracle")

    def make(self, kind: str, qid: int):
        def run():
            if kind == "q1":
                if not _tables_equal(tpch.q1(self.lineitem), self.want_q1):
                    self.wrong.append(f"{qid}: q1 diverged")
            elif kind == "q6":
                if tpch.q6(self.lineitem) != self.want_q6:
                    self.wrong.append(f"{qid}: q6 diverged")
            else:
                if not _tables_equal(tpcds.q98(self.store), self.want_q98):
                    self.wrong.append(f"{qid}: q98 diverged")
            self._pool_leg()
            self.end_times[qid] = time.perf_counter()
            return kind

        return run


def run_bench(args) -> int:
    pool = None
    pool_payload = pool_want = None
    storm = args.chaos or args.gray
    profile = args.profile or (_GRAY_PROFILE if args.gray else _CHAOS_PROFILE)
    if storm:
        faultinj.configure_from_file(profile)
        if not retry.is_enabled():
            # the chaos tier is meaningless without the recovery loop
            retry.configure(max_attempts=10, base_delay_ms=2,
                            max_delay_ms=50, seed=17)
            retry.enable()
        if args.pool_size > 0:
            from spark_rapids_jni_tpu import sidecar, sidecar_pool

            pool_payload = _groupby_payload()
            pool_want = sidecar._dispatch(
                sidecar.OP_GROUPBY_SUM_F32, pool_payload, "cpu"
            )
            pool = sidecar_pool.SidecarPool(
                size=args.pool_size, deadline_s=60, heartbeat_s=1e9,
                startup_timeout_s=args.startup_timeout,
                env={"SRJT_FAULTINJ_CONFIG": profile},
            )
            pool.call_arena(sidecar.OP_GROUPBY_SUM_F32, pool_payload)

    wl = _Workload(args.rows, args.seed, pool, pool_payload, pool_want,
                   pool_ops=args.pool_ops)
    print(f"# oracles computed sequentially in {wl.oracle_secs:.1f}s "
          f"(compile-warm)", flush=True)

    sched = serve.Scheduler(
        max_concurrent=args.max_concurrent,
        queue_depth=args.queue_depth,
        name="bench",
    )
    mix = ["q1", "q6", "q1", "q6", "q98"]
    handles = {}
    submit_times = {}
    shed: dict = {}
    bad_shed: list = []
    t0 = time.perf_counter()
    try:
        for i in range(args.queries):
            t_next = t0 + i / args.offered_qps
            dt = t_next - time.perf_counter()
            if dt > 0:
                time.sleep(dt)
            kind = mix[i % len(mix)]
            tenant = f"tenant{i % args.tenants}"
            try:
                submit_times[i] = time.perf_counter()
                handles[i] = sched.submit(
                    wl.make(kind, i),
                    tenant=tenant,
                    deadline_s=args.deadline_s,
                    priority=5 if i % 11 == 0 else 0,
                )
            except Overloaded as e:
                shed[e.cause] = shed.get(e.cause, 0) + 1
            except Exception as e:  # a shed MUST be Overloaded, period
                bad_shed.append(f"{i}: {type(e).__name__}: {e}")

        completed = {}
        failures: dict = {}
        for i, h in sorted(handles.items()):
            try:
                completed[i] = h.result(args.deadline_s + 60)
            except Overloaded as e:
                # evicted from the queue by a higher-priority arrival
                shed[e.cause] = shed.get(e.cause, 0) + 1
            except DeadlineExceeded:
                failures["deadline_exceeded"] = (
                    failures.get("deadline_exceeded", 0) + 1
                )
            except Exception as e:
                failures[type(e).__name__] = (
                    failures.get(type(e).__name__, 0) + 1
                )
                bad_shed.append(f"{i}: {type(e).__name__}: {e}")
        t_last = max(wl.end_times.values()) if wl.end_times else t0
        if args.gray and pool is not None:
            # the gray contract includes the RECOVERY: the ramp's fault
            # budget has exhausted by now, so the background probes must
            # reinstate the quarantined worker — wait (bounded) for the
            # probe loop to finish its clean run
            wait_end = time.perf_counter() + args.gray_wait
            while time.perf_counter() < wait_end and (
                _counter("sidecar.pool.quarantines") == 0
                or _counter("sidecar.pool.reinstatements") == 0
            ):
                time.sleep(0.2)
    finally:
        sched.shutdown(drain=False, timeout_s=60)
        if pool is not None:
            pool.shutdown()
        faultinj.disable()

    lat_ms = sorted(
        (wl.end_times[i] - submit_times[i]) * 1e3 for i in completed
    )
    if lat_ms:
        p50, p99, p999 = np.percentile(lat_ms, [50, 99, 99.9])
    else:
        p50 = p99 = p999 = float("nan")
    span = max(t_last - t0, 1e-9)
    qps = len(completed) / span
    shed_total = _counter("serve.shed_total")
    failovers = _counter("sidecar.pool.failovers")
    quarantines = _counter("sidecar.pool.quarantines")
    reinstatements = _counter("sidecar.pool.reinstatements")
    hedges_launched = _counter("sidecar.pool.hedges_launched")
    hedges_won = _counter("sidecar.pool.hedges_won")
    pool_calls = _counter("sidecar.pool.calls")
    from spark_rapids_jni_tpu.utils import knobs as knobs_mod

    hedge_budget_pct = knobs_mod.get_float("SRJT_HEDGE_BUDGET_PCT")
    row = {
        "metric": "serve_gray_qps" if args.gray else "serve_mixed_qps",
        "value": round(qps, 2),
        "unit": "qps",
        "offered_qps": args.offered_qps,
        "queries": args.queries,
        "completed": len(completed),
        "shed": sum(shed.values()),
        "shed_causes": shed,
        "failures": failures,
        "wrong_answers": len(wl.wrong),
        "p50_ms": round(float(p50), 2),
        "p99_ms": round(float(p99), 2),
        "p999_ms": round(float(p999), 2),
        "deadline_s": args.deadline_s,
        "max_concurrent": args.max_concurrent,
        "tenants": args.tenants,
        "rows": args.rows,
        "chaos": bool(args.chaos),
        "gray": bool(args.gray),
        "pool_size": args.pool_size if storm else 0,
        "failovers": failovers,
        "shed_total_counter": shed_total,
        "expired_in_queue": _counter("serve.expired_in_queue"),
        "quarantines": quarantines,
        "reinstatements": reinstatements,
        "hedges_launched": hedges_launched,
        "hedges_won": hedges_won,
        "hedges_cancelled": _counter("sidecar.pool.hedges_cancelled"),
        "hedges_suppressed": _counter("sidecar.pool.hedges_suppressed"),
        "pool_calls": pool_calls,
        "hedge_budget_pct": hedge_budget_pct,
        "adaptive_timeout_clamps": _counter("sidecar.adaptive_timeout_clamps"),
        "bit_identical": not wl.wrong,
    }
    _emit(row)
    if metrics.is_enabled():
        _emit({"metrics": metrics.stage_report("serve_bench")})
    if tracing.is_enabled():
        # per-stage trace summary (ISSUE 12): span, trace and
        # flushed-trace counts next to the metrics line (which span
        # grew is read from the span log)
        from spark_rapids_jni_tpu.utils import trace_sink

        _emit({"trace": {"stage": "serve_bench",
                         **trace_sink.stage_summary()}})

    rc = 0
    if wl.wrong:
        print(f"WRONG ANSWERS ({len(wl.wrong)}): {wl.wrong[:5]}",
              file=sys.stderr)
        rc = 1
    if bad_shed:
        print(f"non-Overloaded admission failures: {bad_shed[:5]}",
              file=sys.stderr)
        rc = 1
    if storm:
        # invariants shared by both storm tiers: bounded tails, and a
        # workload that actually ran
        tier = "gray" if args.gray else "chaos"
        if lat_ms and p999 > args.deadline_s * 1e3:
            print(f"p999 {p999:.0f} ms exceeds the {args.deadline_s}s "
                  f"deadline under the {tier} storm: enforcement broke",
                  file=sys.stderr)
            rc = 1
        if not completed:
            print(f"{tier} tier completed zero queries", file=sys.stderr)
            rc = 1
    if args.chaos:
        if shed_total <= 0:
            print("chaos tier shed nothing (serve.shed_total == 0)",
                  file=sys.stderr)
            rc = 1
        if args.pool_size > 0 and failovers <= 0:
            print("crash storm produced no pool failover", file=sys.stderr)
            rc = 1
    if args.gray:
        if quarantines <= 0:
            print("gray storm quarantined nothing "
                  "(sidecar.pool.quarantines == 0)", file=sys.stderr)
            rc = 1
        if reinstatements <= 0:
            print("quarantined worker never reinstated after the ramp "
                  "(sidecar.pool.reinstatements == 0)", file=sys.stderr)
            rc = 1
        if hedges_won <= 0:
            print("hedged dispatch won no race "
                  "(sidecar.pool.hedges_won == 0)", file=sys.stderr)
            rc = 1
        # the hedge budget is a hard ceiling on extra dispatch volume
        if hedges_launched * 100.0 > hedge_budget_pct * max(pool_calls, 1):
            print(f"hedge volume {hedges_launched} of {pool_calls} calls "
                  f"exceeds the {hedge_budget_pct}% budget", file=sys.stderr)
            rc = 1
    return rc


_CACHE_COUNTERS = (
    "cache.hits", "cache.misses", "cache.rebinds", "cache.rebind_fallbacks",
    "cache.share", "cache.share_fallback", "cache.sub_hits",
    "cache.sub_misses", "cache.evictions", "cache.sub_evictions",
    "cache.evict_injected", "cache.insert_verified", "cache.insert_rejected",
)


def _cache_combos(rows: int, seed: int):
    """The parameterized workload: three plan STRUCTURES, four literal
    BINDINGS each (12 combos). Within a structure only literal values
    differ, so after the first full compile the plan cache serves the
    other three bindings via the rebind path, and a repeat of any combo
    is an exact-variant hit."""
    from spark_rapids_jni_tpu import plan as P

    lineitem = {"lineitem": tpch.gen_lineitem(rows, seed=seed)}
    store = dict(tpcds.gen_store(max(rows // 2, 1000), seed=seed))

    def q1_like(qty):
        return P.Aggregate(
            P.Filter(P.Scan("lineitem"),
                     P.pcol("l_quantity") < P.plit(qty)),
            keys=("l_returnflag", "l_linestatus"),
            aggs=(P.AggSpec("l_extendedprice", "sum", "sum_price"),
                  P.AggSpec("l_quantity", "sum", "sum_qty")),
        )

    def q6_like(disc):
        return P.Aggregate(
            P.Filter(P.Scan("lineitem"),
                     (P.pcol("l_discount") >= P.plit(0.02))
                     & (P.pcol("l_discount") <= P.plit(disc))
                     & (P.pcol("l_quantity") < P.plit(24.0))),
            keys=(),
            aggs=(P.AggSpec("l_extendedprice", "sum", "revenue"),),
        )

    def q98_like(moy):
        return P.Aggregate(
            P.Join(
                P.Join(P.Scan("store_sales"),
                       P.Filter(P.Scan("date_dim"),
                                P.pcol("d_moy") == P.plit(moy)),
                       on=(("ss_sold_date_sk", "d_date_sk"),)),
                P.Scan("item"),
                on=(("ss_item_sk", "i_item_sk"),),
            ),
            keys=("i_category_id",),
            aggs=(P.AggSpec("ss_ext_sales_price", "sum", "sales"),),
        )

    combos = []
    for qty in (24.0, 25.0, 26.0, 27.0):
        combos.append(("q1", q1_like(qty), lineitem))
    for disc in (0.04, 0.05, 0.06, 0.07):
        combos.append(("q6", q6_like(disc), lineitem))
    for moy in (1, 2, 3, 4):
        combos.append(("q98", q98_like(moy), store))
    return combos


def _cache_pass(combos, oracles, dup: int, deadline_s: float,
                max_concurrent: int, queue_depth: int, label: str):
    """Submit every combo in a burst of ``dup`` duplicates through a
    fresh cache-armed scheduler; harvest each handle on its own thread
    so the recorded latency is submit -> result() return (compile /
    cache lookup happens inside submit, so a cold compile is charged to
    the query that paid it). Returns (latencies_ms, wrong, shed,
    failed, span_s)."""
    import threading

    sched = serve.Scheduler(max_concurrent=max_concurrent,
                            queue_depth=queue_depth,
                            name=f"cache-{label}")
    lat_ms: list = []
    wrong: list = []
    failed: list = []
    shed = [0]
    lock = threading.Lock()
    harvesters = []

    def harvest(h, t_submit, cid):
        try:
            got = h.result(deadline_s + 60)
        except Overloaded:
            with lock:
                shed[0] += 1
            return
        except Exception as e:
            with lock:
                failed.append(f"{cid}: {type(e).__name__}: {e}")
            return
        t_done = time.perf_counter()
        ok = _tables_equal(got, oracles[cid])
        with lock:
            lat_ms.append((t_done - t_submit) * 1e3)
            if not ok:
                wrong.append(f"{label}/{cid}: diverged from uncached "
                             f"oracle")

    t0 = time.perf_counter()
    try:
        for cid, (kind, node, tables) in enumerate(combos):
            for d in range(dup):
                t_submit = time.perf_counter()
                try:
                    h = sched.submit(node, tables,
                                     tenant=f"t{(cid + d) % 3}",
                                     deadline_s=deadline_s)
                except Overloaded:
                    with lock:
                        shed[0] += 1
                    continue
                th = threading.Thread(target=harvest,
                                      args=(h, t_submit, cid),
                                      name=f"harvest-{label}-{cid}-{d}")
                th.start()
                harvesters.append(th)
        for th in harvesters:
            th.join(deadline_s + 120)
    finally:
        sched.shutdown(drain=False, timeout_s=60)
    return lat_ms, wrong, shed[0], failed, max(time.perf_counter() - t0,
                                               1e-9)


def run_cache_bench(args) -> int:
    """--cache (ISSUE 17): cold/warm serving through the plan +
    subresult caches, bit-exactness against uncached oracles, and the
    warm-economics gates (or the storm-survival gates with --chaos)."""
    os.environ.setdefault("SRJT_PLAN_CACHE", "1")
    os.environ.setdefault("SRJT_SUBRESULT_CACHE", "1")
    from spark_rapids_jni_tpu import cache as srjt_cache
    from spark_rapids_jni_tpu import plan as P

    srjt_cache.reset()
    combos = _cache_combos(args.rows, args.seed)
    # uncached sequential oracles FIRST (also warms the XLA compile
    # cache, so the cold pass measures the cache subsystem's own costs,
    # not first-touch device compilation)
    t0 = time.perf_counter()
    oracles = {
        cid: P.compile_ir(node, tables, name=f"oracle.{kind}{cid}")()
        for cid, (kind, node, tables) in enumerate(combos)
    }
    print(f"# {len(combos)} uncached oracles in "
          f"{time.perf_counter() - t0:.1f}s (compile-warm)", flush=True)

    profile = args.profile or _CACHE_PROFILE
    if args.chaos:
        faultinj.configure_from_file(profile)
        if not retry.is_enabled():
            retry.configure(max_attempts=10, base_delay_ms=2,
                            max_delay_ms=50, seed=17)
            retry.enable()

    before = {n: _counter(n) for n in _CACHE_COUNTERS}
    passes = {}
    try:
        for label in ("cold", "warm"):
            lat, wrong, shed, failed, span = _cache_pass(
                combos, oracles, args.cache_dup, args.deadline_s,
                args.max_concurrent, args.queue_depth, label)
            snap = {n: _counter(n) for n in _CACHE_COUNTERS}
            delta = {n: snap[n] - before[n] for n in _CACHE_COUNTERS}
            before = snap
            passes[label] = {
                "lat": lat, "wrong": wrong, "shed": shed,
                "failed": failed, "span": span, "delta": delta,
            }
    finally:
        faultinj.disable()

    cold, warm = passes["cold"], passes["warm"]
    offered = len(combos) * args.cache_dup

    def pcts(lat):
        if not lat:
            return float("nan"), float("nan")
        p50, p99 = np.percentile(lat, [50, 99])
        return float(p50), float(p99)

    cold_p50, cold_p99 = pcts(cold["lat"])
    warm_p50, warm_p99 = pcts(warm["lat"])
    cold_qps = len(cold["lat"]) / cold["span"]
    warm_qps = len(warm["lat"]) / warm["span"]
    wd = warm["delta"]
    warm_lookups = wd["cache.hits"] + wd["cache.misses"]
    hit_rate = wd["cache.hits"] / warm_lookups if warm_lookups else 0.0
    share = (cold["delta"]["cache.share"] + wd["cache.share"])
    evict_injected = (cold["delta"]["cache.evict_injected"]
                      + wd["cache.evict_injected"])
    wrong = cold["wrong"] + warm["wrong"]
    failed = cold["failed"] + warm["failed"]
    speedup = warm_qps / cold_qps if cold_qps > 0 else float("inf")

    row = {
        "metric": "serve_cached_qps",
        "value": round(warm_qps, 2),
        "unit": "qps",
        "cold_qps": round(cold_qps, 2),
        "speedup": round(speedup, 2),
        "hit_rate": round(hit_rate, 4),
        "share": share,
        "offered_per_pass": offered,
        "completed_cold": len(cold["lat"]),
        "completed_warm": len(warm["lat"]),
        "shed_cold": cold["shed"],
        "shed_warm": warm["shed"],
        "wrong_answers": len(wrong),
        "cold_p50_ms": round(cold_p50, 2),
        "cold_p99_ms": round(cold_p99, 2),
        "warm_p50_ms": round(warm_p50, 2),
        "warm_p99_ms": round(warm_p99, 2),
        "cold_counters": cold["delta"],
        "warm_counters": wd,
        "chaos": bool(args.chaos),
        "rows": args.rows,
        "dup": args.cache_dup,
        "bit_identical": not wrong,
    }
    _emit(row)
    if metrics.is_enabled():
        _emit({"metrics": metrics.stage_report("serve_cache_bench")})

    rc = 0
    if wrong:
        print(f"WRONG ANSWERS ({len(wrong)}): {wrong[:5]}",
              file=sys.stderr)
        rc = 1
    if failed:
        print(f"unexpected failures ({len(failed)}): {failed[:5]}",
              file=sys.stderr)
        rc = 1
    if not cold["lat"] or not warm["lat"]:
        print("cache bench completed zero queries in a pass",
              file=sys.stderr)
        rc = 1
    if args.chaos:
        # storm gates only: the economics gates below are meaningless
        # while cache_evict is shooting entries down mid-lookup
        if evict_injected <= 0:
            print("chaos storm injected no cache eviction "
                  "(cache.evict_injected == 0)", file=sys.stderr)
            rc = 1
    else:
        if hit_rate < 0.8:
            print(f"warm hit rate {hit_rate:.2f} < 0.8", file=sys.stderr)
            rc = 1
        if warm_qps < 3.0 * cold_qps:
            print(f"warm {warm_qps:.1f} qps < 3x cold {cold_qps:.1f} qps",
                  file=sys.stderr)
            rc = 1
        if warm_p99 > cold_p99:
            print(f"warm p99 {warm_p99:.1f} ms worse than cold "
                  f"{cold_p99:.1f} ms", file=sys.stderr)
            rc = 1
        if share <= 0:
            print("duplicate bursts never shared an in-flight "
                  "computation (cache.share == 0)", file=sys.stderr)
            rc = 1
    return rc


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=50_000,
                    help="lineitem rows (store fact is rows/2)")
    ap.add_argument("--queries", type=int, default=120)
    ap.add_argument("--offered-qps", type=float, default=30.0,
                    help="fixed offered load (arrival schedule)")
    ap.add_argument("--max-concurrent", type=int, default=4)
    ap.add_argument("--queue-depth", type=int, default=32)
    ap.add_argument("--tenants", type=int, default=3)
    ap.add_argument("--deadline-s", type=float, default=30.0,
                    help="per-query budget, spanning queue wait")
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--chaos", action="store_true",
                    help="arm ci/chaos_serve.json while serving and "
                    "gate on the chaos invariants")
    ap.add_argument("--gray", action="store_true",
                    help="arm ci/chaos_gray.json (one ramped-slow "
                    "worker) and gate on the tail-tolerance "
                    "invariants: quarantine + reinstate + hedges won")
    ap.add_argument("--cache", action="store_true",
                    help="cold/warm cached-serving tier (ISSUE 17): "
                    "plan + subresult caches armed, duplicate bursts, "
                    "bit-exactness vs uncached oracles; with --chaos, "
                    "arms ci/chaos_cache.json instead")
    ap.add_argument("--cache-dup", type=int, default=4,
                    help="duplicate submissions per combo burst (the "
                    "in-flight sharing pressure)")
    ap.add_argument("--gray-wait", type=float, default=45.0,
                    help="max seconds to wait post-workload for the "
                    "quarantined worker's reinstatement")
    ap.add_argument("--profile", default=None,
                    help="chaos profile path (default ci/chaos_serve."
                    "json, or ci/chaos_gray.json with --gray)")
    ap.add_argument("--pool-size", type=int, default=2,
                    help="REAL sidecar workers for the chaos crash leg "
                    "(0 = no pool)")
    ap.add_argument("--pool-ops", type=int, default=1,
                    help="arena ops per query through the pool (the "
                    "gray tier raises this so the health scorer sees "
                    "enough samples)")
    ap.add_argument("--startup-timeout", type=float, default=180.0)
    args = ap.parse_args()
    if args.cache:
        return run_cache_bench(args)
    return run_bench(args)


if __name__ == "__main__":
    sys.exit(main())
