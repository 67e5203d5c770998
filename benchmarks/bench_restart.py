"""Restart-recovery benchmark: kill -9 a serving coordinator mid-storm,
then prove the restarted process answers every durably-journaled query
bit-identically with zero duplicate executions (srjt-durable, ISSUE 20).

One scenario, one ``restart_recovery`` BENCH row (JSON lines, the
bench.py discipline; ``SRJT_RESULTS`` appends to a file):

1. **The doomed coordinator** (a child process, journal + spill
   manifests + durable OOC checkpoints armed against shared dirs)
   serves a mixed parameterized-plan storm to completion, runs an
   out-of-core q1 that checkpoints two of four partitions durably and
   then faults mid-stream, parks two opaque blockers on the dispatch
   slots, queues one journaled-but-never-dispatched plan query, arms
   ``ci/chaos_restart.json`` — the next manifest write and the next
   journal append are both TORN mid-frame, exactly what a kill -9
   racing the disk produces — writes one last (torn) submission, and
   SIGKILLs itself.
2. **The recovered coordinator** (this process) replays the journal
   (truncating the torn tail), re-attaches the surviving checkpoint
   frames via the manifest scan, answers every DONE query from its
   journaled digest (verified against a freshly computed oracle's
   bits), refuses to invent the torn submission, resubmits the
   incomplete plan query through the rebind path, and resumes the
   out-of-core query past the two re-attached partitions
   (``ooc.partition_resumes`` crossing processes).

A CPU functional gate: the doomed child is pinned to
``JAX_PLATFORMS=cpu`` (a parent that has touched the device holds the
chip, so the child could not have it), and the row is no device number.

Gates (exit 1): zero wrong answers, ``replays`` == 1 with a truncated
tail, ``reattached`` > 0, ``resumes`` > 0, manifest rot counted on the
torn sidecar, zero duplicate executions of DONE work, and the torn
submission absent from recovery. The row also carries a journal-on vs
journal-off p50 submit-latency probe (report-only; the off posture's
serving economics are gated by the premerge serve tier, where the
journal is unarmed).

Usage::

    python benchmarks/bench_restart.py
    SRJT_RESULTS=artifacts/restart_metrics.jsonl \
        python benchmarks/bench_restart.py
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

os.environ.setdefault("SRJT_METRICS_ENABLED", "1")  # counters feed the rows
os.environ.setdefault("JAX_PLATFORMS", "cpu")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import numpy as np

from spark_rapids_jni_tpu import memgov, serve
from spark_rapids_jni_tpu import plan as P
from spark_rapids_jni_tpu.columnar import Table
from spark_rapids_jni_tpu.columnar.column import Column
from spark_rapids_jni_tpu.models import tpch
from spark_rapids_jni_tpu.serve import journal as JM
from spark_rapids_jni_tpu.utils import faultinj, knobs, metrics
from spark_rapids_jni_tpu.utils.errors import RetryableError  # noqa: F401 (child leg)

_RESTART_PROFILE = os.path.join(_REPO, "ci", "chaos_restart.json")

# the deterministic mid-stream OOC failure: partitions 0 and 1
# checkpoint (durably), partition 2 faults — shared with the child leg
OOC_FAULT = {"seed": 7, "faults": {"plan.ooc.partition": {
    "type": "retryable", "percent": 100, "after": 2,
    "interceptionCount": 1}}}

# the journaled-but-incomplete submissions: the first survives the
# crash and must be resubmitted bit-identically; the second's journal
# append is torn by ci/chaos_restart.json and must NOT be invented
PENDING = (("pend-keep", 64, 0.5), ("pend-torn", 81, 0.45))


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


# ---------------------------------------------------------------------------
# the workload, importable by BOTH processes (the child does
# ``import bench_restart``) so the plan structures — and so the
# parameterized fingerprints and OOC checkpoint keys — match exactly
# ---------------------------------------------------------------------------


def gen_fact(rows: int, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    return {"fact": Table(
        [Column.from_numpy(np.arange(rows, dtype=np.int64)),
         Column.from_numpy(rng.integers(0, 5, rows).astype(np.int64)),
         Column.from_numpy(rng.random(rows))],
        ["v", "k", "p"])}


def storm_plan(cut, factor):
    """One parameterized structure, many literal bindings: every storm
    query rebinds through the same plan-cache template in recovery."""
    return P.Aggregate(
        P.Filter(P.Scan("fact"),
                 (P.pcol("v") < P.plit(cut))
                 & (P.pcol("p") < P.plit(factor))),
        keys=("k",), aggs=(P.AggSpec("v", "sum", "s"),))


def storm_combos(done: int):
    return [(f"done-{i}", 10 + 7 * i, 0.55 + 0.04 * i) for i in range(done)]


def ooc_ir():
    """TPC-H q1's sort-over-aggregate shape — what ``find_target``
    admits for partitioned out-of-core execution."""
    return P.Sort(
        P.Aggregate(
            P.Filter(P.Scan("lineitem"),
                     P.pcol("l_quantity") >= P.plit(0.0)),
            keys=("l_returnflag", "l_linestatus"),
            aggs=(
                P.AggSpec("l_quantity", "sum", "sum_qty"),
                P.AggSpec("l_extendedprice", "sum", "sum_price"),
                P.AggSpec(None, "count_all", "count_order"),
            ),
        ),
        keys=(("l_returnflag", True), ("l_linestatus", True)),
    )


def gen_ooc_tables(rows: int, seed: int) -> dict:
    return {"lineitem": tpch.gen_lineitem(rows, seed=seed)}


def _noop():
    return 0


# ---------------------------------------------------------------------------
# the doomed coordinator
# ---------------------------------------------------------------------------

_CHILD = """
import os, sys, signal, threading
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, {repo!r})
sys.path.insert(0, {benchdir!r})
import numpy as np
import bench_restart as br
from spark_rapids_jni_tpu import memgov
from spark_rapids_jni_tpu import plan as P
from spark_rapids_jni_tpu.serve.scheduler import Scheduler
from spark_rapids_jni_tpu.utils import faultinj

fact = br.gen_fact({rows}, {seed})
s = Scheduler(max_concurrent=2, name="pre-crash")
handles = []
for idem, cut, factor in br.storm_combos({done}):
    handles.append(s.submit(br.storm_plan(cut, factor), fact,
                            tenant="t%d" % (len(handles) % 3),
                            idempotency_key=idem))
for h in handles:
    h.result(120)

# the OOC leg: two durable partition checkpoints, then a deterministic
# mid-stream fault -- the surviving frames + manifests are what the
# restarted process re-attaches and resumes past
ooc_tabs = br.gen_ooc_tables({ooc_rows}, {seed})
faultinj.configure(br.OOC_FAULT)
with memgov.enabled():
    cp = P.compile_ir(br.ooc_ir(), ooc_tabs, name="restart_ooc")
    assert isinstance(cp, P.OutOfCorePlan), "OOC never armed"
    try:
        cp()
        raise SystemExit("the OOC leg was supposed to fault mid-stream")
    except br.RetryableError:
        pass
faultinj.disable()

# park opaque blockers on both dispatch slots so the final submissions
# stay QUEUED: journaled, never dispatched
gates, started = [], []
for _ in range(2):
    g, st = threading.Event(), threading.Event()
    gates.append(g)
    started.append(st)

    def blk(st=st, g=g):
        st.set()
        g.wait(120)

    s.submit(blk, tenant="t0")
for st in started:
    st.wait(60)
idem, cut, factor = br.PENDING[0]
s.submit(br.storm_plan(cut, factor), fact, tenant="t1",
         idempotency_key=idem)

# the torn-write finale (ci/chaos_restart.json): the next manifest
# write and the next journal append are truncated mid-frame
faultinj.configure_from_file({profile!r})
sac = memgov.catalog().register(
    "restart.sacrificial", [np.arange(32, dtype=np.float64) * 1.5],
    kind="partition")
sac.spill(to_disk=True)                      # torn manifest
idem, cut, factor = br.PENDING[1]
s.submit(br.storm_plan(cut, factor), fact, tenant="t1",
         idempotency_key=idem)               # torn journal append
open(os.path.join({outdir!r}, "ready"), "w").write("1")
os.kill(os.getpid(), signal.SIGKILL)
"""


# ---------------------------------------------------------------------------
# the recovered coordinator
# ---------------------------------------------------------------------------

_COUNTERS = (
    "journal.replays", "journal.replayed_records",
    "journal.truncated_records", "journal.idempotent_hits",
    "journal.recovered_resubmits", "journal.recovery_skipped",
    "memgov.reattached", "memgov.manifest_rot", "memgov.orphans_reclaimed",
    "ooc.partition_resumes",
)


def _submit_p50_ms(name: str, n: int) -> float:
    """Median submit() wall time for trivial queries — the journal's
    admission-path cost when armed (one fsync'd append per submit)."""
    lats = []
    sched = serve.Scheduler(max_concurrent=2, name=name)
    try:
        for _ in range(n):
            t0 = time.perf_counter()
            h = sched.submit(_noop, tenant="probe")
            lats.append((time.perf_counter() - t0) * 1e3)
            h.result(10)
    finally:
        sched.shutdown(drain=False, timeout_s=10)
    return float(np.percentile(lats, 50)) if lats else float("nan")


def run(args) -> int:
    tmp = tempfile.mkdtemp(prefix="srjt-restart-")
    jdir = os.path.join(tmp, "journal")
    sdir = os.path.join(tmp, "spill")
    os.makedirs(jdir)
    os.makedirs(sdir)
    durable_env = {
        "SRJT_JOURNAL_DIR": jdir,
        "SRJT_SPILL_DIR": sdir,
        "SRJT_SPILL_MANIFESTS": "1",
        "SRJT_OOC_DURABLE_CHECKPOINTS": "1",
        "SRJT_OOC_ENABLED": "1",
        "SRJT_OOC_PARTITIONS": "4",
        "SRJT_DEVICE_MEMORY_BUDGET": str(36 * 1024),
        "JAX_PLATFORMS": "cpu",
    }
    wrong: list = []
    try:
        child_src = _CHILD.format(
            repo=_REPO, benchdir=os.path.join(_REPO, "benchmarks"),
            outdir=tmp, profile=_RESTART_PROFILE, rows=args.rows,
            ooc_rows=args.ooc_rows, done=args.done, seed=args.seed)
        t0 = time.perf_counter()
        child = subprocess.Popen(
            [sys.executable, "-c", child_src],
            env=dict(os.environ, **durable_env), cwd=_REPO)
        child.wait(timeout=600)
        child_secs = time.perf_counter() - t0
        if child.returncode != -signal.SIGKILL:
            print(f"child exited {child.returncode}, not SIGKILL — the "
                  "storm never reached the crash", file=sys.stderr)
            return 1
        if not os.path.exists(os.path.join(tmp, "ready")):
            print("child died before the staged kill", file=sys.stderr)
            return 1

        # -- the restart: arm this process identically and recover ----------
        os.environ.update(durable_env)
        before = {n: _counter(n) for n in _COUNTERS}
        t1 = time.perf_counter()
        jrn = JM.active()
        if jrn is None:
            print("journal did not arm in the recovered process",
                  file=sys.stderr)
            return 1
        cat = memgov.catalog()  # the factory hook runs persist.startup()

        # DONE work answers from the journaled digest — verified
        # against a freshly computed oracle's bits, never re-executed
        fact = gen_fact(args.rows, args.seed)
        oracles = {}
        for idem, cut, factor in storm_combos(args.done):
            oracles[idem] = P.compile_ir(
                storm_plan(cut, factor), fact, name=f"oracle.{idem}")()
            hit = jrn.done_digest(idem)
            if hit is None:
                wrong.append(f"{idem}: journaled digest missing")
            elif JM.result_digest(oracles[idem]) != hit[1]:
                wrong.append(f"{idem}: journaled digest diverges from "
                             "the oracle's bits")

        sched = serve.Scheduler(max_concurrent=2, name="recovered")
        try:
            for idem, cut, factor in storm_combos(args.done):
                ans = sched.submit(
                    storm_plan(cut, factor), fact, tenant="t0",
                    idempotency_key=idem).result(60)
                if not isinstance(ans, serve.DigestAnswer):
                    wrong.append(f"{idem}: duplicate submission "
                                 "re-executed instead of answering by "
                                 "digest")
                elif not ans.matches(oracles[idem]):
                    wrong.append(f"{idem}: recorded digest rejects the "
                                 "oracle's bits")

            # journaled-but-incomplete work resubmits through the
            # rebind path; the torn record must never resurface
            from spark_rapids_jni_tpu.plan.rewrites import (
                parameterized_fingerprint,
            )

            template = storm_plan(0, 0.0)
            tkey = parameterized_fingerprint(template).key
            rep = JM.recover(
                sched,
                lambda rec: (template, fact) if rec.get("pf") == tkey
                else None)
            by_idem = {rec.get("idem"): h for rec, h in rep["resubmitted"]}
            if "pend-torn" in by_idem:
                wrong.append("the torn submission was invented back "
                             "into existence")
            keep = by_idem.get("pend-keep")
            if keep is None:
                wrong.append("the surviving incomplete submission was "
                             "not resubmitted")
            else:
                idem, cut, factor = PENDING[0]
                want = P.compile_ir(storm_plan(cut, factor), fact,
                                    name="oracle.pend")()
                if not _tables_equal(keep.result(120), want):
                    wrong.append("pend-keep: resubmitted answer "
                                 "diverged from the oracle")
        finally:
            sched.shutdown(drain=False, timeout_s=30)

        # the OOC query resumes past the two re-attached checkpoints
        ooc_tabs = gen_ooc_tables(args.ooc_rows, args.seed)
        ooc_oracle = P.compile_ir(ooc_ir(), ooc_tabs,
                                  name="restart_ooc_oracle")()
        with memgov.enabled():
            cp = P.compile_ir(ooc_ir(), ooc_tabs, name="restart_ooc")
            if not isinstance(cp, P.OutOfCorePlan):
                wrong.append("OOC never armed in the recovered process")
            else:
                if not _tables_equal(cp(), ooc_oracle):
                    wrong.append("resumed OOC answer diverged from the "
                                 "in-core oracle")
        recovery_secs = time.perf_counter() - t1
        d = {n: _counter(n) - before[n] for n in _COUNTERS}

        # the journal's admission cost, report-only (the off posture's
        # serving economics are gated by the premerge serve tier)
        p50_on = _submit_p50_ms("probe-on", args.probe)
        os.environ.pop("SRJT_JOURNAL_DIR", None)
        JM.reset()
        p50_off = _submit_p50_ms("probe-off", args.probe)

        duplicate_executions = args.done - d["journal.idempotent_hits"]
        row = {
            "metric": "restart_recovery",
            "value": args.done + 1,  # digest-answered DONE + resubmitted
            "unit": "queries",
            "done": args.done,
            "replays": d["journal.replays"],
            "replayed_records": d["journal.replayed_records"],
            "truncated_records": d["journal.truncated_records"],
            "idempotent_hits": d["journal.idempotent_hits"],
            "duplicate_executions": duplicate_executions,
            "recovered_resubmits": d["journal.recovered_resubmits"],
            "recovery_skipped": d["journal.recovery_skipped"],
            "reattached": d["memgov.reattached"],
            "manifest_rot": d["memgov.manifest_rot"],
            "orphans_reclaimed": d["memgov.orphans_reclaimed"],
            "resumes": d["ooc.partition_resumes"],
            "child_secs": round(child_secs, 2),
            "recovery_secs": round(recovery_secs, 2),
            "submit_p50_on_ms": round(p50_on, 3),
            "submit_p50_off_ms": round(p50_off, 3),
            "wrong_answers": len(wrong),
            "bit_identical": not wrong,
        }
        _emit(row)
        if metrics.is_enabled():
            _emit({"metrics": metrics.stage_report("restart_bench")})

        rc = 0
        if wrong:
            print(f"WRONG ANSWERS ({len(wrong)}): {wrong[:5]}",
                  file=sys.stderr)
            rc = 1
        gates = (
            ("replays", d["journal.replays"], 1),
            ("replayed_records", d["journal.replayed_records"],
             3 * args.done + 5),
            ("truncated_records", d["journal.truncated_records"], 1),
            ("idempotent_hits", d["journal.idempotent_hits"], args.done),
            ("recovered_resubmits", d["journal.recovered_resubmits"], 1),
            ("reattached", d["memgov.reattached"], 1),
            ("manifest_rot", d["memgov.manifest_rot"], 1),
            ("resumes", d["ooc.partition_resumes"], 1),
        )
        for name, got, need in gates:
            if got < need:
                print(f"{name} {got} < {need}: recovery did not exercise "
                      "the durable path", file=sys.stderr)
                rc = 1
        if duplicate_executions != 0:
            print(f"{duplicate_executions} DONE queries re-executed after "
                  "the restart", file=sys.stderr)
            rc = 1
        return rc
    finally:
        faultinj.disable()
        JM.reset()
        shutil.rmtree(tmp, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=1000,
                    help="fact rows for the serving storm (small enough "
                    "that the in-core estimate fits the 36 KB budget "
                    "the OOC leg arms)")
    ap.add_argument("--ooc-rows", type=int, default=3000,
                    help="lineitem rows for the out-of-core leg (the "
                    "36 KB budget forces 4-way degradation)")
    ap.add_argument("--done", type=int, default=4,
                    help="storm queries completed before the kill")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--probe", type=int, default=40,
                    help="trivial submissions per journal-overhead probe")
    args = ap.parse_args()
    return run(args)


if __name__ == "__main__":
    sys.exit(main())
