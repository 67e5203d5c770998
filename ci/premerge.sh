#!/usr/bin/env bash
# Premerge gate (reference ci/premerge-build.sh analog): native build,
# hermetic test suite on the virtual CPU mesh, driver entry compile
# check, and a bench smoke. Run from the repo root.
set -euo pipefail

# built here for the JNI harness below; every pytest tier builds (or
# refreshes) native/build/libsrjt.so itself before it collects
# (tests/conftest.py), so no test count depends on this line
cmake -S native -B native/build -G Ninja
ninja -C native/build

# JNI tier executed without a JVM: fabricated-JNIEnv harness drives the
# Java_* entry points in libsrjt_jnitest.so (engine + veneer, the
# single-.so jar shape) end to end — marshalling, CastException
# construction, handle registry, leak accounting (VERDICT r4 item 2)
python - <<'EOF'
import pyarrow as pa, pyarrow.parquet as pq
t = pa.table({"a": pa.array(range(1000), pa.int32()),
              "b": pa.array([f"s{i}" for i in range(1000)]),
              "c": pa.array([float(i) for i in range(1000)])})
pq.write_table(t, "/tmp/srjt_jni_harness.parquet")
EOF
./native/build/jni_harness ./native/build/libsrjt_jnitest.so \
  /tmp/srjt_jni_harness.parquet 1000

# correctness-tooling tier (ISSUEs 7 + 11, layer 1): srjt-lint AND the
# srjt-race static pass must be clean — undeclared/undocumented SRJT
# knobs (now including tests/ and benchmarks/), taxonomy-violating
# raises, unsuppressed broad excepts, stub-pattern regressions, blind
# blocking calls, mixed guarded/unguarded attribute access (SRJT008),
# check-then-act splits (SRJT009), and bare mutable-global mutation
# (SRJT010) all fail the merge here, before any test runs. Findings
# are archived as SARIF next to the other artifacts (exit-code parity
# with text mode, so the gate semantics are unchanged).
mkdir -p artifacts
python -m spark_rapids_jni_tpu.analysis.lint --format=sarif --out artifacts/srjt_lint.sarif
python -m spark_rapids_jni_tpu.analysis.races --format=sarif --out artifacts/srjt_race.sarif

# srjt-plancheck tier (ISSUE 15): the plan-IR verifier over EVERY
# checked-in plan (well-formedness, every fired rewrite's
# translation-validation obligation discharged, per-stage estimate
# monotonicity), then the fixed-seed random-plan differential fuzzer —
# >= 50 generated plans run rewrite->compile->execute against a
# direct-plan-interpretation oracle, any mismatch bisected to the
# first semantics-breaking rewrite. The gate is artifact-based:
# artifacts/plan_verify.jsonl must carry every registry plan with
# zero violations AND the fuzz record with zero mismatches;
# artifacts/plancheck.sarif is archived next to the other SARIF.
rm -f artifacts/plan_verify.jsonl
JAX_PLATFORMS=cpu python -m spark_rapids_jni_tpu.analysis.plancheck \
  --format=sarif --out artifacts/plancheck.sarif \
  --report artifacts/plan_verify.jsonl
timeout -k 10 600 env JAX_PLATFORMS=cpu \
  python -m spark_rapids_jni_tpu.analysis.planfuzz \
  --report artifacts/plan_verify.jsonl
python - <<'EOF'
import json
rows = [json.loads(s) for s in open("artifacts/plan_verify.jsonl")]
plans = {r["query"]: r for r in rows if r["kind"] == "plan"}
fuzz = [r for r in rows if r["kind"] == "fuzz"]
from spark_rapids_jni_tpu.models.tpcds_plans import PLAN_QUERIES
want = set(PLAN_QUERIES) | {"q3", "q55", "q3x4", "q55x4"}
missing = sorted(want - set(plans))
assert not missing, f"plans missing from plan_verify.jsonl: {missing}"
bad = {q: r for q, r in plans.items() if r["violations"]}
assert not bad, f"plancheck violations: {bad}"
assert all(r["obligations"] >= 1 for r in plans.values()), \
    "a checked-in plan emitted no rewrite obligations (prune at minimum)"
assert fuzz, "no fuzz record archived"
total = sum(r["plans"] for r in fuzz)
assert total >= 50, f"fuzz smoke covered only {total} plans (need >= 50)"
assert all(r["mismatches"] == 0 and r["violations"] == 0 for r in fuzz), fuzz
fired = {}
for r in fuzz:
    for rule, n in r["rewrites"].items():
        fired[rule] = fired.get(rule, 0) + n
# srjt-cbo (ISSUE 19): the fixed-seed corpus deterministically drives
# the cost-based search — all three enumeration rules must fire (and
# therefore discharge) across the fuzzed plans
for rule in ("cbo_reorder_joins", "cbo_build_side", "cbo_join_strategy"):
    assert fired.get(rule), f"CBO rule {rule} never fired across the fuzz corpus"
print(f"plancheck tier: {len(plans)} plans verified "
      f"({sum(r['obligations'] for r in plans.values())} obligations "
      f"discharged), {total} fuzzed plans / 0 mismatches, "
      f"fuzz rewrites {fired} -> artifacts/plan_verify.jsonl")
EOF

# fast tier: the measured heavy tail (tests/conftest.py _SLOW_TESTS)
# runs nightly (ci/nightly.sh); this keeps the premerge gate usable on
# a 1-core box (VERDICT r3 item 9). SRJT_LOCKDEP=1 (ISSUE 7, layer 2)
# arms the lock-order instrumentation so every concurrency test in the
# tier doubles as a deadlock probe, and SRJT_RACE=1 (ISSUE 11, layer 2)
# rides the same shim: per-thread vector clocks over every
# lock/Event/Thread/Semaphore/Barrier edge, with the scheduler's
# tenant lanes, the pool's worker-health records and hedge budget, the
# memgov catalog map, and the metrics registry all tracked — an
# unordered access lands as race_pairs in the same per-process report
# and fails the same merge gate. The armed tier must stay within 1.5x
# its unarmed wall-clock (the shim is proportional to sync-op count,
# not data volume). Each process (incl. spawned sidecar workers, which
# inherit the env) drops artifacts/lockdep/lockdep_<pid>.json at exit,
# merged and gated after the chaos tiers.
rm -rf artifacts/lockdep
SRJT_LOCKDEP=1 SRJT_RACE=1 python -m pytest tests/ -q -m "not slow"

# robustness + observability tier: the chaos suite re-runs the
# end-to-end distributed pipeline under the storm profile (retryable +
# delay faults at 30%) with the retry orchestrator armed THROUGH the
# env knobs (the parity test honors SRJT_RETRY_* when
# SRJT_RETRY_ENABLED is set), asserting results bit-identical to
# fault-free runs — a retry/backoff/supervision regression fails
# premerge, not production (ISSUE 1). ISSUE 2 runs the same storm with
# the METRICS subsystem armed: the metrics suite's chaos-integration
# tests assert counter values match injected fault counts bit-exactly,
# and the structured JSON-lines event log is archived as a premerge
# artifact next to the BENCH rows.
mkdir -p artifacts
rm -f artifacts/chaos_metrics.jsonl
SRJT_LOCKDEP=1 SRJT_FAULTINJ_CONFIG=ci/chaos_storm.json SRJT_RETRY_ENABLED=1 \
  SRJT_RETRY_MAX_ATTEMPTS=10 SRJT_RETRY_BASE_DELAY_MS=1 \
  SRJT_RETRY_MAX_DELAY_MS=8 SRJT_RETRY_SEED=99 \
  SRJT_METRICS_ENABLED=1 SRJT_METRICS_LOG=artifacts/chaos_metrics.jsonl \
  python -m pytest tests/test_chaos.py tests/test_metrics.py -q
# the event log must exist and parse as JSON lines (artifact contract)
python - <<'EOF'
import json, sys
lines = [json.loads(s) for s in open("artifacts/chaos_metrics.jsonl")]
assert lines, "chaos run produced no metrics events"
assert all("ts" in r and "event" in r for r in lines)
print(f"archived {len(lines)} metrics events -> artifacts/chaos_metrics.jsonl")
EOF
# deadline + circuit-breaker tier (ISSUE 3): the hang-storm profile
# wedges hash_partition for 30 s at a time — far past the tight
# SRJT_DEADLINE_SEC below — so every query must either complete or
# raise DeadlineExceeded within budget. The hard `timeout` wrapper IS
# the assertion that the subsystem works: a single uninterrupted hang
# (or a wedged/leaked worker) blows the harness ceiling and fails the
# gate. Runs the full deadline suite: budget propagation, backoff
# truncation, breaker open->half-open->closed, spawn reaping, and the
# storm acceptance test (which honors these env knobs).
timeout -k 10 600 env SRJT_LOCKDEP=1 SRJT_FAULTINJ_CONFIG=ci/chaos_hang.json \
  SRJT_DEADLINE_SEC=3 SRJT_RETRY_ENABLED=1 SRJT_RETRY_MAX_ATTEMPTS=10 \
  SRJT_RETRY_BASE_DELAY_MS=1 SRJT_RETRY_MAX_DELAY_MS=8 SRJT_RETRY_SEED=99 \
  SRJT_METRICS_ENABLED=1 \
  python -m pytest tests/test_deadline.py -q

# memory-governor tier (ISSUE 4): the full memgov suite under a TIGHT
# ambient device budget with metrics + the event log armed — admission
# FIFO/byte-exactness, spill round-trips, deadline-truncated waits, and
# the squeeze acceptance (spills + retry splits interleave, TPC-H q1
# bit-identical). The chaos test inside loads ci/chaos_memgov.json
# (spill_fail storm on the demotion choke point). Afterwards the
# archived event log must PROVE forced spills happened: nonzero
# memgov.spill volume is the artifact contract, mirroring the
# chaos_metrics.jsonl gate above.
rm -f artifacts/memgov_events.jsonl
SRJT_LOCKDEP=1 SRJT_DEVICE_MEMORY_BUDGET=400000 SRJT_SPILL_ENABLED=1 \
  SRJT_RETRY_ENABLED=0 \
  SRJT_METRICS_ENABLED=1 SRJT_METRICS_LOG=artifacts/memgov_events.jsonl \
  python -m pytest tests/test_memgov.py -q
python - <<'EOF'
import json
lines = [json.loads(s) for s in open("artifacts/memgov_events.jsonl")]
assert lines, "memgov tier produced no events"
spilled = sum(r.get("nbytes", 0) for r in lines if r["event"] == "memgov.spill")
assert spilled > 0, "low-budget tier forced no spills (memgov.spilled_bytes == 0)"
kinds = {r["event"] for r in lines}
assert "memgov.pressure" in kinds, "no pressure-loop events recorded"
print(f"archived {len(lines)} memgov events ({spilled} bytes spilled) "
      "-> artifacts/memgov_events.jsonl")
EOF

# out-of-core tier (srjt-ooc, ISSUE 18): the full ooc suite with the
# strategy armed and the ambient device budget PINCHED below the
# q1-shape working set — selection, verifier discharge of the
# partitioning rewrite, the >=4x-budget bit-identity acceptance, the
# ci/chaos_ooc.json storm on a real 2-worker pool (failed/corrupt
# partition spills + a kill -9 mid-partition), pin discipline against
# the pressure loop, and per-partition serve admission. The artifact
# gate reads the run reports every completed OOC run appends to
# SRJT_OOC_METRICS: degraded runs really streamed >1 spill-backed
# partition (partitions>1, spills>0) and the storm really resumed from
# a checkpoint (resumes>0) — with zero test failures (= zero wrong
# answers) above it. The BENCH row prices the degradation: an
# out-of-core pass over an in-core-feasible dataset must stay within
# 2x of the unconstrained wall (the row carries its own gate_max so
# the number and its bar travel together).
rm -f artifacts/ooc_metrics.jsonl artifacts/bench_ooc.jsonl
timeout -k 10 900 env SRJT_LOCKDEP=1 SRJT_OOC_ENABLED=1 \
  SRJT_DEVICE_MEMORY_BUDGET=36864 \
  SRJT_OOC_METRICS=artifacts/ooc_metrics.jsonl \
  python -m pytest tests/test_ooc.py -q
python bench.py --ooc | tee artifacts/bench_ooc.jsonl
python - <<'EOF'
import json
rows = [json.loads(s) for s in open("artifacts/ooc_metrics.jsonl")]
assert rows, "ooc tier produced no run reports"
assert all(r["ooc"] for r in rows)
assert any(r["partitions"] > 1 for r in rows), "no partitioned run recorded"
spills = sum(r["spills"] for r in rows)
assert spills > 0, "pinched-budget tier forced no partition spills"
resumes = sum(r["resumes"] for r in rows)
assert resumes > 0, "no partition resume recorded under the chaos storm"
bench = [json.loads(s) for s in open("artifacts/bench_ooc.jsonl")
         if s.strip()]
row = next(r for r in bench if r.get("metric") == "ooc_overhead")
assert row["raw"]["bit_identical"], "ooc bench diverged"
assert row["value"] <= row["gate_max"], (
    f"out-of-core overhead {row['value']}x exceeds the "
    f"{row['gate_max']}x degradation bar")
print(f"ooc tier: {len(rows)} degraded runs ({spills} spills, "
      f"{resumes} resumes) -> artifacts/ooc_metrics.jsonl; "
      f"ooc_overhead {row['value']}x (gate {row['gate_max']}x) "
      "-> artifacts/bench_ooc.jsonl")
EOF

# restart tier (srjt-durable, ISSUE 20): a child coordinator serves a
# journaled mixed-plan storm (journal + spill manifests + durable OOC
# checkpoints armed against shared dirs), checkpoints two of four OOC
# partitions, arms ci/chaos_restart.json — the next manifest write and
# the next journal append TORN mid-frame, what a kill -9 racing the
# disk produces — and SIGKILLs itself mid-storm. The recovered process
# (the bench parent) must replay the journal past the torn tail,
# answer every DONE query from its recorded digest (verified against
# a recomputed oracle's bits, zero re-executions), refuse to invent
# the torn submission, resubmit the surviving incomplete query through
# the rebind path, and resume the OOC query past the re-attached
# checkpoints — ooc.partition_resumes crossing PROCESSES. The artifact
# gate re-asserts the row's own verdict: replays/reattached/resumes
# all nonzero, a truncated record, manifest rot counted on the torn
# sidecar, zero duplicate executions, bit-identical throughout.
rm -f artifacts/restart_metrics.jsonl
timeout -k 10 900 env JAX_PLATFORMS=cpu SRJT_LOCKDEP=1 \
  SRJT_METRICS_ENABLED=1 \
  SRJT_RESULTS=artifacts/restart_metrics.jsonl \
  python benchmarks/bench_restart.py
python - <<'EOF'
import json
rows = [json.loads(s) for s in open("artifacts/restart_metrics.jsonl")
        if s.strip()]
row = next(r for r in rows if r.get("metric") == "restart_recovery")
assert row["bit_identical"], "restart tier recovered a wrong answer"
assert row["replays"] > 0, "recovered process never replayed the journal"
assert row["truncated_records"] > 0, "the torn journal tail never landed"
assert row["reattached"] > 0, "no checkpoint re-attached across the restart"
assert row["resumes"] > 0, "no cross-process partition resume recorded"
assert row["manifest_rot"] > 0, "the torn manifest was never caught"
assert row["duplicate_executions"] == 0, (
    f"{row['duplicate_executions']} DONE queries re-executed after restart")
assert row["recovered_resubmits"] > 0, "incomplete work never resubmitted"
print(f"restart tier: {row['replayed_records']} records replayed "
      f"({row['truncated_records']} truncated), {row['reattached']} "
      f"checkpoints re-attached, {row['resumes']} partition resumes, "
      f"{row['idempotent_hits']} digest answers, 0 duplicate executions "
      "-> artifacts/restart_metrics.jsonl")
EOF

# crash-storm tier (ISSUE 5): the full sidecar-pool + integrity suite
# with the crash/corrupt chaos profile armed INSIDE real workers — a
# pool of 2 survives kill -9 mid-query (failover + arena re-hydration)
# and every injected corruption surfaces as DataCorruption, never a
# wrong answer. The hard timeout is the leaked/wedged-worker assertion;
# the archived event log must PROVE the storm fired: nonzero
# sidecar.pool.failovers (worker deaths failed over) and nonzero
# sidecar.integrity.crc_mismatch (corruptions caught) are the artifact
# contract, with zero test failures above them.
rm -f artifacts/crash_metrics.jsonl
timeout -k 10 900 env SRJT_LOCKDEP=1 SRJT_RETRY_ENABLED=1 SRJT_RETRY_MAX_ATTEMPTS=10 \
  SRJT_RETRY_BASE_DELAY_MS=1 SRJT_RETRY_MAX_DELAY_MS=8 SRJT_RETRY_SEED=99 \
  SRJT_METRICS_ENABLED=1 SRJT_METRICS_LOG=artifacts/crash_metrics.jsonl \
  python -m pytest tests/test_sidecar_pool.py -q
python - <<'EOF'
import json
lines = [json.loads(s) for s in open("artifacts/crash_metrics.jsonl")]
assert lines, "crash-storm tier produced no events"
kinds = {r["event"] for r in lines}
assert "sidecar.pool.worker_death" in kinds, "no worker death recorded"
assert "sidecar.pool.respawn" in kinds, "no respawn recorded"
assert "sidecar.pool.rehydrate" in kinds, "no arena re-hydration recorded"
assert "integrity.crc_mismatch" in kinds, "no corruption caught"
deaths = sum(1 for r in lines if r["event"] == "sidecar.pool.worker_death")
failovers = sum(1 for r in lines
                if r["event"] == "sidecar.pool.worker_death" and r.get("live", 0) > 0)
mismatches = sum(1 for r in lines if r["event"] == "integrity.crc_mismatch")
assert failovers > 0, "no failover observed (every death left the pool dark)"
assert mismatches > 0, "no crc_mismatch observed"
print(f"archived {len(lines)} crash events ({deaths} deaths, "
      f"{failovers} failovers, {mismatches} corruptions caught) "
      "-> artifacts/crash_metrics.jsonl")
EOF

# data-plane tier (ISSUE 6): the slab-arena / frame-codec / TCP-exchange
# suite env-armed (retry + metrics + event log) under a hard timeout.
# The two-REAL-process acceptance inside arms ci/chaos_crash.json's
# exchange keys in the peer: one kill -9 mid-serve and one frame
# corruption, final distributed groupby bit-identical. The archived
# event log must PROVE the storm fired — a caught frame corruption
# (integrity.crc_mismatch) and a peer respawn are the artifact
# contract. The session-scoped slab-leak assertion in tests/conftest.py
# rides every pytest invocation in this file.
rm -f artifacts/data_plane_metrics.jsonl
timeout -k 10 900 env SRJT_LOCKDEP=1 SRJT_RETRY_ENABLED=1 SRJT_RETRY_MAX_ATTEMPTS=10 \
  SRJT_RETRY_BASE_DELAY_MS=1 SRJT_RETRY_MAX_DELAY_MS=8 SRJT_RETRY_SEED=99 \
  SRJT_METRICS_ENABLED=1 SRJT_METRICS_LOG=artifacts/data_plane_metrics.jsonl \
  python -m pytest tests/test_data_plane.py -q
python - <<'EOF'
import json
lines = [json.loads(s) for s in open("artifacts/data_plane_metrics.jsonl")]
assert lines, "data-plane tier produced no events"
kinds = {r["event"] for r in lines}
assert "integrity.crc_mismatch" in kinds, "no frame corruption caught"
assert "exchange.peer_respawn" in kinds, "no peer crash/respawn recorded"
print(f"archived {len(lines)} data-plane events -> "
      "artifacts/data_plane_metrics.jsonl")
EOF

# cluster tier (ISSUE 16): the N-rank membership / fencing / recovery
# suite env-armed under a hard timeout. The 4-process acceptance inside
# arms ci/chaos_cluster.json in the children: rank 2 SIGKILLs itself
# mid-frame on its first payload serve, rank 3 rides a transient
# netsplit, rank 1 serves with latency jitter — and the distributed
# groupby must stay bit-identical to the single-host oracle with
# exactly one membership death. The archived event log must PROVE the
# machinery engaged, not just that tests passed: a cluster.transition
# into DEAD and a cluster.recovery republish under the bumped
# generation are the artifact contract.
rm -f artifacts/cluster_metrics.jsonl
timeout -k 10 900 env SRJT_LOCKDEP=1 SRJT_RETRY_ENABLED=1 SRJT_RETRY_MAX_ATTEMPTS=10 \
  SRJT_RETRY_BASE_DELAY_MS=1 SRJT_RETRY_MAX_DELAY_MS=8 SRJT_RETRY_SEED=99 \
  SRJT_METRICS_ENABLED=1 SRJT_METRICS_LOG=artifacts/cluster_metrics.jsonl \
  python -m pytest tests/test_cluster.py -q
python - <<'EOF'
import json
lines = [json.loads(s) for s in open("artifacts/cluster_metrics.jsonl")]
assert lines, "cluster tier produced no events"
deaths = [r for r in lines
          if r["event"] == "cluster.transition" and r.get("new") == "dead"]
assert deaths, "no membership transition into DEAD recorded"
recoveries = [r for r in lines if r["event"] == "cluster.recovery"]
assert recoveries, "no lineage recovery republish recorded"
assert all(r["generation"] >= 2 for r in recoveries), \
    "a recovery ran under the pre-death generation (fence not bumped)"
print(f"archived {len(lines)} cluster events ({len(deaths)} deaths, "
      f"{len(recoveries)} recoveries) -> artifacts/cluster_metrics.jsonl")
EOF

# serving tier (ISSUE 8): the full serve suite (incl. the slow
# chaos-under-load acceptance) env-armed, then bench_serve's chaos
# gate — a crash+hang+reject storm WHILE serving a mixed q1/q6/q98
# workload through a REAL worker pool of 2. The bench exits nonzero
# unless every completed query is bit-identical to its sequential
# oracle, every shed surfaced as retryable Overloaded (never a
# timeout), and p999 stays under the per-query deadline; the archived
# artifacts must additionally PROVE the storm fired — failovers > 0
# (kill -9 healed by a living peer) and shed_total > 0 are the
# artifact contract. SRJT_LOCKDEP=1 rides along: the dispatcher's new
# lock sites feed the merged zero-cycle gate below.
rm -f artifacts/serve_metrics.jsonl artifacts/bench_serve.jsonl
timeout -k 10 900 env SRJT_LOCKDEP=1 SRJT_RACE=1 SRJT_RETRY_ENABLED=1 SRJT_RETRY_MAX_ATTEMPTS=10 \
  SRJT_RETRY_BASE_DELAY_MS=1 SRJT_RETRY_MAX_DELAY_MS=8 SRJT_RETRY_SEED=99 \
  SRJT_METRICS_ENABLED=1 SRJT_METRICS_LOG=artifacts/serve_metrics.jsonl \
  python -m pytest tests/test_serve.py -q
timeout -k 10 900 env SRJT_LOCKDEP=1 SRJT_RACE=1 SRJT_RETRY_ENABLED=1 SRJT_RETRY_MAX_ATTEMPTS=10 \
  SRJT_RETRY_BASE_DELAY_MS=2 SRJT_RETRY_MAX_DELAY_MS=50 SRJT_RETRY_SEED=99 \
  SRJT_METRICS_ENABLED=1 SRJT_METRICS_LOG=artifacts/serve_metrics.jsonl \
  SRJT_RESULTS=artifacts/bench_serve.jsonl \
  python benchmarks/bench_serve.py --chaos --rows 5000 --queries 24 \
  --offered-qps 2 --deadline-s 60 --max-concurrent 3 --pool-size 2
python - <<'EOF'
import json
rows = [json.loads(s) for s in open("artifacts/bench_serve.jsonl")]
bench = [r for r in rows if r.get("metric") == "serve_mixed_qps"]
assert bench, "no serve BENCH row emitted"
b = bench[-1]
assert b["wrong_answers"] == 0 and b["bit_identical"], b
assert b["failovers"] > 0, "crash storm produced no pool failover"
assert b["shed_total_counter"] > 0, "no shed recorded (serve.shed_total == 0)"
assert b["completed"] > 0 and b["value"] > 0, "no sustained throughput"
assert b["p999_ms"] <= b["deadline_s"] * 1000, "p999 exceeds the deadline"
lines = [json.loads(s) for s in open("artifacts/serve_metrics.jsonl")]
kinds = {r["event"] for r in lines}
assert "serve.shed" in kinds, "no shed event archived"
assert "serve.submit" in kinds and "serve.done" in kinds
failovers = sum(1 for r in lines
                if r["event"] == "sidecar.pool.worker_death"
                and r.get("live", 0) > 0)
assert failovers > 0, "no failover-with-living-peers in the event log"
print(f"serve tier: {b['completed']} queries at {b['value']} qps "
      f"(p50 {b['p50_ms']} / p99 {b['p99_ms']} / p999 {b['p999_ms']} ms), "
      f"{b['shed_total_counter']} sheds, {b['failovers']} failovers "
      "-> artifacts/serve_metrics.jsonl")
EOF

# gray-failure tier (ISSUE 9): the serve bench against a REAL pool of
# 3 with ONE worker ramped into persistent slowness (ci/chaos_gray.json
# keys its delay ramp to w1's SRJT_FAULTINJ_WORKER tag — a gray
# failure, not a crash). The tail-tolerance contract is gated from the
# archived artifacts, not test-self-certified: every completed query
# bit-identical to its sequential oracle, p999 <= the deadline, the
# slow worker QUARANTINED and later REINSTATED after the ramp ends,
# hedged dispatch WON at least one race, and the hedge volume within
# its budget. SRJT_LOCKDEP=1 rides along: the quarantine/hedge lock
# sites feed the merged zero-cycle gate below.
rm -f artifacts/gray_metrics.jsonl artifacts/bench_gray.jsonl
timeout -k 10 900 env SRJT_LOCKDEP=1 SRJT_RACE=1 SRJT_RETRY_ENABLED=1 SRJT_RETRY_MAX_ATTEMPTS=10 \
  SRJT_RETRY_BASE_DELAY_MS=2 SRJT_RETRY_MAX_DELAY_MS=50 SRJT_RETRY_SEED=99 \
  SRJT_METRICS_ENABLED=1 SRJT_METRICS_LOG=artifacts/gray_metrics.jsonl \
  SRJT_RESULTS=artifacts/bench_gray.jsonl \
  SRJT_HEDGE_BUDGET_PCT=25 SRJT_ADAPTIVE_TIMEOUT_FLOOR_S=2 \
  SRJT_QUARANTINE_PROBE_INTERVAL_S=0.2 \
  python benchmarks/bench_serve.py --gray --rows 4000 --queries 36 \
  --offered-qps 2 --deadline-s 90 --max-concurrent 3 --pool-size 3 \
  --pool-ops 3
python - <<'EOF'
import json
rows = [json.loads(s) for s in open("artifacts/bench_gray.jsonl")]
bench = [r for r in rows if r.get("metric") == "serve_gray_qps"]
assert bench, "no gray BENCH row emitted"
b = bench[-1]
assert b["wrong_answers"] == 0 and b["bit_identical"], b
assert b["quarantines"] > 0, "slow worker never quarantined"
assert b["reinstatements"] > 0, "quarantined worker never reinstated"
assert b["hedges_won"] > 0, "hedged dispatch won no race"
assert b["completed"] > 0 and b["value"] > 0, "no sustained throughput"
assert b["p999_ms"] <= b["deadline_s"] * 1000, "p999 exceeds the deadline"
assert b["hedges_launched"] * 100.0 <= (
    b["hedge_budget_pct"] * max(b["pool_calls"], 1)
), "hedge volume exceeded its budget"
lines = [json.loads(s) for s in open("artifacts/gray_metrics.jsonl")]
kinds = {r["event"] for r in lines}
assert "sidecar.pool.quarantine" in kinds, "no quarantine event archived"
assert "sidecar.pool.reinstate" in kinds, "no reinstate event archived"
assert "sidecar.pool.hedge_won" in kinds, "no hedge_won event archived"
print(f"gray tier: {b['completed']} queries at {b['value']} qps "
      f"(p50 {b['p50_ms']} / p99 {b['p99_ms']} / p999 {b['p999_ms']} ms), "
      f"{b['quarantines']} quarantines, {b['reinstatements']} reinstated, "
      f"{b['hedges_won']}/{b['hedges_launched']} hedges won/launched "
      "-> artifacts/gray_metrics.jsonl")
EOF

# trace tier (ISSUE 12): the distributed-tracing suite env-armed —
# including the real-pool acceptance, which runs a crash-profile storm
# (per-worker delay ramp + kill -9 mid-STATS) through a REAL pool of 2
# with SRJT_TRACE_ENABLED=1 and per-process span logs. The merge gate
# is artifact-based, not test-self-certified: tracemerge joins the
# client's and both workers' logs and must show a trace containing the
# FAILOVER (two pool.request attempts on distinct workers under one
# pool.call span), the HEDGED sibling pair with the winner marked
# exactly once, a cross-process worker span, and ZERO orphan spans
# (every span's parent resolves within its trace — --gate-orphans
# exits 1 otherwise). The Perfetto-loadable export is archived too.
rm -f artifacts/trace_spans*.jsonl artifacts/trace_merged.json \
  artifacts/trace_perfetto.json
timeout -k 10 900 env SRJT_LOCKDEP=1 SRJT_RACE=1 \
  SRJT_TRACE_ENABLED=1 SRJT_TRACE_LOG=artifacts/trace_spans.jsonl \
  SRJT_METRICS_ENABLED=1 SRJT_RETRY_ENABLED=1 SRJT_RETRY_MAX_ATTEMPTS=10 \
  SRJT_RETRY_BASE_DELAY_MS=1 SRJT_RETRY_MAX_DELAY_MS=8 SRJT_RETRY_SEED=99 \
  python -m pytest tests/test_tracing.py -q
python -m spark_rapids_jni_tpu.analysis.tracemerge \
  "artifacts/trace_spans*.jsonl" --format json \
  --out artifacts/trace_merged.json --gate-orphans
python -m spark_rapids_jni_tpu.analysis.tracemerge \
  "artifacts/trace_spans*.jsonl" --format chrome \
  --out artifacts/trace_perfetto.json
python - <<'EOF'
import json
rep = json.load(open("artifacts/trace_merged.json"))
assert rep["traces"], "trace tier archived no merged traces"
assert rep["orphans"] == 0, f"{rep['orphans']} orphan spans"
failover = hedged = chain = False
for t in rep["traces"].values():
    spans = t["spans"]
    by_id = {s["span"]: s for s in spans}
    for call in (s for s in spans if s["name"] == "pool.call"):
        kids = [s for s in spans
                if s.get("parent") == call["span"]
                and s["name"] == "pool.request"]
        if len(kids) >= 2 and len({
            (s.get("annotations") or {}).get("wid") for s in kids
        }) >= 2:
            failover = True
    legs = [s for s in spans if s["name"] == "pool.hedge_leg"]
    by_parent = {}
    for s in legs:
        by_parent.setdefault(s["parent"], []).append(s)
    for pair in by_parent.values():
        if len(pair) == 2 and sum(
            1 for s in pair if (s.get("annotations") or {}).get("winner")
        ) == 1:
            hedged = True
    # the acceptance chain: a CROSS-PROCESS worker span whose ancestor
    # walk reaches submit -> queue -> admission -> op -> wire -> worker
    client_pids = {s["pid"] for s in spans if s["name"] == "serve.query"}
    for w in (s for s in spans if s["name"] == "sidecar.worker_op"
              and client_pids and s["pid"] not in client_pids):
        names, cur = set(), w
        while cur.get("parent") in by_id:
            cur = by_id[cur["parent"]]
            names.add(cur["name"])
        if {"sidecar.request", "pool.call", "serve.run",
                "serve.query"} <= names and any(
            n.startswith("op.") for n in names
        ) and {"serve.queue_wait", "memgov.admission_wait"} <= {
            s["name"] for s in spans
        }:
            chain = True
doc = json.load(open("artifacts/trace_perfetto.json"))
assert doc["traceEvents"], "empty Perfetto export"
assert failover, "merged traces show no failover (two attempts, two workers)"
assert hedged, "merged traces show no hedged sibling pair with one winner"
assert chain, ("no cross-process query tree spans submit -> queue -> "
               "admission -> op -> wire -> worker")
print(f"trace tier: {len(rep['traces'])} traces, 0 orphans, "
      f"failover+hedge spans and the cross-process submit->worker "
      "chain present -> artifacts/trace_merged.json / trace_perfetto.json")
EOF

# plan-compiler tier (ISSUE 14): the srjt-plan suite — IR/rewrite unit
# tier plus EVERY green plan query against its pandas oracle, the two
# hand-built greens (q3/q55) re-expressed as plans and asserted
# bit-identical to their fused originals, rewrite idempotence, and the
# schema contract (inferred dtypes == executed dtypes) — runs env-armed
# with the MEMORY GOVERNOR ON (a generous budget: the point is that
# admission runs, not that it starves) and the per-query report knob
# set. The merge gate is artifact-based: artifacts/plan_compile.jsonl
# must carry every registry query with node counts and rewrites fired,
# ZERO estimate-vs-actual peak-byte blowups over 2.5x (4x -> 3x in
# ISSUE 15 when the width model gained the per-row validity lane; 3x
# -> 2.5x in ISSUE 19 with the sketch-calibrated row estimates), every
# multi-join green's cost-chosen order at or below the author order on
# modeled cost, and the metrics log must PROVE memgov admission
# consumed nonzero plan-derived estimates (the ISSUE 14 acceptance
# assertion). SRJT_LOCKDEP/RACE ride along and feed the merged
# zero-cycle gate below.
rm -f artifacts/plan_compile.jsonl artifacts/plan_metrics.jsonl
timeout -k 10 900 env SRJT_LOCKDEP=1 SRJT_RACE=1 \
  SRJT_DEVICE_MEMORY_BUDGET=268435456 SRJT_SPILL_ENABLED=1 \
  SRJT_METRICS_ENABLED=1 SRJT_METRICS_LOG=artifacts/plan_metrics.jsonl \
  SRJT_PLAN_REPORT=artifacts/plan_compile.jsonl \
  python -m pytest tests/test_plan.py tests/test_plan_queries.py -q
python - <<'EOF'
import json
rows = [json.loads(s) for s in open("artifacts/plan_compile.jsonl")]
assert rows, "compiler tier produced no plan reports"
by = {}
for r in rows:
    by[r["query"]] = r  # last execution per query wins
from spark_rapids_jni_tpu.models.tpcds_plans import PLAN_QUERIES
missing = sorted(set(PLAN_QUERIES) - set(by))
assert not missing, f"green plan queries missing from the report: {missing}"
assert len(PLAN_QUERIES) >= 15, "fewer than 15 compiler-green queries"
for name in ("q3", "q55"):
    assert name in by, f"re-expressed green {name} not exercised"
blowups = {}
for q, r in by.items():
    assert r["nodes_raw"] > 0 and r["nodes_optimized"] > 0, r
    assert isinstance(r["rewrites"], dict), r
    assert r["est_peak_bytes"] > 0, r
    if r["peak_blowup"] is not None and r["peak_blowup"] > 2.5:
        blowups[q] = r["peak_blowup"]
assert not blowups, f"estimate-vs-actual peak blowups > 2.5x: {blowups}"
# srjt-cbo (ISSUE 19): on every checked-in multi-join plan the
# cost-based search ran, and the order it chose beats or ties the
# author order under the same model (the search records the author
# cost BEFORE enumerating, so a regression here means the search
# actively picked a worse plan)
multi = {q: r for q, r in by.items() if (r.get("join_count") or 0) >= 2}
assert multi, "no multi-join green carried a modeled cost (CBO never ran)"
cost_regressions = {
    q: (r["modeled_cost_author"], r["modeled_cost_chosen"])
    for q, r in multi.items()
    if r["modeled_cost_chosen"] is not None
    and r["modeled_cost_chosen"] > r["modeled_cost_author"] + 1e-6
}
assert not cost_regressions, \
    f"cost-chosen order worse than author order: {cost_regressions}"
fired = {}
for q in PLAN_QUERIES:
    for rule, n in by[q]["rewrites"].items():
        fired[rule] = fired.get(rule, 0) + n
for rule in ("decorrelate_scalar_agg", "expand_grouping_sets",
             "setop_to_joins", "exists_to_semijoin", "having_to_filter"):
    assert fired.get(rule), f"rewrite {rule} never fired across the greens"
fused = sum(by[q]["fused_stages"] for q in PLAN_QUERIES)
assert fused > 0, "no query lowered through the fused pipeline tier"
events = [json.loads(s) for s in open("artifacts/plan_metrics.jsonl")]
admits = [e for e in events if e["event"] == "plan.admit"]
assert admits and all(e["nbytes"] > 0 for e in admits), \
    "memgov admission saw no nonzero plan-derived estimates"
print(f"plan tier: {len(PLAN_QUERIES)} compiler-green queries "
      f"({fused} fused stages), rewrites {fired}, "
      f"{len(multi)} multi-join plans cost-checked, "
      f"{len(admits)} plan-derived admissions, 0 blowups "
      "-> artifacts/plan_compile.jsonl")
EOF

# cache tier (ISSUE 17): the srjt-cache suite with BOTH cache layers
# armed (plan cache + memgov-governed subresult cache) and the race /
# lockdep shims riding along — param-fingerprint properties over the
# planfuzz corpus, single-flight attach/cancel/leader-failure,
# spill-then-rematerialize bit-exactness, generation-bump
# invalidation, and the serve integration (bad-estimate normalization,
# forecast shed, chaos storm). Then bench_serve --cache runs the
# cold/warm economics gate (its OWN exit code enforces warm hit rate
# >= 0.8, >= 3x warm QPS at equal-or-better p99, in-flight sharing
# > 0, and bit-exactness vs uncached oracles) and --cache --chaos
# re-runs both passes under the ci/chaos_cache.json eviction/spill/
# reject storm (zero wrong answers while entries are shot down
# mid-lookup). The merge gate is artifact-based on top of the exit
# codes: the archived BENCH rows must SHOW the warm hit rate, the
# sharing, and zero wrong answers, and the metrics log must carry
# cache events.
rm -f artifacts/cache_metrics.jsonl artifacts/bench_cache.jsonl
timeout -k 10 900 env JAX_PLATFORMS=cpu SRJT_LOCKDEP=1 SRJT_RACE=1 \
  SRJT_PLAN_CACHE=1 SRJT_SUBRESULT_CACHE=1 \
  SRJT_METRICS_ENABLED=1 SRJT_METRICS_LOG=artifacts/cache_metrics.jsonl \
  python -m pytest tests/test_cache.py -q
timeout -k 10 900 env JAX_PLATFORMS=cpu \
  SRJT_METRICS_ENABLED=1 SRJT_METRICS_LOG=artifacts/cache_metrics.jsonl \
  SRJT_RESULTS=artifacts/bench_cache.jsonl \
  python benchmarks/bench_serve.py --cache --rows 20000
timeout -k 10 900 env JAX_PLATFORMS=cpu \
  SRJT_METRICS_ENABLED=1 SRJT_METRICS_LOG=artifacts/cache_metrics.jsonl \
  SRJT_RESULTS=artifacts/bench_cache.jsonl \
  python benchmarks/bench_serve.py --cache --chaos --rows 20000
python - <<'EOF'
import json
rows = [json.loads(s) for s in open("artifacts/bench_cache.jsonl")]
bench = [r for r in rows if r.get("metric") == "serve_cached_qps"]
plain = [r for r in bench if not r["chaos"]]
storm = [r for r in bench if r["chaos"]]
assert plain and storm, f"missing cache BENCH rows: {len(bench)}"
b = plain[-1]
assert b["wrong_answers"] == 0 and b["bit_identical"], b
assert b["hit_rate"] >= 0.8, f"warm hit rate {b['hit_rate']} < 0.8"
assert b["share"] > 0, "no in-flight sharing recorded (cache.share == 0)"
assert b["value"] >= 3.0 * b["cold_qps"], \
    f"warm {b['value']} qps < 3x cold {b['cold_qps']} qps"
assert b["warm_p99_ms"] <= b["cold_p99_ms"], b
s = storm[-1]
assert s["wrong_answers"] == 0 and s["bit_identical"], s
ev = (s["cold_counters"]["cache.evict_injected"]
      + s["warm_counters"]["cache.evict_injected"])
assert ev > 0, "chaos storm injected no cache eviction"
lines = [json.loads(l) for l in open("artifacts/cache_metrics.jsonl")]
assert lines, "cache tier produced no metrics events"
print(f"cache tier: warm {b['value']} qps ({b['speedup']}x cold, "
      f"hit rate {b['hit_rate']}, {b['share']} shares), storm survived "
      f"{ev} injected evictions / 0 wrong answers "
      "-> artifacts/cache_metrics.jsonl")
EOF

# lockdep + race gate (ISSUEs 7 + 11, layer 2): merge every
# per-process report the armed tiers above dropped (fast tier + the
# chaos tiers + the serve and gray tiers, incl. spawned
# sidecar/exchange workers — the env rides into children) and fail on
# any lock-order cycle, self-deadlock, OR race pair. The fast + serve
# + gray tiers ran with SRJT_RACE=1, so the merged report must show
# the detector was armed and found ZERO unordered accesses to the
# tracked state (tests/test_races.py proves the same gate trips on a
# seeded race). The merged graph is archived as
# artifacts/lockdep_report.json; blocking-while-locked events are
# reported but advisory (the deadline tier owns that risk).
python -m spark_rapids_jni_tpu.analysis.lockdep \
  --merge artifacts/lockdep --out artifacts/lockdep_report.json
python - <<'EOF'
import json
rep = json.load(open("artifacts/lockdep_report.json"))
assert rep["reports"] > 0, "lockdep armed but no process wrote a report"
assert not rep["cycles"] and not rep["self_deadlocks"], rep["cycles"]
assert not rep["site_cycles"], rep["site_cycles"]  # cross-process inversions
assert rep["race_armed"], "race tiers ran but no report carries race_armed"
assert not rep["race_pairs"], rep["race_pairs"]  # srjt-race layer 2
assert rep["race_total"] == 0, rep["race_total"]
print(f"lockdep: {rep['reports']} reports, {len(rep['locks'])} lock sites, "
      f"{len(rep['edges'])} edges, 0 cycles, 0 races "
      "-> artifacts/lockdep_report.json")
EOF

# pool-scaling gate (ISSUE 6 acceptance): arena-resident ops/s at pool
# size 2 must be >= 1.5x pool size 1 on the bench_pool workload (REAL
# spawned workers, 20 ms worker-side latency floor, 8 client threads).
# Under the PR 5 single-buffer arena this ratio was ~1.0 by
# construction; the per-request slab regions are what buy the overlap.
# The 2-process exchange MB/s row rides along and must verify the
# distributed groupby bit-identical before it is emitted.
rm -f artifacts/bench_pool.jsonl
timeout -k 10 600 env SRJT_RESULTS=artifacts/bench_pool.jsonl \
  python benchmarks/bench_pool.py --sizes 1,2 --ops 40 --threads 8 \
  --delay-ms 20 --exchange-rows 150000
python - <<'EOF'
import json
rows = [json.loads(s) for s in open("artifacts/bench_pool.jsonl")]
pool = {r["pool_size"]: r["value"] for r in rows
        if r.get("metric") == "pool_arena_ops_per_s"}
assert 1 in pool and 2 in pool, f"missing pool sizes in BENCH rows: {pool}"
ratio = pool[2] / pool[1]
assert ratio >= 1.5, (
    f"pool 2 scaling {ratio:.2f}x < 1.5x over pool 1 "
    f"({pool[2]:.1f} vs {pool[1]:.1f} ops/s): arena ops serialized?")
exch = [r for r in rows if r.get("metric") == "exchange_2proc_mb_per_s"]
assert exch and exch[0].get("bit_identical"), "no verified exchange BENCH row"
print(f"pool scaling {ratio:.2f}x (1={pool[1]:.1f}, 2={pool[2]:.1f} ops/s), "
      f"exchange {exch[0]['value']} MB/s -> artifacts/bench_pool.jsonl")
EOF

# N-rank exchange scaling gate (ISSUE 16 acceptance): aggregate
# exchange MB/s at world 4 must be >= 2.5x world 2 on the nrank stage
# (REAL spawned peer ranks, an injected per-serve latency floor so the
# ratio measures pull CONCURRENCY, not socket bandwidth — perfect
# scaling doubles both the payload and the parallel pulls hiding the
# floor). Each row is emitted only after the distributed groupby
# verified bit-identical to the single-host oracle at that world.
timeout -k 10 600 env SRJT_RESULTS=artifacts/bench_pool.jsonl \
  python benchmarks/bench_pool.py --stage nrank --nrank-worlds 2,4 \
  --nrank-rows-per-rank 20000
python - <<'EOF'
import json
rows = [json.loads(s) for s in open("artifacts/bench_pool.jsonl")]
nrank = {r["world"]: r for r in rows
         if r.get("metric") == "exchange_nrank_mb_per_s"}
assert 2 in nrank and 4 in nrank, f"missing nrank worlds: {sorted(nrank)}"
assert all(r["bit_identical"] for r in nrank.values()), \
    "an nrank row was emitted without oracle verification"
ratio = nrank[4]["value"] / nrank[2]["value"]
assert ratio >= 2.5, (
    f"world-4 exchange scaling {ratio:.2f}x < 2.5x over world 2 "
    f"({nrank[4]['value']} vs {nrank[2]['value']} MB/s): pulls serialized?")
print(f"nrank exchange scaling {ratio:.2f}x "
      f"(world2={nrank[2]['value']}, world4={nrank[4]['value']} MB/s) "
      "-> artifacts/bench_pool.jsonl")
EOF

# kernel tier (ISSUE 13): the join/decode parity suite re-runs with
# the Pallas tier FORCED through the interpreter (the exact kernel
# bodies the chip runs, hermetic on CPU) and the event log armed, then
# both kernel-tier microbench axes run env-armed. The gate is
# artifact-based: the dispatch.tier events and BENCH-row tier fields
# must PROVE the pallas path actually engaged (a silently-dead tier
# that falls back everywhere passes tests but fails here), every row
# must be bit-identical to its XLA twin, and vs_baseline_worst must
# not regress — informational (> 0, recorded) on CPU where the
# interpreter is the executor, and >= 2.0x on a real TPU backend (the
# ISSUE 13 acceptance bar, enforced by the same gate when premerge
# runs on-chip).
rm -f artifacts/kernel_tier_metrics.jsonl artifacts/bench_kernel_tier.jsonl
SRJT_PALLAS_INTERPRET=1 SRJT_PALLAS_DECODE=1 SRJT_METRICS_ENABLED=1 \
  SRJT_METRICS_LOG=artifacts/kernel_tier_metrics.jsonl \
  python -m pytest tests/test_pallas_kernels.py -q
SRJT_PALLAS_INTERPRET=1 SRJT_RESULTS=artifacts/bench_kernel_tier.jsonl \
  python benchmarks/microbench.py --bench join --rows 20000 --reps 2
SRJT_PALLAS_INTERPRET=1 SRJT_PALLAS_DECODE=1 SRJT_RESULTS=artifacts/bench_kernel_tier.jsonl \
  python benchmarks/microbench.py --bench ragged_decode --rows 20000 --reps 2
python - <<'EOF'
import json
events = [json.loads(s) for s in open("artifacts/kernel_tier_metrics.jsonl")]
tiers = [r for r in events if r["event"] == "dispatch.tier"]
assert any(r.get("tier") == "pallas" for r in tiers), \
    "parity suite ran but no dispatch served from the pallas tier"
assert any(r.get("tier") == "xla" for r in tiers), \
    "forced-fallback tests recorded no xla-tier dispatch"
rows = [json.loads(s) for s in open("artifacts/bench_kernel_tier.jsonl")]
by = {r["bench"]: r for r in rows if "bench" in r}
for name in ("join_inner_paged", "ragged_decode_fused"):
    b = by.get(name)
    assert b, f"no {name} BENCH row emitted"
    assert b["tier"] == "pallas", f"{name}: pallas tier did not engage ({b['tier']})"
    assert b["bit_identical"], f"{name}: kernel result diverged from the XLA twin"
    assert b["vs_baseline_worst"] > 0, b
    if b["fingerprint"]["backend"] == "tpu":
        assert b["vs_baseline_worst"] >= 2.0, (
            f"{name}: on-chip kernel tier regressed below the 2x acceptance "
            f"bar (vs_baseline_worst={b['vs_baseline_worst']})")
print("kernel tier: pallas engaged in parity suite; " + "; ".join(
    f"{n} {by[n]['vs_baseline']}x vs XLA (worst {by[n]['vs_baseline_worst']}x, "
    f"bit-identical)" for n in ("join_inner_paged", "ragged_decode_fused")))
EOF

# (the disabled-mode overhead guard —
# tests/test_metrics.py::test_disabled_mode_is_noop — runs in the fast
# tier above with SRJT_METRICS_ENABLED unset, i.e. exactly the
# production posture it guards; no separate invocation needed)

JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python __graft_entry__.py

python benchmarks/microbench.py --bench groupby --rows 65536 --reps 3
