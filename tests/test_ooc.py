"""Out-of-core partitioned execution tier (srjt-ooc, ISSUE 18).

When a compiled plan's estimated working set exceeds the admitted
device budget, plan/ooc.py degrades it to K hash-partitioned,
spill-backed passes streamed through the same compiled pipeline, with
partials merged by plan/distribute.merge_partials. The contract under
test: the degraded path is BIT-IDENTICAL to the unconstrained oracle —
including under the ci/chaos_ooc.json storm (failed/corrupt partition
spills, a mid-stream kill, a kill -9'd pool worker) — partition
catalog entries never outlive the query (success, failure, or deadline
expiry), the pressure loop never evicts the run's own pinned in-flight
partition, and serve admission admits the per-partition peak instead
of the inadmissible whole-plan estimate.

ci/premerge.sh runs this file in a dedicated ooc tier (pinched budget,
chaos armed, metrics archived) and gates on artifacts/ooc_metrics.jsonl.
"""

import json
import os
import signal

import numpy as np
import pytest

import spark_rapids_jni_tpu  # noqa: F401

from spark_rapids_jni_tpu import memgov
from spark_rapids_jni_tpu import plan as P
from spark_rapids_jni_tpu.models.tpch import gen_lineitem
from spark_rapids_jni_tpu.utils import deadline, faultinj, metrics
from spark_rapids_jni_tpu.utils.errors import DeadlineExceeded, RetryableError

_OOC_CHAOS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "ci", "chaos_ooc.json",
)


pytestmark = pytest.mark.usefixtures("clean_state")


@pytest.fixture
def _ooc_env(monkeypatch):
    """Arm out-of-core with a deterministic 4-way split and a budget
    the q1-style aggregate's estimate exceeds several-fold (the
    sketch-calibrated estimate is 132 KB for 3000 rows — srjt-cbo
    closed the old 0.75x filter-selectivity underestimate — so 36 KB
    forces the degradation while each 33 KB quarter still admits)."""
    monkeypatch.setenv("SRJT_OOC_ENABLED", "1")
    monkeypatch.setenv("SRJT_OOC_PARTITIONS", "4")
    monkeypatch.setenv("SRJT_DEVICE_MEMORY_BUDGET", str(36 * 1024))
    yield


def _counter(name: str) -> int:
    return metrics.registry().counter(name).value


def _q1_ir():
    """TPC-H q1's shape through the plan IR: filtered scan ->
    grouped aggregate -> total-order sort over the group keys (the
    shape find_target admits for partitioned execution)."""
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


def _col_bytes(table):
    return [np.asarray(c.data).tobytes() for c in table.columns]


@pytest.fixture(scope="module")
def q1_case():
    """(tables, ir, oracle bytes) — the oracle compiled WITHOUT memgov
    or any budget, i.e. the unconstrained in-core answer."""
    lineitem = gen_lineitem(3000, seed=7)
    tables = {"lineitem": lineitem}
    ir = _q1_ir()
    oracle = P.compile_ir(ir, tables, name="ooc_oracle")()
    return tables, ir, _col_bytes(oracle)


# ---------------------------------------------------------------------------
# strategy selection + obligation discharge
# ---------------------------------------------------------------------------


class TestSelection:
    def test_off_by_default(self, q1_case, monkeypatch):
        """SRJT_OOC_ENABLED down: a pinched budget changes nothing
        about plan compilation (the seed posture)."""
        tables, ir, _ = q1_case
        # explicit delenv: the premerge ooc tier arms SRJT_OOC_ENABLED
        # ambiently and this test is about the UNARMED posture
        monkeypatch.delenv("SRJT_OOC_ENABLED", raising=False)
        monkeypatch.setenv("SRJT_DEVICE_MEMORY_BUDGET", str(32 * 1024))
        with memgov.enabled():
            cp = P.compile_ir(ir, tables, name="off")
        assert not isinstance(cp, P.OutOfCorePlan)

    def test_not_selected_when_plan_fits(self, q1_case, monkeypatch):
        tables, ir, _ = q1_case
        monkeypatch.setenv("SRJT_OOC_ENABLED", "1")
        monkeypatch.setenv("SRJT_DEVICE_MEMORY_BUDGET", str(1 << 30))
        with memgov.enabled():
            cp = P.compile_ir(ir, tables, name="fits")
        assert not isinstance(cp, P.OutOfCorePlan)

    def test_selected_and_verifier_discharged(self, q1_case, _ooc_env):
        """The partitioning decision is a REWRITE with a PLAN006-style
        obligation: the K filtered-aggregate branches must be verified
        equivalent to the original aggregate, and the per-partition
        peak must be the whole-plan estimate split K ways."""
        tables, ir, _ = q1_case
        with memgov.enabled():
            cp = P.compile_ir(ir, tables, name="sel")
            assert isinstance(cp, P.OutOfCorePlan)
            assert cp.partitions == 4
            assert cp.partition_memory_bytes < cp.estimated_memory_bytes
            assert cp.rewrites_fired.get("partition_for_ooc") == 1
            assert any(ob.rule == "partition_for_ooc"
                       for ob in cp.obligations)
            # discharge through the standard verifier machinery — an
            # undischarged obligation is exactly PLAN006
            schemas = {t: {n: c.dtype for n, c in zip(tbl.names, tbl.columns)}
                       for t, tbl in tables.items()}
            vs = P.verify_obligations(cp.obligations, schemas)
            assert vs == [], [str(v) for v in vs]
            ve = P.verify_estimates(cp)
            assert ve == [], [str(v) for v in ve]

    def test_tampered_partition_branch_raises_plan006(self, q1_case,
                                                      _ooc_env):
        """The discharger is not a rubber stamp: a branch whose filter
        selects the WRONG partition id (dropped/duplicated rows) must
        fail discharge."""
        from spark_rapids_jni_tpu.plan import exprs as ex
        from spark_rapids_jni_tpu.plan.ooc import partition_rewrite

        tables, ir, _ = q1_case
        with memgov.enabled():
            cp = P.compile_ir(ir, tables, name="tamper")
        agg = next(ob for ob in cp.obligations
                   if ob.rule == "partition_for_ooc").before
        union = partition_rewrite(agg, 4)
        bad = P.UnionAll(tuple(
            P.Aggregate(
                P.Filter(agg.input,
                         ex.ppart(agg.keys, 4) == ex.plit(0)),  # all br 0
                keys=agg.keys, aggs=agg.aggs)
            for _ in union.branches
        ))
        import dataclasses

        from spark_rapids_jni_tpu.plan.verifier import _d_partition_ooc

        good_ob = next(ob for ob in cp.obligations
                       if ob.rule == "partition_for_ooc")
        assert _d_partition_ooc(good_ob, None) == []
        tampered = dataclasses.replace(good_ob, after=bad)
        assert _d_partition_ooc(tampered, None), \
            "wrong-partition filter must not discharge"


# ---------------------------------------------------------------------------
# bit-identity: dataset >= 4x budget
# ---------------------------------------------------------------------------


class TestBitIdentical:
    def test_q1_aggregate_4x_budget_bit_identical(self, q1_case,
                                                  monkeypatch):
        """The acceptance scenario: working set >=4x the admitted
        budget, the degraded run streams spill-backed partitions and
        lands bit-identical to the unconstrained oracle, releasing
        every partition catalog entry."""
        tables, ir, want = q1_case
        # size the budget FROM the measured estimate so the >=4x ratio
        # holds by construction, whatever the row count
        est = P.compile_ir(ir, tables, name="probe").estimated_memory_bytes
        budget = est // 4
        monkeypatch.setenv("SRJT_OOC_ENABLED", "1")
        monkeypatch.setenv("SRJT_OOC_PARTITIONS", "0")
        monkeypatch.setenv("SRJT_DEVICE_MEMORY_BUDGET", str(budget))
        spills0 = _counter("memgov.spills") + _counter("memgov.disk_spills")
        with memgov.enabled():
            cp = P.compile_ir(ir, tables, name="ooc4x")
            assert isinstance(cp, P.OutOfCorePlan)
            assert cp.estimated_memory_bytes >= 4 * budget
            out = cp()
            assert _col_bytes(out) == want
            # partitions at rest really were spill-backed
            assert (_counter("memgov.spills")
                    + _counter("memgov.disk_spills")) > spills0
            assert memgov.catalog().kind_stats("partition") == (0, 0)

    def test_auto_partition_count(self, q1_case, monkeypatch):
        """SRJT_OOC_PARTITIONS=0 (auto): K is derived so the
        per-partition peak fits half the budget."""
        tables, ir, want = q1_case
        monkeypatch.setenv("SRJT_OOC_ENABLED", "1")
        monkeypatch.setenv("SRJT_OOC_PARTITIONS", "0")
        monkeypatch.setenv("SRJT_DEVICE_MEMORY_BUDGET", str(64 * 1024))
        with memgov.enabled():
            cp = P.compile_ir(ir, tables, name="auto")
            assert isinstance(cp, P.OutOfCorePlan)
            assert cp.partitions >= 2
            assert cp.partition_memory_bytes <= max(1, (64 * 1024) // 2)
            assert _col_bytes(cp()) == want


# ---------------------------------------------------------------------------
# failure paths: resume, corrupt spill, deadline, chaos storm
# ---------------------------------------------------------------------------


class TestFailurePaths:
    def test_midstream_failure_checkpoints_then_resumes(self, q1_case,
                                                        _ooc_env):
        """A mid-partition crash leaves completed-partition checkpoints
        in the catalog; the retried call resumes past them instead of
        recomputing (the counter is the proof) and still lands
        bit-identical."""
        tables, ir, want = q1_case
        faultinj.configure({"seed": 1, "faults": {"plan.ooc.partition": {
            "type": "retryable", "percent": 100, "after": 2,
            "interceptionCount": 1}}})
        with memgov.enabled():
            cp = P.compile_ir(ir, tables, name="resume")
            assert isinstance(cp, P.OutOfCorePlan)
            with pytest.raises(RetryableError):
                cp()
            ent, _ = memgov.catalog().kind_stats("partition")
            assert ent >= 1, "checkpoints must survive a retryable failure"
            r0 = _counter("ooc.partition_resumes")
            out = cp()
            assert _counter("ooc.partition_resumes") > r0
            assert _col_bytes(out) == want
            assert memgov.catalog().kind_stats("partition") == (0, 0)

    def test_corrupt_partition_spill_lineage_recomputes(self, q1_case,
                                                        monkeypatch):
        """Bit-rot on a partition spill frame: the catalog's CRC gate
        retires the entry, and the run recomputes the hole from
        lineage instead of returning a wrong answer."""
        tables, ir, want = q1_case
        monkeypatch.setenv("SRJT_OOC_ENABLED", "1")
        monkeypatch.setenv("SRJT_OOC_PARTITIONS", "4")
        monkeypatch.setenv("SRJT_DEVICE_MEMORY_BUDGET", str(36 * 1024))
        # a tiny host budget cascades partition spills host -> disk,
        # where the CRC framing (and the corrupt rule) lives
        monkeypatch.setenv("SRJT_HOST_MEMORY_BUDGET", "1024")
        memgov.reset()
        faultinj.configure({"seed": 2, "faults": {"memgov.spill.frame": {
            "type": "corrupt", "percent": 100, "interceptionCount": 2}}})
        l0 = _counter("ooc.lineage_recomputes")
        with memgov.enabled():
            cp = P.compile_ir(ir, tables, name="rot")
            assert isinstance(cp, P.OutOfCorePlan)
            out = cp()
            assert _col_bytes(out) == want
            assert _counter("ooc.lineage_recomputes") > l0
            assert memgov.catalog().kind_stats("partition") == (0, 0)

    def test_deadline_expiry_releases_all_partition_entries(self, q1_case,
                                                            _ooc_env):
        """Deadline expiry mid-stream is a CANCELLATION, not a resume
        point: every partition catalog entry (inputs AND checkpoints)
        must be released on the way out."""
        tables, ir, _ = q1_case
        with memgov.enabled():
            cp = P.compile_ir(ir, tables, name="dl")
            assert isinstance(cp, P.OutOfCorePlan)
            with pytest.raises(DeadlineExceeded):
                with deadline.scope(0.0001):
                    cp()
            assert memgov.catalog().kind_stats("partition") == (0, 0)

    @pytest.mark.slow
    def test_chaos_ooc_storm_on_real_pool_bit_identical(self, q1_case,
                                                        monkeypatch):
        """The acceptance storm, ONE source of truth with the premerge
        tier: ci/chaos_ooc.json arms failed partition spills, corrupt
        spill frames, and a mid-stream kill; a REAL 2-worker sidecar
        pool carries the prefetcher's device path and one worker is
        kill -9'd mid-partition. The run must finish bit-identical
        with >0 partition resumes and zero leaked entries."""
        from spark_rapids_jni_tpu import sidecar_pool

        tables, ir, want = q1_case
        monkeypatch.setenv("SRJT_OOC_ENABLED", "1")
        monkeypatch.setenv("SRJT_OOC_PARTITIONS", "4")
        monkeypatch.setenv("SRJT_DEVICE_MEMORY_BUDGET", str(36 * 1024))
        monkeypatch.setenv("SRJT_HOST_MEMORY_BUDGET", "1024")
        memgov.reset()
        faultinj.configure_from_file(_OOC_CHAOS)
        deaths0 = _counter("sidecar.pool.worker_deaths")
        pool = sidecar_pool.SidecarPool(
            size=2, deadline_s=60, heartbeat_s=1e9, startup_timeout_s=180,
        )
        monkeypatch.setattr(sidecar_pool, "_POOL", pool)
        from spark_rapids_jni_tpu.plan import compiler as compiler_mod

        real_lower = compiler_mod.lower_ir
        killed = []

        def killing_lower(node, tbls, name="plan"):
            # kill -9 one real worker mid-partition: the per-partition
            # sub-plan compile for partition 1 is "mid-stream" by
            # construction
            if name.endswith(".ooc1") and not killed:
                victim = pool._workers[pool._rr % pool.size]
                os.kill(victim.proc.pid, signal.SIGKILL)
                killed.append(victim)
            return real_lower(node, tbls, name=name)

        monkeypatch.setattr(compiler_mod, "lower_ir", killing_lower)
        try:
            r0 = _counter("ooc.partition_resumes")
            with memgov.enabled():
                cp = P.compile_ir(ir, tables, name="storm")
                assert isinstance(cp, P.OutOfCorePlan)
                out = None
                for _ in range(5):  # the storm's mid-stream kill raises
                    try:
                        out = cp()
                        break
                    except RetryableError:
                        continue
                assert out is not None, "storm run never completed"
                assert _col_bytes(out) == want, "WRONG ANSWER under storm"
                assert _counter("ooc.partition_resumes") > r0
                assert killed, "the kill -9 hook never fired"
                assert memgov.catalog().kind_stats("partition") == (0, 0)
            pool.call(0, b"")  # OP_PING: route once post-kill so the
            # supervisor observes the death even if every prefetch ping
            # hit the surviving worker
            assert _counter("sidecar.pool.worker_deaths") > deaths0
        finally:
            pool.shutdown()


# ---------------------------------------------------------------------------
# pin discipline: the pressure loop vs the in-flight partition
# ---------------------------------------------------------------------------


class TestPinDiscipline:
    def test_spill_until_never_touches_pinned_partition(self):
        """Self-eviction livelock regression (unit level): pressure
        demands more than everything, the pinned in-flight partition
        stays device-resident, and spill_until RETURNS (frees what it
        can) instead of spinning on the unspillable entry."""
        import jax.numpy as jnp

        cat = memgov.BufferCatalog()
        inflight = cat.register("ooc.t.in.0", jnp.arange(4096),
                                kind="partition")
        atrest = cat.register("ooc.t.in.1", jnp.arange(4096),
                              kind="partition")
        inflight.pin()
        try:
            freed = cat.spill_until(1 << 40, name="pressure")
            assert inflight.tier == "device", \
                "pressure loop evicted the pinned in-flight partition"
            assert atrest.tier != "device"
            assert freed > 0
        finally:
            inflight.unpin()
            cat.close()

    def test_inflight_partition_pinned_during_compute(self, q1_case,
                                                      _ooc_env,
                                                      monkeypatch):
        """End-to-end: at every per-partition compute the input entry
        is PINNED, so a concurrent pressure squeeze (simulated at the
        compile hook, the widest window) can never evict it out from
        under the running sub-plan."""
        from spark_rapids_jni_tpu.plan import compiler as compiler_mod

        tables, ir, want = q1_case
        real_lower = compiler_mod.lower_ir
        seen = []

        def checking_lower(node, tbls, name="plan"):
            if ".ooc" in name:
                cat = memgov.catalog()
                pinned = [
                    h for h in list(cat._entries.values())
                    if h.kind == "partition" and h.pinned
                ]
                seen.append(len(pinned))
                # adversarial squeeze mid-compute: must not touch the
                # pinned input (and must not livelock)
                cat.spill_until(1 << 40, name="test-squeeze")
                assert all(h.tier == "device" for h in pinned)
            return real_lower(node, tbls, name=name)

        monkeypatch.setattr(compiler_mod, "lower_ir", checking_lower)
        with memgov.enabled():
            cp = P.compile_ir(ir, tables, name="pin")
            assert isinstance(cp, P.OutOfCorePlan)
            out = cp()
        assert _col_bytes(out) == want
        assert seen and all(n >= 1 for n in seen), \
            f"unpinned compute window: {seen}"


# ---------------------------------------------------------------------------
# serve admission: per-partition peak, counted downgrade
# ---------------------------------------------------------------------------


class TestServeAdmission:
    def test_submit_admits_per_partition_peak(self, q1_case, _ooc_env):
        """An OOC plan's whole-plan estimate exceeds the budget BY
        CONSTRUCTION — serve.submit must pre-admit the per-partition
        peak instead (else the scheduler rejects the very strategy
        chosen to fit) and count the downgrade."""
        from spark_rapids_jni_tpu.serve import Scheduler

        tables, ir, want = q1_case
        adm0 = _counter("memgov.ooc_admissions")
        s = Scheduler(max_concurrent=1, queue_depth=4, name="ooc-adm")
        try:
            with memgov.enabled():
                h = s.submit(ir, tables, tenant="ooc")
                assert h._memory_bytes is not None
                assert h._memory_bytes <= 36 * 1024, \
                    "admission saw the whole-plan estimate"
                out = h.result(timeout_s=600)
            assert _col_bytes(out) == want
            assert _counter("memgov.ooc_admissions") > adm0
            assert memgov.catalog().kind_stats("partition") == (0, 0)
        finally:
            s.shutdown(drain=False, timeout_s=10.0)


# ---------------------------------------------------------------------------
# cost-model partition count (srjt-cbo, ISSUE 19)
# ---------------------------------------------------------------------------


class TestCostModelPartitions:
    def test_choose_k_is_minimal_fit(self, monkeypatch):
        """Unit contract: smallest K whose calibrated per-partition
        peak fits HALF the budget; 0 when max_parts cannot fit."""
        from spark_rapids_jni_tpu.plan.stats.model import (
            choose_ooc_partitions, reset_calibration)

        monkeypatch.setenv("SRJT_CBO_CALIBRATION", "/nonexistent/cal.jsonl")
        reset_calibration()
        try:
            # 16 KiB estimate vs 4 KiB budget: ceil(16Ki/8) == 2 KiB
            # == budget//2, so K == 8 exactly at factor 1.0
            assert choose_ooc_partitions(16 << 10, 4 << 10) == 8
            assert choose_ooc_partitions(1 << 30, 1024, max_parts=64) == 0
        finally:
            reset_calibration()

    def test_model_chosen_k_overhead_bounded(self, q1_case, monkeypatch):
        """Regression for the ISSUE 19 satellite: with NO partition
        override, a plan ~4x over budget gets its K from the cost
        model, within 2x of the minimal half-budget fit (no
        pathological over-partitioning), and the degraded run stays
        bit-identical to the in-core oracle."""
        from spark_rapids_jni_tpu.plan.stats.model import reset_calibration

        tables, ir, want = q1_case
        plain = P.compile_ir(ir, tables, name="k_plain")
        est = plain.estimated_memory_bytes
        budget = max(1024, est // 4)
        monkeypatch.setenv("SRJT_OOC_ENABLED", "1")
        monkeypatch.delenv("SRJT_OOC_PARTITIONS", raising=False)
        monkeypatch.setenv("SRJT_DEVICE_MEMORY_BUDGET", str(budget))
        monkeypatch.setenv("SRJT_CBO_CALIBRATION", "/nonexistent/cal.jsonl")
        reset_calibration()
        try:
            with memgov.enabled():
                cp = P.compile_ir(ir, tables, name="k_model")
            assert isinstance(cp, P.OutOfCorePlan)
            floor = -(-est // max(1, budget // 2))
            assert floor <= cp.partitions <= 2 * floor
            # the per-partition peak the serve tier admits really fits
            assert cp.partition_memory_bytes * 2 <= budget
            assert _col_bytes(cp()) == want
        finally:
            reset_calibration()

    def test_knob_still_overrides_model(self, q1_case, monkeypatch, _ooc_env):
        """SRJT_OOC_PARTITIONS stays an explicit override: the model
        never second-guesses an armed K."""
        tables, ir, want = q1_case
        with memgov.enabled():
            cp = P.compile_ir(ir, tables, name="k_override")
        assert isinstance(cp, P.OutOfCorePlan)
        assert cp.partitions == 4
        assert _col_bytes(cp()) == want


# ---------------------------------------------------------------------------
# the run report (the premerge artifact gate's source)
# ---------------------------------------------------------------------------


class TestMetricsArtifact:
    def test_run_report_jsonl(self, q1_case, monkeypatch, tmp_path):
        """SRJT_OOC_METRICS: every completed OOC run appends one JSON
        line — partitions/resumes/spills — the premerge ooc tier's
        artifact gate consumes exactly this file."""
        tables, ir, want = q1_case
        path = tmp_path / "ooc_metrics.jsonl"
        monkeypatch.setenv("SRJT_OOC_ENABLED", "1")
        monkeypatch.setenv("SRJT_OOC_PARTITIONS", "4")
        monkeypatch.setenv("SRJT_DEVICE_MEMORY_BUDGET", str(36 * 1024))
        monkeypatch.setenv("SRJT_OOC_METRICS", str(path))
        with memgov.enabled():
            cp = P.compile_ir(ir, tables, name="art")
            assert isinstance(cp, P.OutOfCorePlan)
            assert _col_bytes(cp()) == want
        lines = [json.loads(ln) for ln in
                 path.read_text().strip().splitlines()]
        assert len(lines) == 1
        rec = lines[0]
        assert rec["ooc"] is True and rec["partitions"] == 4
        assert rec["spills"] >= 0 and rec["resumes"] == 0
        assert rec["partition_peak_bytes"] < rec["est_peak_bytes"]
