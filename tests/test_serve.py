"""Concurrent serving runtime tests (serve/, ISSUE 8): submission API,
per-tenant weighted-fair QoS, overload shedding (every shed a retryable
Overloaded at admission), deadline interaction (expired-in-queue,
cooperative cancel), shutdown discipline, and the fast chaos-under-load
acceptance (storm while serving, bit-identical results)."""

import threading
import time

import numpy as np
import pytest

from spark_rapids_jni_tpu import serve
from spark_rapids_jni_tpu.serve.scheduler import Scheduler
from spark_rapids_jni_tpu.utils import deadline, faultinj, metrics, retry
from spark_rapids_jni_tpu.utils.errors import (
    DeadlineExceeded,
    Overloaded,
    RetryableError,
    classify,
)


@pytest.fixture
def sched():
    s = Scheduler(max_concurrent=2, queue_depth=4, name="t")
    yield s
    assert s.shutdown(drain=False, timeout_s=30.0), "scheduler leaked threads"


def _block_slots(s, n, tenant="blocker"):
    """Occupy n dispatch slots until the returned event is set."""
    ev = threading.Event()
    handles = [s.submit(ev.wait, 30, tenant=tenant) for _ in range(n)]
    deadline_t = time.monotonic() + 5
    while time.monotonic() < deadline_t:
        if sum(1 for h in handles if h.status() == "running") == n:
            return ev, handles
        time.sleep(0.002)
    raise AssertionError("slots never filled")


# ---------------------------------------------------------------------------
# submission API
# ---------------------------------------------------------------------------


class TestSubmit:
    def test_result_roundtrip(self, sched):
        h = sched.submit(lambda a, b=1: a + b, 4, b=5, tenant="u")
        assert h.result(10) == 9
        assert h.status() == "done"
        assert h.done() and h.exception() is None

    def test_non_callable_rejected(self, sched):
        with pytest.raises(TypeError):
            sched.submit(42)

    def test_queries_run_concurrently_across_slots(self, sched):
        # a 2-party barrier only passes if both queries hold slots at once
        bar = threading.Barrier(2, timeout=5)
        hs = [sched.submit(bar.wait, tenant="u") for _ in range(2)]
        for h in hs:
            h.result(10)

    def test_fn_exception_surfaces_unchanged(self, sched):
        def boom():
            raise ValueError("bad input")

        h = sched.submit(boom, tenant="u")
        with pytest.raises(ValueError, match="bad input"):
            h.result(10)
        assert h.status() == "failed"

    def test_result_timeout_leaves_query_running(self, sched):
        ev = threading.Event()
        h = sched.submit(ev.wait, 30, tenant="u")
        with pytest.raises(TimeoutError):
            h.result(0.05)
        ev.set()
        assert h.result(10) is True

    def test_status_transitions(self, sched):
        ev, _ = _block_slots(sched, 2)
        h = sched.submit(lambda: 7, tenant="u")
        assert h.status() == "queued"
        ev.set()
        assert h.result(10) == 7
        assert h.status() == "done"

    def test_compiled_pipeline_is_submittable(self, sched):
        # anything callable is a query — the compiled-plan path included
        from spark_rapids_jni_tpu.models import tpch

        li = tpch.gen_lineitem(500, seed=11)
        want = tpch.q6(li)
        h = sched.submit(tpch.q6, li, tenant="u")
        assert h.result(60) == want


# ---------------------------------------------------------------------------
# per-tenant QoS: bounded queues + weighted-fair dispatch
# ---------------------------------------------------------------------------


class TestQoS:
    def test_queue_full_fast_fails_with_overloaded(self, sched):
        ev, _ = _block_slots(sched, 2)
        for _ in range(4):  # fill tenant queue (depth 4)
            sched.submit(lambda: 1, tenant="a")
        before = metrics.registry().value("serve.shed_total")
        with pytest.raises(Overloaded) as ei:
            sched.submit(lambda: 1, tenant="a")
        assert ei.value.cause == "queue_full"
        assert ei.value.retry_after_s and ei.value.retry_after_s > 0
        assert isinstance(ei.value, RetryableError)  # retryable taxonomy
        assert metrics.registry().value("serve.shed_total") == before + 1
        ev.set()

    def test_full_queue_never_buffers_unboundedly(self, sched):
        ev, _ = _block_slots(sched, 2)
        for _ in range(4):
            sched.submit(lambda: 1, tenant="a")
        # 50 more submissions: every one fast-fails, none buffers
        refused = 0
        for _ in range(50):
            try:
                sched.submit(lambda: 1, tenant="a")
            except Overloaded:
                refused += 1
        assert refused == 50
        assert sched.snapshot()["tenants"]["a"]["queued"] == 4
        ev.set()

    def test_queue_full_sheds_lowest_priority_first(self, sched):
        ev, _ = _block_slots(sched, 2)
        low = [sched.submit(lambda: 1, tenant="a", priority=0)
               for _ in range(4)]
        high = sched.submit(lambda: 2, tenant="a", priority=5)
        # one low-priority victim was evicted with Overloaded, the
        # high-priority query took its room
        shed = [h for h in low if h.status() == "shed"]
        assert len(shed) == 1
        exc = shed[0].exception()
        assert isinstance(exc, Overloaded) and exc.cause == "queue_full"
        ev.set()
        assert high.result(10) == 2

    def test_equal_priority_does_not_evict(self, sched):
        ev, _ = _block_slots(sched, 2)
        queued = [sched.submit(lambda: 1, tenant="a", priority=3)
                  for _ in range(4)]
        with pytest.raises(Overloaded):
            sched.submit(lambda: 1, tenant="a", priority=3)
        assert all(h.status() == "queued" for h in queued)
        ev.set()

    def test_one_tenant_queue_full_does_not_block_another(self, sched):
        ev, _ = _block_slots(sched, 2)
        for _ in range(4):
            sched.submit(lambda: 1, tenant="a")
        with pytest.raises(Overloaded):
            sched.submit(lambda: 1, tenant="a")
        h = sched.submit(lambda: "b ok", tenant="b")  # b admits fine
        assert h.status() == "queued"
        ev.set()
        assert h.result(10) == "b ok"

    def test_weighted_fair_dispatch_alternates_equal_weights(self):
        s = Scheduler(max_concurrent=1, queue_depth=16, name="wf")
        try:
            ev, _ = _block_slots(s, 1)
            order = []
            for _ in range(4):
                s.submit(order.append, "A", tenant="A")
                s.submit(order.append, "B", tenant="B")
            ev.set()
            assert s.shutdown(drain=True, timeout_s=30)
            # stride scheduling: strict alternation at equal weight
            assert "".join(order) == "ABABABAB"
        finally:
            s.shutdown(drain=False, timeout_s=30)

    def test_weighted_fair_respects_weights(self):
        s = Scheduler(max_concurrent=1, queue_depth=32, name="wf2")
        try:
            ev, _ = _block_slots(s, 1)
            order = []
            for _ in range(8):
                s.submit(order.append, "A", tenant="A", weight=3.0)
                s.submit(order.append, "B", tenant="B", weight=1.0)
            ev.set()
            assert s.shutdown(drain=True, timeout_s=30)
            # 3:1 stride: in any window of 8 dispatches A gets ~6
            assert order[:8].count("A") >= 5
        finally:
            s.shutdown(drain=False, timeout_s=30)

    def test_pass_floor_tracks_pre_increment_min(self):
        # the stride floor must be the PRE-increment minimum: one
        # dispatch of a low-weight lane (huge stride) must not vault
        # the floor ahead, or every tenant entering at the floor would
        # queue behind the whole backlog
        s = Scheduler(max_concurrent=1, name="floor")
        try:
            s.submit(lambda: 1, tenant="lo", weight=0.01).result(10)
            with s._cond:
                lo_pass = s._tenants["lo"].pass_
                floor = s._pass_floor
            assert floor < lo_pass, (
                f"floor {floor} inflated to the post-increment pass "
                f"{lo_pass}"
            )
        finally:
            s.shutdown(drain=False, timeout_s=30)

    def test_admission_fairness_aggressor_cannot_starve_victim(self):
        """The acceptance fairness bar: with the aggressor's queue
        saturated the whole run, the victim's completed throughput
        stays within 25% of its fair share (half the slots at equal
        weight)."""
        s = Scheduler(max_concurrent=2, queue_depth=4, name="fair")
        try:
            stop = threading.Event()
            completed = {"agg": 0, "vic": 0}
            lock = threading.Lock()

            def work(tag):
                time.sleep(0.004)
                with lock:
                    completed[tag] += 1

            def aggressor():
                while not stop.is_set():
                    try:
                        s.submit(work, "agg", tenant="aggressor")
                    except Overloaded:
                        time.sleep(0.001)

            at = threading.Thread(target=aggressor, daemon=True)
            at.start()
            time.sleep(0.05)  # let the storm saturate its queue
            t_end = time.monotonic() + 1.2
            vic_shed = 0
            while time.monotonic() < t_end:
                try:
                    s.submit(work, "vic", tenant="victim")
                except Overloaded:
                    vic_shed += 1
                time.sleep(0.004)
            stop.set()
            at.join(10)
            s.shutdown(drain=True, timeout_s=30)
            total = completed["agg"] + completed["vic"]
            fair = total / 2
            assert completed["vic"] >= 0.75 * fair, (
                f"victim starved: {completed['vic']} of {total} completed "
                f"(fair share {fair:.0f}, shed {vic_shed})"
            )
            # and the aggressor's queue really was saturated: it shed
            assert metrics.registry().value("serve.shed.queue_full") > 0
        finally:
            s.shutdown(drain=False, timeout_s=30)


# ---------------------------------------------------------------------------
# overload controller: pressure, DOA, breaker, injected rejects
# ---------------------------------------------------------------------------


class TestOverload:
    def test_doa_deadline_fast_fails(self, sched):
        with pytest.raises(Overloaded) as ei:
            sched.submit(lambda: 1, tenant="u", deadline_s=0)
        assert ei.value.cause == "doa_deadline"

    def test_doa_from_expired_ambient_scope(self, sched):
        with deadline.scope(0.01):
            time.sleep(0.03)
            with pytest.raises(Overloaded) as ei:
                sched.submit(lambda: 1, tenant="u")
        assert ei.value.cause == "doa_deadline"

    def test_ambient_scope_clamps_submitted_budget(self, sched):
        seen = {}

        def probe():
            seen["rem"] = deadline.remaining()

        with deadline.scope(0.5):
            h = sched.submit(probe, tenant="u", deadline_s=60.0)
            h.result(10)
        assert seen["rem"] <= 0.5

    def test_queue_age_pressure_sheds(self):
        s = Scheduler(max_concurrent=1, queue_depth=8,
                      max_queue_age_s=0.05, name="age")
        try:
            ev, _ = _block_slots(s, 1)
            s.submit(lambda: 1, tenant="a")  # will sit and age
            time.sleep(0.12)
            with pytest.raises(Overloaded) as ei:
                s.submit(lambda: 1, tenant="b", priority=0)
            assert ei.value.cause == "pressure"
            # higher priority still displaces the aged victim
            h = s.submit(lambda: "vip", tenant="b", priority=9)
            ev.set()
            assert h.result(10) == "vip"
        finally:
            s.shutdown(drain=False, timeout_s=30)

    def test_global_queued_cap_sheds(self):
        s = Scheduler(max_concurrent=1, queue_depth=8, max_queued=2,
                      name="cap")
        try:
            ev, _ = _block_slots(s, 1)
            s.submit(lambda: 1, tenant="a")
            s.submit(lambda: 1, tenant="b")
            with pytest.raises(Overloaded) as ei:
                s.submit(lambda: 1, tenant="c")
            assert ei.value.cause == "pressure"
            ev.set()
        finally:
            s.shutdown(drain=False, timeout_s=30)

    def test_tenant_full_under_global_cap_evicts_exactly_one(self):
        # both limits tripped at once: one admission displaces ONE
        # victim, never two (the tenant eviction keeps the global
        # count flat, so the cap stays honored)
        s = Scheduler(max_concurrent=1, queue_depth=2, max_queued=2,
                      name="one-evict")
        try:
            ev, _ = _block_slots(s, 1)
            low = [s.submit(lambda: 1, tenant="a", priority=0)
                   for _ in range(2)]
            before = metrics.registry().value("serve.shed_total")
            h = s.submit(lambda: "vip", tenant="a", priority=7)
            assert metrics.registry().value("serve.shed_total") == before + 1
            assert sum(1 for q in low if q.status() == "shed") == 1
            assert s.snapshot()["queued"] == 2
            ev.set()
            assert h.result(10) == "vip"
        finally:
            s.shutdown(drain=False, timeout_s=30)

    def test_idle_lanes_pruned_under_tenant_churn(self):
        # per-session tenant ids must not grow the lane map unboundedly
        s = Scheduler(max_concurrent=2, name="churn")
        try:
            for i in range(200):
                s.submit(lambda: 1, tenant=f"session-{i}").result(10)
            assert len(s.snapshot()["tenants"]) <= 80
        finally:
            s.shutdown(drain=True, timeout_s=30)

    def test_base_exception_lands_in_handle_and_slot_survives(self, sched):
        def bail():
            raise SystemExit(3)

        h = sched.submit(bail, tenant="u")
        with pytest.raises(SystemExit):
            h.result(10)
        assert h.status() == "failed"
        # the dispatch slot survived user code calling sys.exit
        assert sched.submit(lambda: "alive", tenant="u").result(10) == "alive"

    def test_injected_reject_sheds_deterministically(self, sched, clean_state):
        """Satellite: faultinj's `reject` kind keyed serve.admit forces
        shed decisions without real overload."""
        before = metrics.registry().value("serve.shed.injected")
        faultinj.configure({"faults": {"serve.admit": {
            "type": "reject", "percent": 100, "delayMs": 125,
            "interceptionCount": 2}}})
        for _ in range(2):
            with pytest.raises(Overloaded) as ei:
                sched.submit(lambda: 1, tenant="u")
            assert ei.value.cause == "injected"
            assert ei.value.retry_after_s == pytest.approx(0.125)
        # budget exhausted: the third submission admits
        assert sched.submit(lambda: 3, tenant="u").result(10) == 3
        assert metrics.registry().value("serve.shed.injected") == before + 2

    def test_breaker_dark_pool_sheds_device_only_work(self, sched, clean_state):
        from spark_rapids_jni_tpu import sidecar

        br = sidecar.breaker()
        br.configure(threshold=1, cooldown_s=60)
        br.record_failure("test: pool dark")
        assert br.state() == "open"
        with pytest.raises(Overloaded) as ei:
            sched.submit(lambda: 1, tenant="u", host_eligible=False)
        assert ei.value.cause == "breaker"
        # host-engine-eligible work keeps flowing while dark
        assert sched.submit(lambda: "host ok", tenant="u").result(10) \
            == "host ok"


# ---------------------------------------------------------------------------
# deadline interaction (satellite): expiry in queue, cooperative cancel
# ---------------------------------------------------------------------------


class TestDeadlines:
    def test_expired_in_queue_never_dispatches(self, sched):
        ev, _ = _block_slots(sched, 2)
        ran = []
        before = metrics.registry().value("serve.expired_in_queue")
        h = sched.submit(lambda: ran.append(1), tenant="u", deadline_s=0.04)
        time.sleep(0.1)  # expire while both slots stay busy
        ev.set()
        with pytest.raises(DeadlineExceeded, match="expired in queue"):
            h.result(10)
        assert h.status() == "expired"
        assert ran == [], "an expired query must never dispatch"
        assert metrics.registry().value("serve.expired_in_queue") == before + 1

    def test_cancel_queued_completes_immediately(self, sched):
        ev, _ = _block_slots(sched, 2)
        ran = []
        h = sched.submit(lambda: ran.append(1), tenant="u")
        assert h.cancel("changed my mind")
        with pytest.raises(DeadlineExceeded, match="changed my mind"):
            h.result(10)
        assert h.status() == "cancelled" and ran == []
        ev.set()

    def test_cancel_running_unwinds_via_cancel_token(self, sched):
        entered = threading.Event()

        def loop():
            entered.set()
            while True:
                deadline.check("loop")  # the op_boundary cancel point
                time.sleep(0.002)

        h = sched.submit(loop, tenant="u")
        assert entered.wait(5)
        assert h.cancel("operator stop")
        with pytest.raises(DeadlineExceeded, match="operator stop"):
            h.result(10)
        assert h.status() == "cancelled"
        # the slot survived the unwind: the next query runs clean
        assert sched.submit(lambda: "after", tenant="u").result(10) == "after"

    def test_running_budget_bounds_the_fn(self, sched):
        def loop():
            while True:
                deadline.check("loop")
                time.sleep(0.002)

        t0 = time.monotonic()
        h = sched.submit(loop, tenant="u", deadline_s=0.15)
        with pytest.raises(DeadlineExceeded):
            h.result(10)
        assert time.monotonic() - t0 < 5.0
        assert h.status() == "failed"  # budget expiry, not a cancel

    def test_queue_wait_spends_the_budget(self, sched):
        ev, _ = _block_slots(sched, 2)
        seen = {}

        def probe():
            seen["rem"] = deadline.remaining()

        h = sched.submit(probe, tenant="u", deadline_s=5.0)
        time.sleep(0.2)
        ev.set()
        h.result(10)
        assert seen["rem"] < 4.9, "the queue wait must come out of the budget"

    def test_cancel_final_state_returns_false(self, sched):
        h = sched.submit(lambda: 1, tenant="u")
        h.result(10)
        assert h.cancel() is False


# ---------------------------------------------------------------------------
# shutdown discipline (satellite): drain semantics + no leaked threads
# ---------------------------------------------------------------------------


class TestShutdown:
    def test_drain_completes_queued_queries(self):
        s = Scheduler(max_concurrent=1, queue_depth=8, name="sd1")
        ev, _ = _block_slots(s, 1)
        hs = [s.submit(lambda i=i: i, tenant="u") for i in range(4)]
        ev.set()
        assert s.shutdown(drain=True, timeout_s=30)
        assert [h.result(1) for h in hs] == [0, 1, 2, 3]

    def test_nodrain_sheds_queued_with_overloaded_shutting_down(self):
        s = Scheduler(max_concurrent=1, queue_depth=8, name="sd2")
        ev, _ = _block_slots(s, 1)
        hs = [s.submit(lambda: 1, tenant="u") for _ in range(3)]
        ev.set()
        assert s.shutdown(drain=False, timeout_s=30)
        for h in hs:
            with pytest.raises(Overloaded) as ei:
                h.result(1)
            assert ei.value.cause == "shutting_down"

    def test_nodrain_cancels_inflight_and_joins(self):
        s = Scheduler(max_concurrent=1, queue_depth=8, name="sd3")
        entered = threading.Event()

        def loop():
            entered.set()
            while True:
                deadline.check("loop")
                time.sleep(0.002)

        h = s.submit(loop, tenant="u")
        assert entered.wait(5)
        assert s.shutdown(drain=False, timeout_s=30)
        assert h.status() == "cancelled"

    def test_submit_after_shutdown_raises_overloaded(self):
        s = Scheduler(max_concurrent=1, name="sd4")
        assert s.shutdown(drain=True, timeout_s=30)
        with pytest.raises(Overloaded) as ei:
            s.submit(lambda: 1)
        assert ei.value.cause == "shutting_down"

    def test_no_leaked_threads_after_shutdown(self):
        s = Scheduler(max_concurrent=3, name="sd5")
        names = {w.name for w in s._workers}
        assert s.shutdown(drain=True, timeout_s=30)
        assert not any("sd5" in rep for rep in serve.leak_report()), (
            "a fully-joined scheduler must leave the leak report"
        )
        alive = {t.name for t in threading.enumerate() if t.name in names}
        assert not alive, f"leaked dispatch threads: {alive}"

    def test_shutdown_is_idempotent(self):
        s = Scheduler(max_concurrent=1, name="sd6")
        assert s.shutdown(drain=True, timeout_s=30)
        assert s.shutdown(drain=True, timeout_s=30)

    def test_default_scheduler_roundtrip(self):
        h = serve.submit(lambda: 99, tenant="u")
        assert h.result(10) == 99
        serve.shutdown_scheduler(drain=True, timeout_s=30)
        assert serve.live_scheduler_count() == 0


# ---------------------------------------------------------------------------
# observability + taxonomy
# ---------------------------------------------------------------------------


class TestObservability:
    def test_overloaded_taxonomy_contract(self):
        e = Overloaded("x", retry_after_s=1.5, cause="queue_full")
        assert isinstance(e, RetryableError)
        assert e.retry_after_s == 1.5 and e.cause == "queue_full"
        # stringified Overloaded crossing a process boundary stays
        # retryable through the classifier
        got = classify(RuntimeError("sidecar worker: Overloaded: shed"))
        assert isinstance(got, RetryableError)

    def test_stats_section_shape(self, sched):
        sched.submit(lambda: 1, tenant="u").result(10)
        sec = serve.stats_section()
        assert sec is not None
        for key in ("submitted", "completed", "shed_total",
                    "expired_in_queue", "shed", "schedulers"):
            assert key in sec
        assert set(sec["shed"]) == set(serve.SHED_CAUSES)
        snap = [s for s in sec["schedulers"] if s["name"] == "t"]
        assert snap and snap[0]["slots"] == 2

    def test_stats_report_carries_serve_section(self, sched):
        from spark_rapids_jni_tpu import runtime

        rep = runtime.stats_report()
        assert "serve" in rep and rep["serve"] is not None

    def test_queue_wait_and_e2e_histograms_when_armed(self):
        with metrics.enabled():
            s = Scheduler(max_concurrent=1, name="obs")
            try:
                s.submit(lambda: 1, tenant="u").result(10)
            finally:
                s.shutdown(drain=True, timeout_s=30)
            snap = metrics.registry().snapshot()["histograms"]
            assert snap["serve.queue_wait_us"]["count"] >= 1
            assert snap["serve.e2e_us"]["count"] >= 1


# ---------------------------------------------------------------------------
# chaos under load (fast tier): storm while serving, bit-identical
# ---------------------------------------------------------------------------


class TestChaosUnderLoad:
    def test_storm_while_serving_yields_bit_identical_results(self):
        """Mixed q1/q6 at concurrency 4 under a retryable+delay+reject
        storm: every completed query bit-identical to the sequential
        oracle, every shed surfaced as Overloaded (never a timeout),
        shed_total > 0."""
        from spark_rapids_jni_tpu.models import tpch

        li = tpch.gen_lineitem(2000, seed=5)
        want1 = tpch.q1(li)
        want6 = tpch.q6(li)
        w1 = {n: np.asarray(want1.column(n).data) for n in want1.names}

        def run_q1():
            got = tpch.q1(li)
            for n in got.names:
                assert np.array_equal(np.asarray(got.column(n).data), w1[n])
            return "q1"

        def run_q6():
            assert tpch.q6(li) == want6
            return "q6"

        faultinj.configure({"seed": 77, "faults": {
            "serve.admit": {"type": "reject", "percent": 25,
                            "delayMs": 100},
            "groupby_aggregate": {"type": "retryable", "percent": 30,
                                  "delayMs": 5},
        }})
        s = Scheduler(max_concurrent=4, queue_depth=16, name="chaos")
        shed = 0
        handles = []
        try:
            with retry.enabled(max_attempts=10, base_delay_ms=1,
                               max_delay_ms=8, seed=3):
                for i in range(40):
                    fn = run_q1 if i % 2 else run_q6
                    tenant = f"t{i % 3}"
                    try:
                        handles.append(s.submit(fn, tenant=tenant,
                                                deadline_s=120))
                    except Overloaded:
                        shed += 1
                    except Exception as e:  # noqa: BLE001
                        raise AssertionError(
                            f"shed surfaced as {type(e).__name__}, "
                            "not Overloaded") from e
                results = [h.result(300) for h in handles]
        finally:
            faultinj.disable()
            assert s.shutdown(drain=False, timeout_s=60)
        assert shed > 0, "the reject storm never shed"
        assert len(results) == 40 - shed
        assert set(results) <= {"q1", "q6"}
        assert metrics.registry().value("serve.shed_total") > 0
