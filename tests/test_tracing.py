"""srjt-trace: distributed per-query tracing + flight recorder (ISSUE 12).

Covers the tentpole end to end: gated no-op stubs, span nesting and
context propagation (incl. the contextvars hand-off into threads), the
cross-process wire protocol (sidecar TRACE flag bit, exchange traced
GET verb), the flight recorder's slow/shed/failed capture, the
tracemerge join + orphan gate + Chrome export, and the per-layer
instrumentation (op boundary, retry attempts/splits, memgov admission
and spill, serve scheduler, pool routing/hedging).

The slow acceptance (``TestRealPoolCrossProcess``) runs a traced query
through a REAL pool of 2 with one hedged request and one kill -9
failover, then merges the per-process span logs and asserts the tree:
hedge legs are siblings with the winner marked exactly once, the
failover retry is a child of the original op span, and a worker span
from another pid resolves to its client-side parent — zero orphans.
ci/premerge.sh runs this file env-armed in the dedicated trace tier and
gates the archived artifacts.
"""

import json
import os
import struct
import threading
import time

import numpy as np
import pytest

from spark_rapids_jni_tpu import memgov, runtime, serve, sidecar, sidecar_pool
from spark_rapids_jni_tpu.analysis import tracemerge
from spark_rapids_jni_tpu.utils import (
    dispatch,
    faultinj,
    knobs,
    metrics,
    retry,
    trace_sink,
    tracing,
)
from spark_rapids_jni_tpu.utils.errors import Overloaded, RetryableError

from _inproc import InProcWorker, inproc_spawn


pytestmark = pytest.mark.usefixtures("own_span_log")


def _log_spans():
    path = trace_sink.resolved_log_path()
    if path is None or not os.path.exists(path):
        return []
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return [r for r in out if r.get("kind") == "span"]


def _wait_for_span(name, timeout_s=5.0):
    end = time.monotonic() + timeout_s
    while time.monotonic() < end:
        hits = [s for s in _log_spans() if s["name"] == name]
        if hits:
            return hits
        time.sleep(0.02)
    raise AssertionError(f"span {name!r} never reached the log")


# ---------------------------------------------------------------------------
# gate + stubs
# ---------------------------------------------------------------------------


class TestGateAndStubs:
    def test_disabled_is_all_noops(self):
        assert not tracing.is_enabled()
        assert tracing.start_trace("q") is None
        assert tracing.wire_context() is None
        with tracing.span("x", a=1) as sp:
            sp.annotate(b=2)  # null span: a pass
        tracing.closed_span("y", 0.1)
        tracing.annotate(c=3)
        assert tracing.current_context() is None
        assert _log_spans() == []
        assert trace_sink.recorder().last(5) == []

    def test_set_enabled_roundtrip(self):
        tracing.set_enabled(True)
        try:
            assert tracing.is_enabled()
        finally:
            tracing.set_enabled(False)
        assert not tracing.is_enabled()

    def test_span_outside_any_context_is_noop_even_armed(self):
        with tracing.enabled():
            with tracing.span("stray") as sp:
                assert sp is tracing._NULL_SPAN
        assert _log_spans() == []

    def test_sampler_zero_disables_roots(self, monkeypatch):
        monkeypatch.setenv("SRJT_TRACE_SAMPLE", "0")
        with tracing.enabled():
            qt = tracing.start_trace("q")
            # an UNSAMPLED trace is a real (silent) context, not None:
            # inner layers must see "a decision was made" (see below)
            assert qt is not None and not qt.ctx.sampled
            with qt.activate():
                with tracing.span("inner") as sp:
                    assert sp is tracing._NULL_SPAN
                assert tracing.wire_context() is None
            qt.finish("ok")
        assert metrics.registry().value("trace.unsampled") >= 1
        assert trace_sink.recorder().last(5) == []
        assert _log_spans() == []

    def test_unsampled_query_suppresses_op_auto_roots(self, monkeypatch):
        """The sampler's decision covers the WHOLE query: an unsampled
        serve submission must not let every inner op boundary re-roll
        and mint one-op fragment traces."""
        monkeypatch.setenv("SRJT_TRACE_SAMPLE", "0")

        @dispatch.op_boundary("frag_op")
        def frag_op():
            return 1

        with tracing.enabled():
            qt = tracing.start_trace("serve.query")
            with qt.activate():
                for _ in range(5):
                    assert frag_op() == 1
            qt.finish("ok")
        assert trace_sink.recorder().last(10) == []
        assert _log_spans() == []


class TestProfileTo:
    def test_disabled_never_touches_the_profiler(self, monkeypatch):
        import jax

        def boom(*a, **k):
            raise AssertionError("profiler touched while disabled")

        monkeypatch.setattr(jax.profiler, "start_trace", boom)
        monkeypatch.setattr(jax.profiler, "stop_trace", boom)
        with tracing.profile_to("/nonexistent"):
            pass

    def test_start_failure_tears_down_and_propagates(self, monkeypatch):
        import jax

        stopped = []

        def bad_start(*a, **k):
            raise RuntimeError("partial setup")

        monkeypatch.setattr(jax.profiler, "start_trace", bad_start)
        monkeypatch.setattr(
            jax.profiler, "stop_trace", lambda: stopped.append(1)
        )
        with tracing.enabled():
            with pytest.raises(RuntimeError, match="partial setup"):
                with tracing.profile_to("/tmp/x"):
                    raise AssertionError("body must not run")
        assert stopped == [1]  # the half-armed session was torn down

    def test_body_failure_still_stops(self, monkeypatch):
        import jax

        calls = []
        monkeypatch.setattr(
            jax.profiler, "start_trace", lambda *a, **k: calls.append("start")
        )
        monkeypatch.setattr(
            jax.profiler, "stop_trace", lambda: calls.append("stop")
        )
        with tracing.enabled():
            with pytest.raises(ValueError):
                with tracing.profile_to("/tmp/x"):
                    raise ValueError("body")
        assert calls == ["start", "stop"]


# ---------------------------------------------------------------------------
# spans, context, wire codec
# ---------------------------------------------------------------------------


class TestSpans:
    def test_nesting_and_parentage(self):
        with tracing.enabled():
            qt = tracing.start_trace("q", tenant="t")
            with qt.activate():
                with tracing.span("outer") as o:
                    with tracing.span("inner") as i:
                        assert i.parent_id == o.span_id
                        assert i.depth == o.depth + 1
            qt.finish("ok")
        rec = trace_sink.recorder().worst()
        by_name = {s["name"]: s for s in rec["spans"]}
        assert by_name["outer"]["parent"] == by_name["q"]["span"]
        assert by_name["inner"]["parent"] == by_name["outer"]["span"]
        assert by_name["q"]["parent"] is None

    def test_error_status_and_annotations(self):
        with tracing.enabled():
            qt = tracing.start_trace("q")
            with qt.activate():
                with pytest.raises(ValueError):
                    with tracing.span("bad", k=1) as sp:
                        sp.annotate(extra=2)
                        raise ValueError("x")
            qt.finish("failed")
        rec = trace_sink.recorder().worst()
        bad = next(s for s in rec["spans"] if s["name"] == "bad")
        assert bad["status"] == "error"
        assert bad["annotations"] == {"k": 1, "extra": 2, "error": "ValueError"}
        assert rec["status"] == "failed" and rec.get("flushed")

    def test_span_cap_counts_overflow_but_log_is_uncapped(self, monkeypatch):
        monkeypatch.setenv("SRJT_TRACE_MAX_SPANS", "16")
        with tracing.enabled():
            qt = tracing.start_trace("q")
            with qt.activate():
                for i in range(20):
                    with tracing.span(f"s{i}"):
                        pass
            qt.finish("ok")
        rec = trace_sink.recorder().worst()
        assert rec["dropped_spans"] == 20 - 16 + 1  # +1: the root itself
        assert len(_log_spans()) == 21  # every span + root reached the log

    def test_context_rides_copy_context_into_threads(self):
        import contextvars

        seen = {}

        def child():
            with tracing.span("threaded") as sp:
                seen["parent"] = sp.parent_id

        with tracing.enabled():
            qt = tracing.start_trace("q")
            with qt.activate():
                with tracing.span("launcher") as lsp:
                    ctx = contextvars.copy_context()
                    t = threading.Thread(target=ctx.run, args=(child,))
                    t.start()
                    t.join()
                    assert seen["parent"] == lsp.span_id
            qt.finish("ok")

    def test_wire_codec_roundtrip(self):
        assert tracing.TRACE_CTX_LEN == 17
        with tracing.enabled():
            qt = tracing.start_trace("q")
            with qt.activate():
                blob = tracing.wire_context()
                assert blob is not None and len(blob) == 17
                tid, parent, sampled = tracing.decode_wire_context(blob)
                assert tid == qt.ctx.trace_id
                assert parent == qt.root.span_id
                assert sampled
            qt.finish("ok")

    def test_remote_scope_parents_to_wire_span(self):
        with tracing.enabled():
            qt = tracing.start_trace("q")
            with qt.activate():
                blob = tracing.wire_context()
            tid, parent, sampled = tracing.decode_wire_context(blob)
            with tracing.remote_scope(tid, parent, sampled):
                with tracing.span("remote") as sp:
                    assert sp.parent_id == parent
                    assert sp.ctx.trace_id == tid
                    assert sp.ctx.remote
            qt.finish("ok")

    def test_per_process_log_file_carries_pid(self):
        path = trace_sink.resolved_log_path()
        assert f".{os.getpid()}." in os.path.basename(path)


# ---------------------------------------------------------------------------
# flight recorder + explain
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def _mk(self, status="ok", dur=0.01, name="q"):
        return {"kind": "trace", "trace": "00", "name": name,
                "status": status, "duration_s": dur, "spans": [],
                "dropped_spans": 0, "metrics_delta": {}}

    def test_ring_is_bounded(self):
        r = trace_sink.FlightRecorder(capacity=3)
        for i in range(10):
            r.record(self._mk(name=f"q{i}"))
        assert [x["name"] for x in r.last(10)] == ["q7", "q8", "q9"]
        assert r.snapshot()["recorded"] == 10

    def test_non_ok_always_flushes_ok_does_not(self):
        r = trace_sink.FlightRecorder(capacity=8)
        r.record(self._mk("ok"))
        r.record(self._mk("shed"))
        r.record(self._mk("failed"))
        flags = [x.get("flushed", False) for x in r.last(3)]
        assert flags == [False, True, True]

    def test_slow_query_flushes(self, monkeypatch):
        monkeypatch.setenv("SRJT_SLOW_QUERY_SEC", "0.5")
        r = trace_sink.FlightRecorder(capacity=8)
        r.record(self._mk("ok", dur=0.1))
        r.record(self._mk("ok", dur=0.9))
        flags = [x.get("flushed", False) for x in r.last(2)]
        assert flags == [False, True]

    def test_worst_prefers_failures_then_duration(self):
        r = trace_sink.FlightRecorder(capacity=8)
        r.record(self._mk("ok", dur=9.0, name="slow_ok"))
        r.record(self._mk("failed", dur=0.1, name="fast_fail"))
        assert r.worst()["name"] == "fast_fail"

    def test_explain_last_renders_tree(self):
        with tracing.enabled():
            qt = tracing.start_trace("q", tenant="acme")
            with qt.activate():
                with tracing.span("stage_a"):
                    with tracing.span("stage_b"):
                        pass
            qt.finish("ok")
        text = runtime.explain_last()
        assert "stage_a" in text and "stage_b" in text
        assert "tenant=acme" in text
        # indentation proves nesting: b deeper than a
        la = next(l for l in text.splitlines() if "stage_a" in l)
        lb = next(l for l in text.splitlines() if "stage_b" in l)
        assert len(lb) - len(lb.lstrip()) > len(la) - len(la.lstrip())

    def test_explain_last_none_when_untraced(self):
        assert runtime.explain_last() is None

    def test_stats_report_carries_trace_section(self):
        rep = runtime.stats_report()
        assert "trace" in rep
        assert "spans" in rep["trace"] and "recorder" in rep["trace"]

    def test_stage_report_carries_trace_section(self):
        rep = metrics.stage_report("t")
        assert set(rep["trace"]) == {"spans", "traces", "flushed"}

    def test_stage_summary_shape(self):
        with tracing.enabled():
            qt = tracing.start_trace("q")
            with qt.activate():
                with tracing.span("a"):
                    pass
            qt.finish("ok")
        s = trace_sink.stage_summary()
        # the shorter dict (ISSUE 26): three registry counters, no
        # per-span histogram or depth gauge behind them
        assert set(s) == {"spans", "traces", "flushed"}
        assert s["spans"] >= 2 and s["traces"] >= 1
        reg = metrics.registry()
        assert reg.peek("trace.span_us") is None
        assert reg.peek("trace.max_depth") is None


# ---------------------------------------------------------------------------
# tracemerge
# ---------------------------------------------------------------------------


def _span(trace, span, parent, name, ts=1.0, pid=1, **ann):
    rec = {"kind": "span", "trace": trace, "span": span, "parent": parent,
           "name": name, "ts": ts, "dur_us": 100.0, "pid": pid, "tid": 1,
           "status": "ok"}
    if ann:
        rec["annotations"] = ann
    return rec


class TestTracemerge:
    def _write(self, path, recs):
        with open(path, "w") as f:
            for r in recs:
                f.write(json.dumps(r) + "\n")

    def test_merge_joins_files_by_trace_id(self, tmp_path):
        a = str(tmp_path / "client.1.jsonl")
        b = str(tmp_path / "worker.2.jsonl")
        self._write(a, [
            _span("t1", "r", None, "root", ts=1.0),
            _span("t1", "c", "r", "request", ts=1.1),
        ])
        self._write(b, [_span("t1", "w", "c", "worker_op", ts=1.2, pid=2)])
        merged = tracemerge.merge(tracemerge.load_spans([a, b]))
        assert merged["orphans"] == 0
        t = merged["traces"]["t1"]
        assert [s["name"] for s in t["spans"]] == ["root", "request",
                                                   "worker_op"]
        assert t["pids"] == [1, 2]
        assert t["roots"] == ["r"]

    def test_orphans_detected_and_gated(self, tmp_path):
        p = str(tmp_path / "x.jsonl")
        self._write(p, [
            _span("t1", "r", None, "root"),
            _span("t1", "o", "missing", "stray"),
        ])
        merged = tracemerge.merge(tracemerge.load_spans([p]))
        assert merged["orphans"] == 1
        assert merged["traces"]["t1"]["orphans"] == ["o"]
        out = str(tmp_path / "m.json")
        rc = tracemerge.main([p, "--format", "json", "--out", out,
                              "--gate-orphans"])
        assert rc == 1
        rc = tracemerge.main([p, "--format", "json", "--out", out])
        assert rc == 0

    def test_chrome_export_is_perfetto_shaped(self, tmp_path):
        p = str(tmp_path / "x.jsonl")
        self._write(p, [_span("t1", "r", None, "root", wid=3)])
        out = str(tmp_path / "chrome.json")
        assert tracemerge.main([p, "--format", "chrome", "--out", out]) == 0
        doc = json.load(open(out))
        ev = doc["traceEvents"][0]
        assert ev["ph"] == "X" and ev["name"] == "root"
        assert ev["args"]["trace"] == "t1" and ev["args"]["wid"] == 3
        assert ev["dur"] == 100.0

    def test_torn_lines_and_duplicates_are_tolerated(self, tmp_path):
        p = str(tmp_path / "x.jsonl")
        with open(p, "w") as f:
            f.write(json.dumps(_span("t1", "r", None, "root")) + "\n")
            f.write(json.dumps(_span("t1", "r", None, "root")) + "\n")  # dup
            f.write('{"kind": "span", "trace": "t1", TORN')  # killed writer
        merged = tracemerge.merge(tracemerge.load_spans([p]))
        assert len(merged["traces"]["t1"]["spans"]) == 1

    def test_glob_loading(self, tmp_path):
        for i in range(3):
            self._write(str(tmp_path / f"s.{i}.jsonl"),
                        [_span("t1", f"x{i}", None, f"n{i}")])
        spans = tracemerge.load_spans([str(tmp_path / "s.*.jsonl")])
        assert len(spans) == 3

    def test_tree_rendering(self, tmp_path):
        p = str(tmp_path / "x.jsonl")
        self._write(p, [
            _span("t1", "r", None, "root", ts=1.0),
            _span("t1", "c", "r", "child", ts=1.1, pid=2),
        ])
        merged = tracemerge.merge(tracemerge.load_spans([p]))
        text = tracemerge.render_tree(merged)
        assert "root" in text and "child" in text and "pid 2" in text


# ---------------------------------------------------------------------------
# layer instrumentation: op boundary, retry, memgov
# ---------------------------------------------------------------------------


class TestOpBoundary:
    def test_outermost_auto_roots_one_op_trace(self):
        @dispatch.op_boundary("trace_toy")
        def toy(x):
            return x * 2

        with tracing.enabled():
            assert toy(3) == 6
        rec = trace_sink.recorder().worst()
        assert rec["name"] == "op.trace_toy" and rec["status"] == "ok"

    def test_nested_boundary_is_a_child_span(self):
        @dispatch.op_boundary("trace_inner")
        def inner(x):
            return x + 1

        @dispatch.op_boundary("trace_outer")
        def outer(x):
            return inner(x)

        with tracing.enabled():
            assert outer(1) == 2
        rec = trace_sink.recorder().worst()
        by_name = {s["name"]: s for s in rec["spans"]}
        assert (by_name["op.trace_inner"]["parent"]
                == by_name["op.trace_outer"]["span"])

    def test_retry_attempts_annotate_the_op_span(self):
        calls = {"n": 0}

        @dispatch.op_boundary("trace_flaky")
        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise RetryableError("transient")
            return "ok"

        with tracing.enabled():
            with retry.enabled(base_delay_ms=1, max_delay_ms=2):
                assert flaky() == "ok"
        rec = trace_sink.recorder().worst()
        op = next(s for s in rec["spans"] if s["name"] == "op.trace_flaky")
        assert op["annotations"]["retry_attempts"] == 2
        assert op["annotations"]["retry_error"] == "RetryableError"

    def test_split_recursion_is_child_spans(self):
        def fn(b):
            if len(b) > 2:
                raise RetryableError("RESOURCE_EXHAUSTED: batch too big")
            return list(b)

        with tracing.enabled():
            qt = tracing.start_trace("splitq")
            with qt.activate():
                out = retry.retry_with_split(
                    fn, [1, 2, 3, 4],
                    split=lambda b: (b[:len(b) // 2], b[len(b) // 2:]),
                    combine=lambda ps: sum(ps, []),
                    op_name="splitop",
                )
            qt.finish("ok")
        assert out == [1, 2, 3, 4]
        rec = next(r for r in trace_sink.recorder().last(5)
                   if r["name"] == "splitq")
        splits = [s for s in rec["spans"] if s["name"] == "retry.split"]
        assert len(splits) == 2
        assert all(s["annotations"]["depth"] == 1 for s in splits)

    def test_disabled_tracing_records_nothing(self):
        @dispatch.op_boundary("trace_quiet")
        def quiet():
            return 1

        assert quiet() == 1
        assert trace_sink.recorder().last(5) == []
        assert _log_spans() == []


class TestMemgovSpans:
    def test_admission_wait_span(self):
        ctrl = memgov.AdmissionController(capacity_fn=lambda: 1 << 30)
        with tracing.enabled():
            qt = tracing.start_trace("memq")
            with qt.activate():
                with ctrl.acquire(4096, name="toy"):
                    pass
            qt.finish("ok")
        rec = next(r for r in trace_sink.recorder().last(5)
                   if r["name"] == "memq")
        adm = next(s for s in rec["spans"]
                   if s["name"] == "memgov.admission_wait")
        assert adm["annotations"] == {"op": "toy", "nbytes": 4096}

    def test_spill_and_rematerialize_spans(self):
        import jax.numpy as jnp

        cat = memgov.BufferCatalog()
        h = cat.register("trace.buf", jnp.arange(64, dtype=jnp.int32))
        with tracing.enabled():
            qt = tracing.start_trace("spillq")
            with qt.activate():
                h.spill()
                got = h.get()
            qt.finish("ok")
        assert np.array_equal(np.asarray(got), np.arange(64))
        rec = next(r for r in trace_sink.recorder().last(5)
                   if r["name"] == "spillq")
        names = [s["name"] for s in rec["spans"]]
        assert "memgov.spill" in names and "memgov.rematerialize" in names
        cat.close()


# ---------------------------------------------------------------------------
# serve scheduler: roots, queue spans, shed/expire capture
# ---------------------------------------------------------------------------


class TestSchedulerTracing:
    def test_completed_query_has_queue_and_run_spans(self):
        with tracing.enabled():
            with serve.Scheduler(max_concurrent=1, name="tr1") as sched:
                h = sched.submit(lambda: 7, tenant="a", deadline_s=10)
                assert h.result(10) == 7
        recs = [r for r in trace_sink.recorder().last(10)
                if r["name"] == "serve.query"]
        assert recs and recs[-1]["status"] == "ok"
        names = [s["name"] for s in recs[-1]["spans"]]
        assert "serve.queue_wait" in names and "serve.run" in names
        ann = recs[-1]["annotations"]
        assert ann["tenant"] == "a" and "query" in ann

    def test_shed_at_admission_reaches_the_recorder(self):
        with tracing.enabled():
            sched = serve.Scheduler(max_concurrent=1, queue_depth=1,
                                    name="tr2")
            try:
                gate = threading.Event()
                blk = sched.submit(gate.wait, tenant="b")
                for _ in range(500):
                    if blk.status() == "running":
                        break
                    time.sleep(0.005)
                q1 = sched.submit(lambda: 1, tenant="b")
                with pytest.raises(Overloaded):
                    sched.submit(lambda: 2, tenant="b")
                gate.set()
                q1.result(10)
                blk.result(10)
            finally:
                sched.shutdown()
        sheds = [r for r in trace_sink.recorder().last(20)
                 if r["status"] == "shed"]
        assert sheds, "shed query never reached the flight recorder"
        assert sheds[-1]["annotations"]["shed_cause"] == "queue_full"
        assert sheds[-1].get("flushed")

    def test_injected_shed_is_captured(self):
        faultinj.configure({"faults": {"serve.admit": {"type": "reject"}}})
        with tracing.enabled():
            sched = serve.Scheduler(max_concurrent=1, name="tr3")
            try:
                with pytest.raises(Overloaded):
                    sched.submit(lambda: 1, tenant="x")
            finally:
                sched.shutdown()
        sheds = [r for r in trace_sink.recorder().last(10)
                 if r["status"] == "shed"]
        assert sheds and sheds[-1]["annotations"]["shed_cause"] == "injected"

    def test_failed_query_flushes_with_metrics_delta(self):
        def boom():
            raise ValueError("query exploded")

        with tracing.enabled():
            with serve.Scheduler(max_concurrent=1, name="tr4") as sched:
                h = sched.submit(boom, tenant="a")
                with pytest.raises(ValueError):
                    h.result(10)
        rec = next(r for r in reversed(trace_sink.recorder().last(10))
                   if r["status"] == "failed")
        assert rec.get("flushed")
        assert rec["metrics_delta"].get("serve.failed", 0) >= 1

    def test_cancel_in_queue_is_captured(self):
        with tracing.enabled():
            sched = serve.Scheduler(max_concurrent=1, queue_depth=4,
                                    name="tr5")
            try:
                gate = threading.Event()
                blk = sched.submit(gate.wait, tenant="a")
                for _ in range(500):
                    if blk.status() == "running":
                        break
                    time.sleep(0.005)
                q = sched.submit(lambda: 1, tenant="a")
                assert q.cancel("operator said so")
                gate.set()
                blk.result(10)
            finally:
                sched.shutdown()
        recs = [r for r in trace_sink.recorder().last(10)
                if r["status"] == "cancelled"]
        assert recs
        assert recs[-1]["annotations"]["cancel_reason"] == "operator said so"


# ---------------------------------------------------------------------------
# cross-process wire propagation (in-process worker / exchange pair)
# ---------------------------------------------------------------------------


def _groupby_payload(n=200, k=8, seed=3):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, k, n).astype(np.int64)
    vals = rng.standard_normal(n).astype(np.float32)
    return struct.pack("<IQ", k, n) + keys.tobytes() + vals.tobytes()


class TestSidecarWirePropagation:
    def test_worker_span_parents_to_client_request_span(self):
        w = InProcWorker()
        payload = _groupby_payload()
        want = sidecar._dispatch(sidecar.OP_GROUPBY_SUM_F32, payload, "cpu")
        c = sidecar.SupervisedClient(w.sock_path, deadline_s=20,
                                     heartbeat_s=1e9)
        try:
            with tracing.enabled():
                qt = tracing.start_trace("wireq")
                with qt.activate():
                    resp = c.request(sidecar.OP_GROUPBY_SUM_F32, payload)
                qt.finish("ok")
            assert resp == want
            spans = _wait_for_span("sidecar.worker_op")
            req = _wait_for_span("sidecar.request")[0]
            wrk = spans[0]
            assert wrk["parent"] == req["span"]
            assert wrk["trace"] == req["trace"]
            assert wrk["annotations"]["op"] == "GROUPBY_SUM_F32"
        finally:
            c.close()
            w.kill()

    def test_untraced_request_keeps_legacy_framing(self):
        w = InProcWorker()
        payload = _groupby_payload()
        want = sidecar._dispatch(sidecar.OP_GROUPBY_SUM_F32, payload, "cpu")
        c = sidecar.SupervisedClient(w.sock_path, deadline_s=20,
                                     heartbeat_s=1e9)
        try:
            # tracing disabled: no TRACE flag, no blob, answers intact
            assert c.request(sidecar.OP_GROUPBY_SUM_F32, payload) == want
            # armed but NO active context: still no flag on the wire
            with tracing.enabled():
                assert (
                    c.request(sidecar.OP_GROUPBY_SUM_F32, payload) == want
                )
            assert _log_spans() == []
        finally:
            c.close()
            w.kill()

    def test_pool_failover_retry_is_child_of_the_op_span(self):
        pool = sidecar_pool.SidecarPool(
            size=2, deadline_s=20, heartbeat_s=1e9, spawn_fn=inproc_spawn
        )
        payload = _groupby_payload()
        want = sidecar._dispatch(sidecar.OP_GROUPBY_SUM_F32, payload, "cpu")
        try:
            with tracing.enabled():
                with retry.enabled(base_delay_ms=1, max_delay_ms=2,
                                   max_attempts=6):
                    qt = tracing.start_trace("poolq")
                    with qt.activate():
                        assert pool.call_arena(
                            sidecar.OP_GROUPBY_SUM_F32, payload
                        ) == want
                        f0 = metrics.registry().value(
                            "sidecar.pool.failovers"
                        )
                        pool._workers[0].proc.kill()
                        for _ in range(4):
                            assert pool.call_arena(
                                sidecar.OP_GROUPBY_SUM_F32, payload
                            ) == want
                            if metrics.registry().value(
                                "sidecar.pool.failovers"
                            ) > f0:
                                break
                    qt.finish("ok")
        finally:
            pool.shutdown()
        rec = next(r for r in trace_sink.recorder().last(5)
                   if r["name"] == "poolq")
        spans = rec["spans"]
        failover_calls = []
        for call in (s for s in spans if s["name"] == "pool.call"):
            kids = [s for s in spans
                    if s.get("parent") == call["span"]
                    and s["name"] == "pool.request"]
            wids = {s["annotations"]["wid"] for s in kids}
            if len(kids) >= 2 and len(wids) >= 2:
                failover_calls.append((call, kids))
        assert failover_calls, (
            "no pool.call span carries two pool.request attempts on "
            "distinct workers (the failover retry as a child of the "
            "original op span)"
        )
        _, kids = failover_calls[0]
        statuses = sorted(s["status"] for s in kids)
        assert statuses == ["error", "ok"]


class TestExchangePropagation:
    def test_serve_span_parents_to_fetch_span(self):
        import jax.numpy as jnp

        from spark_rapids_jni_tpu.columnar import Column, Table
        from spark_rapids_jni_tpu.columnar.dtype import DType, TypeId
        from spark_rapids_jni_tpu.parallel import shuffle

        t = Table(
            [Column(DType(TypeId.INT64),
                    data=jnp.arange(10, dtype=jnp.int64))],
            names=["a"],
        )
        a = shuffle.TcpExchange(rank=0)
        b = shuffle.TcpExchange(rank=1)
        try:
            b.publish(0, {0: t})
            with tracing.enabled():
                qt = tracing.start_trace("exq")
                with qt.activate():
                    got = a.fetch(b.address, 0, 0)
                qt.finish("ok")
            assert np.array_equal(
                np.asarray(got.columns[0].data), np.arange(10)
            )
            srv = _wait_for_span("exchange.serve")[0]
            fetch = _wait_for_span("exchange.fetch")[0]
            assert srv["parent"] == fetch["span"]
            assert srv["trace"] == fetch["trace"]
            # untraced fetch (no active context) keeps the plain verb
            got2 = a.fetch(b.address, 0, 0)
            assert got2.num_rows == 10
        finally:
            a.close()
            b.close()


class TestFullChain:
    def test_submit_queue_admission_op_wire_worker_chain(self):
        """The acceptance chain, in-process: a served query's trace
        nests serve.run -> op span -> memgov admission AND the pool's
        wire spans, connected by parent links end to end."""
        pool = sidecar_pool.SidecarPool(
            size=1, deadline_s=20, heartbeat_s=1e9, spawn_fn=inproc_spawn
        )
        payload = _groupby_payload()
        want = sidecar._dispatch(sidecar.OP_GROUPBY_SUM_F32, payload, "cpu")

        @dispatch.op_boundary("chain_op")
        def chain_op():
            return pool.call_arena(sidecar.OP_GROUPBY_SUM_F32, payload)

        try:
            with tracing.enabled(), memgov.enabled():
                with serve.Scheduler(max_concurrent=1, name="chain") as s:
                    h = s.submit(chain_op, tenant="acme", deadline_s=30)
                    assert h.result(30) == want
        finally:
            pool.shutdown()
        rec = next(r for r in trace_sink.recorder().last(10)
                   if r["name"] == "serve.query")
        spans = {s["span"]: s for s in rec["spans"]}

        def ancestors(s):
            out = []
            while s.get("parent") in spans:
                s = spans[s["parent"]]
                out.append(s["name"])
            return out

        by_name = {}
        for s in rec["spans"]:
            by_name.setdefault(s["name"], s)
        assert "serve.run" in by_name
        op = by_name["op.chain_op"]
        assert "serve.run" in ancestors(op)
        adm = by_name["memgov.admission_wait"]
        assert "op.chain_op" in ancestors(adm)
        req = by_name["sidecar.request"]
        chain = ancestors(req)
        assert "pool.call" in chain and "op.chain_op" in chain \
            and "serve.query" in chain
        # the worker half ran in-process here; the real-pool acceptance
        # below proves the cross-pid link
        wrk = _wait_for_span("sidecar.worker_op")[0]
        assert wrk["trace"] == rec["trace"]


# ---------------------------------------------------------------------------
# the real-pool acceptance: hedge + kill -9 failover, merged cross-process
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestRealPoolCrossProcess:
    def test_hedge_and_failover_merge_across_processes(
        self, tmp_path, monkeypatch
    ):
        # per-worker chaos: w0's GROUPBY answers slowly (the hedge
        # trigger) and w0 self-SIGKILLs on its first STATS (the
        # failover); w1 runs the same profile clean. The delay holds
        # fire for the first 10 matching dispatches (`after`) so the
        # warm-up fills the op-class histogram with FAST samples — the
        # hedge trigger's p50 ceiling is a pollution guard, and a p50
        # that is itself the straggler's latency would (correctly)
        # never arm the defense.
        profile = {
            "faults": {
                "sidecar.worker.GROUPBY_SUM_F32@w0": {
                    "type": "delay", "delayMs": 400, "percent": 100,
                    "after": 10,
                },
                "sidecar.worker.STATS@w0": {
                    "type": "crash", "percent": 100,
                },
            },
            "seed": 7,
        }
        profile_path = str(tmp_path / "trace_chaos.json")
        with open(profile_path, "w") as f:
            json.dump(profile, f)
        # span-log base: the CI tier's env path when set (so the
        # premerge gate sees these spans), else test-local
        base = knobs.get_str("SRJT_TRACE_LOG") or str(
            tmp_path / "trace_spans.jsonl"
        )
        trace_sink.set_log_path(base)
        # hedging armed wide open; quarantine off so the delayed worker
        # stays routable (the hedge needs a slow primary to race)
        monkeypatch.setenv("SRJT_HEDGE_MIN_SAMPLES", "1")
        monkeypatch.setenv("SRJT_HEDGE_BUDGET_PCT", "100")
        monkeypatch.setenv("SRJT_HEDGE_SHED_WINDOW_S", "0.001")
        monkeypatch.setenv("SRJT_QUARANTINE_ENABLED", "0")
        payload = _groupby_payload()
        want = sidecar._dispatch(sidecar.OP_GROUPBY_SUM_F32, payload, "cpu")
        reg = metrics.registry()
        pool = sidecar_pool.SidecarPool(
            size=2, deadline_s=30, heartbeat_s=1e9,
            startup_timeout_s=180,
            env={
                "SRJT_FAULTINJ_CONFIG": profile_path,
                "SRJT_TRACE_ENABLED": "1",
                "SRJT_TRACE_LOG": base,
            },
        )
        client_pid = os.getpid()

        # the acceptance QUERY: one op_boundary-wrapped callable
        # submitted through the serve scheduler with memgov armed, so
        # the merged tree spans submit -> queue -> admission -> op ->
        # wire -> worker (cross-process) for ONE query
        @dispatch.op_boundary("acceptance_op")
        def acceptance_op():
            hedges0 = reg.value("sidecar.pool.hedges_won")
            for _ in range(10):
                assert pool.call_arena(
                    sidecar.OP_GROUPBY_SUM_F32, payload
                ) == want
                if reg.value("sidecar.pool.hedges_won") > hedges0:
                    break
            assert reg.value("sidecar.pool.hedges_won") > hedges0, \
                "hedged dispatch never won a race"
            fail0 = reg.value("sidecar.pool.failovers")
            for _ in range(6):
                pool.call(sidecar.OP_STATS)
                if reg.value("sidecar.pool.failovers") > fail0:
                    break
            assert reg.value("sidecar.pool.failovers") > fail0, \
                "kill -9 never produced a failover"
            return "done"

        try:
            with tracing.enabled(), memgov.enabled(), retry.enabled(
                base_delay_ms=1, max_delay_ms=4, max_attempts=8
            ):
                # warm the op class with FAST samples so the hedge
                # trigger arms well below the coming 400 ms straggler
                # (the delay rule's `after` keeps w0 clean here); the
                # workers' jax compiles also happen outside the trace
                for _ in range(24):
                    assert pool.call_arena(
                        sidecar.OP_GROUPBY_SUM_F32, payload
                    ) == want
                with serve.Scheduler(max_concurrent=1, name="acc") as s:
                    h = s.submit(acceptance_op, tenant="acme")
                    assert h.result(120) == "done"
        finally:
            pool.shutdown()
        rec = next(
            r for r in reversed(trace_sink.recorder().last(10))
            if r["name"] == "serve.query" and r["status"] == "ok"
        )
        trace_hex = rec["trace"]
        # merge every per-process log (client + both workers) and
        # assert the acceptance tree
        root, ext = os.path.splitext(base)
        pattern = f"{root}.*{ext or '.jsonl'}"

        def merged_trace():
            merged = tracemerge.merge(tracemerge.load_spans([pattern]))
            return merged["traces"].get(trace_hex)

        t = None
        end = time.monotonic() + 30
        while time.monotonic() < end:
            t = merged_trace()
            if t is not None and not t["orphans"] and any(
                s["name"] == "pool.hedge_leg" for s in t["spans"]
            ):
                legs = [s for s in t["spans"]
                        if s["name"] == "pool.hedge_leg"]
                if len(legs) % 2 == 0:
                    break
            time.sleep(0.25)
        assert t is not None, f"trace {trace_hex} missing from the merge"
        spans = t["spans"]
        # 1) zero orphans: every span's parent resolves in the trace
        assert t["orphans"] == [], t["orphans"]
        # 2) hedge legs are SIBLINGS and the winner is marked once
        legs = [s for s in spans if s["name"] == "pool.hedge_leg"]
        assert legs, "no hedge legs in the merged trace"
        by_parent = {}
        for s in legs:
            by_parent.setdefault(s["parent"], []).append(s)
        raced = [v for v in by_parent.values() if len(v) == 2]
        assert raced, "hedge legs are not siblings under one pool.call"
        winners = [s for pair in raced for s in pair
                   if (s.get("annotations") or {}).get("winner")]
        assert len(winners) == 1, (
            f"winner marked {len(winners)} times, expected exactly once"
        )
        winner_pair = next(p for p in raced if any(
            (s.get("annotations") or {}).get("winner") for s in p))
        assert {s["annotations"]["leg"] for s in winner_pair} == {
            "primary", "hedge"
        }
        # 3) the failover retry is a CHILD of the original op span
        by_id = {s["span"]: s for s in spans}
        failover = None
        for call in (s for s in spans if s["name"] == "pool.call"):
            kids = [s for s in spans
                    if s.get("parent") == call["span"]
                    and s["name"] == "pool.request"]
            if (len(kids) >= 2
                    and len({k["annotations"]["wid"] for k in kids}) >= 2):
                failover = (call, kids)
        assert failover is not None, (
            "no pool.call with a failed attempt and its retry on a "
            "different worker"
        )
        # 4) cross-process: a worker span from another pid resolves to
        # its client-side parent
        wrk = [s for s in spans if s["name"] == "sidecar.worker_op"
               and s["pid"] != client_pid]
        assert wrk, "no worker-process span joined the trace"
        for s in wrk:
            assert s["parent"] in by_id
            assert by_id[s["parent"]]["pid"] == client_pid
            assert by_id[s["parent"]]["name"] == "sidecar.request"
        # 5) the acceptance chain: submit -> queue -> admission -> op
        # -> wire -> worker, connected by parent links end to end
        def ancestor_names(s):
            out = []
            cur = s
            while cur.get("parent") in by_id:
                cur = by_id[cur["parent"]]
                out.append(cur["name"])
            return out

        chain = ancestor_names(wrk[0])
        for expected in ("sidecar.request", "pool.call",
                         "op.acceptance_op", "serve.run", "serve.query"):
            assert expected in chain, (expected, chain)
        names = {s["name"] for s in spans}
        assert "serve.queue_wait" in names
        assert "memgov.admission_wait" in names
        # 6) the tree renders
        text = tracemerge.render_tree(
            tracemerge.merge(tracemerge.load_spans([pattern])),
            only=trace_hex,
        )
        assert "pool.hedge_leg" in text and "sidecar.worker_op" in text
