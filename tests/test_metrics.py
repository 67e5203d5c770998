"""Runtime metrics subsystem (ISSUE 2): registry semantics (threaded
increments, log2 histogram bucketing, disabled-mode no-ops), the
structured JSON-lines event log, the cross-layer stats_report, the
sidecar STATS protocol verb, and the chaos-integration exactness
contract — retry/split counters must match the faults injected by
utils/faultinj.py BIT-EXACTLY (deterministic budgets, percent=100)."""

import json
import threading

import numpy as np
import pytest

import spark_rapids_jni_tpu  # noqa: F401
import jax.numpy as jnp

from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.columnar import dtype as dt
from spark_rapids_jni_tpu.utils import faultinj, memory, metrics, retry
from spark_rapids_jni_tpu.utils.dispatch import op_boundary
from spark_rapids_jni_tpu.utils.errors import FatalDeviceError, RetryableError


@pytest.fixture(autouse=True)
def _zeroed_registry(clean_state):
    """Every test pins its own arming (``clean_state`` puts back what the
    environment says: the premerge observability tier runs this file with
    SRJT_METRICS_ENABLED=1) and leaves a zeroed registry."""
    metrics.reset()
    yield
    metrics.reset()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


def test_counter_threaded_increments_are_exact():
    c = metrics.registry().counter("t.threads")
    n_threads, per = 8, 5000

    def work():
        for _ in range(per):
            c.inc()

    ts = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == n_threads * per


def test_histogram_log2_buckets():
    h = metrics.registry().histogram("t.hist")
    # bucket k spans [2^(k-1), 2^k); bucket 0 holds < 1
    for v in (0, 0.5, 1, 1.9, 2, 3, 4, 7, 8, 1000):
        h.record(v)
    snap = h._snapshot()
    assert snap["count"] == 10
    assert snap["min"] == 0 and snap["max"] == 1000
    assert snap["buckets"] == {
        "0": 2,      # 0, 0.5
        "1": 2,      # 1, 1.9
        "2": 2,      # 2, 3
        "4": 2,      # 4, 7
        "8": 1,      # 8
        "512": 1,    # 1000 in [512, 1024)
    }


def test_registry_type_collision_is_loud():
    metrics.registry().counter("t.kind")
    with pytest.raises(TypeError, match="already registered"):
        metrics.registry().gauge("t.kind")


def test_gauge_set_and_snapshot_shape():
    metrics.registry().gauge("t.g").set(41)
    metrics.registry().counter("t.c").inc(3)
    snap = metrics.snapshot()
    assert snap["gauges"]["t.g"] == 41
    assert snap["counters"]["t.c"] == 3
    assert set(snap) == {"counters", "gauges", "histograms"}
    json.dumps(snap)  # must be JSON-serializable as-is


def test_reset_zeroes_but_keeps_names():
    metrics.registry().counter("t.r").inc(5)
    metrics.registry().histogram("t.rh").record(9)
    metrics.reset()
    assert metrics.registry().counter("t.r").value == 0
    assert metrics.registry().histogram("t.rh").count == 0
    assert "t.r" in metrics.registry().names()


# ---------------------------------------------------------------------------
# disabled-mode overhead guard
# ---------------------------------------------------------------------------


def test_disabled_mode_is_noop():
    """The overhead-guard contract (premerge asserts this test): with
    metrics disarmed, the gated accessors hand out no-op stubs, the op
    boundary records nothing and reads no clock-derived state, and the
    event log stays untouched — an instrumented hot path costs one
    boolean read."""

    @op_boundary("metrics_guard_op")
    def op():
        return 11

    with metrics.disabled():
        c = metrics.counter("guard.c")
        c.inc(100)
        metrics.histogram("guard.h").record(5)
        metrics.gauge("guard.g").set(5)
        with metrics.timer("guard.t"):
            pass
        metrics.event("guard.event", x=1)
        assert op() == 11
    names = metrics.registry().names()
    assert not any(n.startswith("guard.") for n in names)
    assert not any(n.startswith("op.metrics_guard_op") for n in names)
    # the stub is shared and inert
    assert c.value == 0


def test_enabled_op_boundary_records_calls_and_wall_time():
    @op_boundary("metrics_timed_op")
    def op():
        return 5

    with metrics.enabled():
        for _ in range(3):
            assert op() == 5
        snap = metrics.snapshot()
    assert snap["counters"]["op.metrics_timed_op.calls"] == 3
    h = snap["histograms"]["op.metrics_timed_op.wall_us"]
    assert h["count"] == 3 and h["sum"] >= 0


# ---------------------------------------------------------------------------
# structured event log
# ---------------------------------------------------------------------------


def test_event_log_json_lines(tmp_path):
    path = str(tmp_path / "events.jsonl")
    with metrics.enabled(log_path=path):
        metrics.event("unit.test", op="x", n=3)
        metrics.event("unit.test2", nested={"a": 1})
    metrics.close_log()
    lines = [json.loads(s) for s in open(path).read().splitlines()]
    assert [r["event"] for r in lines] == ["unit.test", "unit.test2"]
    assert lines[0]["op"] == "x" and lines[0]["n"] == 3
    assert lines[1]["nested"] == {"a": 1}
    assert all("ts" in r for r in lines)


def test_event_log_disabled_without_path(tmp_path):
    with metrics.enabled():  # armed, but no path configured
        prev = metrics.log_path()
        metrics.set_log_path(None)
        try:
            metrics.event("nowhere")
        finally:
            metrics.set_log_path(prev)
    # nothing to assert beyond "did not raise"; the payoff is above


# ---------------------------------------------------------------------------
# chaos integration: counters match injected faults EXACTLY
# ---------------------------------------------------------------------------


def test_retry_counters_match_injected_fault_budget():
    """percent=100 + interceptionCount=N makes the injector fire on
    exactly the first N dispatches of the op; with the orchestrator
    armed the metrics must read exactly N retries of the injected
    class, N+1 attempts, one op call — bit-exact, not >=."""

    @op_boundary("metrics_chaos_op")
    def op():
        return 42

    faultinj.configure(
        {"seed": 7, "faults": {"metrics_chaos_op": {
            "type": "retryable", "percent": 100, "interceptionCount": 4}}}
    )
    with metrics.enabled(), retry.enabled(max_attempts=10, base_delay_ms=0):
        assert op() == 42
    snap = metrics.snapshot()["counters"]
    assert snap["retry.retries"] == 4
    assert snap["retry.retries.RetryableError"] == 4
    assert snap["retry.attempts"] == 5  # 4 failures + the success
    assert snap["op.metrics_chaos_op.calls"] == 1
    assert snap.get("retry.fatal", 0) == 0
    assert snap.get("retry.exhausted", 0) == 0
    # the always-on retry stats agree with the registry mirror
    s = retry.stats()
    assert s["retries"] == 4 and s["attempts"] == 5


def test_fatal_fault_counts_once_and_never_retries():
    @op_boundary("metrics_fatal_op")
    def op():
        return 1

    faultinj.configure(
        {"faults": {"metrics_fatal_op": {
            "type": "fatal", "percent": 100, "interceptionCount": 1}}}
    )
    with metrics.enabled(), retry.enabled(max_attempts=5, base_delay_ms=0):
        with pytest.raises(FatalDeviceError):
            op()
    snap = metrics.snapshot()["counters"]
    assert snap["retry.fatal"] == 1
    assert snap.get("retry.retries", 0) == 0  # fatal NEVER retries


def test_split_counters_match_split_tree():
    """Deterministic split tree: an 8-row batch failing RESOURCE_
    EXHAUSTED above 2 rows splits 8 -> 4+4 -> (2,2)+(2,2): exactly 3
    split events, 4 leaf successes."""
    calls = []

    def fn(b):
        calls.append(len(b))
        if len(b) > 2:
            raise RetryableError("RESOURCE_EXHAUSTED: batch too big")
        return sum(b)

    with metrics.enabled():
        out = retry.retry_with_split(
            fn, list(range(8)),
            split=lambda b: (b[: len(b) // 2], b[len(b) // 2:]),
            combine=lambda ps: sum(ps),
            policy=retry.RetryPolicy(max_attempts=1, split_depth=4),
        )
    assert out == sum(range(8))
    snap = metrics.snapshot()["counters"]
    assert snap["retry.splits"] == 3
    assert snap["retry.splits.RetryableError"] == 3
    assert retry.stats()["splits"] == 3


def test_chaos_event_log_records_each_injected_fault(tmp_path):
    path = str(tmp_path / "chaos.jsonl")

    @op_boundary("metrics_logged_op")
    def op():
        return 9

    faultinj.configure(
        {"faults": {"metrics_logged_op": {
            "type": "retryable", "percent": 100, "interceptionCount": 2}}}
    )
    with metrics.enabled(log_path=path), retry.enabled(
        max_attempts=5, base_delay_ms=0
    ):
        assert op() == 9
    metrics.close_log()
    events = [json.loads(s) for s in open(path).read().splitlines()]
    backoffs = [e for e in events if e["event"] == "retry.backoff"]
    assert len(backoffs) == 2  # one line per injected fault
    assert all(e["op"] == "metrics_logged_op" for e in backoffs)


# ---------------------------------------------------------------------------
# memory split counter migration (satellite 1)
# ---------------------------------------------------------------------------


def test_split_retry_count_is_registry_alias():
    before = memory.split_retry_count()
    assert before == metrics.registry().counter("memory.split_retries").value
    memory._note_split()
    assert memory.split_retry_count() == before + 1
    assert metrics.registry().counter("memory.split_retries").value == before + 1


def test_split_counter_counts_with_metrics_disabled():
    # the migration must not regress the always-on contract: splits
    # count whether or not the hot-path tier is armed
    with metrics.disabled():
        before = memory.split_retry_count()
        memory._note_split()
        assert memory.split_retry_count() == before + 1


# ---------------------------------------------------------------------------
# shuffle instrumentation (distributed tier)
# ---------------------------------------------------------------------------


def test_shuffle_exchange_records_bytes_and_escalations():
    import jax

    from spark_rapids_jni_tpu.parallel import mesh as mesh_mod, shuffle

    assert len(jax.devices()) == 8, "conftest must force the 8-device CPU mesh"
    mesh = mesh_mod.make_mesh({"data": 8})
    rng = np.random.default_rng(5)
    n = 8 * 64
    # heavy skew: everything lands on a few shards, forcing the
    # capacity=4 start to escalate geometrically
    keys = rng.integers(0, 3, n).astype(np.int64)
    t = Table(
        [Column(dt.INT64, data=jnp.asarray(keys)),
         Column(dt.INT64, data=jnp.asarray(rng.integers(0, 100, n)))],
        ["k", "v"],
    )
    part, _ = shuffle.hash_partition(t, 8, ["k"])
    t_s = mesh_mod.shard_table_rows(part, mesh)
    with metrics.enabled():
        pairs, mask, overflow = shuffle.exchange_by_key(
            t_s, ["k"], mesh, capacity=4, on_overflow="retry"
        )
        snap = metrics.snapshot()
    assert not bool(np.asarray(overflow).any())
    c = snap["counters"]
    assert c["shuffle.exchanges"] == 1
    assert c["shuffle.bytes_exchanged"] >= 2 * n * 8  # two i64 columns
    assert c["shuffle.capacity_retries"] >= 1
    assert snap["histograms"]["shuffle.exchange_us"]["count"] == 1
    # the orchestrator's own stats saw the same escalations
    assert retry.stats()["capacity_retries"] == c["shuffle.capacity_retries"]


# ---------------------------------------------------------------------------
# stats_report: the end-to-end snapshot
# ---------------------------------------------------------------------------


def test_stats_report_sections_and_pretty_render():
    from spark_rapids_jni_tpu import runtime

    @op_boundary("metrics_report_op")
    def op():
        return 1

    with metrics.enabled():
        op()
        rep = runtime.stats_report()
        assert set(rep) >= {"metrics", "retry", "memory", "native_sidecar"}
        assert rep["metrics"]["counters"]["op.metrics_report_op.calls"] == 1
        assert rep["memory"]["split_retries"] == memory.split_retry_count()
        json.dumps(rep)  # the snapshot artifact is JSON-clean
        text = runtime.stats_report(pretty=True)
    assert isinstance(text, str)
    assert "op.metrics_report_op.calls" in text


def test_bench_stage_report_shape():
    with metrics.enabled():
        with metrics.timer("bench.stage_x"):
            pass
        rep = metrics.stage_report("stage_x")
    assert rep["stage"] == "stage_x"
    assert "bench.stage_x" in rep["ops"]
    assert set(rep["shuffle"]) == {"exchanges", "bytes_exchanged",
                                   "capacity_retries"}
    assert "retries" in rep["retry"]
    assert "split_retries" in rep["memory"]


# ---------------------------------------------------------------------------
# sidecar STATS protocol verb (worker side, pure Python — no native lib)
# ---------------------------------------------------------------------------


def test_sidecar_stats_verb_and_fold(tmp_path):
    from spark_rapids_jni_tpu import sidecar

    proc, sock = sidecar.spawn_worker(startup_timeout_s=120)
    try:
        with metrics.enabled():
            client = sidecar.SupervisedClient(sock, deadline_s=60,
                                              heartbeat_s=1e9)
            with client:
                assert client.ping() == "cpu"
                stats = client.worker_stats()
                counters = stats["snapshot"]["counters"]
                # 2 PINGs: spawn_worker's startup handshake + the
                # explicit heartbeat above (ISSUE 3 spawn hardening)
                assert counters["sidecar.worker.requests.PING"] == 2
                assert counters["sidecar.worker.requests.STATS"] == 1
                # folded into THIS process's registry as gauges
                snap = metrics.snapshot()
                assert snap["gauges"]["sidecar.worker.requests.PING"] == 2
                # client-side supervision counters recorded too
                assert snap["counters"]["sidecar.heartbeats"] == 1
                # the stats poll must NOT count itself into the
                # data-path counters it reports (native-client parity)
                assert snap["counters"].get("sidecar.requests", 0) == 0
                # a real data op DOES count
                tbl = Table(
                    [Column(dt.INT32, data=jnp.arange(8, dtype=jnp.int32))],
                    ["a"],
                )
                client.request(sidecar.OP_CONVERT_TO_ROWS,
                               sidecar.as_bytes(sidecar._write_table(tbl)))
                snap = metrics.snapshot()
                assert snap["counters"]["sidecar.requests"] == 1
                assert snap["histograms"]["sidecar.request_us"]["count"] == 1
    finally:
        if proc.poll() is None:
            proc.terminate()
        proc.wait(timeout=30)


def test_sidecar_degrade_records_fallback_metrics(tmp_path):
    """A worker-side fatal fault degrades to the host engine and the
    registry shows exactly one fallback event."""
    from spark_rapids_jni_tpu import sidecar

    cfg = tmp_path / "faults.json"
    cfg.write_text(
        '{"faults": {"convert_to_rows": {"type": "fatal", "percent": 100}}}'
    )
    proc, sock = sidecar.spawn_worker(
        startup_timeout_s=120, env={"SRJT_FAULTINJ_CONFIG": str(cfg)}
    )
    try:
        with metrics.enabled():
            client = sidecar.SupervisedClient(sock, deadline_s=60,
                                              heartbeat_s=1e9)
            with client:
                tbl = Table(
                    [Column(dt.INT32, data=jnp.arange(16, dtype=jnp.int32))],
                    ["a"],
                )
                payload = sidecar.as_bytes(sidecar._write_table(tbl))
                with retry.enabled(max_attempts=3, base_delay_ms=1):
                    resp = client.call(sidecar.OP_CONVERT_TO_ROWS, payload)
                assert resp == sidecar.as_bytes(sidecar._dispatch(
                    sidecar.OP_CONVERT_TO_ROWS, payload, "cpu"
                ))
            snap = metrics.snapshot()["counters"]
        assert snap["sidecar.host_fallbacks"] == 1
        assert client.host_fallbacks == 1  # instance attr stays in step
    finally:
        if proc.poll() is None:
            proc.terminate()
        proc.wait(timeout=30)
