"""Tail-tolerant execution tier (ISSUE 9): gray-failure quarantine,
hedged dispatch, and adaptive timeouts.

Fast-tier coverage of the three defenses:

- HEALTH SCORER + QUARANTINE: streaming quantiles off the log2
  histograms, per-worker EWMA/jitter, strike-based gray detection,
  background probes, K-clean reinstatement, quarantine-aware routing
  (_pick preference + all-gray fallback) and the notify-backed
  quarantine-aware wait_healthy.
- HEDGED DISPATCH: the both-responses race (winner counted once, the
  loser's region released, no double completion), the global budget,
  and the memgov/shed pressure disarm.
- ADAPTIVE TIMEOUTS: clamp bounds (never above the static knob, never
  below the floor, cold classes keep the knob) at both the helper and
  the SupervisedClient.

The in-process worker trick is the test_sidecar_pool one: real
protocol traffic served by sidecar._handle_conn threads in this
process — no jax child boot per test. The real-pool gray storm runs in
ci/premerge.sh's gray tier (bench_serve --gray against 3 spawned
workers).
"""

import threading
import time

import pytest

import spark_rapids_jni_tpu  # noqa: F401
from spark_rapids_jni_tpu import serve, sidecar, sidecar_pool
from spark_rapids_jni_tpu.utils import deadline as deadline_mod
from spark_rapids_jni_tpu.utils import faultinj, knobs, metrics
from spark_rapids_jni_tpu.utils.errors import (
    FatalDeviceError,
    Overloaded,
    RetryableError,
)

from _inproc import InProcWorker, groupby_payload, inproc_spawn


def _counter(name):
    return metrics.registry().value(name)


pytestmark = pytest.mark.usefixtures("clean_state")


def _seed_hist(name, values_us):
    h = metrics.registry().histogram(name)
    for v in values_us:
        h.record(v)
    return h


# ---------------------------------------------------------------------------
# metrics primitives: quantile + KeyedEwma
# ---------------------------------------------------------------------------


class TestHistogramQuantile:
    def test_empty_is_none(self):
        assert metrics.Histogram().quantile(0.5) is None

    def test_bad_q_raises(self):
        with pytest.raises(ValueError):
            metrics.Histogram().quantile(1.5)

    def test_single_value(self):
        h = metrics.Histogram()
        h.record(42)
        assert h.quantile(0.0) == 42
        assert h.quantile(0.5) == 42
        assert h.quantile(1.0) == 42

    def test_bounds_and_monotonicity(self):
        h = metrics.Histogram()
        vals = [1, 3, 7, 20, 100, 900, 5000] * 20
        for v in vals:
            h.record(v)
        qs = [h.quantile(q) for q in (0.0, 0.25, 0.5, 0.9, 0.99, 1.0)]
        assert qs[0] == 1 and qs[-1] == 5000
        assert all(a <= b for a, b in zip(qs, qs[1:]))

    def test_log2_factor_accuracy(self):
        # a quantile read off log2 buckets is good to a factor of 2
        h = metrics.Histogram()
        for _ in range(1000):
            h.record(1000)
        for _ in range(10):
            h.record(64000)
        p50 = h.quantile(0.5)
        assert 500 <= p50 <= 2000
        p999 = h.quantile(0.999)
        assert p999 >= 32000

    def test_bucket_zero(self):
        h = metrics.Histogram()
        for _ in range(10):
            h.record(0)
        assert h.quantile(0.5) == 0

    def test_single_bucket_mass(self):
        # ISSUE 11 satellite: every sample in ONE log2 bucket ([16,32))
        # — interpolation must stay inside the bucket AND inside the
        # recorded min/max for every q, including the exact edges
        h = metrics.Histogram()
        for v in (17, 19, 23, 29, 31) * 40:
            h.record(v)
        for q in (0.0, 0.01, 0.25, 0.5, 0.75, 0.99, 1.0):
            est = h.quantile(q)
            assert 17 <= est <= 31, (q, est)
        assert h.quantile(0.0) == 17
        assert h.quantile(1.0) == 31

    def test_q0_q1_exact_bounds(self):
        # q=0 is the recorded min and q=1 the recorded max, never an
        # interpolated bucket edge — the clamp contract callers of
        # p0/p100 rely on
        h = metrics.Histogram()
        for v in (5, 100, 3000, 70000):
            h.record(v)
        assert h.quantile(0.0) == 5
        assert h.quantile(1.0) == 70000


class TestKeyedEwma:
    def test_update_and_jitter(self):
        e = metrics.KeyedEwma(alpha=0.5)
        assert e.update("a", 10.0) == 10.0
        assert e.update("a", 20.0) == 15.0
        assert e.jitter("a") == 5.0  # 0.5 * |20-10|
        assert e.count("a") == 2
        assert e.get("missing", -1) == -1

    def test_bounded_eviction_is_lru(self):
        e = metrics.KeyedEwma(max_keys=2)
        e.update("a", 1.0)
        e.update("b", 2.0)
        e.update("a", 1.0)  # refresh a; b is now the oldest
        e.update("c", 3.0)  # evicts b
        assert len(e) == 2
        assert e.get("b") is None
        assert e.get("a") is not None and e.get("c") is not None

    def test_validation(self):
        with pytest.raises(ValueError):
            metrics.KeyedEwma(alpha=0.0)
        with pytest.raises(ValueError):
            metrics.KeyedEwma(max_keys=0)

    def test_concurrent_update_during_lru_eviction(self):
        # ISSUE 11 satellite: updates that force LRU evictions while
        # other threads read/snapshot the same map — the bound must
        # hold, nothing may raise, and every surviving entry must be a
        # coherent [ewma, jitter, count, seq] record. The
        # race-detector-armed variant (tracked map, vector clocks)
        # lives in tests/test_races.py.
        import threading

        e = metrics.KeyedEwma(alpha=0.4, max_keys=8)
        stop = threading.Event()
        errors = []

        def churn(base):
            try:
                for i in range(400):
                    e.update(f"{base}.{i % 16}", float(i))
            except Exception as exc:  # pragma: no cover - failure path
                errors.append(exc)

        def read():
            try:
                while not stop.is_set():
                    e.get("a.0")
                    e.jitter("b.1")
                    snap = e.snapshot()
                    for rec in snap.values():
                        assert set(rec) == {"ewma", "jitter", "count"}
            except Exception as exc:  # pragma: no cover - failure path
                errors.append(exc)

        ts = [threading.Thread(target=churn, args=(b,)) for b in "abc"]
        r = threading.Thread(target=read)
        r.start()
        for t in ts:
            t.start()
        for t in ts:
            t.join(20)
        stop.set()
        r.join(20)
        assert not errors
        assert len(e) <= 8


# ---------------------------------------------------------------------------
# adaptive timeouts
# ---------------------------------------------------------------------------


class TestAdaptiveTimeout:
    def test_cold_class_keeps_static(self, monkeypatch):
        monkeypatch.setenv("SRJT_ADAPTIVE_TIMEOUT_MIN_SAMPLES", "40")
        _seed_hist("test.adapt.cold_us", [100] * 10)
        budget, clamped = metrics.adaptive_timeout_s("test.adapt.cold_us", 600.0)
        assert budget == 600.0 and not clamped

    def test_warm_fast_class_clamps_to_floor(self, monkeypatch):
        monkeypatch.setenv("SRJT_ADAPTIVE_TIMEOUT_MIN_SAMPLES", "40")
        monkeypatch.setenv("SRJT_ADAPTIVE_TIMEOUT_FLOOR_S", "2.0")
        _seed_hist("test.adapt.fast_us", [1000] * 50)  # 1 ms op
        budget, clamped = metrics.adaptive_timeout_s("test.adapt.fast_us", 600.0)
        assert budget == 2.0 and clamped

    def test_never_exceeds_static(self, monkeypatch):
        monkeypatch.setenv("SRJT_ADAPTIVE_TIMEOUT_MIN_SAMPLES", "40")
        _seed_hist("test.adapt.slow_us", [int(500e6)] * 50)  # 500 s op
        budget, clamped = metrics.adaptive_timeout_s("test.adapt.slow_us", 600.0)
        assert budget == 600.0 and not clamped

    def test_disabled_keeps_static(self, monkeypatch):
        monkeypatch.setenv("SRJT_ADAPTIVE_TIMEOUT_ENABLED", "0")
        _seed_hist("test.adapt.off_us", [1000] * 200)
        budget, clamped = metrics.adaptive_timeout_s("test.adapt.off_us", 600.0)
        assert budget == 600.0 and not clamped

    def test_client_op_budget_counts_clamps(self, monkeypatch):
        monkeypatch.setenv("SRJT_ADAPTIVE_TIMEOUT_MIN_SAMPLES", "40")
        monkeypatch.setenv("SRJT_ADAPTIVE_TIMEOUT_FLOOR_S", "1.0")
        c = sidecar.SupervisedClient("/nonexistent.sock", deadline_s=600.0,
                                     heartbeat_s=1e9)
        name = f"sidecar.op_lat_us.{sidecar.op_name(sidecar.OP_ZORDER)}"
        _seed_hist(name, [2000] * 60)  # 2 ms q99 -> 8 ms, floored to 1 s
        before = _counter("sidecar.adaptive_timeout_clamps")
        budget = c._op_budget_s(sidecar.OP_ZORDER)
        assert budget == 1.0
        assert _counter("sidecar.adaptive_timeout_clamps") == before + 1
        # cold classes keep the static knob and count nothing
        budget = c._op_budget_s(sidecar.OP_DECIMAL128_DIV)
        assert budget == 600.0
        assert _counter("sidecar.adaptive_timeout_clamps") == before + 1

    def test_request_budget_never_exceeds_remaining_deadline(self, monkeypatch):
        """The adaptive budget composes UNDER the query budget: a
        nearly-dead deadline scope bounds the socket deadline below
        whatever the quantiles say (the old clamp, unchanged)."""
        monkeypatch.setenv("SRJT_ADAPTIVE_TIMEOUT_MIN_SAMPLES", "1")
        monkeypatch.setenv("SRJT_ADAPTIVE_TIMEOUT_FLOOR_S", "50.0")
        w = InProcWorker()
        try:
            c = sidecar.SupervisedClient(w.sock_path, deadline_s=600.0,
                                         heartbeat_s=1e9)
            name = f"sidecar.op_lat_us.{sidecar.op_name(sidecar.OP_PING)}"
            _seed_hist(name, [100] * 10)
            t0 = time.monotonic()
            with deadline_mod.scope(0.25):
                # a live worker answers instantly; the point is the
                # request cannot park past the 0.25 s budget even
                # though the adaptive floor is 50 s
                assert c.ping() == "cpu"
            assert time.monotonic() - t0 < 5.0
            c.close()
        finally:
            w.kill()


# ---------------------------------------------------------------------------
# faultinj per-worker rule keys
# ---------------------------------------------------------------------------


class TestFaultinjWorkerKeys:
    CFG = {
        "seed": 7,
        "faults": {
            "myop@w1": {"type": "fatal", "percent": 100},
            "myop": {"type": "retryable", "percent": 100},
            "fam.*@w1": {"type": "fatal", "percent": 100},
            "fam.*": {"type": "retryable", "percent": 100},
            "*@w1": {"type": "fatal", "percent": 100},
            "*": {"type": "retryable", "percent": 100},
        },
    }

    def test_tagged_process_prefers_worker_keys(self, monkeypatch):
        monkeypatch.setenv("SRJT_FAULTINJ_WORKER", "w1")
        faultinj.configure(self.CFG)
        with pytest.raises(FatalDeviceError):
            faultinj.maybe_inject("myop")  # exact@tag beats exact
        with pytest.raises(FatalDeviceError):
            faultinj.maybe_inject("fam.x")  # prefix@tag beats prefix
        with pytest.raises(FatalDeviceError):
            faultinj.maybe_inject("other")  # *@tag beats *

    def test_untagged_process_ignores_worker_keys(self, monkeypatch):
        monkeypatch.delenv("SRJT_FAULTINJ_WORKER", raising=False)
        faultinj.configure(self.CFG)
        with pytest.raises(RetryableError):
            faultinj.maybe_inject("myop")
        with pytest.raises(RetryableError):
            faultinj.maybe_inject("fam.x")
        with pytest.raises(RetryableError):
            faultinj.maybe_inject("other")

    def test_foreign_tag_never_matches(self, monkeypatch):
        monkeypatch.setenv("SRJT_FAULTINJ_WORKER", "w2")
        faultinj.configure({
            "seed": 7,
            "faults": {"gray@w1": {"type": "fatal", "percent": 100}},
        })
        faultinj.maybe_inject("gray")  # no rule for w2: clean dispatch

    def test_single_gray_worker_profile_shape(self, monkeypatch):
        """The chaos_gray.json shape: a delay ramp keyed to one worker
        fires there and ONLY there."""
        cfg = {
            "seed": 7,
            "faults": {
                "sidecar.worker.PING@w1": {
                    "type": "fatal", "percent": 100,
                },
            },
        }
        monkeypatch.setenv("SRJT_FAULTINJ_WORKER", "w0")
        faultinj.configure(cfg)
        faultinj.maybe_inject("sidecar.worker.PING")  # clean on w0
        monkeypatch.setenv("SRJT_FAULTINJ_WORKER", "w1")
        faultinj.configure(cfg)
        with pytest.raises(FatalDeviceError):
            faultinj.maybe_inject("sidecar.worker.PING")

    def test_pool_stamps_worker_tags(self):
        seen = {}

        def spawn_fn(startup_timeout_s=None, env=None):
            w = InProcWorker()
            seen[len(seen)] = dict(env or {})
            return w, w.sock_path

        pool = sidecar_pool.SidecarPool(
            size=2, deadline_s=10, heartbeat_s=1e9, spawn_fn=spawn_fn
        )
        try:
            tags = sorted(e.get("SRJT_FAULTINJ_WORKER") for e in seen.values())
            assert tags == ["w0", "w1"]
        finally:
            pool.shutdown()


# ---------------------------------------------------------------------------
# gray-failure quarantine
# ---------------------------------------------------------------------------


def _warm_op(name_us, fast_us=1000, n=40):
    metrics.reset()
    _seed_hist(name_us, [fast_us] * n)


class TestQuarantine:
    def test_strikes_quarantine_and_probe_reinstates(self, monkeypatch):
        # the first probe sleeps a whole second, leaving a quiet window
        # for the quarantined-state asserts; the live-read knob then
        # drops to 50 ms for a fast reinstatement run
        monkeypatch.setenv("SRJT_QUARANTINE_PROBE_INTERVAL_S", "1.0")
        monkeypatch.setenv("SRJT_QUARANTINE_STRIKES", "3")
        pool = sidecar_pool.SidecarPool(
            size=2, deadline_s=10, heartbeat_s=1e9, spawn_fn=inproc_spawn
        )
        try:
            name = f"sidecar.op_lat_us.{sidecar.op_name(sidecar.OP_PING)}"
            _warm_op(name)  # pool-wide p50 ~ 1 ms
            w1 = pool._workers[1]
            for _ in range(3):  # 3 samples at 100x the p50
                pool._note_latency(w1, sidecar.OP_PING, 0.1)
            assert w1.quarantined
            assert pool.routable_count() == 1
            assert _counter("sidecar.pool.quarantines") == 1
            assert metrics.registry().value("sidecar.pool.quarantined") == 1
            # routing prefers the healthy peer exclusively
            for _ in range(8):
                assert pool._pick() is pool._workers[0]
            # quarantine-aware wait_healthy: a gray worker is unhealthy
            assert pool.wait_healthy(timeout_s=0.2) is False
            monkeypatch.setenv("SRJT_QUARANTINE_PROBE_INTERVAL_S", "0.05")
            # the in-proc worker answers probes in microseconds: after
            # K clean probes the slot is reinstated (notify-backed wait
            # wakes the instant it happens)
            assert pool.wait_healthy(timeout_s=10.0) is True
            assert not w1.quarantined
            assert w1.strikes == 0
            assert _counter("sidecar.pool.reinstatements") == 1
            assert _counter("sidecar.pool.quarantine_probes") >= 3
            picked = {pool._pick().wid for _ in range(4)}
            assert picked == {0, 1}  # back in the rotation
        finally:
            pool.shutdown()

    def test_dirty_probes_hold_quarantine(self, monkeypatch):
        monkeypatch.setenv("SRJT_QUARANTINE_PROBE_INTERVAL_S", "0.05")
        monkeypatch.setenv("SRJT_QUARANTINE_STRIKES", "2")
        # a probe threshold no real round-trip can meet: every probe is
        # dirty, the clean run never starts
        monkeypatch.setenv("SRJT_QUARANTINE_PROBE_SLOW_S", "0.000000001")
        pool = sidecar_pool.SidecarPool(
            size=2, deadline_s=10, heartbeat_s=1e9, spawn_fn=inproc_spawn
        )
        try:
            name = f"sidecar.op_lat_us.{sidecar.op_name(sidecar.OP_PING)}"
            _warm_op(name)
            w1 = pool._workers[1]
            for _ in range(2):
                pool._note_latency(w1, sidecar.OP_PING, 0.1)
            assert w1.quarantined
            deadline = time.monotonic() + 0.6
            while time.monotonic() < deadline:
                time.sleep(0.05)
            assert w1.quarantined  # probes ran, none was clean
            assert _counter("sidecar.pool.quarantine_probes") >= 2
            assert w1.clean_probes == 0
            # restoring a reachable threshold lets the run complete
            monkeypatch.setenv("SRJT_QUARANTINE_PROBE_SLOW_S", "0.25")
            assert pool.wait_healthy(timeout_s=10.0) is True
        finally:
            pool.shutdown()

    def test_timeouts_strike_even_cold(self, monkeypatch):
        """A request timeout is the unambiguous slow signal: it strikes
        even before the op class has any baseline samples."""
        monkeypatch.setenv("SRJT_QUARANTINE_STRIKES", "2")
        monkeypatch.setenv("SRJT_QUARANTINE_PROBE_INTERVAL_S", "5")
        pool = sidecar_pool.SidecarPool(
            size=2, deadline_s=10, heartbeat_s=1e9, spawn_fn=inproc_spawn
        )
        try:
            metrics.reset()
            w0 = pool._workers[0]
            pool._note_latency(w0, sidecar.OP_ZORDER, 10.0, timed_out=True)
            assert not w0.quarantined
            pool._note_latency(w0, sidecar.OP_ZORDER, 10.0, timed_out=True)
            assert w0.quarantined
        finally:
            pool.shutdown()

    def test_clean_samples_pay_strikes_back(self, monkeypatch):
        monkeypatch.setenv("SRJT_QUARANTINE_STRIKES", "3")
        pool = sidecar_pool.SidecarPool(
            size=2, deadline_s=10, heartbeat_s=1e9, spawn_fn=inproc_spawn
        )
        try:
            name = f"sidecar.op_lat_us.{sidecar.op_name(sidecar.OP_PING)}"
            _warm_op(name)
            w1 = pool._workers[1]
            pool._note_latency(w1, sidecar.OP_PING, 0.1)
            pool._note_latency(w1, sidecar.OP_PING, 0.1)
            assert w1.strikes == 2
            pool._note_latency(w1, sidecar.OP_PING, 0.001)  # clean
            assert w1.strikes == 1
            pool._note_latency(w1, sidecar.OP_PING, 0.1)
            assert not w1.quarantined  # 2 < 3: the flap never tripped
        finally:
            pool.shutdown()

    def test_all_quarantined_falls_back_not_dark(self, monkeypatch):
        """Degraded routing beats a dark pool: with every live worker
        gray, _pick falls back (counted) and calls still complete."""
        monkeypatch.setenv("SRJT_QUARANTINE_PROBE_INTERVAL_S", "60")
        pool = sidecar_pool.SidecarPool(
            size=2, deadline_s=10, heartbeat_s=1e9, spawn_fn=inproc_spawn
        )
        try:
            metrics.reset()
            with pool._lock:
                for w in pool._workers:
                    pool._quarantine_locked(w, "test")
            assert pool.routable_count() == 0
            assert pool.live_count() == 2
            before = _counter("sidecar.pool.quarantine_fallbacks")
            assert pool._pick() is not None
            assert _counter("sidecar.pool.quarantine_fallbacks") == before + 1
            assert pool.call(sidecar.OP_PING) == b"cpu"
        finally:
            pool.shutdown()

    def test_death_clears_quarantine_state(self, monkeypatch):
        monkeypatch.setenv("SRJT_QUARANTINE_PROBE_INTERVAL_S", "60")
        pool = sidecar_pool.SidecarPool(
            size=2, deadline_s=10, heartbeat_s=1e9, spawn_fn=inproc_spawn
        )
        try:
            metrics.reset()
            w1 = pool._workers[1]
            with pool._lock:
                pool._quarantine_locked(w1, "test")
            w1.proc.kill()
            pool._on_worker_failure(w1, RetryableError("UNAVAILABLE"))
            # gray -> dead: the respawned slot starts with a clean record
            assert not w1.quarantined
            assert metrics.registry().value("sidecar.pool.quarantined") == 0
            assert pool.wait_healthy(timeout_s=10.0) is True
        finally:
            pool.shutdown()


# ---------------------------------------------------------------------------
# hedged dispatch
# ---------------------------------------------------------------------------


class TestHedgedDispatch:
    def test_both_responses_arrive_winner_counted_once(self, monkeypatch):
        """The hedge race where BOTH legs answer: exactly one response
        reaches the caller, the loser's region is released, counters
        reconcile (one launched, at most one won, one cancelled)."""
        monkeypatch.setenv("SRJT_HEDGE_BUDGET_PCT", "100")
        pool = sidecar_pool.SidecarPool(
            size=2, deadline_s=20, heartbeat_s=1e9, spawn_fn=inproc_spawn
        )
        try:
            metrics.reset()
            payload = groupby_payload()
            want = sidecar._dispatch(sidecar.OP_GROUPBY_SUM_F32, payload, "cpu")
            # both in-proc workers serve the op ~50 ms slow, so both
            # legs are in flight when the race settles
            faultinj.configure({
                "seed": 11,
                "faults": {
                    "sidecar.worker.GROUPBY_SUM_F32": {
                        "type": "delay", "percent": 100, "delayMs": 60,
                    },
                },
            })
            # force the hedge trigger: fire the duplicate immediately
            monkeypatch.setattr(
                pool, "_hedge_delay_s", lambda op, primary: 0.001
            )
            got = pool.call_arena(sidecar.OP_GROUPBY_SUM_F32, payload)
            assert got == want
            assert _counter("sidecar.pool.hedges_launched") == 1
            assert _counter("sidecar.pool.hedges_cancelled") == 1
            assert _counter("sidecar.pool.hedges_won") in (0, 1)
            # the loser leg (bounded by the 60 ms injected delay)
            # releases its distinct region: no leases survive
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                if pool._slab is not None and pool._slab.outstanding == 0:
                    break
                time.sleep(0.02)
            assert pool._slab.outstanding == 0
            assert _counter("sidecar.pool.region_leaks") == 0
        finally:
            pool.shutdown()

    def test_hedge_wins_when_primary_is_slow(self, monkeypatch):
        """The tail-defense contract: one gray worker's slow leg loses
        to the hedge on the healthy peer, and the answer is correct."""
        monkeypatch.setenv("SRJT_HEDGE_BUDGET_PCT", "100")
        monkeypatch.setenv("SRJT_FAULTINJ_WORKER", "w9")  # inert tag
        pool = sidecar_pool.SidecarPool(
            size=2, deadline_s=20, heartbeat_s=1e9, spawn_fn=inproc_spawn
        )
        try:
            metrics.reset()
            payload = groupby_payload()
            want = sidecar._dispatch(sidecar.OP_GROUPBY_SUM_F32, payload, "cpu")
            # the first GROUPBY dispatch hangs 2 s (the in-proc workers
            # share this process's injector, so the budget of 1 means
            # only the primary leg pays it; the hedge runs clean)
            faultinj.configure({
                "seed": 11,
                "faults": {
                    "sidecar.worker.GROUPBY_SUM_F32": {
                        "type": "delay", "percent": 100, "delayMs": 2000,
                        "interceptionCount": 1,
                    },
                },
            })
            monkeypatch.setattr(
                pool, "_hedge_delay_s", lambda op, primary: 0.05
            )
            t0 = time.monotonic()
            got = pool.call_arena(sidecar.OP_GROUPBY_SUM_F32, payload)
            elapsed = time.monotonic() - t0
            assert got == want
            assert _counter("sidecar.pool.hedges_launched") == 1
            assert _counter("sidecar.pool.hedges_won") == 1
            assert elapsed < 1.5, (
                f"hedge should beat the 2 s straggler, took {elapsed:.2f}s"
            )
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                if pool._slab.outstanding == 0:
                    break
                time.sleep(0.05)
            assert pool._slab.outstanding == 0
        finally:
            pool.shutdown()

    def test_budget_arithmetic(self, monkeypatch):
        monkeypatch.setenv("SRJT_HEDGE_BUDGET_PCT", "10")
        pool = sidecar_pool.SidecarPool(
            size=2, deadline_s=10, heartbeat_s=1e9, spawn_fn=inproc_spawn
        )
        try:
            metrics.reset()
            reg = metrics.registry()
            reg.counter("sidecar.pool.calls").inc(100)
            reg.counter("sidecar.pool.hedges_launched").inc(9)
            assert pool._hedge_budget_ok()  # 10th hedge of 100 calls: at budget
            reg.counter("sidecar.pool.hedges_launched").inc(1)
            assert not pool._hedge_budget_ok()  # 11th would exceed 10%
        finally:
            pool.shutdown()

    def test_disarmed_under_memgov_pressure(self, monkeypatch):
        """The acceptance contract: hedging drops to zero while memgov
        pressure is active — metrics-asserted via the suppression
        counter, with the trigger conditions otherwise satisfied."""
        monkeypatch.setenv("SRJT_HEDGE_MIN_SAMPLES", "10")
        pool = sidecar_pool.SidecarPool(
            size=2, deadline_s=10, heartbeat_s=1e9, spawn_fn=inproc_spawn
        )
        try:
            metrics.reset()
            name = f"sidecar.op_lat_us.{sidecar.op_name(sidecar.OP_PING)}"
            _seed_hist(name, [1000] * 20)
            w0 = pool._workers[0]
            # warm + healthy peer: hedging would arm...
            assert pool._hedge_delay_s(sidecar.OP_PING, w0) is not None
            # ...until injected memgov pressure disarms it
            from spark_rapids_jni_tpu import memgov

            monkeypatch.setattr(memgov, "is_enabled", lambda: True)
            metrics.registry().gauge("memgov.queue_depth").set(1)
            before = _counter("sidecar.pool.hedges_suppressed")
            assert pool._hedge_delay_s(sidecar.OP_PING, w0) is None
            assert _counter("sidecar.pool.hedges_suppressed") == before + 1
            launched = _counter("sidecar.pool.hedges_launched")
            assert pool.call(sidecar.OP_PING) == b"cpu"
            assert _counter("sidecar.pool.hedges_launched") == launched
        finally:
            pool.shutdown()

    def test_disarmed_inside_shed_window(self, monkeypatch):
        monkeypatch.setenv("SRJT_HEDGE_MIN_SAMPLES", "10")
        monkeypatch.setenv("SRJT_HEDGE_SHED_WINDOW_S", "5.0")
        pool = sidecar_pool.SidecarPool(
            size=2, deadline_s=10, heartbeat_s=1e9, spawn_fn=inproc_spawn
        )
        try:
            metrics.reset()
            name = f"sidecar.op_lat_us.{sidecar.op_name(sidecar.OP_PING)}"
            _seed_hist(name, [1000] * 20)
            w0 = pool._workers[0]
            reg = metrics.registry()
            reg.gauge("serve.last_shed_s").set(time.monotonic())
            assert pool._hedge_delay_s(sidecar.OP_PING, w0) is None
            # an old shed is outside the window: hedging re-arms
            reg.gauge("serve.last_shed_s").set(time.monotonic() - 60.0)
            assert pool._hedge_delay_s(sidecar.OP_PING, w0) is not None
        finally:
            pool.shutdown()

    def test_cold_class_and_single_worker_never_hedge(self, monkeypatch):
        monkeypatch.setenv("SRJT_HEDGE_MIN_SAMPLES", "10")
        pool = sidecar_pool.SidecarPool(
            size=1, deadline_s=10, heartbeat_s=1e9, spawn_fn=inproc_spawn
        )
        try:
            metrics.reset()
            w0 = pool._workers[0]
            # single worker: no peer for the duplicate
            name = f"sidecar.op_lat_us.{sidecar.op_name(sidecar.OP_PING)}"
            _seed_hist(name, [1000] * 20)
            assert pool._hedge_delay_s(sidecar.OP_PING, w0) is None
        finally:
            pool.shutdown()
        pool = sidecar_pool.SidecarPool(
            size=2, deadline_s=10, heartbeat_s=1e9, spawn_fn=inproc_spawn
        )
        try:
            metrics.reset()  # cold class: no samples at all
            w0 = pool._workers[0]
            assert pool._hedge_delay_s(sidecar.OP_ZORDER, w0) is None
        finally:
            pool.shutdown()


# ---------------------------------------------------------------------------
# quarantine-aware serving + stats plumbing
# ---------------------------------------------------------------------------


class TestServeQuarantineRouting:
    def test_all_gray_pool_sheds_device_only_work(self, monkeypatch):
        monkeypatch.setenv("SRJT_QUARANTINE_PROBE_INTERVAL_S", "60")
        pool = sidecar_pool.connect_pool(
            size=1, deadline_s=10, heartbeat_s=1e9, spawn_fn=inproc_spawn
        )
        sched = serve.Scheduler(max_concurrent=1, name="tail-test")
        try:
            with pool._lock:
                pool._quarantine_locked(pool._workers[0], "test")
            with pytest.raises(Overloaded) as ei:
                sched.submit(lambda: 1, host_eligible=False)
            assert ei.value.cause == "quarantine"
            assert _counter("serve.shed.quarantine") >= 1
            # host-eligible work keeps flowing through the same pool
            assert sched.submit(lambda: 41 + 1).result(10) == 42
            # reinstatement restores device-only admission
            with pool._lock:
                pool._reinstate_locked(pool._workers[0])
            assert sched.submit(lambda: 7, host_eligible=False).result(10) == 7
        finally:
            sched.shutdown(drain=False, timeout_s=10)
            sidecar_pool.shutdown_pool()

    def test_shed_stamps_hedge_disarm_gauge(self):
        sched = serve.Scheduler(max_concurrent=1, queue_depth=1,
                                name="tail-stamp")
        try:
            faultinj.configure({
                "seed": 3,
                "faults": {"serve.admit": {"type": "reject", "percent": 100,
                                            "interceptionCount": 1}},
            })
            with pytest.raises(Overloaded):
                sched.submit(lambda: 1)
            stamp = metrics.registry().value("serve.last_shed_s", None)
            assert stamp is not None
            assert time.monotonic() - stamp < 10.0
        finally:
            sched.shutdown(drain=False, timeout_s=10)


class TestStatsSections:
    def test_report_sections_present(self):
        from spark_rapids_jni_tpu import runtime

        rep = runtime.stats_report()
        assert set(rep["health"]) >= {
            "quarantines", "reinstatements", "probes", "quarantined_now",
        }
        assert set(rep["hedge"]) >= {
            "launched", "won", "cancelled", "suppressed",
            "adaptive_timeout_clamps",
        }
        stage = metrics.stage_report("tail")
        assert "health" in stage and "hedge" in stage
        assert "adaptive_timeout_clamps" in stage["hedge"]

    def test_knobs_declared(self):
        for k in (
            "SRJT_QUARANTINE_ENABLED", "SRJT_QUARANTINE_SLOW_FACTOR",
            "SRJT_QUARANTINE_STRIKES", "SRJT_QUARANTINE_MIN_SAMPLES",
            "SRJT_QUARANTINE_PROBES", "SRJT_QUARANTINE_PROBE_INTERVAL_S",
            "SRJT_QUARANTINE_PROBE_SLOW_S", "SRJT_HEDGE_ENABLED",
            "SRJT_HEDGE_BUDGET_PCT", "SRJT_HEDGE_MIN_SAMPLES",
            "SRJT_HEDGE_MIN_DELAY_S", "SRJT_HEDGE_SHED_WINDOW_S",
            "SRJT_ADAPTIVE_TIMEOUT_ENABLED", "SRJT_ADAPTIVE_TIMEOUT_MULT",
            "SRJT_ADAPTIVE_TIMEOUT_FLOOR_S",
            "SRJT_ADAPTIVE_TIMEOUT_MIN_SAMPLES", "SRJT_FAULTINJ_WORKER",
        ):
            assert knobs.is_declared(k), k
