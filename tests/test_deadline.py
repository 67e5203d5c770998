"""Deadline / cancellation / circuit-breaker tier (ISSUE 3 acceptance).

Covers: budget propagation through nested op boundaries, backoff
truncation to the remaining budget, DeadlineExceeded (never a raw
socket timeout) on budget expiry through the supervised sidecar client,
breaker open -> half-open -> closed transitions with registry-visible
counts, the interruptible ``hang`` fault kind, spawn_worker child
reaping on failed startups, and the chaos acceptance run: hang +
retryable storm under a tight SRJT_DEADLINE_SEC where every query
either completes or raises DeadlineExceeded within budget.

ci/premerge.sh runs this file a second time with SRJT_FAULTINJ_CONFIG
pointing at ci/chaos_hang.json and a tight SRJT_DEADLINE_SEC under a
hard harness timeout — proving no wedged worker outlives the gate.
"""

import os
import socket
import struct
import threading
import time

import numpy as np
import pytest

import spark_rapids_jni_tpu  # noqa: F401
import jax.numpy as jnp

from spark_rapids_jni_tpu import sidecar
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.columnar import dtype as dt
from spark_rapids_jni_tpu.utils import deadline, faultinj, knobs, metrics, retry
from spark_rapids_jni_tpu.utils.deadline import CancelToken, CircuitBreaker, Deadline
from spark_rapids_jni_tpu.utils.dispatch import op_boundary
from spark_rapids_jni_tpu.utils.errors import DeadlineExceeded, RetryableError

_HANG_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "ci", "chaos_hang.json",
)


pytestmark = pytest.mark.usefixtures("clean_state")


# ---------------------------------------------------------------------------
# Deadline / CancelToken primitives
# ---------------------------------------------------------------------------


class TestDeadline:
    def test_remaining_expired_with_injected_clock(self):
        t = [0.0]
        d = Deadline(2.0, clock=lambda: t[0])
        assert d.remaining() == pytest.approx(2.0)
        assert not d.expired() and not d.done()
        t[0] = 2.5
        assert d.remaining() == pytest.approx(-0.5)
        assert d.expired() and d.done()
        with pytest.raises(DeadlineExceeded, match="budget exhausted"):
            d.check("op_x")

    def test_unbounded_deadline_never_expires(self):
        d = Deadline(None)
        assert d.remaining() == float("inf")
        assert not d.expired()
        d.check("ok")  # no raise

    def test_cancel_token_first_reason_wins(self):
        tok = CancelToken()
        assert not tok.cancelled()
        tok.cancel("root cause")
        tok.cancel("echo")
        assert tok.cancelled() and tok.reason == "root cause"
        d = Deadline(100.0, token=tok)
        assert d.done() and not d.expired()
        with pytest.raises(DeadlineExceeded, match="root cause"):
            d.check("op_y")

    def test_budget_must_be_positive(self):
        with pytest.raises(ValueError):
            Deadline(0)
        with pytest.raises(ValueError):
            deadline.set_default_budget(-1)


class TestScope:
    def test_scope_installs_and_restores(self):
        assert deadline.current() is None
        with deadline.scope(5.0) as d:
            assert deadline.current() is d
            assert deadline.remaining() <= 5.0
        assert deadline.current() is None
        assert deadline.remaining() == float("inf")

    def test_nested_scope_never_extends_the_budget(self):
        with deadline.scope(0.5) as outer:
            with deadline.scope(99.0) as inner:
                # min(99, outer remaining): the query budget wins
                assert inner._t_end <= outer._t_end
                assert inner.remaining() <= 0.5
            with deadline.scope(0.01) as tight:
                assert tight.remaining() <= 0.01  # shrinking is allowed

    def test_nested_scope_shares_the_cancel_token(self):
        with deadline.scope(10.0) as outer:
            with deadline.scope() as inner:
                assert inner.token is outer.token
                outer.cancel("query killed")
                with pytest.raises(DeadlineExceeded, match="query killed"):
                    inner.check("nested")

    def test_module_check_is_noop_without_scope(self):
        deadline.check("anything")  # must not raise

    def test_cancel_helper(self):
        assert deadline.cancel("x") is False  # no scope
        with deadline.scope(10.0) as d:
            assert deadline.cancel("stop") is True
            assert d.cancelled()


# ---------------------------------------------------------------------------
# op_boundary propagation (ambient + per-call budgets)
# ---------------------------------------------------------------------------


class TestOpBoundaryDeadline:
    def test_ambient_budget_opens_one_scope_at_the_outermost_boundary(self):
        seen = []

        @op_boundary("dl_inner_op")
        def inner():
            seen.append(deadline.current())
            return 1

        @op_boundary("dl_outer_op")
        def outer():
            seen.append(deadline.current())
            return inner()

        # no budget anywhere: no scope materializes
        outer()
        assert seen == [None, None]

        seen.clear()
        deadline.set_default_budget(5.0)
        outer()
        assert seen[0] is not None and seen[0] is seen[1]  # ONE shared scope
        assert seen[0].budget_s == 5.0
        assert deadline.current() is None  # closed with the outer op

    def test_per_call_deadline_kwarg_opens_a_scope(self):
        seen = []

        @op_boundary("dl_kwarg_op")
        def op():
            seen.append(deadline.current())
            return "ok"

        assert op(deadline_s=2.0) == "ok"
        assert seen[0] is not None and seen[0].budget_s == 2.0
        assert op() == "ok"
        assert seen[1] is None  # no ambient, no kwarg: seed contract

    def test_expired_enclosing_budget_stops_nested_dispatch_before_the_body(self):
        ran = []

        @op_boundary("dl_never_op")
        def op():
            ran.append(1)

        with deadline.scope(0.01):
            time.sleep(0.03)
            with pytest.raises(DeadlineExceeded):
                op()
        assert ran == []  # the boundary refused to start the body


# ---------------------------------------------------------------------------
# retry orchestrator: truncation + budget give-up
# ---------------------------------------------------------------------------


class TestRetryDeadline:
    def test_backoff_crossing_the_deadline_raises_without_sleeping(self):
        """A backoff that would cross the deadline is truncated to
        nothing: the orchestrator raises DeadlineExceeded immediately —
        the post-sleep outcome is already determined — returning the
        residual budget to the caller instead of sleeping it out."""
        sleeps = []
        pol = retry.RetryPolicy(
            max_attempts=3, base_delay_ms=60000, jitter=0.0, sleep=sleeps.append
        )

        def bad():
            raise RetryableError("transient")

        t0 = time.monotonic()
        with deadline.scope(0.5):
            with pytest.raises(DeadlineExceeded) as ei:
                retry.call_with_retry(bad, policy=pol, op_name="trunc_op")
        assert time.monotonic() - t0 < 0.4  # residual budget returned
        assert sleeps == []  # the 60s backoff was never slept
        assert isinstance(ei.value.__cause__, RetryableError)
        s = retry.stats()
        assert s["backoff_truncated"] == 1
        assert s["deadline_exceeded"] == 1

    def test_backoff_inside_the_budget_sleeps_normally(self):
        sleeps = []
        pol = retry.RetryPolicy(
            max_attempts=3, base_delay_ms=10, jitter=0.0, sleep=sleeps.append
        )

        def bad():
            raise RetryableError("transient")

        with deadline.scope(30.0):
            with pytest.raises(RetryableError):
                retry.call_with_retry(bad, policy=pol, op_name="fit_op")
        assert len(sleeps) == 2  # both backoffs fit and were slept
        assert retry.stats()["backoff_truncated"] == 0

    def test_budget_expiry_raises_deadline_exceeded_chained_to_last_error(self):
        def slow_bad():
            time.sleep(0.03)
            raise RetryableError("transient under budget")

        pol = retry.RetryPolicy(max_attempts=50, base_delay_ms=1, jitter=0.0)
        t0 = time.monotonic()
        with deadline.scope(0.1):
            with pytest.raises(DeadlineExceeded) as ei:
                retry.call_with_retry(slow_bad, policy=pol, op_name="budget_op")
        assert time.monotonic() - t0 < 2.0  # gave up on budget, not attempts
        assert isinstance(ei.value.__cause__, RetryableError)
        assert not isinstance(ei.value, RetryableError)  # non-retryable member
        s = retry.stats()
        assert s["deadline_exceeded"] == 1
        assert s["exhausted"] == 0  # "gave up on budget", NOT "on attempts"

    def test_cancel_token_stops_split_retry(self):
        from spark_rapids_jni_tpu.utils.memory import MemoryBudgetExceeded

        calls = []

        def fn(batch):
            calls.append(len(batch))
            deadline.cancel("operator hit stop")
            raise MemoryBudgetExceeded("RESOURCE_EXHAUSTED: too big")

        pol = retry.RetryPolicy(max_attempts=1, split_depth=8)
        with deadline.scope():  # unbounded, token-only scope
            with pytest.raises(DeadlineExceeded, match="operator hit stop"):
                retry.retry_with_split(
                    fn, list(range(64)),
                    split=lambda b: (b[: len(b) // 2], b[len(b) // 2:]),
                    combine=lambda parts: sum(parts, []),
                    policy=pol, op_name="split_op",
                )
        assert len(calls) == 1  # cancelled before ANY split recursion

    def test_no_deadline_keeps_seed_retry_contract(self):
        attempts = []

        def flaky():
            attempts.append(1)
            if len(attempts) < 3:
                raise RetryableError("transient")
            return "done"

        pol = retry.RetryPolicy(max_attempts=5, base_delay_ms=0)
        assert retry.call_with_retry(flaky, policy=pol) == "done"
        assert retry.stats()["deadline_exceeded"] == 0
        assert retry.stats()["backoff_truncated"] == 0


# ---------------------------------------------------------------------------
# the `hang` fault kind (interruptible wedged-dispatch analog)
# ---------------------------------------------------------------------------


class TestHangFault:
    def test_hang_interrupted_by_deadline(self):
        faultinj.configure(
            {"faults": {"hang_op_a": {"type": "hang", "percent": 100,
                                      "delayMs": 30000}}}
        )

        @op_boundary("hang_op_a")
        def op():
            return "ok"

        t0 = time.monotonic()
        with pytest.raises(DeadlineExceeded, match="hang fault"):
            op(deadline_s=0.3)
        elapsed = time.monotonic() - t0
        assert 0.25 <= elapsed < 3.0  # the budget fired, not the 30s wedge

    def test_hang_interrupted_by_cancel_token(self):
        faultinj.configure(
            {"faults": {"hang_op_b": {"type": "hang", "percent": 100,
                                      "delayMs": 30000}}}
        )

        @op_boundary("hang_op_b")
        def op():
            return "ok"

        t0 = time.monotonic()
        with deadline.scope() as d:  # unbounded: only the token can stop it
            threading.Timer(0.15, d.cancel, args=("chaos abort",)).start()
            with pytest.raises(DeadlineExceeded, match="chaos abort"):
                op()
        assert time.monotonic() - t0 < 3.0

    def test_short_hang_completes_without_deadline(self):
        faultinj.configure(
            {"faults": {"hang_op_c": {"type": "hang", "percent": 100,
                                      "delayMs": 40}}}
        )

        @op_boundary("hang_op_c")
        def op():
            return "ok"

        t0 = time.monotonic()
        assert op() == "ok"
        assert time.monotonic() - t0 >= 0.04  # the hang really slept

    def test_hang_default_delay_is_far_past_deadlines(self):
        faultinj.configure({"faults": {"x": {"type": "hang"}}})
        rule = faultinj._state.rules["x"]
        assert rule.delay_ms == 30000.0  # not the delay kind's 50ms blip

    def test_kind_whitelist_and_validation(self):
        with pytest.raises(ValueError, match="unknown fault type"):
            faultinj.configure({"faults": {"x": {"type": "wedge"}}})
        with pytest.raises(ValueError):
            faultinj.configure(
                {"faults": {"x": {"type": "hang", "delayMs": -1}}}
            )
        faultinj.configure({"faults": {"x": {"type": "hang", "delayMs": 5}}})
        assert faultinj.is_enabled()


# ---------------------------------------------------------------------------
# circuit breaker state machine (unit, injected clock)
# ---------------------------------------------------------------------------


class TestCircuitBreaker:
    def test_opens_at_threshold_half_open_probe_closes(self):
        t = [0.0]
        br = CircuitBreaker("test.br_a", threshold=3, cooldown_s=10,
                            clock=lambda: t[0])
        assert br.allow() and br.state() == "closed"
        br.record_failure("dead worker")
        br.record_failure("dead worker")
        assert br.state() == "closed"  # below threshold
        br.record_failure("dead worker")
        assert br.state() == "open"
        assert not br.allow()  # fast-fail while open
        t[0] = 10.5  # cooldown elapsed
        assert br.allow()  # the half-open probe
        assert br.state() == "half_open"
        assert not br.allow()  # only ONE probe in flight
        br.record_success()
        assert br.state() == "closed"
        snap = br.snapshot()
        assert snap["opened_total"] == 1
        assert snap["half_opened_total"] == 1
        assert snap["closed_total"] == 1
        assert snap["fast_fails_total"] == 2
        assert snap["last_trip_cause"] == "dead worker"

    def test_half_open_probe_failure_reopens_and_restarts_cooldown(self):
        t = [0.0]
        br = CircuitBreaker("test.br_b", threshold=1, cooldown_s=5,
                            clock=lambda: t[0])
        br.record_failure("boom")
        assert br.state() == "open"
        t[0] = 6.0
        assert br.allow()  # half-open probe
        br.record_failure("still dead")
        assert br.state() == "open"
        assert not br.allow()  # cooldown restarted at t=6
        t[0] = 11.5
        assert br.allow() and br.state() == "half_open"
        assert br.snapshot()["opened_total"] == 2

    def test_success_resets_the_consecutive_run(self):
        br = CircuitBreaker("test.br_c", threshold=3, cooldown_s=5)
        br.record_failure("a")
        br.record_failure("b")
        br.record_success()  # the run is consecutive, not cumulative
        br.record_failure("c")
        br.record_failure("d")
        assert br.state() == "closed"
        br.record_failure("e")
        assert br.state() == "open"

    def test_transitions_land_registry_direct_without_metrics_armed(self):
        with metrics.disabled():  # the production-default posture
            br = CircuitBreaker("test.br_d", threshold=1, cooldown_s=5)
            br.record_failure("boom")
            reg = metrics.registry()
            assert reg.value("test.br_d.opened_total") >= 1
            assert reg.value("test.br_d.state") == 1  # open
            br.allow()
            assert reg.value("test.br_d.fast_fails_total") >= 1

    def test_knob_validation(self):
        with pytest.raises(ValueError):
            CircuitBreaker("test.br_e", threshold=0)
        with pytest.raises(ValueError):
            CircuitBreaker("test.br_f", cooldown_s=0)
        br = CircuitBreaker("test.br_g", threshold=2, cooldown_s=1)
        with pytest.raises(ValueError):
            br.configure(threshold=-1)


# ---------------------------------------------------------------------------
# SupervisedClient: budget-derived socket deadlines + breaker integration
# ---------------------------------------------------------------------------


class _FakeWorker:
    """Minimal wire-protocol peer on a unix socket: answers PING with
    backend b"fake" (other ops with an empty ok). ``wedge=True`` makes
    it consume requests and never answer — the hung-worker analog;
    ``error_msg`` makes every reply a status-1 error frame carrying it
    — the worker-side taxonomy-over-the-wire analog."""

    def __init__(self, sock_path: str, wedge: bool = False,
                 error_msg: bytes = None):
        self.sock_path = sock_path
        self.wedge = wedge
        self.error_msg = error_msg
        self._stop = threading.Event()
        self._srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self._srv.bind(sock_path)
        self._srv.listen(8)
        self._srv.settimeout(0.1)
        self._t = threading.Thread(target=self._serve, daemon=True)
        self._t.start()

    def _serve(self):
        while not self._stop.is_set():
            try:
                conn, _ = self._srv.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            threading.Thread(target=self._handle, args=(conn,), daemon=True).start()

    def _handle(self, conn):
        try:
            while True:
                hdr = sidecar._recv_exact(conn, 12)
                op, plen = struct.unpack("<IQ", hdr)
                if op & sidecar.CRC_FLAG:
                    # integrity-framed request (ISSUE 5): consume the
                    # 4-byte trailer to stay framed; replying without
                    # the flag is the legacy-peer posture
                    sidecar._recv_exact(conn, 4)
                    op &= ~sidecar.CRC_FLAG
                if plen:
                    sidecar._recv_exact(conn, plen)
                if self.wedge:
                    continue  # consumed, never answered: the hang
                if self.error_msg is not None:
                    conn.sendall(
                        struct.pack("<IQ", sidecar.STATUS_ERROR,
                                    len(self.error_msg)) + self.error_msg
                    )
                    continue
                op &= ~sidecar.ARENA_FLAG
                resp = b"fake" if op == sidecar.OP_PING else b""
                conn.sendall(struct.pack("<IQ", sidecar.STATUS_OK, len(resp)) + resp)
        except (ConnectionError, OSError):
            pass
        finally:
            conn.close()

    def close(self):
        self._stop.set()
        self._srv.close()
        self._t.join(timeout=2)
        try:
            os.unlink(self.sock_path)
        except FileNotFoundError:
            pass


class TestSupervisedClientDeadline:
    def test_budget_expiry_raises_deadline_exceeded_never_socket_timeout(
        self, tmp_path
    ):
        """Acceptance: with a budget active, a wedged worker surfaces
        DeadlineExceeded at min(socket deadline, remaining budget) —
        never a raw socket timeout, never the 600s default."""
        w = _FakeWorker(str(tmp_path / "wedge.sock"), wedge=True)
        try:
            client = sidecar.SupervisedClient(
                w.sock_path, deadline_s=60.0, heartbeat_s=1e9
            )
            with client:
                t0 = time.monotonic()
                with deadline.scope(0.4):
                    with pytest.raises(DeadlineExceeded):
                        client.request(sidecar.OP_PING, b"")
                assert time.monotonic() - t0 < 5.0  # budget won over 60s
                assert client._sock is None  # desync discipline held
        finally:
            w.close()

    def test_socket_deadline_without_budget_stays_retryable(self, tmp_path):
        """No deadline scope: the seed's per-request contract is
        untouched — a wedged worker is a RetryableError."""
        w = _FakeWorker(str(tmp_path / "wedge2.sock"), wedge=True)
        try:
            client = sidecar.SupervisedClient(
                w.sock_path, deadline_s=0.3, heartbeat_s=1e9
            )
            with client:
                with pytest.raises(RetryableError, match="DEADLINE_EXCEEDED"):
                    client.request(sidecar.OP_PING, b"")
        finally:
            w.close()

    def test_connect_aborts_when_budget_is_gone(self, tmp_path):
        client = sidecar.SupervisedClient(
            str(tmp_path / "nope.sock"), deadline_s=30.0
        )
        with deadline.scope(0.01):
            time.sleep(0.03)
            t0 = time.monotonic()
            with pytest.raises(DeadlineExceeded):
                client.connect()
            assert time.monotonic() - t0 < 1.0  # no dial was paid

    def test_breaker_trips_fast_fails_and_half_open_probe_restores(
        self, tmp_path
    ):
        """The full breaker arc through the real client: consecutive
        supervision failures open it; open requests degrade to the host
        engine with NO dial; after the cooldown the half-open probe
        rides a now-healthy worker and device mode is restored — all
        visible in runtime.stats_report()."""
        from spark_rapids_jni_tpu import runtime

        sock = str(tmp_path / "flaky.sock")
        br = sidecar.breaker()
        br.configure(threshold=2, cooldown_s=0.2)
        client = sidecar.SupervisedClient(sock, deadline_s=0.3, heartbeat_s=1e9)
        with client, retry.enabled(max_attempts=2, base_delay_ms=1):
            # no worker at the path: two degraded calls trip the breaker
            for _ in range(2):
                assert client.call(sidecar.OP_PING, b"") == b"host-fallback"
            assert br.state() == "open"
            assert client.host_fallbacks == 2

            # open: fast-fail to host — no dial, no timeout wait
            t0 = time.monotonic()
            assert client.call(sidecar.OP_PING, b"") == b"host-fallback"
            assert time.monotonic() - t0 < 0.1
            assert client.host_fallbacks == 3
            assert br.snapshot()["fast_fails_total"] >= 1

            # the worker comes back; after the cooldown the half-open
            # probe restores device mode
            w = _FakeWorker(sock)
            try:
                time.sleep(0.25)
                assert client.call(sidecar.OP_PING, b"") == b"fake"  # device!
                assert br.state() == "closed"
                snap = br.snapshot()
                assert snap["opened_total"] == 1
                assert snap["half_opened_total"] == 1
                assert snap["closed_total"] == 1

                rep = runtime.stats_report()
                assert rep["breaker"]["state"] == "closed"
                assert rep["breaker"]["opened_total"] == 1
                assert rep["breaker"]["half_opened_total"] == 1
            finally:
                w.close()

    def test_user_cancel_is_not_a_breaker_failure(self, tmp_path):
        """Cooperative cancellation (a user stopping their query) says
        nothing about device health: the breaker must stay closed —
        only budget expiry and supervision faults count as failures."""
        w = _FakeWorker(str(tmp_path / "wc.sock"), wedge=True)
        try:
            br = sidecar.breaker()
            br.configure(threshold=1, cooldown_s=60)
            # a cancel cannot interrupt a BLOCKED recv — it is noticed
            # at the next check point, here the per-request socket
            # deadline — so keep that short
            client = sidecar.SupervisedClient(
                w.sock_path, deadline_s=0.4, heartbeat_s=1e9
            )
            with client, retry.enabled(max_attempts=3, base_delay_ms=1):
                with deadline.scope() as d:  # unbounded, token-only
                    threading.Timer(0.15, d.cancel, args=("user stop",)).start()
                    with pytest.raises(DeadlineExceeded, match="user stop"):
                        client.call(sidecar.OP_PING, b"")
            assert br.state() == "closed"  # no health verdict recorded
        finally:
            w.close()

    def test_worker_side_deadline_exceeded_maps_and_counts_as_failure(
        self, tmp_path
    ):
        """A worker whose OWN budget died (it inherits SRJT_DEADLINE_SEC
        through spawn_worker's env) stringifies DeadlineExceeded over
        the wire; the client must re-raise it as DeadlineExceeded — not
        a raw RuntimeError — and the breaker must record a FAILURE,
        never a healthy-transport success."""
        w = _FakeWorker(
            str(tmp_path / "wd.sock"),
            error_msg=b"DeadlineExceeded: hash_partition: deadline budget "
                      b"exhausted (budget=3s)",
        )
        try:
            br = sidecar.breaker()
            br.configure(threshold=1, cooldown_s=60)
            client = sidecar.SupervisedClient(
                w.sock_path, deadline_s=5.0, heartbeat_s=1e9
            )
            with client, retry.enabled(max_attempts=3, base_delay_ms=1):
                with pytest.raises(DeadlineExceeded, match="sidecar worker"):
                    client.call(sidecar.OP_PING, b"")
            assert br.state() == "open"
            assert client.host_fallbacks == 0
        finally:
            w.close()

    def test_deadline_expiry_counts_as_breaker_failure_but_propagates(
        self, tmp_path
    ):
        """A budget that dies waiting on the device path is a
        supervision failure for breaker accounting, but the caller gets
        DeadlineExceeded — never a host fallback there is no time for."""
        w = _FakeWorker(str(tmp_path / "wedge3.sock"), wedge=True)
        try:
            br = sidecar.breaker()
            br.configure(threshold=1, cooldown_s=60)
            client = sidecar.SupervisedClient(
                w.sock_path, deadline_s=60.0, heartbeat_s=1e9
            )
            with client, retry.enabled(max_attempts=3, base_delay_ms=1):
                with deadline.scope(0.3):
                    with pytest.raises(DeadlineExceeded):
                        client.call(sidecar.OP_PING, b"")
            assert br.state() == "open"
            assert br.snapshot()["last_trip_cause"] == "deadline"
            assert client.host_fallbacks == 0  # no fallback on a dead budget
        finally:
            w.close()


# ---------------------------------------------------------------------------
# spawn_worker: no leaked child on any failed startup (ISSUE 3 satellite)
# ---------------------------------------------------------------------------


class TestSpawnWorkerReap:
    @staticmethod
    def _capture_popen(monkeypatch):
        import subprocess

        procs = []
        real = subprocess.Popen

        class Recording(real):
            def __init__(self, *a, **k):
                super().__init__(*a, **k)
                procs.append(self)

        monkeypatch.setattr(subprocess, "Popen", Recording)
        return procs

    def test_startup_timeout_terminates_and_reaps(self, monkeypatch, tmp_path):
        procs = self._capture_popen(monkeypatch)
        stub = tmp_path / "never_binds"
        stub.write_text("#!/bin/sh\nexec sleep 60\n")
        stub.chmod(0o755)
        with pytest.raises(RuntimeError, match="timed out"):
            sidecar.spawn_worker(
                sock_path=str(tmp_path / "w.sock"),
                python_exe=str(stub),
                startup_timeout_s=0.3,
            )
        assert len(procs) == 1
        assert procs[0].poll() is not None  # terminated AND reaped

    def test_exit_during_startup_is_reaped(self, monkeypatch, tmp_path):
        procs = self._capture_popen(monkeypatch)
        stub = tmp_path / "dies"
        stub.write_text("#!/bin/sh\nexit 3\n")
        stub.chmod(0o755)
        with pytest.raises(RuntimeError, match="exited during startup"):
            sidecar.spawn_worker(
                sock_path=str(tmp_path / "w2.sock"),
                python_exe=str(stub),
                startup_timeout_s=5.0,
            )
        assert len(procs) == 1
        assert procs[0].returncode == 3  # collected, not a zombie


# ---------------------------------------------------------------------------
# chaos acceptance: hang + retryable storm under a tight budget
# ---------------------------------------------------------------------------


class TestChaosHangStorm:
    def test_every_query_completes_or_raises_deadline_exceeded_in_budget(self):
        """ISSUE 3 acceptance: under the hang-storm profile
        (ci/chaos_hang.json — 30s hangs + retryable faults) with a
        tight budget, every query either completes or raises
        DeadlineExceeded, never exceeding the budget by more than a
        probe interval, and never surfacing a raw RetryableError/socket
        timeout. Honors the premerge env (SRJT_FAULTINJ_CONFIG /
        SRJT_DEADLINE_SEC / SRJT_RETRY_*) like the storm tier does."""
        from spark_rapids_jni_tpu.ops.aggregate import groupby_aggregate

        budget = knobs.get_float("SRJT_DEADLINE_SEC", default=1.5)
        rng = np.random.default_rng(7)
        n = 512
        t = Table(
            [
                Column(dt.INT64, data=jnp.asarray(rng.integers(0, 13, n))),
                Column(dt.INT64, data=jnp.asarray(rng.integers(-100, 100, n))),
            ],
            ["k", "v"],
        )

        def query():
            from spark_rapids_jni_tpu.parallel import shuffle

            part, _ = shuffle.hash_partition(t, 4, ["k"])
            return groupby_aggregate(part.select(["k"]), part, [("v", "sum")])

        expect = np.asarray(query().column("v_sum").data).tobytes()  # warm jit

        faultinj.configure_from_file(
            knobs.get_str("SRJT_FAULTINJ_CONFIG") or _HANG_PATH
        )
        deadline.set_default_budget(budget)
        if knobs.get_bool("SRJT_RETRY_ENABLED"):
            arm = retry.enabled()  # premerge path: operator env knobs win
        else:
            arm = retry.enabled(max_attempts=10, base_delay_ms=1,
                                max_delay_ms=8, seed=99)
        outcomes = {"ok": 0, "deadline": 0}
        with arm:
            for _ in range(8):
                t0 = time.monotonic()
                try:
                    out = query()
                    assert np.asarray(out.column("v_sum").data).tobytes() == expect
                    outcomes["ok"] += 1
                except DeadlineExceeded:
                    outcomes["deadline"] += 1
                # the bound the subsystem advertises: budget + one probe
                # interval of slack, never the 30s wedge
                assert time.monotonic() - t0 <= budget + 1.0
        faultinj.disable()
        # the storm did real work: at least one query died on budget,
        # and the give-up is counted as such
        assert outcomes["deadline"] >= 1, outcomes
        assert retry.stats()["deadline_exceeded"] >= 1
