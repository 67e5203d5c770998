"""Retry orchestrator unit tier (utils/retry.py): backoff shape,
fatal/retryable discipline, retry-with-split reassembly, op-boundary
integration with the fault injector, and the shuffle capacity re-try
loop. The end-to-end fault-storm parity runs in tests/test_chaos.py.
"""

import os

import numpy as np
import pytest

import spark_rapids_jni_tpu  # noqa: F401
import jax
import jax.numpy as jnp

from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.columnar import dtype as dt
from spark_rapids_jni_tpu.utils import errors, faultinj, retry
from spark_rapids_jni_tpu.utils.memory import MemoryBudgetExceeded


pytestmark = pytest.mark.usefixtures("clean_state")


def _policy(**kw):
    kw.setdefault("base_delay_ms", 1)
    kw.setdefault("max_delay_ms", 4)
    kw.setdefault("jitter", 0.0)
    kw.setdefault("sleep", lambda s: None)
    return retry.RetryPolicy(**kw)


class TestPolicy:
    def test_backoff_doubles_and_caps(self):
        p = retry.RetryPolicy(base_delay_ms=10, max_delay_ms=35, jitter=0.0)
        assert [p.backoff_ms(a) for a in range(4)] == [10, 20, 35, 35]

    def test_jitter_bounds_and_determinism(self):
        p1 = retry.RetryPolicy(base_delay_ms=100, jitter=0.25, seed=7)
        p2 = retry.RetryPolicy(base_delay_ms=100, jitter=0.25, seed=7)
        d1 = [p1.backoff_ms(0) for _ in range(50)]
        d2 = [p2.backoff_ms(0) for _ in range(50)]
        assert d1 == d2  # seeded jitter is reproducible
        assert all(75.0 <= d <= 125.0 for d in d1)
        assert len(set(d1)) > 1  # and actually jitters

    def test_from_env(self):
        env = {
            "SRJT_RETRY_MAX_ATTEMPTS": "7",
            "SRJT_RETRY_BASE_DELAY_MS": "3",
            "SRJT_RETRY_MAX_DELAY_MS": "50",
            "SRJT_RETRY_JITTER": "0",
            "SRJT_RETRY_SPLIT_DEPTH": "5",
        }
        p = retry.RetryPolicy.from_env(env)
        assert p.max_attempts == 7
        assert p.base_delay_ms == 3
        assert p.max_delay_ms == 50
        assert p.jitter == 0
        assert p.split_depth == 5

    def test_malformed_env_falls_back(self):
        with pytest.warns(UserWarning, match="malformed"):
            p = retry.RetryPolicy.from_env({"SRJT_RETRY_BASE_DELAY_MS": "soon"})
        assert p.base_delay_ms == 25.0

    def test_nonpositive_env_attempts_fall_back(self):
        with pytest.warns(UserWarning, match="must be > 0"):
            p = retry.RetryPolicy.from_env({"SRJT_RETRY_MAX_ATTEMPTS": "0"})
        assert p.max_attempts == 4

    def test_env_float_positive_gate(self):
        # the shared parser the sidecar deadline knobs go through: a
        # zero deadline would make sockets non-blocking, not unbounded
        with pytest.warns(UserWarning, match="must be > 0"):
            v = retry.env_float({"X": "0"}, "X", 600.0, positive=True)
        assert v == 600.0
        assert retry.env_float({"X": "2.5"}, "X", 600.0, positive=True) == 2.5

    def test_jitter_never_exceeds_max_delay(self):
        p = retry.RetryPolicy(base_delay_ms=900, max_delay_ms=1000, jitter=0.25, seed=1)
        assert all(p.backoff_ms(a) <= 1000.0 for a in range(6) for _ in range(20))

    def test_validation(self):
        with pytest.raises(ValueError):
            retry.RetryPolicy(max_attempts=0)
        with pytest.raises(ValueError):
            retry.RetryPolicy(jitter=1.5)
        with pytest.raises(ValueError):
            retry.RetryPolicy(split_depth=-1)


class TestCallWithRetry:
    def test_succeeds_after_transients(self):
        slept = []
        p = _policy(max_attempts=4, sleep=lambda s: slept.append(s))
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise errors.RetryableError("transient")
            return "ok"

        assert retry.call_with_retry(flaky, policy=p) == "ok"
        assert calls["n"] == 3
        assert len(slept) == 2  # one backoff per retry
        s = retry.stats()
        assert s["retries"] == 2 and s["exhausted"] == 0

    def test_fatal_never_retries(self):
        calls = {"n": 0}

        def dead():
            calls["n"] += 1
            raise errors.FatalDeviceError("chip gone")

        with pytest.raises(errors.FatalDeviceError):
            retry.call_with_retry(dead, policy=_policy(max_attempts=5))
        assert calls["n"] == 1
        assert retry.stats()["fatal"] == 1

    def test_exhaustion_raises_last_error(self):
        calls = {"n": 0}

        def always():
            calls["n"] += 1
            raise errors.RetryableError(f"attempt {calls['n']}")

        with pytest.raises(errors.RetryableError, match="attempt 3"):
            retry.call_with_retry(always, policy=_policy(max_attempts=3))
        assert calls["n"] == 3
        assert retry.stats()["exhausted"] == 1

    def test_host_errors_pass_through_uncounted(self):
        def bad():
            raise ValueError("caller bug")

        with pytest.raises(ValueError):
            retry.call_with_retry(bad, policy=_policy())
        assert retry.stats()["retries"] == 0


class TestOpBoundaryIntegration:
    def _table(self):
        return Table([Column.from_pylist([5, 6, 7, 8], dt.INT64)], ["k"])

    def test_armed_boundary_recovers_injected_retryables(self):
        from spark_rapids_jni_tpu.parallel.shuffle import hash_partition

        faultinj.configure(
            {"seed": 3,
             "faults": {"hash_partition": {"type": "retryable", "percent": 100,
                                           "interceptionCount": 2}}}
        )
        with retry.enabled(base_delay_ms=1, max_attempts=4, jitter=0.0):
            out, offsets = hash_partition(self._table(), 2, ["k"])
        assert sorted(out.column("k").data.tolist()) == [5, 6, 7, 8]
        assert retry.stats()["retries"] == 2

    def test_disarmed_boundary_keeps_seed_contract(self):
        from spark_rapids_jni_tpu.parallel.shuffle import hash_partition

        faultinj.configure(
            {"faults": {"hash_partition": {"type": "retryable", "percent": 100}}}
        )
        with pytest.raises(errors.RetryableError):
            hash_partition(self._table(), 2, ["k"])

    def test_armed_boundary_never_retries_fatal(self):
        from spark_rapids_jni_tpu.parallel.shuffle import hash_partition

        faultinj.configure(
            {"faults": {"hash_partition": {"type": "fatal", "percent": 100}}}
        )
        with retry.enabled(base_delay_ms=1):
            with pytest.raises(errors.FatalDeviceError):
                hash_partition(self._table(), 2, ["k"])
        assert retry.stats()["retries"] == 0

    def test_nested_boundaries_share_one_retry_loop(self):
        from spark_rapids_jni_tpu.utils.dispatch import op_boundary

        @op_boundary("nested_inner")
        def inner():
            return "never"  # the injected fault fires at the boundary

        @op_boundary("nested_outer")
        def outer():
            return inner()

        faultinj.configure(
            {"faults": {"nested_inner": {"type": "retryable", "percent": 100}}}
        )
        with retry.enabled(max_attempts=3, base_delay_ms=1, jitter=0.0):
            with pytest.raises(errors.RetryableError):
                outer()
        # only the OUTERMOST boundary retries: 3 total attempts, not
        # 3 (outer) x 3 (inner) = 9 multiplied re-runs
        assert retry.stats()["attempts"] == 3


class TestRetryWithSplit:
    def _table(self, n=64):
        return Table(
            [
                Column.from_pylist(list(range(n)), dt.INT64),
                Column.from_pylist([i % 7 for i in range(n)], dt.INT32),
            ],
            ["v", "k"],
        )

    def test_splits_and_reassembles(self):
        t = self._table(64)
        max_rows = 20  # anything larger "exhausts the device"

        def op(batch):
            if batch.num_rows > max_rows:
                raise MemoryBudgetExceeded(
                    f"RESOURCE_EXHAUSTED: {batch.num_rows} rows > {max_rows}"
                )
            out = batch.column("v").data * 2
            return Table([Column(dt.INT64, data=out)], ["v2"])

        got = retry.retry_with_split(op, t, policy=_policy(max_attempts=1, split_depth=3))
        assert got.num_rows == 64
        assert got.column("v2").data.tolist() == [2 * i for i in range(64)]
        assert retry.stats()["splits"] >= 3  # 64 -> 32 -> 16 needed two levels

    def test_depth_exhaustion_raises(self):
        t = self._table(32)

        def never(batch):
            raise MemoryBudgetExceeded("RESOURCE_EXHAUSTED: always")

        with pytest.raises(MemoryBudgetExceeded):
            retry.retry_with_split(
                never, t, policy=_policy(max_attempts=1, split_depth=2)
            )

    def test_non_exhaustion_retryable_never_splits(self):
        t = self._table(8)
        calls = {"n": 0}

        def flaky(batch):
            calls["n"] += 1
            raise errors.RetryableError("UNAVAILABLE: transport flake")

        with pytest.raises(errors.RetryableError):
            retry.retry_with_split(flaky, t, policy=_policy(max_attempts=2))
        assert calls["n"] == 2  # bounded retry only, no halving
        assert retry.stats()["splits"] == 0

    def test_custom_split_combine(self):
        def op(xs):
            if len(xs) > 2:
                raise errors.RetryableError("RESOURCE_EXHAUSTED: list too big")
            return [x + 1 for x in xs]

        got = retry.retry_with_split(
            op,
            [1, 2, 3, 4, 5],
            split=lambda xs: (xs[: len(xs) // 2], xs[len(xs) // 2:]),
            combine=lambda parts: [y for p in parts for y in p],
            policy=_policy(max_attempts=1, split_depth=3),
        )
        assert got == [2, 3, 4, 5, 6]


class TestFaultinjExtensions:
    def test_delay_fault_sleeps(self, monkeypatch):
        import spark_rapids_jni_tpu.utils.faultinj as fi

        slept = []
        monkeypatch.setattr(fi.time, "sleep", lambda s: slept.append(s))
        faultinj.configure(
            {"faults": {"op_x": {"type": "delay", "percent": 100, "delayMs": 40}}}
        )
        faultinj.maybe_inject("op_x")  # no raise
        assert slept == [0.04]

    def test_after_skips_initial_dispatches(self):
        faultinj.configure(
            {"faults": {"op_y": {"type": "retryable", "percent": 100, "after": 3}}}
        )
        for _ in range(3):
            faultinj.maybe_inject("op_y")  # armed only after 3 calls
        with pytest.raises(errors.RetryableError):
            faultinj.maybe_inject("op_y")

    def test_ramp_scales_probability_in(self):
        # percent=100 with ramp=4: effective 25/50/75/100 — with a seed
        # the sequence of fires is deterministic; the LAST armed call
        # (eff 100%) must always fire
        faultinj.configure(
            {"seed": 11,
             "faults": {"op_z": {"type": "retryable", "percent": 100, "ramp": 4}}}
        )
        fired = []
        for i in range(4):
            try:
                faultinj.maybe_inject("op_z")
                fired.append(False)
            except errors.RetryableError:
                fired.append(True)
        assert fired[3] is True  # ramp completed: full percent
        faultinj.configure(
            {"seed": 11,
             "faults": {"op_z": {"type": "retryable", "percent": 100, "ramp": 4}}}
        )
        fired2 = []
        for i in range(4):
            try:
                faultinj.maybe_inject("op_z")
                fired2.append(False)
            except errors.RetryableError:
                fired2.append(True)
        assert fired == fired2  # seeded storm is reproducible

    def test_bad_schedule_values_rejected(self):
        with pytest.raises(ValueError):
            faultinj.configure(
                {"faults": {"x": {"type": "delay", "delayMs": -1}}}
            )
        with pytest.raises(ValueError):
            faultinj.configure({"faults": {"x": {"type": "retryable", "after": -2}}})


class TestShuffleCapacityRetry:
    @pytest.fixture(scope="class")
    def mesh8(self):
        from spark_rapids_jni_tpu.parallel import mesh as mesh_mod

        assert len(jax.devices()) == 8
        return mesh_mod.make_mesh({"data": 8})

    def test_retry_mode_escalates_and_completes(self, mesh8):
        from spark_rapids_jni_tpu.parallel import mesh as mesh_mod, shuffle

        n = 8 * 8
        vals = jnp.arange(n, dtype=jnp.int64)
        dest = jnp.zeros((n,), jnp.int32)  # extreme skew: all to shard 0
        sh = mesh_mod.row_sharding(mesh8)
        (recv,), mask, overflow = shuffle.all_to_all_exchange(
            [jax.device_put(vals, sh)], jax.device_put(dest, sh), mesh8,
            capacity=2, on_overflow="retry",
        )
        assert not bool(np.asarray(overflow).any())
        got = sorted(np.asarray(recv)[np.asarray(mask)].tolist())
        assert got == list(range(n))  # every row landed, none dropped
        assert retry.stats()["capacity_retries"] >= 1  # 2 -> 4 -> 8 doublings

    def test_exchange_by_key_retry_mode(self, mesh8):
        from spark_rapids_jni_tpu.parallel import mesh as mesh_mod, shuffle

        n = 8 * 16
        keys = np.zeros(n, np.int64)  # one key: worst-case skew
        vals = np.arange(n, dtype=np.int64)
        t = Table(
            [Column(dt.INT64, data=jnp.asarray(keys)),
             Column(dt.INT64, data=jnp.asarray(vals))],
            ["k", "v"],
        )
        t_s = mesh_mod.shard_table_rows(t, mesh8)
        pairs, mask, overflow = shuffle.exchange_by_key(
            t_s, ["k"], mesh8, capacity=2, on_overflow="retry"
        )
        assert not bool(np.asarray(overflow).any())
        m = np.asarray(mask).reshape(-1)
        got = sorted(np.asarray(pairs[1][0]).reshape(-1)[m].tolist())
        assert got == list(range(n))

    def test_invalid_mode_rejected(self, mesh8):
        from spark_rapids_jni_tpu.parallel import shuffle

        with pytest.raises(ValueError, match="on_overflow"):
            shuffle.exchange_by_key(
                Table([Column.from_pylist([1], dt.INT64)], ["k"]), ["k"],
                mesh8, on_overflow="ignore",
            )


class TestTransportClassification:
    def test_sidecar_transport_faults_are_retryable(self):
        for text in (
            "Connection refused",
            "Connection reset by peer",
            "Broken pipe",
        ):
            assert isinstance(
                errors.classify(OSError(text)), errors.RetryableError
            ), text

    def test_generic_timeout_stays_fatal(self):
        # "timed out" appears in wedged-mesh backend errors too: the
        # conservative fatal classification must win there; sidecar
        # deadlines carry their own DEADLINE_EXCEEDED marker
        assert isinstance(
            errors.classify(RuntimeError("collective barrier timed out")),
            errors.FatalDeviceError,
        )

    def test_unknown_stays_fatal(self):
        assert isinstance(
            errors.classify(RuntimeError("novel explosion")), errors.FatalDeviceError
        )


class TestRuntimeWiring:
    def test_device_heartbeat_safe_without_native(self):
        from spark_rapids_jni_tpu import runtime

        # regardless of whether libsrjt.so is built, the probe must be
        # a safe boolean — False when nothing is connected
        assert runtime.device_heartbeat() in (False, True)
