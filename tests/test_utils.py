"""Aux tier tests: fault injection (deterministic, budgeted, hot-reload),
error classification, tracing scopes, and the op_boundary preamble —
the chaos tier the reference drives via libcufaultinj.so + JSON configs
(SURVEY §2.4), here exercised hermetically in-process."""

import json
import os

import numpy as np
import pytest

import spark_rapids_jni_tpu  # noqa: F401
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.columnar import dtype as dt
from spark_rapids_jni_tpu.ops.aggregate import groupby_aggregate
from spark_rapids_jni_tpu.utils import dispatch, errors, faultinj, tracing


pytestmark = pytest.mark.usefixtures("clean_state")


def _small_table():
    k = Table([Column.from_pylist([1, 1, 2], dt.INT32)], ["k"])
    v = Table([Column.from_pylist([1, 2, 3], dt.INT64)], ["v"])
    return k, v


class TestFaultInj:
    def test_disabled_by_default(self):
        assert not faultinj.is_enabled()
        k, v = _small_table()
        groupby_aggregate(k, v, [("v", "sum")])  # no fault

    def test_named_fault_fires(self):
        faultinj.configure(
            {"seed": 1, "faults": {"groupby_aggregate": {"type": "retryable", "percent": 100}}}
        )
        k, v = _small_table()
        with pytest.raises(errors.RetryableError, match="injected"):
            groupby_aggregate(k, v, [("v", "sum")])

    def test_wildcard_and_fatal(self):
        faultinj.configure({"seed": 1, "faults": {"*": {"type": "fatal", "percent": 100}}})
        k, v = _small_table()
        with pytest.raises(errors.FatalDeviceError):
            groupby_aggregate(k, v, [("v", "sum")])

    def test_interception_budget(self):
        faultinj.configure(
            {
                "seed": 1,
                "faults": {
                    "groupby_aggregate": {
                        "type": "exception",
                        "percent": 100,
                        "interceptionCount": 2,
                    }
                },
            }
        )
        k, v = _small_table()
        for _ in range(2):
            with pytest.raises(RuntimeError):
                groupby_aggregate(k, v, [("v", "sum")])
        out = groupby_aggregate(k, v, [("v", "sum")])  # budget exhausted
        assert out.num_rows == 2

    def test_deterministic_seed(self):
        hits = []
        for _ in range(2):
            faultinj.configure(
                {"seed": 777, "faults": {"groupby_aggregate": {"type": "exception", "percent": 40}}}
            )
            k, v = _small_table()
            pattern = []
            for _ in range(20):
                try:
                    groupby_aggregate(k, v, [("v", "sum")])
                    pattern.append(0)
                except RuntimeError:
                    pattern.append(1)
            hits.append(pattern)
        assert hits[0] == hits[1]  # same seed -> same interception sequence
        assert sum(hits[0]) > 0

    def test_hot_reload(self, tmp_path):
        cfg = tmp_path / "faults.json"
        cfg.write_text(json.dumps({"faults": {}}))
        faultinj.configure_from_file(str(cfg))
        k, v = _small_table()
        groupby_aggregate(k, v, [("v", "sum")])  # no faults configured

        new = {"faults": {"groupby_aggregate": {"type": "retryable", "percent": 100}}}
        cfg.write_text(json.dumps(new))
        os.utime(cfg, (0, 0))  # force mtime change even on coarse clocks
        with pytest.raises(errors.RetryableError):
            groupby_aggregate(k, v, [("v", "sum")])

    def test_bad_config_rejected(self):
        with pytest.raises(ValueError, match="unknown fault type"):
            faultinj.configure({"faults": {"x": {"type": "nonsense"}}})


class TestErrors:
    def test_classify_retryable(self):
        e = errors.classify(RuntimeError("RESOURCE_EXHAUSTED: hbm oom"))
        assert isinstance(e, errors.RetryableError)

    def test_classify_fatal_unknown(self):
        e = errors.classify(RuntimeError("backend exploded in a new way"))
        assert isinstance(e, errors.FatalDeviceError)

    def test_classify_deadline_exceeded_retryable(self):
        # "DEAD" is word-bounded: it must not swallow DEADLINE_EXCEEDED
        e = errors.classify(RuntimeError("DEADLINE_EXCEEDED: op timed out"))
        assert isinstance(e, errors.RetryableError)

    def test_classify_mixed_markers_fatal_wins(self):
        # A dead accelerator often surfaces with a retryable-looking
        # suffix; retrying batches on a dead device strands the
        # executor, so fatal must win.
        e = errors.classify(
            RuntimeError("INTERNAL: Accelerator t5 channel UNAVAILABLE")
        )
        assert isinstance(e, errors.FatalDeviceError)

    def test_host_errors_pass_through(self):
        with pytest.raises(ValueError):
            errors.classify(ValueError("bad argument"))

    def test_op_boundary_classifies(self):
        @dispatch.op_boundary("boom_op")
        def boom():
            raise RuntimeError("UNAVAILABLE: link down")

        with pytest.raises(errors.RetryableError):
            boom()

    def test_op_boundary_host_error_unwrapped(self):
        @dispatch.op_boundary("val_op")
        def bad():
            raise ValueError("plain host error")

        with pytest.raises(ValueError):
            bad()


class TestTracing:
    def test_func_range_off_and_on(self):
        assert not tracing.is_enabled()
        with tracing.func_range("x"):
            pass
        tracing.set_enabled(True)
        try:
            k, v = _small_table()
            out = groupby_aggregate(k, v, [("v", "sum")])  # runs under named_scope
            assert out.num_rows == 2
        finally:
            tracing.set_enabled(False)


class _FakeDevice:
    def __init__(self, platform, device_kind, stats):
        self.platform, self.device_kind, self._stats = platform, device_kind, stats

    def memory_stats(self):
        return self._stats


class TestBackendBudget:
    """utils/memory._resolve_backend_budget: read from the device, else
    from a table keyed by device_kind that raises on an unknown kind."""

    @pytest.mark.parametrize("dev,want", [
        # the chip reports its limit: half of it is the per-op budget
        (_FakeDevice("tpu", "TPU v5 lite", {"bytes_limit": 12 << 30, "bytes_in_use": 0}), 6 << 30),
        # no limit reported: the device_kind table (v5e: 16 GB)
        (_FakeDevice("tpu", "TPU v5 lite", None), 8 << 30),
        (_FakeDevice("tpu", "TPU v5 lite", {}), 8 << 30),
        # the CPU backend reports nothing: host-RAM share
        (_FakeDevice("cpu", "cpu", None), 4 << 30),
    ])
    def test_budget_sources(self, monkeypatch, dev, want):
        import jax

        from spark_rapids_jni_tpu.utils import memory

        monkeypatch.setattr(jax, "local_devices", lambda: [dev])
        monkeypatch.setattr(memory, "_STATS_DEV", None)
        assert memory._resolve_backend_budget() == want

    @pytest.mark.parametrize("dev", [
        _FakeDevice("tpu", "TPU v9 imaginary", None),
        _FakeDevice("gpu", "some gpu", None),
    ])
    def test_unknown_kind_is_an_error_not_a_default(self, monkeypatch, dev):
        import jax

        from spark_rapids_jni_tpu.utils import memory

        monkeypatch.setattr(jax, "local_devices", lambda: [dev])
        with pytest.raises(RuntimeError, match="reports no bytes_limit"):
            memory._resolve_backend_budget()

    def test_probe_failure_propagates(self, monkeypatch):
        import jax

        from spark_rapids_jni_tpu.utils import memory

        def boom():
            raise OSError("backend did not start")

        monkeypatch.setattr(jax, "local_devices", boom)
        with pytest.raises(OSError):
            memory._resolve_backend_budget()
