"""The harness's own guards: the two decisions tests/conftest.py takes for
every file (is the native library there; what is pristine process state)
and the in-process worker, held to what the other files rely on."""

import shutil
import socket
import time

import pytest

import conftest
from _inproc import InProcWorker
from spark_rapids_jni_tpu import sidecar
from spark_rapids_jni_tpu.parallel import shuffle
from spark_rapids_jni_tpu.utils import deadline, faultinj, metrics, retry, tracing

pytestmark = pytest.mark.usefixtures("clean_state")


def test_native_gate_does_not_skip_where_a_toolchain_is(request):
    """The gate must not rot back into a silent skip: with cmake and ninja
    on the PATH the library was built before collection, this process's
    first (and cached) look found it, and the ``native`` fixture hands out
    the runtime."""
    if shutil.which("cmake") is None or shutil.which("ninja") is None:
        pytest.skip("no native toolchain: the native tests skip here, as the header says")
    from spark_rapids_jni_tpu import runtime

    assert runtime.native_available(), "libsrjt.so was not there at this process's first look"
    try:
        assert request.getfixturevalue("native") is runtime
    except pytest.skip.Exception as e:
        pytest.fail(f"the native gate skipped with a toolchain present: {e}")


def test_no_toolchain_and_no_library_is_reported_not_raised(monkeypatch, tmp_path):
    monkeypatch.setattr(conftest, "REPO", str(tmp_path))
    monkeypatch.setattr(shutil, "which", lambda tool: None)
    status = conftest._build_native()
    assert status.startswith(conftest._NATIVE_ABSENT)
    assert "cmake" in status and "ninja" in status


def test_reset_process_state_puts_back_a_fresh_process():
    pristine_breaker = deadline.CircuitBreaker("tests.pristine").snapshot()
    env_tracing, env_metrics = tracing.is_enabled(), metrics.is_enabled()
    peer = shuffle.exchange_breaker("127.0.0.1:9")

    faultinj.configure({"faults": {"nothing.*": {"type": "retryable", "percent": 100}}})
    retry.enable()
    retry.record_capacity_retry()
    deadline.set_default_budget(1.5)
    br = sidecar.breaker()
    br.configure(threshold=1, cooldown_s=60)
    br.record_failure("test: dark")
    for _ in range(pristine_breaker["threshold"]):
        peer.record_failure("test: dark")
    tracing.set_enabled(not env_tracing)
    (metrics.disable if env_metrics else metrics.enable)()
    metrics.registry().counter("sidecar.worker.requests").inc()
    assert br.state() == peer.state() == "open"

    found = "; ".join(conftest.reset_process_state())

    for what in ("fault injection", "retry", "deadline budget", "sidecar breaker",
                 "exchange breakers", "tracing", "metrics"):
        assert what in found, (what, found)
    assert not faultinj.is_enabled()
    assert not retry.is_enabled()
    assert retry.stats()["capacity_retries"] == 0
    assert deadline.default_budget() is None
    now = br.snapshot()
    assert {k: now[k] for k in ("state", "threshold", "cooldown_s")} == {
        k: pristine_breaker[k] for k in ("state", "threshold", "cooldown_s")
    }
    assert peer.state() == "closed"
    assert tracing.is_enabled() == env_tracing
    assert metrics.is_enabled() == env_metrics
    assert not any(n.startswith("sidecar.worker.") for n in metrics.registry()._metrics)
    assert conftest.reset_process_state() == []


def test_killed_inproc_worker_leaves_no_thread_behind():
    w = InProcWorker()
    client = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    try:
        client.connect(w.sock_path)
        client.settimeout(5)
        give_up = time.monotonic() + 5
        while not w._handlers and time.monotonic() < give_up:
            time.sleep(0.01)  # until the accept loop has started the handler
        w.kill()
        assert client.recv(1) == b""  # what a client of a SIGKILLed worker observes
    finally:
        client.close()
        w.kill()
    assert w._handlers, "the worker never served the connection"
    assert not w._t.is_alive()
    assert not any(t.is_alive() for t in w._handlers)
