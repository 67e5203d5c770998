"""End-to-end chaos tier (ISSUE 1 acceptance): a distributed pipeline
(hash_partition -> exchange_by_key -> groupby aggregate) runs under an
injected fault storm — retryable faults at 30%, delay faults included —
and must complete THROUGH the retry orchestrator with results
bit-identical to the fault-free run. Sidecar supervision: injected
fatal faults / a killed worker degrade to the in-process host-CPU
engine within the configured deadline — no hang, no silent drop.

ci/premerge.sh runs this file with SRJT_FAULTINJ_CONFIG pointing at
ci/chaos_storm.json (the env-file activation path); standalone runs
fall back to the same profile configured programmatically.
"""

import os
import struct
import time

import numpy as np
import pytest

import spark_rapids_jni_tpu  # noqa: F401
import jax
import jax.numpy as jnp

from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.columnar import dtype as dt
from spark_rapids_jni_tpu.utils import errors, faultinj, knobs, retry

# the premerge storm profile: retryable faults at 30% on every pipeline
# stage, an injected-latency fault on the all-to-all, `after`/`ramp`
# scheduling in the mix. ONE source of truth — standalone runs load the
# same file premerge points SRJT_FAULTINJ_CONFIG at, so the two paths
# cannot drift.
_STORM_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "ci", "chaos_storm.json",
)


pytestmark = pytest.mark.usefixtures("clean_state")


@pytest.fixture(scope="module")
def mesh8():
    from spark_rapids_jni_tpu.parallel import mesh as mesh_mod

    assert len(jax.devices()) == 8, "conftest must force the 8-device CPU mesh"
    return mesh_mod.make_mesh({"data": 8})


def _pipeline(keys, vi, vf_bits, mesh):
    """hash_partition -> exchange_by_key (capacity re-try) -> groupby
    agg; returns the key-sorted result table's raw bytes per column so
    parity checks are BIT-identical, not approx."""
    from spark_rapids_jni_tpu.ops.aggregate import groupby_aggregate
    from spark_rapids_jni_tpu.parallel import mesh as mesh_mod, shuffle

    t = Table(
        [
            Column(dt.INT64, data=jnp.asarray(keys)),
            Column(dt.INT64, data=jnp.asarray(vi)),
            Column(dt.FLOAT64, data=jnp.asarray(vf_bits)),
        ],
        ["k", "vi", "vf"],
    )
    part, _offsets = shuffle.hash_partition(t, mesh.shape["data"], ["k"])
    t_s = mesh_mod.shard_table_rows(part, mesh)
    # deliberately undersized capacity: the storm run AND the clean run
    # both exercise the geometric capacity re-try loop
    pairs, mask, overflow = shuffle.exchange_by_key(
        t_s, ["k"], mesh, capacity=8, on_overflow="retry"
    )
    assert not bool(np.asarray(overflow).any())
    m = np.asarray(mask).reshape(-1)
    k = np.asarray(pairs[0][0]).reshape(-1)[m]
    rvi = np.asarray(pairs[1][0]).reshape(-1)[m]
    rvf = np.asarray(pairs[2][0]).reshape(-1)[m]
    tr = Table(
        [
            Column(dt.INT64, data=jnp.asarray(k)),
            Column(dt.INT64, data=jnp.asarray(rvi)),
            Column(dt.FLOAT64, data=jnp.asarray(rvf)),
        ],
        ["k", "vi", "vf"],
    )
    out = groupby_aggregate(
        tr.select(["k"]), tr, [("vi", "sum"), ("vf", "sum"), ("vi", "count")]
    )
    # key-sorted output + exact (order-independent) aggregates ->
    # byte-level comparison is meaningful
    return {
        name: np.asarray(out.column(name).data).tobytes()
        for name in ["k", "vi_sum", "vf_sum", "vi_count"]
    }


def _inputs():
    rng = np.random.default_rng(424242)
    n = 8 * 64
    keys = rng.integers(0, 13, n).astype(np.int64)  # skewed: forces capacity re-try
    vi = rng.integers(-1000, 1000, n).astype(np.int64)
    vf_bits = rng.standard_normal(n).astype(np.float64).view(np.uint64)
    return keys, vi, vf_bits


def test_chaos_parity_retryable_storm(mesh8):
    """The acceptance pipeline: fault-free result == fault-storm result,
    bit for bit, with the orchestrator doing real work (retries and
    capacity escalations both observed). Three storm passes give the
    `after`/`ramp` schedules room to arm and the 30% rules enough
    dispatches to fire deterministically under the profile seed."""
    keys, vi, vf_bits = _inputs()
    clean = _pipeline(keys, vi, vf_bits, mesh8)
    retry.reset_stats()

    faultinj.configure_from_file(
        knobs.get_str("SRJT_FAULTINJ_CONFIG") or _STORM_PATH
    )
    if knobs.get_bool("SRJT_RETRY_ENABLED"):
        # premerge path: honor the operator's SRJT_RETRY_* env knobs
        # (ci/premerge.sh sets attempts/delays for the gate)
        arm = retry.enabled()
    else:
        arm = retry.enabled(max_attempts=10, base_delay_ms=1, max_delay_ms=8, seed=99)
    with arm:
        for _ in range(3):
            stormy = _pipeline(keys, vi, vf_bits, mesh8)
            assert stormy == clean  # bit-identical through the storm
    faultinj.disable()

    s = retry.stats()
    assert s["capacity_retries"] >= 1  # skew forced 8 -> ... escalation
    assert s["retries"] >= 1  # the storm actually fired and was recovered
    assert s["fatal"] == 0


def test_chaos_storm_without_orchestrator_fails(mesh8):
    """Counterfactual: the same storm with the orchestrator DISARMED
    kills the pipeline — proving the parity above is the orchestrator's
    doing, not storm under-configuration."""
    keys, vi, vf_bits = _inputs()
    faultinj.configure(
        {"seed": 7, "faults": {"hash_partition": {"type": "retryable", "percent": 100}}}
    )
    with pytest.raises(errors.RetryableError):
        _pipeline(keys, vi, vf_bits, mesh8)


def test_delay_storm_completes_identically(mesh8):
    """A pure latency storm (the wedged-kernel analog) must change
    timing only — results stay bit-identical with NO retries needed."""
    keys, vi, vf_bits = _inputs()
    clean = _pipeline(keys, vi, vf_bits, mesh8)
    faultinj.configure(
        {"seed": 5,
         "faults": {"*": {"type": "delay", "percent": 50, "delayMs": 2}}}
    )
    slow = _pipeline(keys, vi, vf_bits, mesh8)
    assert slow == clean


# ---------------------------------------------------------------------------
# sidecar connection supervision: degrade-to-host under fatal faults
# ---------------------------------------------------------------------------


class TestSidecarSupervision:
    """One spawned worker, three supervision scenarios in sequence:
    heartbeat, worker-side fatal fault -> host degrade (worker
    survives), chaos worker death mid-op -> host degrade (bounded by
    the deadline, no hang)."""

    @pytest.fixture(scope="class")
    def worker(self, tmp_path_factory):
        from spark_rapids_jni_tpu import sidecar

        tmp = tmp_path_factory.mktemp("chaos")
        cfg = tmp / "worker_faults.json"
        cfg.write_text(
            '{"faults": {"convert_to_rows": {"type": "fatal", "percent": 100}}}'
        )
        proc, sock = sidecar.spawn_worker(
            startup_timeout_s=120,
            env={
                "SRJT_FAULTINJ_CONFIG": str(cfg),
                # GROUPBY_SUM (op 1) murders the worker mid-op
                "SRJT_CHAOS_EXIT_ON_OP": "1",
            },
        )
        yield proc, sock
        if proc.poll() is None:
            proc.terminate()
        proc.wait(timeout=30)
        try:
            os.unlink(sock)
        except FileNotFoundError:
            pass

    def test_supervised_degrade_sequence(self, worker):
        from spark_rapids_jni_tpu import sidecar

        proc, sock = worker
        client = sidecar.SupervisedClient(sock, deadline_s=60, heartbeat_s=0.0)
        with client:
            # 1) heartbeat: PING round-trips and reports the backend
            assert client.ping() == "cpu"

            # 2) worker-side FATAL fault on convert_to_rows: the client
            # must NOT retry a fatal — it degrades straight to the
            # in-process host engine, and the worker stays up
            tbl = Table(
                [Column(dt.INT32, data=jnp.arange(64, dtype=jnp.int32))], ["a"]
            )
            payload = sidecar.as_bytes(sidecar._write_table(tbl))
            t0 = time.monotonic()
            with retry.enabled(max_attempts=3, base_delay_ms=1):
                resp = client.call(sidecar.OP_CONVERT_TO_ROWS, payload)
            elapsed = time.monotonic() - t0
            host = sidecar.as_bytes(sidecar._dispatch(sidecar.OP_CONVERT_TO_ROWS, payload, "cpu"))
            assert resp == host  # host fallback produced the real result
            assert client.host_fallbacks == 1
            assert retry.stats()["retries"] == 0  # fatal: zero retries
            assert elapsed < 60  # bounded, no hang
            assert proc.poll() is None  # fatal fault != dead worker
            assert client.ping() == "cpu"  # connection still healthy

            # 3) chaos exit mid-op: the worker dies after consuming the
            # GROUPBY_SUM request; the client sees a dead transport,
            # retries against a dead socket, and degrades to host
            n, nk = 256, 17
            keys = (np.arange(n) % nk).astype(np.int64)
            vals = np.ones(n, np.float32)
            gp = (
                struct.pack("<IQ", nk, n) + keys.tobytes() + vals.tobytes()
            )
            t0 = time.monotonic()
            with retry.enabled(max_attempts=3, base_delay_ms=1):
                resp = client.call(sidecar.OP_GROUPBY_SUM_F32, gp)
            elapsed = time.monotonic() - t0
            sums = np.frombuffer(resp, np.float32, nk)
            counts = np.frombuffer(resp, np.int64, nk, 4 * nk)
            np.testing.assert_array_equal(counts, np.bincount(keys, minlength=nk))
            np.testing.assert_allclose(sums, np.bincount(keys, weights=vals,
                                                         minlength=nk), rtol=1e-6)
            assert client.host_fallbacks == 2
            assert elapsed < 120  # bounded by deadline x attempts, not a hang
            assert proc.wait(timeout=30) == 42  # the chaos _exit fired

    def test_request_deadline_fires(self, tmp_path):
        """Per-request deadline: a worker WEDGED by an injected delay
        fault (the new `delay` kind, exactly this scenario's tool)
        surfaces DEADLINE_EXCEEDED (retryable) at the client's deadline
        — never an indefinite block — and the desynced connection is
        closed for a fresh redial."""
        from spark_rapids_jni_tpu import sidecar

        cfg = tmp_path / "wedge.json"
        cfg.write_text(
            '{"faults": {"convert_to_rows": '
            '{"type": "delay", "percent": 100, "delayMs": 30000}}}'
        )
        proc, sock = sidecar.spawn_worker(
            startup_timeout_s=120, env={"SRJT_FAULTINJ_CONFIG": str(cfg)}
        )
        try:
            client = sidecar.SupervisedClient(sock, deadline_s=2.0, heartbeat_s=1e9)
            with client:
                assert client.ping() == "cpu"  # PING skips the wedged op
                tbl = Table(
                    [Column(dt.INT32, data=jnp.arange(8, dtype=jnp.int32))], ["a"]
                )
                payload = sidecar.as_bytes(sidecar._write_table(tbl))
                t0 = time.monotonic()
                with pytest.raises(errors.RetryableError, match="DEADLINE_EXCEEDED"):
                    client.request(sidecar.OP_CONVERT_TO_ROWS, payload)
                elapsed = time.monotonic() - t0
                assert elapsed < 15  # the deadline fired, not the 30s wedge
                assert client._sock is None  # desync discipline: closed
        finally:
            proc.terminate()
            proc.wait(timeout=30)
            try:
                os.unlink(sock)
            except FileNotFoundError:
                pass
