"""Sidecar worker pool + end-to-end integrity tier (ISSUE 5).

Covers the crash-tolerance contract from both ends:

- POOL: failover on worker death (in-process fake workers for the fast
  tier; real kill -9 / chaos ``crash`` storms in the slow tier),
  respawn + SET_ARENA re-hydration, pool-scoped breaker accounting
  (one dead worker among living peers never trips it), per-worker
  STATS aggregation.
- INTEGRITY: CRC trailers on wire frames both directions (verified,
  negotiated per frame, legacy interop preserved), CRC-framed disk
  spills (a corrupted-on-disk spill raises retryable DataCorruption
  and re-materializes via the retry machinery, never wrong rows),
  shuffle exchange payload checksums, and the ``corrupt`` fault kind
  the CRC layer must catch.

The in-process worker trick: ``sidecar._handle_conn`` is a plain
function over a socket, so the fast tier serves REAL protocol traffic
from accept-loop threads in this process — full framing, arenas over
SCM_RIGHTS, STATS — without paying a jax child boot per test. Real
subprocess workers run in the slow tier (ci/premerge.sh crash-storm
tier runs them env-armed).
"""

import json
import os
import signal
import socket
import struct
import tempfile
import threading
import time

import numpy as np
import pytest

import spark_rapids_jni_tpu  # noqa: F401
import jax.numpy as jnp

from spark_rapids_jni_tpu import memgov, sidecar, sidecar_pool
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.columnar import dtype as dt
from spark_rapids_jni_tpu.utils import errors, faultinj, integrity, metrics, retry
from spark_rapids_jni_tpu.utils.errors import DataCorruption, RetryableError


def _counter(name):
    return metrics.registry().value(name)


def _scrub_worker_namespace():
    """The in-process worker trick below runs ``_handle_conn`` in THIS
    process, so its always-on request COUNTERS share the registry with
    the ``sidecar.worker.*`` GAUGES other suite files fold remote
    snapshots into — a type clash the two-process deployment can never
    hit. Scrub the namespace both ways (before: earlier folds must not
    break the in-proc worker; after: the in-proc counters must not
    break a later fold under randomized test ordering)."""
    reg = metrics.registry()
    with reg._lock:
        for name in list(reg._metrics):
            if name.startswith("sidecar.worker."):
                del reg._metrics[name]


@pytest.fixture(autouse=True)
def _clean_state():
    faultinj.disable()
    retry.disable()
    retry.reset_stats()
    _scrub_worker_namespace()
    yield
    faultinj.disable()
    retry.disable()
    retry.reset_stats()
    _scrub_worker_namespace()


# ---------------------------------------------------------------------------
# in-process worker: the real protocol loop without a subprocess
# ---------------------------------------------------------------------------


class _InProcWorker:
    """Duck-types the Popen surface SidecarPool supervises, but serves
    ``sidecar._handle_conn`` from threads in THIS process. ``kill()``
    models kill -9: the listener and every live connection drop
    mid-frame, exactly what a client of a SIGKILLed worker observes."""

    def __init__(self):
        self.sock_path = tempfile.mktemp(prefix="srjt-inproc-") + ".sock"
        self.pid = os.getpid()
        self.returncode = None
        self._conns = []
        self._srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self._srv.bind(self.sock_path)
        self._srv.listen(8)
        self._t = threading.Thread(target=self._accept_loop, daemon=True)
        self._t.start()

    def _accept_loop(self):
        while True:
            try:
                conn, _ = self._srv.accept()
            except OSError:
                return  # killed
            self._conns.append(conn)

            def _serve(c=conn):
                try:
                    sidecar._handle_conn(c, "cpu", lambda: None)
                except OSError:
                    pass  # kill() closed the socket under the handler

            threading.Thread(target=_serve, daemon=True).start()

    # Popen surface the pool touches
    def poll(self):
        return self.returncode

    def wait(self, timeout=None):
        return self.returncode if self.returncode is not None else 0

    def terminate(self):
        self.kill()

    def kill(self):
        if self.returncode is None:
            self.returncode = -signal.SIGKILL
        try:
            self._srv.close()
        except OSError:
            pass
        for c in self._conns:
            try:
                c.close()
            except OSError:
                pass
        try:
            os.unlink(self.sock_path)
        except OSError:
            pass


def _inproc_spawn(startup_timeout_s=None, env=None):
    w = _InProcWorker()
    return w, w.sock_path


@pytest.fixture
def inproc_pool():
    pool = sidecar_pool.SidecarPool(
        size=2, deadline_s=20, heartbeat_s=1e9, spawn_fn=_inproc_spawn
    )
    yield pool
    pool.shutdown()


def _groupby_payload(n=600, k=16, seed=3):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, k, n).astype(np.int64)
    vals = rng.standard_normal(n).astype(np.float32)
    return struct.pack("<IQ", k, n) + keys.tobytes() + vals.tobytes()


# ---------------------------------------------------------------------------
# integrity helper unit tests
# ---------------------------------------------------------------------------


class TestIntegrityHelpers:
    def test_checksum_roundtrip_and_mismatch(self):
        data = os.urandom(4096)
        c = integrity.checksum(data)
        integrity.verify(data, c, "unit")  # no raise
        before = _counter("sidecar.integrity.crc_mismatch")
        with pytest.raises(DataCorruption, match="CRC mismatch"):
            integrity.verify(data[:-1] + b"\x00", c, "unit")
        assert _counter("sidecar.integrity.crc_mismatch") == before + 1
        assert _counter("sidecar.integrity.crc_mismatch.unit") >= 1

    def test_disabled_gate_skips_verification(self):
        with integrity.disabled():
            integrity.verify(b"anything", 0xDEAD, "unit")  # silently passes

    def test_corruption_is_retryable(self):
        assert issubclass(DataCorruption, RetryableError)

    def test_pack_unpack(self):
        assert integrity.unpack_crc(integrity.pack_crc(0xDEADBEEF)) == 0xDEADBEEF


# ---------------------------------------------------------------------------
# wire-frame CRC protocol (in-process worker, real SupervisedClient)
# ---------------------------------------------------------------------------


class TestFrameIntegrity:
    def test_crc_framed_request_roundtrip(self):
        w = _InProcWorker()
        try:
            client = sidecar.SupervisedClient(w.sock_path, deadline_s=20, heartbeat_s=1e9)
            with client:
                payload = _groupby_payload()
                before = _counter("sidecar.integrity.frames_checked")
                resp = client.request(sidecar.OP_GROUPBY_SUM_F32, payload)
                assert resp == sidecar._dispatch(
                    sidecar.OP_GROUPBY_SUM_F32, payload, "cpu"
                )
                # both directions verified: worker checked the request,
                # client checked the response
                assert _counter("sidecar.integrity.frames_checked") >= before + 2
        finally:
            w.kill()

    def test_corrupted_request_rejected_by_worker(self):
        """A frame whose trailer doesn't match its payload must answer
        status 1 with the DataCorruption taxonomy prefix — and the
        worker must keep serving."""
        w = _InProcWorker()
        try:
            conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            conn.connect(w.sock_path)
            payload = _groupby_payload()
            bad_crc = integrity.pack_crc(integrity.checksum(payload) ^ 0xFFFF)
            conn.sendall(
                struct.pack(
                    "<IQ", sidecar.OP_GROUPBY_SUM_F32 | sidecar.CRC_FLAG, len(payload)
                )
                + bad_crc
                + payload
            )
            status, rlen = struct.unpack("<IQ", sidecar._recv_exact(conn, 12))
            assert status & sidecar.CRC_FLAG  # the error reply is framed too
            sidecar._recv_exact(conn, 4)  # its trailer
            body = sidecar._recv_exact(conn, rlen)
            assert (status & ~sidecar._FLAG_MASK) == sidecar.STATUS_ERROR
            assert body.startswith(b"DataCorruption:")
            # worker survived: a clean PING round-trips on the same conn
            conn.sendall(struct.pack("<IQ", sidecar.OP_PING, 0))
            status, rlen = struct.unpack("<IQ", sidecar._recv_exact(conn, 12))
            assert status == sidecar.STATUS_OK
            assert sidecar._recv_exact(conn, rlen) == b"cpu"
            conn.close()
        finally:
            w.kill()

    def test_corrupt_fault_caught_by_client_crc(self):
        """The `corrupt` chaos kind flips response bytes after the
        worker checksums: the client's CRC check must convert it into
        DataCorruption — and with the retry orchestrator armed the op
        heals once the fault budget is spent."""
        w = _InProcWorker()
        try:
            client = sidecar.SupervisedClient(w.sock_path, deadline_s=20, heartbeat_s=1e9)
            payload = _groupby_payload()
            want = sidecar._dispatch(sidecar.OP_GROUPBY_SUM_F32, payload, "cpu")
            faultinj.configure(
                {"seed": 11, "faults": {"sidecar.worker.GROUPBY_SUM_F32": {
                    "type": "corrupt", "percent": 100, "interceptionCount": 1}}}
            )
            before = _counter("sidecar.integrity.crc_mismatch")
            with client:
                with pytest.raises(DataCorruption):
                    client.request(sidecar.OP_GROUPBY_SUM_F32, payload)
                assert _counter("sidecar.integrity.crc_mismatch") == before + 1
                # budget spent: the re-fetch returns pristine bytes
                assert client.request(sidecar.OP_GROUPBY_SUM_F32, payload) == want
        finally:
            w.kill()

    def test_corrupt_fault_with_retry_orchestrator_heals(self):
        w = _InProcWorker()
        try:
            client = sidecar.SupervisedClient(w.sock_path, deadline_s=20, heartbeat_s=1e9)
            payload = _groupby_payload()
            want = sidecar._dispatch(sidecar.OP_GROUPBY_SUM_F32, payload, "cpu")
            faultinj.configure(
                {"seed": 11, "faults": {"sidecar.worker.GROUPBY_SUM_F32": {
                    "type": "corrupt", "percent": 100, "interceptionCount": 2}}}
            )
            with client, metrics.enabled(), retry.enabled(
                max_attempts=5, base_delay_ms=1
            ):
                assert client.call(sidecar.OP_GROUPBY_SUM_F32, payload) == want
            assert retry.stats()["retries"] >= 1
            # per-class accounting: corruption retries are visible as
            # their own class (gated counter, hence metrics armed above)
            assert _counter("retry.retries.DataCorruption") >= 1
        finally:
            w.kill()

    def test_integrity_off_is_legacy_framing(self):
        """SRJT_INTEGRITY_CHECKS=0 posture: no CRC flag on the wire,
        no verification — and an injected corruption therefore flows
        through silently (the counterfactual that justifies the
        layer's existence)."""
        w = _InProcWorker()
        try:
            client = sidecar.SupervisedClient(w.sock_path, deadline_s=20, heartbeat_s=1e9)
            payload = _groupby_payload()
            want = sidecar._dispatch(sidecar.OP_GROUPBY_SUM_F32, payload, "cpu")
            with client, integrity.disabled():
                assert client.request(sidecar.OP_GROUPBY_SUM_F32, payload) == want
                faultinj.configure(
                    {"seed": 1, "faults": {"sidecar.worker.GROUPBY_SUM_F32": {
                        "type": "corrupt", "percent": 100, "interceptionCount": 1}}}
                )
                got = client.request(sidecar.OP_GROUPBY_SUM_F32, payload)
                assert got != want  # corruption passed: wrong bytes, no error
        finally:
            w.kill()


# ---------------------------------------------------------------------------
# spill-file CRC (the at-rest half of the integrity layer)
# ---------------------------------------------------------------------------


class TestSpillIntegrity:
    def test_disk_spill_roundtrip_bit_exact(self, tmp_path):
        from spark_rapids_jni_tpu.memgov.catalog import BufferCatalog

        cat = BufferCatalog(spill_dir=str(tmp_path))
        src = np.arange(1000, dtype=np.float64).view(np.uint64)
        h = cat.register("rt", jnp.asarray(src))
        h.spill(to_disk=True)
        assert h.tier == memgov.TIER_DISK
        got = np.asarray(h.get())
        assert got.tobytes() == src.tobytes()
        cat.close()

    def test_corrupted_spill_raises_data_corruption(self, tmp_path):
        from spark_rapids_jni_tpu.memgov.catalog import BufferCatalog

        cat = BufferCatalog(spill_dir=str(tmp_path))
        h = cat.register("bad", jnp.arange(500, dtype=jnp.int64))
        h.spill(to_disk=True)
        path = h._disk_path
        raw = bytearray(open(path, "rb").read())
        raw[len(raw) // 2] ^= 0xFF  # one flipped bit in the payload
        open(path, "wb").write(bytes(raw))
        before = _counter("sidecar.integrity.crc_mismatch")
        with pytest.raises(DataCorruption):
            h.get()
        assert _counter("sidecar.integrity.crc_mismatch") == before + 1
        # the bad copy is retired: the entry is gone, not resident-corrupt
        assert cat.unregister("bad") is False
        cat.close()

    def test_corrupted_spill_rematerializes_via_split_retry(self, tmp_path):
        """The acceptance path: an op whose cached input rotted on disk
        re-computes through the retry/split machinery and lands
        bit-identical — corruption costs a retry, never correctness."""
        from spark_rapids_jni_tpu.memgov.catalog import BufferCatalog

        cat = BufferCatalog(spill_dir=str(tmp_path))
        src = np.arange(256, dtype=np.int64)
        h = cat.register("cache", jnp.asarray(src))
        h.spill(to_disk=True)
        raw = bytearray(open(h._disk_path, "rb").read())
        raw[-3] ^= 0x55
        open(h._disk_path, "wb").write(bytes(raw))

        fetches = {"cached": 0, "recomputed": 0}

        def fetch(batch):
            try:
                out = h.get()  # first attempt: DataCorruption (counted)
                fetches["cached"] += 1
                return out
            except ValueError:
                # entry retired by the corruption: re-materialize from
                # source — what a real op does when its cache is gone
                fetches["recomputed"] += 1
                return jnp.asarray(np.asarray(batch))

        with retry.enabled(max_attempts=4, base_delay_ms=1):
            out = retry.retry_with_split(
                fetch, src, split=lambda b: (b[: len(b) // 2], b[len(b) // 2 :]),
                combine=lambda parts: np.concatenate(parts), op_name="spill_refetch",
            )
        assert np.asarray(out).tobytes() == src.tobytes()
        assert fetches == {"cached": 0, "recomputed": 1}
        assert retry.stats()["retries"] >= 1
        cat.close()

    def test_spill_crc_cost_is_spill_path_only(self, tmp_path):
        """Host-tier spills (the common demotion) never touch the CRC
        machinery — only the disk tier frames."""
        from spark_rapids_jni_tpu.memgov.catalog import BufferCatalog

        cat = BufferCatalog(spill_dir=str(tmp_path))
        before = _counter("sidecar.integrity.spills_checked")
        h = cat.register("host_only", jnp.arange(64, dtype=jnp.int32))
        h.spill(to_disk=False)
        assert np.array_equal(np.asarray(h.get()), np.arange(64))
        assert _counter("sidecar.integrity.spills_checked") == before
        cat.close()


# ---------------------------------------------------------------------------
# shuffle exchange payload checksum
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def mesh8():
    import jax

    from spark_rapids_jni_tpu.parallel import mesh as mesh_mod

    assert len(jax.devices()) == 8, "conftest must force the 8-device CPU mesh"
    return mesh_mod.make_mesh({"data": 8})


class TestExchangeIntegrity:
    def _arrays(self):
        rng = np.random.default_rng(5)
        n = 8 * 32
        vals = jnp.asarray(rng.integers(-1000, 1000, n).astype(np.int64))
        dest = jnp.asarray((rng.integers(0, 8, n)).astype(np.int32))
        return [vals], dest

    def test_clean_exchange_passes_checksum(self, mesh8):
        from spark_rapids_jni_tpu.parallel import shuffle

        arrays, dest = self._arrays()
        before = _counter("sidecar.integrity.exchanges_checked")
        received, mask, overflow = shuffle.all_to_all_exchange(
            arrays, dest, mesh8, capacity=None
        )
        assert not bool(np.asarray(overflow).any())
        assert _counter("sidecar.integrity.exchanges_checked") == before + 1

    def test_tampered_exchange_raises_data_corruption(self, mesh8, monkeypatch):
        from spark_rapids_jni_tpu.parallel import shuffle

        real = shuffle._exchange_once

        def tampered(arrays, dest, mesh, axis, capacity, n_parts):
            received, mask, overflow = real(arrays, dest, mesh, axis, capacity, n_parts)
            flipped = [r.at[0].set(r[0] + 1) for r in received]  # one lane off
            return flipped, mask, overflow

        monkeypatch.setattr(shuffle, "_exchange_once", tampered)
        arrays, dest = self._arrays()
        before = _counter("sidecar.integrity.crc_mismatch")
        with pytest.raises(DataCorruption, match="shuffle.exchange"):
            shuffle.all_to_all_exchange(arrays, dest, mesh8, capacity=None)
        assert _counter("sidecar.integrity.crc_mismatch") == before + 1

    def test_integrity_off_skips_exchange_checksum(self, mesh8):
        from spark_rapids_jni_tpu.parallel import shuffle

        arrays, dest = self._arrays()
        before = _counter("sidecar.integrity.exchanges_checked")
        with integrity.disabled():
            shuffle.all_to_all_exchange(arrays, dest, mesh8, capacity=None)
        assert _counter("sidecar.integrity.exchanges_checked") == before


# ---------------------------------------------------------------------------
# faultinj: the new kinds' config surface + scheduling
# ---------------------------------------------------------------------------


class TestFaultKinds:
    def test_crash_and_corrupt_parse(self):
        faultinj.configure(
            {"faults": {
                "a": {"type": "crash", "percent": 50, "after": 2},
                "b": {"type": "corrupt", "percent": 100, "ramp": 3},
            }}
        )
        assert faultinj.is_enabled()

    def test_unknown_kind_still_rejected(self):
        with pytest.raises(ValueError, match="unknown fault type"):
            faultinj.configure({"faults": {"x": {"type": "meltdown"}}})

    def test_corrupt_budget_and_after_scheduling(self):
        faultinj.configure(
            {"seed": 9, "faults": {"x": {"type": "corrupt", "percent": 100,
                                          "after": 2, "interceptionCount": 1}}}
        )
        data = bytes(64)
        assert faultinj.maybe_corrupt("x", data) == data  # after: held
        assert faultinj.maybe_corrupt("x", data) == data  # after: held
        assert faultinj.maybe_corrupt("x", data) != data  # armed, budget 1
        assert faultinj.maybe_corrupt("x", data) == data  # budget spent

    def test_corrupt_rule_inert_under_maybe_inject(self):
        faultinj.configure(
            {"faults": {"x": {"type": "corrupt", "percent": 100,
                               "interceptionCount": 1}}}
        )
        faultinj.maybe_inject("x")  # must not raise, burn budget, or kill
        data = bytes(16)
        assert faultinj.maybe_corrupt("x", data) != data  # budget intact

    def test_inject_rule_inert_under_maybe_corrupt(self):
        faultinj.configure(
            {"faults": {"x": {"type": "retryable", "percent": 100,
                               "interceptionCount": 1}}}
        )
        data = bytes(16)
        assert faultinj.maybe_corrupt("x", data) == data  # wrong family
        with pytest.raises(RetryableError):
            faultinj.maybe_inject("x")  # budget intact for its own family


# ---------------------------------------------------------------------------
# SET_ARENA re-registration: gauges stay flat across re-uploads
# ---------------------------------------------------------------------------


def _send_set_arena(conn, size):
    import array

    fd = os.memfd_create("rereg-arena")
    os.ftruncate(fd, size)
    hdr = struct.pack("<IQ", sidecar.OP_SET_ARENA, 8) + struct.pack("<Q", size)
    conn.sendmsg(
        [hdr],
        [(socket.SOL_SOCKET, socket.SCM_RIGHTS, array.array("i", [fd]).tobytes())],
    )
    os.close(fd)
    status, rlen = struct.unpack("<IQ", sidecar._recv_exact(conn, 12))
    if rlen:
        sidecar._recv_exact(conn, rlen)
    assert (status & ~sidecar._FLAG_MASK) == sidecar.STATUS_OK


def test_set_arena_reregistration_keeps_gauges_flat():
    """ISSUE 5 satellite: a second SET_ARENA on the same connection
    REPLACES the catalog entry (unregister-then-register) — the
    memgov.arena* gauges must track exactly one arena at the latest
    size, never accumulate."""
    w = _InProcWorker()
    try:
        base = memgov.catalog().snapshot()
        conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        conn.connect(w.sock_path)
        _send_set_arena(conn, 1 << 16)
        snap1 = memgov.catalog().snapshot()
        assert snap1["arenas"] == base["arenas"] + 1
        assert snap1["arena_bytes"] == base["arena_bytes"] + (1 << 16)
        for size in (1 << 18, 1 << 16, 1 << 20):
            _send_set_arena(conn, size)
            snap = memgov.catalog().snapshot()
            assert snap["arenas"] == base["arenas"] + 1, "arena entry leaked"
            assert snap["arena_bytes"] == base["arena_bytes"] + size
        conn.close()
        time.sleep(0.2)  # the conn handler's finally unregisters
        snap_end = memgov.catalog().snapshot()
        assert snap_end["arenas"] == base["arenas"]
        assert snap_end["arena_bytes"] == base["arena_bytes"]
    finally:
        w.kill()


# ---------------------------------------------------------------------------
# pool: routing, failover, respawn, re-hydration (in-process tier)
# ---------------------------------------------------------------------------


class TestPoolFailover:
    def test_round_robin_routing(self, inproc_pool):
        payload = _groupby_payload()
        want = sidecar._dispatch(sidecar.OP_GROUPBY_SUM_F32, payload, "cpu")
        with retry.enabled(max_attempts=4, base_delay_ms=1):
            for _ in range(4):
                assert inproc_pool.call(sidecar.OP_GROUPBY_SUM_F32, payload) == want
        # both workers served traffic
        stats = inproc_pool.worker_stats(fold=False)
        assert set(stats) == {"w0", "w1"}

    def test_kill_one_worker_exactly_one_failover_zero_breaker_trips(
        self, inproc_pool
    ):
        payload = _groupby_payload()
        want = sidecar._dispatch(sidecar.OP_GROUPBY_SUM_F32, payload, "cpu")
        failovers0 = _counter("sidecar.pool.failovers")
        opened0 = _counter("sidecar.breaker.opened_total")
        fallbacks0 = _counter("sidecar.pool.host_fallbacks")
        # kill the worker the router will pick NEXT: the very next call
        # must fail over mid-flight
        victim = inproc_pool._workers[inproc_pool._rr % inproc_pool.size]
        victim.proc.kill()
        with retry.enabled(max_attempts=6, base_delay_ms=1):
            for _ in range(4):
                assert inproc_pool.call(sidecar.OP_GROUPBY_SUM_F32, payload) == want
        assert _counter("sidecar.pool.failovers") == failovers0 + 1
        assert _counter("sidecar.breaker.opened_total") == opened0
        assert _counter("sidecar.pool.host_fallbacks") == fallbacks0
        assert inproc_pool.wait_healthy(20), "respawn did not complete"

    def test_whole_pool_dark_degrades_to_host_and_counts_breaker(self):
        pool = sidecar_pool.SidecarPool(
            size=2, deadline_s=5, heartbeat_s=1e9, spawn_fn=_inproc_spawn
        )
        try:
            payload = _groupby_payload()
            want = sidecar._dispatch(sidecar.OP_GROUPBY_SUM_F32, payload, "cpu")
            fallbacks0 = _counter("sidecar.pool.host_fallbacks")
            # stop the respawner from resurrecting anyone, then kill all
            pool._respawn_max = 0
            for w in pool._workers:
                w.proc.kill()
            with retry.enabled(max_attempts=3, base_delay_ms=1):
                got = pool.call(sidecar.OP_GROUPBY_SUM_F32, payload)
            assert got == want  # results keep flowing: host engine floor
            assert _counter("sidecar.pool.host_fallbacks") == fallbacks0 + 1
        finally:
            pool.shutdown()
            # scrub breaker state for later tests
            sidecar.breaker().reset()

    def test_arena_rehydration_on_respawn(self, inproc_pool):
        payload = _groupby_payload()
        want = sidecar._dispatch(sidecar.OP_GROUPBY_SUM_F32, payload, "cpu")
        inproc_pool.set_arena(1 << 20)
        rehydr0 = _counter("sidecar.pool.rehydrations")
        with retry.enabled(max_attempts=6, base_delay_ms=1):
            assert inproc_pool.call_arena(
                sidecar.OP_GROUPBY_SUM_F32, payload
            ) == want
            victim = inproc_pool._workers[inproc_pool._rr % inproc_pool.size]
            victim.proc.kill()
            # the region is scratch (the response replaces the request
            # payload): the POOL's per-call snapshot replays the request
            # bytes under a fresh generation across failover attempts
            assert inproc_pool.call_arena(
                sidecar.OP_GROUPBY_SUM_F32, payload
            ) == want
        assert inproc_pool.wait_healthy(20)
        assert _counter("sidecar.pool.rehydrations") == rehydr0 + 1
        # the respawned worker serves region traffic (slab re-uploaded)
        with retry.enabled(max_attempts=6, base_delay_ms=1):
            for _ in range(2):
                assert inproc_pool.call_arena(
                    sidecar.OP_GROUPBY_SUM_F32, payload
                ) == want

    def test_stream_ops_work_after_slab_arena(self, inproc_pool):
        """Slab-mode connections never answer STREAM ops through the
        arena (that opportunism is what serialized the whole pool):
        stream requests keep streaming after the slab exists, and the
        responses arrive promptly on the socket."""
        payload = _groupby_payload()
        want = sidecar._dispatch(sidecar.OP_GROUPBY_SUM_F32, payload, "cpu")
        inproc_pool.ensure_slab()
        t0 = time.monotonic()
        with retry.enabled(max_attempts=4, base_delay_ms=1):
            for _ in range(3):
                assert inproc_pool.call(sidecar.OP_GROUPBY_SUM_F32, payload) == want
        assert time.monotonic() - t0 < 5, "stream op stalled after slab upload"

    def test_arena_survives_client_reconnect(self, inproc_pool):
        """Worker-side arena state is per-connection: a client redial
        (timeout, desync close) silently drops it, so the pool must
        replay SET_ARENA on the fresh connection — a region op after a
        reconnect stays on the device path, never a host fallback."""
        payload = _groupby_payload()
        want = sidecar._dispatch(sidecar.OP_GROUPBY_SUM_F32, payload, "cpu")
        inproc_pool.ensure_slab()
        rehydr0 = _counter("sidecar.pool.rehydrations")
        fallbacks0 = _counter("sidecar.pool.host_fallbacks")
        with retry.enabled(max_attempts=4, base_delay_ms=1):
            assert inproc_pool.call_arena(
                sidecar.OP_GROUPBY_SUM_F32, payload
            ) == want
            # force redials on every slot WITHOUT killing any worker
            for w in inproc_pool._workers:
                w.client.close()
            assert inproc_pool.call_arena(
                sidecar.OP_GROUPBY_SUM_F32, payload
            ) == want
        assert _counter("sidecar.pool.rehydrations") == rehydr0 + 1
        assert _counter("sidecar.pool.host_fallbacks") == fallbacks0
        assert inproc_pool.live_count() == 2  # nobody was declared dead

    def test_oversized_region_write_is_retryable_with_needed_size(
        self, inproc_pool
    ):
        """ISSUE 6 satellite: a request larger than its leased region
        raises RetryableError carrying the needed size (and the
        RESOURCE_EXHAUSTED marker retry-with-split keys on) — never a
        silent truncated write."""
        region = inproc_pool.lease(64)
        try:
            with pytest.raises(RetryableError, match="RESOURCE_EXHAUSTED") as ei:
                region.write(b"x" * (region.capacity + 1))
            assert str(region.capacity + 1) in str(ei.value)  # needed size
            assert retry.is_resource_exhausted(ei.value)  # split engages
        finally:
            region.release()

    def test_legacy_arena_len_overflow_is_retryable(self):
        """The SupervisedClient legacy single-buffer path enforces the
        same contract: arena_len beyond the mapped arena raises
        retryably with the needed size instead of ValueError."""
        import mmap as mmap_mod

        client = sidecar.SupervisedClient("/nonexistent.sock", deadline_s=1)
        client.arena_mm = mmap_mod.mmap(-1, 4096)
        try:
            with pytest.raises(RetryableError, match="RESOURCE_EXHAUSTED"):
                client._raw_request(sidecar.OP_PING, b"", arena_len=8192)
        finally:
            client.arena_mm.close()
            client.arena_mm = None

    def test_shutdown_joins_inflight_respawn_and_reaps(self):
        """shutdown() during an in-flight respawn must JOIN the
        respawner so the worker it was mid-spawning is reaped, not
        orphaned — a daemon thread killed at interpreter exit inside
        spawn_fn leaks a live child that outlives the pool (observed as
        stray sidecar processes holding the parent's stdio pipes)."""
        entered = threading.Event()
        release = threading.Event()
        spawned = []

        def spawn_fn(startup_timeout_s=None, env=None):
            if len(spawned) >= 2:  # a RESPAWN, not an initial spawn
                entered.set()
                release.wait(20)
            w = _InProcWorker()
            spawned.append(w)
            return w, w.sock_path

        pool = sidecar_pool.SidecarPool(
            size=2, deadline_s=5, heartbeat_s=1e9, spawn_fn=spawn_fn
        )
        try:
            victim = pool._workers[0]
            victim.proc.kill()
            pool._on_worker_failure(victim, RetryableError("Socket closed"))
            t = victim.respawn_thread
            assert t is not None
            # shutdown must catch the respawner INSIDE spawn_fn — the
            # leak window this test exists for
            assert entered.wait(10), "respawner never reached spawn_fn"
            # unblock the spawner just after shutdown starts waiting
            threading.Timer(0.2, release.set).start()
            pool.shutdown()
            assert not t.is_alive(), "shutdown returned with respawner live"
            assert len(spawned) == 3
            assert spawned[-1].returncode is not None, (
                "respawned-during-shutdown worker was leaked, not reaped"
            )
        finally:
            release.set()
            pool.shutdown()

    def test_pool_size_env_default(self, monkeypatch):
        monkeypatch.delenv("SRJT_SIDECAR_POOL_SIZE", raising=False)
        pool = sidecar_pool.SidecarPool(spawn_fn=_inproc_spawn)
        try:
            assert pool.size == 1  # today's behavior
        finally:
            pool.shutdown()
        monkeypatch.setenv("SRJT_SIDECAR_POOL_SIZE", "3")
        pool = sidecar_pool.SidecarPool(spawn_fn=_inproc_spawn)
        try:
            assert pool.size == 3
        finally:
            pool.shutdown()


# ---------------------------------------------------------------------------
# STATS aggregation across the pool
# ---------------------------------------------------------------------------


class TestPoolStats:
    def test_worker_stats_keyed_per_worker_and_folded(self, inproc_pool):
        payload = _groupby_payload()
        with retry.enabled(max_attempts=4, base_delay_ms=1):
            for _ in range(2):
                inproc_pool.call(sidecar.OP_GROUPBY_SUM_F32, payload)
        stats = inproc_pool.worker_stats(fold=True)
        assert set(stats) == {"w0", "w1"}
        for s in stats.values():
            assert s["backend"] == "cpu"
            assert "snapshot" in s
        snap = metrics.snapshot()["gauges"]
        # clean per-worker keying: the base sidecar.worker. namespace is
        # stripped before the w<id> prefix — never a stuttered
        # sidecar.worker.w0.sidecar.worker.requests.PING
        assert "sidecar.worker.w0.requests.GROUPBY_SUM_F32" in snap
        assert "sidecar.worker.w1.requests.GROUPBY_SUM_F32" in snap
        assert not any("sidecar.worker.w0.sidecar.worker." in k for k in snap)

    def test_runtime_device_stats_merges_pool_workers(self):
        from spark_rapids_jni_tpu import runtime

        pool = sidecar_pool.connect_pool(
            size=2, deadline_s=20, heartbeat_s=1e9, spawn_fn=_inproc_spawn
        )
        try:
            stats = runtime.device_stats(fold=True)
            assert stats is not None
            assert set(stats["pool_workers"]) == {"w0", "w1"}
        finally:
            sidecar_pool.shutdown_pool()
        assert sidecar_pool.current_pool() is None

    def test_stats_report_has_pool_and_integrity_sections(self, inproc_pool):
        from spark_rapids_jni_tpu import runtime

        rep = runtime.stats_report()
        assert "integrity" in rep and "crc_mismatch" in rep["integrity"]
        assert "pool" in rep  # None without a GLOBAL pool: key present
        srep = metrics.stage_report("x")
        assert "failovers" in srep["pool"]
        assert "crc_mismatch" in srep["integrity"]
        assert json.dumps(rep["integrity"])  # JSON-clean

    def test_pool_snapshot_shape(self, inproc_pool):
        snap = inproc_pool.snapshot()
        assert snap["size"] == 2 and snap["live"] == 2
        assert set(snap["workers"]) == {"w0", "w1"}
        assert json.dumps(snap)  # JSON-clean


# ---------------------------------------------------------------------------
# real subprocess workers: kill -9 + chaos storm (slow tier; premerge
# runs these env-armed in the crash-storm tier)
# ---------------------------------------------------------------------------


def _roundtrip_table_through_pool(pool, table):
    """Ship ``table`` through the pool's device row-conversion pair
    (CONVERT_TO_ROWS -> CONVERT_FROM_ROWS) and rebuild it — the
    mid-query device traffic the failover must carry."""
    payload = sidecar._write_table(table)
    resp = pool.call(sidecar.OP_CONVERT_TO_ROWS, payload)
    (nbatches,) = struct.unpack_from("<I", resp, 0)
    assert nbatches == 1
    pos = 4
    (nrows,) = struct.unpack_from("<Q", resp, pos)
    pos += 8
    offs = resp[pos : pos + 4 * (nrows + 1)]
    pos += 4 * (nrows + 1)
    (blen,) = struct.unpack_from("<Q", resp, pos)
    pos += 8
    blob = resp[pos : pos + blen]
    dtypes = list(table.dtypes())
    req = (
        struct.pack("<I", len(dtypes))
        + np.asarray([int(d.id) for d in dtypes], np.int32).tobytes()
        + np.asarray([getattr(d, "scale", 0) or 0 for d in dtypes], np.int32).tobytes()
        + struct.pack("<Q", nrows)
        + offs
        + struct.pack("<Q", blen)
        + blob
    )
    out = pool.call(sidecar.OP_CONVERT_FROM_ROWS, req)
    rebuilt = sidecar._read_table(out)
    return Table(rebuilt.columns, list(table.names))


class TestRealWorkerPool:
    def test_q1_bit_identical_through_kill9_failover(self):
        """The acceptance scenario: TPC-H q1's device traffic rides a
        pool of 2 REAL workers; one is kill -9'd mid-query. The query
        result must be bit-identical to the host oracle, with exactly
        one failover and zero breaker trips."""
        from spark_rapids_jni_tpu.models.tpch import gen_lineitem, q1

        lineitem = gen_lineitem(300, seed=7)
        oracle = q1(lineitem)
        want = [np.asarray(c.data).tobytes() for c in oracle.columns]

        failovers0 = _counter("sidecar.pool.failovers")
        opened0 = _counter("sidecar.breaker.opened_total")
        pool = sidecar_pool.SidecarPool(
            size=2, deadline_s=60, heartbeat_s=1e9, startup_timeout_s=180
        )
        try:
            with retry.enabled(max_attempts=6, base_delay_ms=1):
                # warm pass, no faults: the device path round-trips
                warm = _roundtrip_table_through_pool(pool, lineitem)
                # kill the worker the router picks next, MID-QUERY
                victim = pool._workers[pool._rr % pool.size]
                os.kill(victim.proc.pid, signal.SIGKILL)
                cold = _roundtrip_table_through_pool(pool, lineitem)
            for t in (warm, cold):
                got = [np.asarray(c.data).tobytes() for c in q1(t).columns]
                assert got == want, "q1 diverged from the host oracle"
            assert _counter("sidecar.pool.failovers") == failovers0 + 1
            assert _counter("sidecar.breaker.opened_total") == opened0
            assert pool.wait_healthy(180), "kill -9 victim was not respawned"
        finally:
            pool.shutdown()

    def test_crash_and_corrupt_storm_survives(self):
        """ci/chaos_crash.json armed inside REAL workers: `crash` SIGKILLs
        a worker mid-op, `corrupt` flips response bytes under the CRC.
        Every op must land exact (failover / re-fetch / host floor), with
        the storm visibly caught in the metrics. ONE source of truth: the
        workers load the same profile ci/premerge.sh documents, so the
        committed file and the gate cannot drift (the test_chaos pattern)."""
        cfg = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "ci", "chaos_crash.json",
        )
        deaths0 = _counter("sidecar.pool.worker_deaths")
        mismatch0 = _counter("sidecar.integrity.crc_mismatch")
        pool = sidecar_pool.SidecarPool(
            size=2, deadline_s=60, heartbeat_s=1e9, startup_timeout_s=180,
            env={"SRJT_FAULTINJ_CONFIG": cfg},
        )
        try:
            payload = _groupby_payload()
            want_g = sidecar._dispatch(sidecar.OP_GROUPBY_SUM_F32, payload, "cpu")
            tbl = Table(
                [Column(dt.INT32, data=jnp.arange(128, dtype=jnp.int32))], ["a"]
            )
            tp = sidecar._write_table(tbl)
            want_c = sidecar._dispatch(sidecar.OP_CONVERT_TO_ROWS, tp, "cpu")
            with retry.enabled(max_attempts=8, base_delay_ms=1):
                for _ in range(4):
                    assert pool.call(sidecar.OP_CONVERT_TO_ROWS, tp) == want_c
                for _ in range(3):
                    assert pool.call(sidecar.OP_GROUPBY_SUM_F32, payload) == want_g
            # the storm actually fired AND was contained
            assert _counter("sidecar.pool.worker_deaths") > deaths0
            assert _counter("sidecar.integrity.crc_mismatch") > mismatch0
        finally:
            pool.shutdown()
            sidecar.breaker().reset()


# ---------------------------------------------------------------------------
# one owner of the chip (ISSUE 22)
# ---------------------------------------------------------------------------


class TestOneChipOwner:
    """A parent that has initialised the TPU backend holds the chip; a
    pool whose workers need it must refuse to start, before any spawn."""

    @pytest.fixture
    def holds_tpu(self, monkeypatch):
        from jax._src import xla_bridge

        monkeypatch.setattr(xla_bridge, "_backends",
                            {**xla_bridge._backends, "tpu": object()})

    @pytest.mark.parametrize("inherited,override", [
        (None, None),              # no JAX_PLATFORMS anywhere: worker takes the chip
        ("tpu,cpu", None),         # what the chip machine exports
        ("cpu", {"JAX_PLATFORMS": "tpu"}),
    ])
    def test_pool_refuses_when_parent_holds_the_chip(
        self, monkeypatch, holds_tpu, inherited, override
    ):
        if inherited is None:
            monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        else:
            monkeypatch.setenv("JAX_PLATFORMS", inherited)
        spawned = []

        def spawn(**kw):
            spawned.append(kw)
            return _inproc_spawn()

        with pytest.raises(errors.FatalDeviceError, match="holds the chip"):
            sidecar_pool.SidecarPool(size=1, spawn_fn=spawn, env=override)
        assert not spawned  # refused before any worker was started

    @pytest.mark.parametrize("inherited,override", [
        ("cpu", None),
        ("tpu,cpu", {"JAX_PLATFORMS": "cpu"}),  # workers pinned to the CPU
    ])
    def test_cpu_workers_are_no_conflict(self, monkeypatch, holds_tpu, inherited, override):
        monkeypatch.setenv("JAX_PLATFORMS", inherited)
        with sidecar_pool.SidecarPool(size=1, spawn_fn=_inproc_spawn, env=override) as pool:
            assert pool.live_count() == 1

    def test_parent_off_the_chip_starts_the_pool(self, monkeypatch):
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)  # the conftest pin is on the live config
        with sidecar_pool.SidecarPool(size=1, spawn_fn=_inproc_spawn) as pool:
            assert pool.live_count() == 1
