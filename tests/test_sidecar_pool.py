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

from _inproc import InProcWorker, groupby_payload, inproc_spawn


def _counter(name):
    return metrics.registry().value(name)


pytestmark = pytest.mark.usefixtures("clean_state")


@pytest.fixture
def inproc_pool():
    pool = sidecar_pool.SidecarPool(
        size=2, deadline_s=20, heartbeat_s=1e9, spawn_fn=inproc_spawn
    )
    yield pool
    pool.shutdown()


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
        w = InProcWorker()
        try:
            client = sidecar.SupervisedClient(w.sock_path, deadline_s=20, heartbeat_s=1e9)
            with client:
                payload = groupby_payload()
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
        w = InProcWorker()
        try:
            conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            conn.connect(w.sock_path)
            payload = groupby_payload()
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
        w = InProcWorker()
        try:
            client = sidecar.SupervisedClient(w.sock_path, deadline_s=20, heartbeat_s=1e9)
            payload = groupby_payload()
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
        w = InProcWorker()
        try:
            client = sidecar.SupervisedClient(w.sock_path, deadline_s=20, heartbeat_s=1e9)
            payload = groupby_payload()
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
        w = InProcWorker()
        try:
            client = sidecar.SupervisedClient(w.sock_path, deadline_s=20, heartbeat_s=1e9)
            payload = groupby_payload()
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
    w = InProcWorker()
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
        payload = groupby_payload()
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
        payload = groupby_payload()
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
            size=2, deadline_s=5, heartbeat_s=1e9, spawn_fn=inproc_spawn
        )
        try:
            payload = groupby_payload()
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
        payload = groupby_payload()
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
        payload = groupby_payload()
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
        payload = groupby_payload()
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
            w = InProcWorker()
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
        pool = sidecar_pool.SidecarPool(spawn_fn=inproc_spawn)
        try:
            assert pool.size == 1  # today's behavior
        finally:
            pool.shutdown()
        monkeypatch.setenv("SRJT_SIDECAR_POOL_SIZE", "3")
        pool = sidecar_pool.SidecarPool(spawn_fn=inproc_spawn)
        try:
            assert pool.size == 3
        finally:
            pool.shutdown()


# ---------------------------------------------------------------------------
# STATS aggregation across the pool
# ---------------------------------------------------------------------------


class TestPoolStats:
    def test_worker_stats_keyed_per_worker_and_folded(self, inproc_pool):
        payload = groupby_payload()
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
            size=2, deadline_s=20, heartbeat_s=1e9, spawn_fn=inproc_spawn
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
    payload = sidecar.as_bytes(sidecar._write_table(table))
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
            payload = groupby_payload()
            want_g = sidecar._dispatch(sidecar.OP_GROUPBY_SUM_F32, payload, "cpu")
            tbl = Table(
                [Column(dt.INT32, data=jnp.arange(128, dtype=jnp.int32))], ["a"]
            )
            tp = sidecar.as_bytes(sidecar._write_table(tbl))
            want_c = sidecar.as_bytes(sidecar._dispatch(sidecar.OP_CONVERT_TO_ROWS, tp, "cpu"))
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
            return inproc_spawn()

        with pytest.raises(errors.FatalDeviceError, match="holds the chip"):
            sidecar_pool.SidecarPool(size=1, spawn_fn=spawn, env=override)
        assert not spawned  # refused before any worker was started

    @pytest.mark.parametrize("inherited,override", [
        ("cpu", None),
        ("tpu,cpu", {"JAX_PLATFORMS": "cpu"}),  # workers pinned to the CPU
    ])
    def test_cpu_workers_are_no_conflict(self, monkeypatch, holds_tpu, inherited, override):
        monkeypatch.setenv("JAX_PLATFORMS", inherited)
        with sidecar_pool.SidecarPool(size=1, spawn_fn=inproc_spawn, env=override) as pool:
            assert pool.live_count() == 1

    def test_parent_off_the_chip_starts_the_pool(self, monkeypatch):
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)  # the conftest pin is on the live config
        with sidecar_pool.SidecarPool(size=1, spawn_fn=inproc_spawn) as pool:
            assert pool.live_count() == 1


# ---------------------------------------------------------------------------
# ISSUE 27: a kept request buffer, a gathered reply
# ---------------------------------------------------------------------------


def _ref_write_table(table) -> bytes:
    """The unframed table encoding as PR 26 had it (``tobytes`` and one
    ``join``): the plain reference the gathered reply must equal."""
    out = [struct.pack("<I", len(table.columns))]
    for col in table.columns:
        d = col.dtype
        out.append(struct.pack("<ii", int(d.id.value), int(d.scale)))
        out.append(struct.pack("<Q", len(col)))
        if col.validity is not None:
            out.append(b"\x01")
            out.append(np.asarray(col.validity, np.uint8).tobytes())
        else:
            out.append(b"\x00")
        if d.id in (dt.TypeId.STRING, dt.TypeId.LIST):
            out.append(np.asarray(col.offsets, np.int32).tobytes())
            raw = (
                np.asarray(col.chars, np.uint8)
                if d.id == dt.TypeId.STRING
                else np.asarray(col.child.data).view(np.uint8)
            )
        else:
            raw = np.asarray(col.data)
        out.append(struct.pack("<Q", raw.nbytes))
        out.append(raw.tobytes())
    return b"".join(out)


def _ref_rows_reply(batches) -> bytes:
    """CONVERT_TO_ROWS's reply, joined: the same plain reference."""
    out = [struct.pack("<I", len(batches))]
    for col in batches:
        blob = np.asarray(col.child.data).view(np.uint8)
        out.append(struct.pack("<Q", len(col)))
        out.append(np.asarray(col.offsets, np.int32).tobytes())
        out.append(struct.pack("<Q", blob.size))
        out.append(blob.tobytes())
    return b"".join(out)


def _from_rows_request(rows, dtypes) -> bytes:
    blob = np.asarray(rows.child.data).view(np.uint8)
    return (
        struct.pack("<I", len(dtypes))
        + np.asarray([int(d.id.value) for d in dtypes], np.int32).tobytes()
        + np.asarray([int(d.scale) for d in dtypes], np.int32).tobytes()
        + struct.pack("<Q", len(rows))
        + np.asarray(rows.offsets, np.int32).tobytes()
        + struct.pack("<Q", blob.size)
        + blob.tobytes()
    )


def _wire_tables(n=97):
    rng = np.random.default_rng(27)
    i32 = Column(dt.INT32, data=jnp.asarray(rng.integers(-999, 999, n), jnp.int32))
    i64 = Column(dt.INT64, data=jnp.asarray(rng.integers(-(2**40), 2**40, n), jnp.int64))
    return {
        "fixed_width": Table([i32, i64]),
        "string": Table([
            Column.from_pylist([f"s{i % 13}" * (i % 4) for i in range(n)], dt.STRING),
            i32,
        ]),
        "validity": Table([
            Column(dt.INT32, data=i32.data, validity=jnp.asarray(rng.random(n) > 0.3)),
            i64,
        ]),
    }


def _wire_case(reply_kind):
    """(op, request bytes, the reply the plain reference encoding gives)."""
    from spark_rapids_jni_tpu.ops.row_conversion import (
        convert_from_rows,
        convert_to_rows,
    )

    if reply_kind == "to_rows":
        table = _wire_tables()["validity"]
        return (
            sidecar.OP_CONVERT_TO_ROWS,
            _ref_write_table(table),
            _ref_rows_reply(convert_to_rows(table)),
        )
    table = _wire_tables()[reply_kind]
    dtypes = list(table.dtypes())
    (rows,) = convert_to_rows(table)
    return (
        sidecar.OP_CONVERT_FROM_ROWS,
        _from_rows_request(rows, dtypes),
        _ref_write_table(convert_from_rows(rows, dtypes)),
    )


class _Wire:
    """One raw connection to an in-process worker, speaking one of the
    three transports at the level of frames: ``exchange`` returns the
    reply's status, CRC trailer and body bytes exactly as they crossed."""

    SIZE = 1 << 20
    OFF = 4096  # where the slab transport puts its one region

    def __init__(self, worker, transport):
        import mmap

        self.transport = transport
        self.conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self.conn.connect(worker.sock_path)
        self.mm = None
        self.generation = 0
        if transport != "stream":
            import array

            fd = os.memfd_create("issue27-arena")
            os.ftruncate(fd, self.SIZE)
            self.mm = mmap.mmap(fd, self.SIZE)
            body = struct.pack("<Q", self.SIZE)
            if transport == "region":
                body += struct.pack("<Q", sidecar.ARENA_MODE_SLAB)
            self.conn.sendmsg(
                [struct.pack("<IQ", sidecar.OP_SET_ARENA, len(body)) + body],
                [(socket.SOL_SOCKET, socket.SCM_RIGHTS,
                  array.array("i", [fd]).tobytes())],
            )
            os.close(fd)
            status, rlen = struct.unpack("<IQ", sidecar._recv_exact(self.conn, 12))
            assert (status, rlen) == (sidecar.STATUS_OK, 0)

    def exchange(self, op, payload):
        crc = integrity.pack_crc(integrity.checksum(payload))
        wire_op = op | sidecar.CRC_FLAG
        if self.transport == "stream":
            self.conn.sendall(struct.pack("<IQ", wire_op, len(payload)) + crc + payload)
        elif self.transport == "arena":
            self.mm[: len(payload)] = payload
            self.conn.sendall(
                struct.pack("<IQ", wire_op | sidecar.ARENA_FLAG, len(payload)) + crc
            )
        else:
            self.generation += 1
            cap = self.SIZE - self.OFF - sidecar.REGION_HDR_LEN
            sidecar.REGION_HDR.pack_into(
                self.mm, self.OFF, sidecar.REGION_MAGIC, self.generation, 7, cap,
                len(payload),
            )
            at = self.OFF + sidecar.REGION_HDR_LEN
            self.mm[at : at + len(payload)] = payload
            desc = sidecar.REGION_DESC.pack(self.OFF, 7, self.generation)
            self.conn.sendall(
                struct.pack("<IQ", wire_op | sidecar.ARENA_FLAG, len(desc)) + crc + desc
            )
        status, rlen = struct.unpack("<IQ", sidecar._recv_exact(self.conn, 12))
        assert status & sidecar.CRC_FLAG
        trailer = sidecar._recv_exact(self.conn, 4)
        if self.transport == "stream":
            assert not status & sidecar.ARENA_FLAG
            body = sidecar._recv_exact(self.conn, rlen) if rlen else b""
        else:
            assert status & sidecar.ARENA_FLAG, "the reply did not ride the arena"
            at = 0 if self.transport == "arena" else self.OFF + sidecar.REGION_HDR_LEN
            body = bytes(self.mm[at : at + rlen])
        return status & ~sidecar._FLAG_MASK, trailer, body

    def ping(self):
        self.conn.sendall(struct.pack("<IQ", sidecar.OP_PING, 0))
        status, rlen = struct.unpack("<IQ", sidecar._recv_exact(self.conn, 12))
        if status & sidecar.ARENA_FLAG:  # the legacy arena answers through itself
            return bytes(self.mm[:rlen])
        return sidecar._recv_exact(self.conn, rlen)

    def close(self):
        self.conn.close()
        if self.mm is not None:
            self.mm.close()


TRANSPORTS = ["stream", "arena", "region"]


@pytest.fixture
def inproc_worker():
    w = InProcWorker()
    yield w
    w.kill()


class TestGatheredReply:
    @pytest.mark.parametrize("transport", TRANSPORTS)
    @pytest.mark.parametrize(
        "reply_kind", ["to_rows", "fixed_width", "string", "validity"]
    )
    def test_wire_bytes_and_trailer_equal_the_joined_encoding(
        self, inproc_worker, transport, reply_kind
    ):
        op, request, want = _wire_case(reply_kind)
        wire = _Wire(inproc_worker, transport)
        try:
            status, trailer, body = wire.exchange(op, request)
        finally:
            wire.close()
        assert status == sidecar.STATUS_OK, body
        assert body == want
        assert trailer == integrity.pack_crc(integrity.checksum(want))
        # the reply reached reply() as pieces, and nothing joined it
        assert _counter("sidecar.worker.reply.gathered_bytes") == len(want)
        assert _counter("sidecar.worker.reply.joined_bytes") == 0

    @pytest.mark.parametrize("pieces", [
        [b"abc"],
        [b"", b"abc"],
        [b"ab", b"", b"c" * 70000, b""],
        [struct.pack("<I", 3), np.arange(5, dtype=np.int32), np.zeros(0, np.uint8),
         np.arange(12, dtype=np.uint32).reshape(3, 4)],
    ], ids=["one", "empty_first", "empty_between_and_last", "host_arrays"])
    def test_running_crc_over_pieces_is_the_crc_of_the_joined_bytes(self, pieces):
        reply = sidecar.ReplyPieces(pieces)
        joined = b"".join(
            p.tobytes() if isinstance(p, np.ndarray) else p for p in pieces
        )
        assert reply.tobytes() == joined and len(reply) == len(joined)
        crc = 0
        for piece in sidecar.pieces_of(reply):
            crc = integrity.checksum(piece, crc)
        assert crc == integrity.checksum(joined)
        # a plain bytes reply is the one-piece case of the same walk
        assert sidecar.pieces_of(joined) == [joined]
        assert sidecar.as_bytes(joined) is joined

    @pytest.mark.parametrize("via", ["stream", "region"])
    def test_corrupt_fault_on_a_gathered_reply_fails_verify_and_heals(self, via):
        op, request, want = _wire_case("to_rows")
        pool = sidecar_pool.SidecarPool(
            size=1, deadline_s=20, heartbeat_s=1e9, spawn_fn=inproc_spawn
        )
        region = None
        try:
            if via == "region":
                region = pool.lease(max(len(request), len(want)))
                region.write(request)
            faultinj.configure(
                {"seed": 27, "faults": {"sidecar.worker.CONVERT_TO_ROWS": {
                    "type": "corrupt", "percent": 100, "interceptionCount": 1}}}
            )
            before = _counter("sidecar.integrity.crc_mismatch")
            client = pool._workers[0].client
            with pytest.raises(DataCorruption) as ei:
                client.request(op, b"" if region else request, region=region)
            assert isinstance(ei.value, RetryableError)
            assert _counter("sidecar.integrity.crc_mismatch") == before + 1
            # budget spent: armed with retry, the same call now heals
            faultinj.configure(
                {"seed": 27, "faults": {"sidecar.worker.CONVERT_TO_ROWS": {
                    "type": "corrupt", "percent": 100, "interceptionCount": 1}}}
            )
            with retry.enabled(max_attempts=4, base_delay_ms=1):
                got = pool.call(op, b"" if region else request, region=region)
            assert got == want
            assert retry.stats()["retries"] >= 1
        finally:
            if region is not None:
                region.release()
            pool.shutdown()
            sidecar.breaker().reset()


class TestKeptRequestBuffer:
    @pytest.mark.parametrize("transport", TRANSPORTS)
    def test_large_small_large_on_one_connection(self, inproc_worker, transport):
        """The buffer grows once, to the largest payload, and is reused
        after; every answer is right, and the first, held by the caller,
        is what it was after the buffer has been overwritten twice."""
        large, small = groupby_payload(n=5000, seed=1), groupby_payload(n=40, seed=2)
        large2 = groupby_payload(n=5000, seed=3)
        wants = [
            sidecar._dispatch(sidecar.OP_GROUPBY_SUM_F32, p, "cpu")
            for p in (large, small, large2)
        ]
        host0 = memgov.catalog().kind_stats("scratch")
        wire = _Wire(inproc_worker, transport)
        try:
            # SET_ARENA's own payload (8 or 16 bytes) made the buffer
            # grow once already on the shared-memory transports
            grows = _counter("sidecar.worker.scratch.grows")
            reuses = _counter("sidecar.worker.scratch.reuses")
            answers, steps = [], []
            for payload in (large, small, large2):
                status, _trailer, body = wire.exchange(
                    sidecar.OP_GROUPBY_SUM_F32, payload
                )
                assert status == sidecar.STATUS_OK
                answers.append(body)
                steps.append((
                    _counter("sidecar.worker.scratch.grows") - grows,
                    _counter("sidecar.worker.scratch.reuses") - reuses,
                ))
            assert steps == [(1, 0), (1, 1), (1, 2)]
            assert answers == wants
            # host memory the worker now holds between requests: one
            # entry of the largest payload's size, seen by the governor
            n, nbytes = memgov.catalog().kind_stats("scratch")
            assert (n - host0[0], nbytes - host0[1]) == (1, len(large))
            # an empty payload reads nothing and counts nothing
            assert wire.ping() == b"cpu"
            assert _counter("sidecar.worker.scratch.reuses") - reuses == 2
        finally:
            wire.close()
        deadline = time.monotonic() + 5
        while memgov.catalog().kind_stats("scratch") != host0:
            assert time.monotonic() < deadline, "scratch entry outlived its connection"
            time.sleep(0.01)  # the handler's finally unregisters

    @pytest.mark.parametrize("who", ["client", "pool"])
    def test_host_fallback_still_returns_bytes(self, who):
        op, request, want = _wire_case("to_rows")
        assert isinstance(sidecar._dispatch(op, request, "cpu"), sidecar.ReplyPieces)
        try:
            if who == "client":
                client = sidecar.SupervisedClient(
                    tempfile.mktemp(prefix="srjt-nobody-") + ".sock",
                    deadline_s=2, heartbeat_s=1e9,
                )
                with retry.enabled(max_attempts=2, base_delay_ms=1):
                    got = client.call(op, request)
                assert client.host_fallbacks == 1
            else:
                pool = sidecar_pool.SidecarPool(
                    size=1, deadline_s=5, heartbeat_s=1e9, spawn_fn=inproc_spawn
                )
                try:
                    pool._respawn_max = 0
                    pool._workers[0].proc.kill()
                    with retry.enabled(max_attempts=2, base_delay_ms=1):
                        got = pool.call(op, request)
                finally:
                    pool.shutdown()
        finally:
            sidecar.breaker().reset()
        assert type(got) is bytes and got == want
