"""Zero-copy columnar data plane v2 (ISSUE 6).

Covers the three layers the slab/frames/exchange refactor added:

- FRAMES: the versioned columnar frame codec (columnar/frames.py) —
  property round-trips over every wire dtype (empty columns and
  null-heavy validity included), tamper -> retryable DataCorruption,
  the integrity-off posture, and the sidecar wire negotiation (framed
  request -> framed response, legacy walker untouched).
- SLAB: the buddy free-list arena (sidecar_pool.ArenaSlab) — size
  classes, coalescing, exhaustion as RESOURCE_EXHAUSTED (the
  retry-with-split class), leak accounting, and the concurrency
  acceptance: two arena-resident ops on two workers provably OVERLAP
  (a barrier inside the worker dispatch under a faultinj ``delay`` —
  the old single-buffer lock would deadlock the barrier).
- TCP EXCHANGE: cross-process hash-partition exchange through frames
  (parallel/shuffle.TcpExchange) — in-process bit-identical
  distributed groupby, tampered exchange -> retryable DataCorruption
  that heals under retry, and the slow-tier two-REAL-process
  acceptance under ci/chaos_crash.json (one injected peer kill -9 +
  one injected frame corruption, final result bit-identical).
"""

import os
import threading
import time

import numpy as np
import pytest

import spark_rapids_jni_tpu  # noqa: F401
import jax.numpy as jnp

from spark_rapids_jni_tpu import sidecar, sidecar_pool
from spark_rapids_jni_tpu.columnar import Column, Table, frames
from spark_rapids_jni_tpu.columnar import dtype as dt
from spark_rapids_jni_tpu.ops.copying import concatenate, slice_table
from spark_rapids_jni_tpu.parallel import shuffle
from spark_rapids_jni_tpu.utils import (
    deadline as deadline_mod,
    faultinj,
    integrity,
    metrics,
    retry,
)
from spark_rapids_jni_tpu.utils.errors import DataCorruption, RetryableError

from _inproc import InProcWorker, groupby_payload, inproc_spawn

pytestmark = pytest.mark.usefixtures("clean_state")


def _counter(name):
    return metrics.registry().value(name)


# ---------------------------------------------------------------------------
# frame codec: property round-trips
# ---------------------------------------------------------------------------


def _fixed_width_cases(rng):
    """One column per fixed-width wire dtype, adversarial bit patterns."""
    cases = []
    for d in (
        dt.INT8, dt.INT16, dt.INT32, dt.INT64,
        dt.UINT8, dt.UINT16, dt.UINT32, dt.UINT64,
        dt.FLOAT32, dt.FLOAT64, dt.BOOL8,
        dt.TIMESTAMP_MICROSECONDS, dt.DURATION_DAYS,
        dt.decimal32(-2), dt.decimal64(-4),
    ):
        np_dt = d.np_dtype
        raw = rng.integers(0, 256, 64 * np_dt.itemsize, dtype=np.uint8)
        data = raw.view(np_dt)
        cases.append(Column(d, data=jnp.asarray(data)))
    # DECIMAL128: [N, 4] uint32 limbs
    limbs = rng.integers(0, 2**32, (64, 4), dtype=np.uint32)
    cases.append(Column(dt.decimal128(-6), data=jnp.asarray(limbs)))
    return cases


class TestFrameRoundtrip:
    def test_all_fixed_width_dtypes_bit_exact(self, rng):
        cols = _fixed_width_cases(rng)
        t = Table(cols)
        out = frames.decode_table(frames.encode_table(t))
        assert len(out.columns) == len(cols)
        for a, b in zip(cols, out.columns):
            assert b.dtype == a.dtype
            assert np.asarray(b.data).tobytes() == np.asarray(a.data).tobytes()

    def test_string_and_list_roundtrip(self):
        s = Column(
            dt.STRING,
            offsets=jnp.asarray(np.array([0, 1, 3, 3, 6], np.int32)),
            chars=jnp.asarray(np.frombuffer(b"abcdef", np.uint8)),
        )
        l = Column(
            dt.LIST,
            offsets=jnp.asarray(np.array([0, 2, 2, 5, 7], np.int32)),
            child=Column(dt.INT8, data=jnp.asarray(np.arange(7, dtype=np.int8))),
        )
        out = frames.decode_table(frames.encode_table(Table([s, l])))
        assert bytes(np.asarray(out.columns[0].chars)) == b"abcdef"
        assert np.array_equal(
            np.asarray(out.columns[0].offsets), [0, 1, 3, 3, 6]
        )
        assert np.array_equal(np.asarray(out.columns[1].child.data), np.arange(7))

    def test_empty_columns_roundtrip(self):
        t = Table([
            Column(dt.INT64, data=jnp.zeros(0, jnp.int64)),
            Column(dt.STRING, offsets=jnp.asarray(np.zeros(1, np.int32)),
                   chars=jnp.asarray(np.zeros(0, np.uint8))),
        ])
        out = frames.decode_table(frames.encode_table(t))
        assert out.num_rows == 0
        assert len(out.columns) == 2

    def test_null_heavy_validity_and_null_count(self, rng):
        validity = rng.random(256) < 0.1  # ~90% null
        t = Table([Column(
            dt.FLOAT32,
            data=jnp.asarray(rng.standard_normal(256).astype(np.float32)),
            validity=jnp.asarray(validity),
        )])
        blob = frames.encode_table(t)
        parts, _ = frames.decode_parts(blob)
        nulls = int((~validity).sum())
        assert all(p.null_count == nulls for p in parts)
        out = frames.decode_table(blob)
        assert np.array_equal(np.asarray(out.columns[0].validity), validity)

    def test_leaves_roundtrip_exact(self, rng):
        leaves = [
            rng.standard_normal(100),
            rng.integers(0, 2**32, (5, 4), dtype=np.uint32),
            np.zeros(0, np.int8),
            np.asarray([True, False, True]),
        ]
        out = frames.decode_leaves(frames.encode_leaves(leaves))
        for a, b in zip(leaves, out):
            assert a.dtype == b.dtype and a.shape == b.shape
            assert a.tobytes() == b.tobytes()

    def test_tampered_frame_raises_retryable_corruption(self, rng):
        blob = bytearray(frames.encode_table(Table(_fixed_width_cases(rng))))
        blob[len(blob) // 2] ^= 0xFF
        before = _counter("sidecar.integrity.crc_mismatch")
        with pytest.raises(DataCorruption):
            frames.decode_table(bytes(blob))
        assert _counter("sidecar.integrity.crc_mismatch") == before + 1
        assert issubclass(DataCorruption, RetryableError)

    def test_truncated_frame_raises_corruption(self):
        blob = frames.encode_table(
            Table([Column(dt.INT64, data=jnp.arange(100, dtype=jnp.int64))])
        )
        with pytest.raises(DataCorruption):
            frames.decode_parts(blob[: len(blob) - 8])

    def test_integrity_off_emits_unchecked_and_skips_verify(self):
        t = Table([Column(dt.INT64, data=jnp.arange(32, dtype=jnp.int64))])
        with integrity.disabled():
            blob = bytearray(frames.encode_table(t))
            checked0 = _counter("sidecar.integrity.frame_decodes_checked")
            blob[-3] ^= 0xFF  # tamper passes: the seed posture
            out = frames.decode_table(bytes(blob))
            assert out.num_rows == 32
            assert _counter("sidecar.integrity.frame_decodes_checked") == checked0
        # checked frames count their decodes
        blob = frames.encode_table(t)
        before = _counter("sidecar.integrity.frame_decodes_checked")
        frames.decode_table(blob)
        assert _counter("sidecar.integrity.frame_decodes_checked") == before + 1

    def test_non_frame_is_value_error_not_corruption(self):
        with pytest.raises(ValueError, match="bad magic"):
            frames.decode_parts(b"not a frame at all........")


# ---------------------------------------------------------------------------
# sidecar wire negotiation: framed request -> framed response
# ---------------------------------------------------------------------------


class TestFramedWire:
    def test_worker_echoes_request_table_format(self):
        w = InProcWorker()
        try:
            client = sidecar.SupervisedClient(
                w.sock_path, deadline_s=20, heartbeat_s=1e9
            )
            t = Table([
                Column(dt.INT32, data=jnp.arange(64, dtype=jnp.int32)),
                Column(dt.INT32, data=jnp.arange(64, 128, dtype=jnp.int32)),
            ])
            with client:
                legacy = client.request(
                    sidecar.OP_ZORDER, sidecar.as_bytes(sidecar._write_table(t, framed=False))
                )
                framed = client.request(
                    sidecar.OP_ZORDER, frames.encode_table(t)
                )
            assert not frames.is_frame(legacy)
            assert frames.is_frame(framed)
            a = sidecar._read_table(legacy)
            b = frames.decode_table(framed)
            assert (
                np.asarray(a.columns[0].child.data).tobytes()
                == np.asarray(b.columns[0].child.data).tobytes()
            )
        finally:
            w.kill()

    def test_read_table_sniffs_frames_at_offset(self):
        t = Table([Column(dt.INT64, data=jnp.arange(10, dtype=jnp.int64))])
        payload = b"\x01\x02\x03\x04" + frames.encode_table(t)
        out = sidecar._read_table(payload, 4)
        assert np.array_equal(np.asarray(out.columns[0].data), np.arange(10))

    def test_dispatch_resets_stale_framed_state(self):
        """A framed request that died mid-op must not leak its
        sniffed-frame flag into the next call on the same thread — the
        pool's host-fallback path calls ``_dispatch`` directly, and a
        stale flag would frame a legacy caller's response."""
        t = Table([Column(dt.INT32, data=jnp.arange(16, dtype=jnp.int32))])
        sidecar._REQ_FMT.framed = True  # stale from an aborted framed op
        resp = sidecar._dispatch(
            sidecar.OP_ZORDER, sidecar.as_bytes(sidecar._write_table(t, framed=False)), "cpu"
        )
        assert not frames.is_frame(sidecar.as_bytes(resp))


# ---------------------------------------------------------------------------
# slab allocator
# ---------------------------------------------------------------------------


class TestArenaSlab:
    def test_power_of_two_classes_and_disjoint_offsets(self):
        slab = sidecar_pool.ArenaSlab(1 << 16)
        try:
            regions = [slab.lease(100) for _ in range(8)]
            offs = {r.offset for r in regions}
            assert len(offs) == 8  # all disjoint
            for r in regions:
                assert (r.capacity + sidecar.REGION_HDR_LEN) & (
                    r.capacity + sidecar.REGION_HDR_LEN - 1
                ) == 0  # block is a power of two
                r.release()
        finally:
            assert slab.close() == 0

    def test_buddy_coalescing_restores_full_slab(self):
        slab = sidecar_pool.ArenaSlab(1 << 16)
        try:
            regions = [slab.lease(3000) for _ in range(4)]
            for r in regions:
                r.release()
            # after coalescing one max-size lease must fit again
            big = slab.lease((1 << 16) - sidecar.REGION_HDR_LEN - 32)
            big.release()
        finally:
            assert slab.close() == 0

    def test_exhaustion_is_resource_exhausted(self):
        slab = sidecar_pool.ArenaSlab(1 << 14)
        held = []
        try:
            with pytest.raises(RetryableError, match="RESOURCE_EXHAUSTED"):
                for _ in range(64):
                    held.append(slab.lease(3000))
            assert retry.is_resource_exhausted(
                RetryableError("x RESOURCE_EXHAUSTED y")
            )
        finally:
            for r in held:
                r.release()
            slab.close()

    def test_oversized_lease_is_resource_exhausted_with_need(self):
        slab = sidecar_pool.ArenaSlab(1 << 14)
        try:
            with pytest.raises(RetryableError, match="RESOURCE_EXHAUSTED"):
                slab.lease(1 << 20)
        finally:
            assert slab.close() == 0

    def test_leaked_region_counted_on_close(self):
        slab = sidecar_pool.ArenaSlab(1 << 14)
        slab.lease(100)  # deliberately leaked
        leaks0 = _counter("sidecar.pool.region_leaks")
        assert slab.close() == 1
        assert _counter("sidecar.pool.region_leaks") == leaks0 + 1
        assert sidecar_pool.arena_leak_report() == []  # closed slabs drop out

    def test_region_header_in_slab_pages(self):
        slab = sidecar_pool.ArenaSlab(1 << 14)
        try:
            r = slab.lease(64)
            r.write(b"payload!")
            magic, gen, rid, cap, plen = sidecar.REGION_HDR.unpack_from(
                slab._mm, r.offset
            )
            assert magic == sidecar.REGION_MAGIC
            assert (gen, rid, cap, plen) == (
                r.generation, r.request_id, r.capacity, 8
            )
            r.release()
        finally:
            assert slab.close() == 0


# ---------------------------------------------------------------------------
# pool concurrency: two arena ops on two workers genuinely overlap
# ---------------------------------------------------------------------------


class TestPoolConcurrency:
    def test_two_region_ops_overlap_across_workers(self, monkeypatch):
        """The ISSUE 6 acceptance mechanism: both region requests must
        be INSIDE worker dispatch simultaneously — a barrier in the
        dispatch path (reached under a faultinj ``delay`` on the worker
        op) releases only if the two ops overlap. The PR 5
        single-buffer arena serialized all pool traffic on one lock, so
        this barrier would time out by construction."""
        pool = sidecar_pool.SidecarPool(
            size=2, deadline_s=20, heartbeat_s=1e9, spawn_fn=inproc_spawn,
            slab_bytes=1 << 20,
        )
        try:
            payload = groupby_payload()
            want = sidecar._dispatch(sidecar.OP_GROUPBY_SUM_F32, payload, "cpu")
            faultinj.configure(
                {"faults": {"sidecar.worker.GROUPBY_SUM_F32": {
                    "type": "delay", "percent": 100, "delayMs": 10}}}
            )
            barrier = threading.Barrier(2, timeout=10)
            real = sidecar._dispatch

            def synced(op, pl, backend):
                if op == sidecar.OP_GROUPBY_SUM_F32:
                    barrier.wait()  # both ops in flight, or timeout
                return real(op, pl, backend)

            monkeypatch.setattr(sidecar, "_dispatch", synced)
            errs = []

            def one_call():
                try:
                    with retry.enabled(max_attempts=4, base_delay_ms=1):
                        assert pool.call_arena(
                            sidecar.OP_GROUPBY_SUM_F32, payload
                        ) == want
                except Exception as e:  # pragma: no cover - surfaced below
                    errs.append(e)

            threads = [threading.Thread(target=one_call) for _ in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(20)
            assert not errs, errs
            assert not barrier.broken, "region ops serialized: no overlap"
            # both workers carried region traffic
            stats = pool.worker_stats(fold=False)
            served = {
                wid: (s["snapshot"]["counters"] or {}).get(
                    "sidecar.worker.requests.GROUPBY_SUM_F32", 0
                )
                for wid, s in stats.items()
            }
            assert all(v >= 1 for v in served.values()), served
        finally:
            pool.shutdown()

    def test_stale_region_generation_is_retryable_desync(self):
        """A clobbered/stale region header answers retryably at the
        worker (the client rewrites and re-sends), never with foreign
        bytes."""
        pool = sidecar_pool.SidecarPool(
            size=1, deadline_s=20, heartbeat_s=1e9, spawn_fn=inproc_spawn,
            slab_bytes=1 << 20,
        )
        try:
            payload = groupby_payload()
            region = pool.lease(len(payload))
            region.write(payload)
            # corrupt the in-slab header's generation behind the pool
            hdr = bytearray(
                pool._slab._mm[region.offset : region.offset + sidecar.REGION_HDR_LEN]
            )
            hdr[4] ^= 0xFF  # generation byte
            pool._slab._mm[region.offset : region.offset + sidecar.REGION_HDR_LEN] = bytes(hdr)
            w = pool._workers[0]
            pool._ensure_arena(w)
            with pytest.raises(RetryableError, match="region header desync"):
                w.client.request(sidecar.OP_GROUPBY_SUM_F32, b"", region=region)
            # pool.call heals it: the snapshot replay rewrites the header
            want = sidecar._dispatch(sidecar.OP_GROUPBY_SUM_F32, payload, "cpu")
            with retry.enabled(max_attempts=4, base_delay_ms=1):
                assert pool.call(
                    sidecar.OP_GROUPBY_SUM_F32, region=region
                ) == want
            region.release()
        finally:
            pool.shutdown()

    def test_stale_generation_reply_answers_via_stream(self):
        """Reply-time re-validation (the failover-clobber race): a
        worker whose region was re-leased/bumped MID-DISPATCH must
        answer through the stream and leave the slab untouched —
        writing would clobber the retry attempt's bytes."""
        pool = sidecar_pool.SidecarPool(
            size=1, deadline_s=20, heartbeat_s=1e9, spawn_fn=inproc_spawn,
            slab_bytes=1 << 20,
        )
        try:
            payload = groupby_payload()
            want = sidecar._dispatch(sidecar.OP_GROUPBY_SUM_F32, payload, "cpu")
            region = pool.lease(len(payload))
            region.write(payload)
            w = pool._workers[0]
            pool._ensure_arena(w)
            # park the worker between request validation and reply()
            faultinj.configure(
                {"faults": {"sidecar.worker.GROUPBY_SUM_F32": {
                    "type": "delay", "percent": 100, "delayMs": 400}}}
            )
            out = {}

            def call():
                out["resp"] = w.client.request(
                    sidecar.OP_GROUPBY_SUM_F32, b"", region=region
                )

            th = threading.Thread(target=call)
            th.start()
            time.sleep(0.1)  # request validated; dispatch inside the delay
            gen_off = region.offset + 4  # u32 magic, then the generation
            pool._slab._mm[gen_off] ^= 0xFF
            th.join(20)
            assert not th.is_alive()
            assert out.get("resp") == want  # stream answer, still correct
            start = region.offset + sidecar.REGION_HDR_LEN
            assert (
                bytes(pool._slab._mm[start:start + len(payload)]) == payload
            ), "stale reply clobbered the region"
            pool._slab._mm[gen_off] ^= 0xFF  # restore before release
            region.release()
        finally:
            pool.shutdown()


# ---------------------------------------------------------------------------
# TCP exchange (in-process tier)
# ---------------------------------------------------------------------------


class TestTcpExchange:
    ROWS = 2000
    SEED = 7

    def _ref(self):
        full = shuffle._demo_table(self.ROWS, seed=self.SEED)
        return full, shuffle._local_groupby_sum(full)

    def test_exchange_mode_env(self, monkeypatch):
        monkeypatch.delenv("SRJT_EXCHANGE_MODE", raising=False)
        assert shuffle.exchange_mode() == "mesh"
        monkeypatch.setenv("SRJT_EXCHANGE_MODE", "tcp")
        assert shuffle.exchange_mode() == "tcp"
        monkeypatch.setenv("SRJT_EXCHANGE_MODE", "bogus")
        with pytest.warns(UserWarning):
            assert shuffle.exchange_mode() == "mesh"

    def test_two_rank_groupby_bit_identical_in_process(self):
        full, ref = self._ref()
        ex0, ex1 = shuffle.TcpExchange(0), shuffle.TcpExchange(1)
        res = {}

        def run_rank(rank, ex, peers):
            lo, hi = shuffle._shard_bounds(self.ROWS, 2, rank)
            with retry.enabled(max_attempts=20, base_delay_ms=5):
                local = ex.exchange_table(
                    slice_table(full, lo, hi), ["k"], peers
                )
                res[rank] = shuffle._local_groupby_sum(local)

        try:
            threads = [
                threading.Thread(
                    target=run_rank, args=(0, ex0, {1: ex1.address})
                ),
                threading.Thread(
                    target=run_rank, args=(1, ex1, {0: ex0.address})
                ),
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            assert set(res) == {0, 1}
            got = concatenate([res[0], res[1]])
            order = np.argsort(np.asarray(got.column("k").data))
            for name in ("k", "s", "c"):
                assert np.array_equal(
                    np.asarray(got.column(name).data)[order],
                    np.asarray(ref.column(name).data),
                ), name
        finally:
            ex0.close()
            ex1.close()

    def test_tampered_exchange_raises_retryable_corruption(self):
        """ISSUE 6 satellite: a tampered TCP exchange must decode to
        retryable DataCorruption (counted), and heal transparently
        under the retry orchestrator once the fault budget is spent."""
        t = Table([Column(dt.INT64, data=jnp.arange(128, dtype=jnp.int64))])
        ex1 = shuffle.TcpExchange(1)
        ex0 = shuffle.TcpExchange(0)
        try:
            ex1.publish(0, {0: t})
            faultinj.configure(
                {"seed": 5, "faults": {"exchange.frame": {
                    "type": "corrupt", "percent": 100, "interceptionCount": 1}}}
            )
            before = _counter("sidecar.integrity.crc_mismatch")
            with pytest.raises(DataCorruption):
                ex0._fetch_once(ex1.address, 0, 0)
            assert _counter("sidecar.integrity.crc_mismatch") == before + 1
            # re-arm: fetch() rides retry and heals
            faultinj.configure(
                {"seed": 5, "faults": {"exchange.frame": {
                    "type": "corrupt", "percent": 100, "interceptionCount": 1}}}
            )
            with retry.enabled(max_attempts=5, base_delay_ms=1):
                out = ex0.fetch(ex1.address, 0, 0)
            assert np.array_equal(
                np.asarray(out.columns[0].data), np.arange(128)
            )
            assert retry.stats()["retries"] >= 1
        finally:
            ex0.close()
            ex1.close()

    def test_epoch_eviction_bounds_retention(self):
        """publish() keeps only the newest ``retain_epochs`` rounds —
        a long-lived runtime must not hoard every encoded partition,
        while the respawn-republish window stays servable."""
        t = Table([Column(dt.INT64, data=jnp.arange(8, dtype=jnp.int64))])
        ex = shuffle.TcpExchange(0, publish_wait_s=0.05, retain_epochs=2)
        try:
            evicted0 = _counter("shuffle.tcp.frames_evicted")
            with metrics.enabled():
                for epoch in range(4):
                    ex.publish(epoch, {1: t})
            with ex._published:
                assert sorted({e for e, _ in ex._frames}) == [2, 3]
            assert _counter("shuffle.tcp.frames_evicted") == evicted0 + 2
            # an evicted epoch answers retryably — never wrong bytes
            with pytest.raises(RetryableError, match="not\\s+published"):
                ex._fetch_once(ex.address, 0, 1)
            # retained epochs still serve
            out = ex._fetch_once(ex.address, 3, 1)
            assert np.array_equal(
                np.asarray(out.columns[0].data), np.arange(8)
            )
            # drop_epoch releases a finished round eagerly
            assert ex.drop_epoch(2) == 1
            with ex._published:
                assert (2, 1) not in ex._frames
        finally:
            ex.close()

    def test_worker_harness_refuses_mesh_mode(self, monkeypatch):
        """An operator forcing SRJT_EXCHANGE_MODE=mesh on a
        cross-process peer is a config error, not something to
        ignore: the harness refuses to start."""
        import types

        monkeypatch.setenv("SRJT_EXCHANGE_MODE", "mesh")
        rc = shuffle._exchange_worker_main(types.SimpleNamespace(
            rank=1, world=2, rows=8, seed=1, epoch=0,
            bind="127.0.0.1:0", peers="",
        ))
        assert rc == 2

    def test_unpublished_partition_is_retryable(self):
        ex1 = shuffle.TcpExchange(1, publish_wait_s=0.05)
        ex0 = shuffle.TcpExchange(0)
        try:
            with pytest.raises(RetryableError, match="not\\s+published"):
                ex0._fetch_once(ex1.address, 9, 9)
        finally:
            ex0.close()
            ex1.close()

    def test_dead_peer_fetch_respects_deadline(self):
        ex0 = shuffle.TcpExchange(0)
        try:
            from spark_rapids_jni_tpu.utils.errors import DeadlineExceeded

            t0 = time.monotonic()
            with pytest.raises((DeadlineExceeded, RetryableError)):
                with deadline_mod.scope(0.5):
                    with retry.enabled(max_attempts=50, base_delay_ms=10):
                        ex0.fetch("127.0.0.1:9", 0, 0)  # discard port: refused
            assert time.monotonic() - t0 < 10
        finally:
            ex0.close()


# ---------------------------------------------------------------------------
# faultinj prefix-wildcard rules (the exchange chaos keying)
# ---------------------------------------------------------------------------


class TestFaultinjPrefixRules:
    def test_prefix_rule_matches_family(self):
        faultinj.configure(
            {"faults": {"exchange.*": {"type": "retryable", "percent": 100,
                                        "interceptionCount": 2}}}
        )
        with pytest.raises(RetryableError):
            faultinj.maybe_inject("exchange.serve")
        with pytest.raises(RetryableError):
            faultinj.maybe_inject("exchange.other")
        faultinj.maybe_inject("sidecar.worker.PING")  # no match, no fire

    def test_exact_beats_prefix_beats_star(self):
        faultinj.configure(
            {"faults": {
                "a.b": {"type": "retryable", "percent": 100},
                "a.*": {"type": "exception", "percent": 100},
                "*": {"type": "fatal", "percent": 100},
            }}
        )
        with pytest.raises(RetryableError):
            faultinj.maybe_inject("a.b")  # exact
        with pytest.raises(RuntimeError):
            faultinj.maybe_inject("a.c")  # prefix family
        from spark_rapids_jni_tpu.utils.errors import FatalDeviceError

        with pytest.raises(FatalDeviceError):
            faultinj.maybe_inject("zzz")  # the floor


# ---------------------------------------------------------------------------
# two REAL processes: crash + corrupt storm over the TCP exchange
# (slow tier; ci/premerge.sh data-plane tier runs it env-armed)
# ---------------------------------------------------------------------------

def _spawn_exchange_child(parent_addr, rows, seed, chaos_cfg=None,
                          respawn_of=None):
    extra = {"JAX_PLATFORMS": "cpu"}
    if chaos_cfg:
        extra["SRJT_FAULTINJ_CONFIG"] = chaos_cfg
    return shuffle.spawn_exchange_peer(
        parent_addr, rows, seed, extra_env=extra, respawn_of=respawn_of
    )


class TestTcpExchangeTwoProcess:
    def test_two_process_groupby_bit_identical_under_chaos(self):
        """The ISSUE 6 acceptance: a 2-process distributed groupby over
        the TCP exchange is bit-identical to the single-process result,
        under deadline + CRC + retry, including ONE injected peer kill
        -9 and ONE injected frame corruption (ci/chaos_crash.json's
        exchange keys, armed inside the peer)."""
        rows, seed = 3000, 11
        cfg = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "ci", "chaos_crash.json",
        )
        full = shuffle._demo_table(rows, seed=seed)
        ref = shuffle._local_groupby_sum(full)
        lo, hi = shuffle._shard_bounds(rows, 2, 0)
        shard0 = slice_table(full, lo, hi)

        ex0 = shuffle.TcpExchange(0)
        proc = proc2 = None
        mismatch0 = _counter("sidecar.integrity.crc_mismatch")
        try:
            proc, child_addr = _spawn_exchange_child(
                ex0.address, rows, seed, chaos_cfg=cfg
            )
            with deadline_mod.scope(300), retry.enabled(
                max_attempts=6, base_delay_ms=5, max_delay_ms=50
            ):
                # epoch 0: the peer's first serve is CORRUPTED under the
                # CRC (caught + re-fetched by retry)
                local0 = ex0.exchange_table(
                    shard0, ["k"], {1: child_addr}, epoch=0
                )
                res0 = shuffle._local_groupby_sum(local0)
                # the result fetch lands on the serve the `crash` rule
                # arms: the peer SIGKILLs itself mid-request
                try:
                    res1 = ex0.fetch(child_addr, 1, 1)
                    crashed = False
                except RetryableError:
                    crashed = True
                assert crashed, "injected peer crash never surfaced"
                assert proc.wait(timeout=120) != 0
                # supervise: clean respawn recomputes deterministically;
                # the harness verifies the predecessor died and emits
                # exchange.peer_respawn itself (the premerge artifact)
                proc2, child_addr = _spawn_exchange_child(
                    ex0.address, rows, seed, respawn_of=proc
                )
                res1 = ex0.fetch(child_addr, 1, 1)
            got = concatenate([res0, Table(res1.columns, ["k", "s", "c"])])
            order = np.argsort(np.asarray(got.column("k").data))
            for name in ("k", "s", "c"):
                assert np.array_equal(
                    np.asarray(got.column(name).data)[order],
                    np.asarray(ref.column(name).data),
                ), f"{name} diverged from the single-process result"
            # the corruption really fired and was caught
            assert _counter("sidecar.integrity.crc_mismatch") > mismatch0
        finally:
            for p in (proc, proc2):
                if p is not None and p.poll() is None:
                    try:
                        p.stdin.close()
                        p.wait(timeout=20)
                    except Exception:
                        p.kill()
            ex0.close()
            shuffle.exchange_breaker().reset()
