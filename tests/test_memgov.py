"""Memory governor test tier (ISSUE 4): byte-weighted admission
control, the spillable buffer catalog, the pressure loop between them,
and the squeeze acceptance — with SRJT_DEVICE_MEMORY_BUDGET pinched
below a query's natural footprint, smoke queries still produce
bit-identical results via spill + split, and the memgov counters show
the recovery happened.

ci/premerge.sh runs this file in a dedicated low-budget tier (tight
budget, metrics + event log armed) and asserts spill volume from the
archived event log.
"""

import os
import threading
import time

import numpy as np
import pytest

import spark_rapids_jni_tpu  # noqa: F401
import jax
import jax.numpy as jnp

from spark_rapids_jni_tpu import memgov
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.columnar import dtype as dt
from spark_rapids_jni_tpu.utils import deadline, faultinj, metrics, retry
from spark_rapids_jni_tpu.utils.dispatch import op_boundary
from spark_rapids_jni_tpu.utils.errors import DeadlineExceeded
from spark_rapids_jni_tpu.utils.memory import MemoryBudgetExceeded

_MEMGOV_CHAOS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "ci", "chaos_memgov.json",
)


pytestmark = pytest.mark.usefixtures("clean_state")


@pytest.fixture(scope="module")
def mesh8():
    from spark_rapids_jni_tpu.parallel import mesh as mesh_mod

    assert len(jax.devices()) == 8, "conftest must force the 8-device CPU mesh"
    return mesh_mod.make_mesh({"data": 8})


def _counter(name: str) -> int:
    return metrics.registry().counter(name).value


def _new_pair(capacity: int, max_wait_s: float = 0.2, **kw):
    cat = memgov.BufferCatalog()
    ctl = memgov.AdmissionController(
        capacity_fn=lambda: capacity, catalog=cat, max_wait_s=max_wait_s, **kw
    )
    return ctl, cat


# ---------------------------------------------------------------------------
# admission controller
# ---------------------------------------------------------------------------


class TestAdmission:
    def test_byte_accounting_exact(self):
        ctl, _ = _new_pair(1000)
        a = ctl.acquire(600, "a")
        assert ctl.in_use() == 600
        b = ctl.acquire(400, "b")
        assert ctl.in_use() == 1000
        snap = ctl.snapshot()
        assert snap["in_use_bytes"] == 1000 and snap["active"] == 2
        a.release()
        assert ctl.in_use() == 400
        a.release()  # idempotent: double release must not go negative
        assert ctl.in_use() == 400
        b.release()
        assert ctl.in_use() == 0 and ctl.snapshot()["active"] == 0

    def test_hopeless_demand_rejects_immediately(self):
        """A request larger than the whole budget — with nothing in
        flight to release and nothing to spill — must raise the
        retryable MemoryBudgetExceeded NOW, not wait out the bound."""
        ctl, _ = _new_pair(1000, max_wait_s=30.0)
        t0 = time.monotonic()
        with pytest.raises(MemoryBudgetExceeded):
            ctl.acquire(1500, "too_big")
        assert time.monotonic() - t0 < 2.0

    def test_sustained_overbudget_raises_retryable(self):
        ctl, _ = _new_pair(1000, max_wait_s=0.15)
        hold = ctl.acquire(800, "holder")
        before = _counter("memgov.rejected")
        with pytest.raises(MemoryBudgetExceeded):
            ctl.acquire(500, "waiter")  # would fit once holder releases
        assert _counter("memgov.rejected") == before + 1
        hold.release()
        ctl.acquire(500, "waiter").release()  # now admits

    def test_fifo_head_blocks_smaller_latecomers(self):
        """FIFO fairness: a small request that WOULD fit may not jump
        the queue past a blocked larger one."""
        ctl, _ = _new_pair(100, max_wait_s=10.0)
        hold = ctl.acquire(80, "hold")
        done = []

        def worker(tag, nb):
            adm = ctl.acquire(nb, name=tag)
            done.append(tag)
            adm.release()

        big = threading.Thread(target=worker, args=("big", 60), daemon=True)
        big.start()
        for _ in range(200):
            if ctl.snapshot()["queue_depth"] == 1:
                break
            time.sleep(0.005)
        small = threading.Thread(target=worker, args=("small", 15), daemon=True)
        small.start()
        for _ in range(200):
            if ctl.snapshot()["queue_depth"] == 2:
                break
            time.sleep(0.005)
        # 80 + 15 <= 100: small FITS — and must still wait behind big
        time.sleep(0.1)
        assert done == []
        hold.release()
        big.join(timeout=5)
        small.join(timeout=5)
        assert sorted(done) == ["big", "small"]
        assert ctl.in_use() == 0

    def test_max_concurrent_cap(self):
        ctl, _ = _new_pair(10_000, max_wait_s=0.15, max_concurrent=1)
        a = ctl.acquire(10, "a")
        with pytest.raises(MemoryBudgetExceeded):
            ctl.acquire(10, "b")  # bytes fit; the op-slot cap blocks
        a.release()
        ctl.acquire(10, "b").release()

    def test_queue_wait_histogram_records(self):
        ctl, _ = _new_pair(100)
        h = metrics.registry().histogram("memgov.queue_wait_us")
        before = h.count
        ctl.acquire(50, "x").release()
        assert h.count == before + 1

    def test_deadline_truncates_wait(self):
        """A blocked admission under a deadline scope raises
        DeadlineExceeded when the budget dies — never waits out the
        (much longer) admission bound."""
        ctl, _ = _new_pair(100, max_wait_s=30.0)
        hold = ctl.acquire(100, "holder")
        t0 = time.monotonic()
        with deadline.scope(0.2):
            with pytest.raises(DeadlineExceeded):
                ctl.acquire(50, "waiter")
        assert time.monotonic() - t0 < 2.0
        hold.release()

    def test_denial_on_dead_budget(self):
        ctl, _ = _new_pair(100, max_wait_s=30.0)
        hold = ctl.acquire(100, "holder")
        with deadline.scope(0.01):
            time.sleep(0.03)  # budget is gone before the acquire
            with pytest.raises(DeadlineExceeded):
                ctl.acquire(50, "late")
        hold.release()


# ---------------------------------------------------------------------------
# spillable buffer catalog
# ---------------------------------------------------------------------------


def _adversarial_leaves():
    """Bit-pattern-hostile payload: NaNs/infs/negative zero in f64,
    full-range u64, bools — a lossy demotion cannot hide."""
    f = np.array(
        [0.0, -0.0, np.nan, np.inf, -np.inf, 1e-308, -1.5, 3.14], np.float64
    )
    u = np.array([0, 1, 2**63, 2**64 - 1, 12345], np.uint64)
    b = np.array([True, False, True], bool)
    return jnp.asarray(f), jnp.asarray(u), jnp.asarray(b)


def _tree_bytes(tree):
    return [np.asarray(x).tobytes() for x in jax.tree_util.tree_leaves(tree)]


class TestCatalog:
    def test_spill_rematerialize_bit_exact(self):
        cat = memgov.BufferCatalog()
        val = _adversarial_leaves()
        want = _tree_bytes(val)
        h = cat.register("adv", val)
        assert h.tier == memgov.TIER_DEVICE
        h.spill()
        assert h.tier == memgov.TIER_HOST and cat.device_bytes() == 0
        assert _tree_bytes(h.get()) == want  # get re-materializes
        assert h.tier == memgov.TIER_DEVICE

    def test_disk_round_trip_bit_exact(self, tmp_path):
        cat = memgov.BufferCatalog(spill_dir=str(tmp_path))
        val = _adversarial_leaves()
        want = _tree_bytes(val)
        h = cat.register("adv", val)
        h.spill(to_disk=True)
        assert h.tier == memgov.TIER_DISK
        assert cat.disk_bytes() == h.nbytes and cat.host_bytes() == 0
        files = os.listdir(tmp_path)
        # spill containers are versioned columnar frames as of ISSUE 6
        assert len(files) == 1 and files[0].endswith(".frm")
        from spark_rapids_jni_tpu.columnar import frames

        with open(os.path.join(tmp_path, files[0]), "rb") as f:
            assert frames.is_frame(f.read(len(frames.MAGIC)))
        assert _tree_bytes(h.get()) == want
        assert h.tier == memgov.TIER_DEVICE
        assert os.listdir(tmp_path) == []  # spill file reclaimed

    def test_legacy_spill_containers_still_load(self, tmp_path):
        """ISSUE 6 migration: spill files written BEFORE the columnar
        frame layout — the SRJTSPL1 CRC envelope around npz, and plain
        unframed npz — must still re-materialize bit-exactly through
        their original read paths."""
        import io

        from spark_rapids_jni_tpu.memgov.catalog import _SPILL_MAGIC
        from spark_rapids_jni_tpu.utils import integrity

        for kind in ("envelope", "plain"):
            cat = memgov.BufferCatalog(spill_dir=str(tmp_path))
            val = _adversarial_leaves()
            want = _tree_bytes(val)
            h = cat.register(f"legacy-{kind}", val)
            leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(val)]
            h.spill(to_disk=True)
            # overwrite the fresh .frm with the pre-ISSUE-6 container
            buf = io.BytesIO()
            np.savez(buf, **{f"a{i}": leaf for i, leaf in enumerate(leaves)})
            blob = buf.getvalue()
            with open(h._disk_path, "wb") as f:
                if kind == "envelope":
                    f.write(_SPILL_MAGIC)
                    f.write(integrity.pack_crc(integrity.checksum(blob)))
                    f.write(len(blob).to_bytes(8, "little"))
                f.write(blob)
            assert _tree_bytes(h.get()) == want, kind

    def test_table_round_trip_bit_exact(self):
        cat = memgov.BufferCatalog()
        t = Table(
            [
                Column(dt.INT64, data=jnp.arange(100),
                       validity=jnp.asarray(np.arange(100) % 3 != 0)),
                Column(dt.FLOAT64, data=jnp.asarray(
                    np.random.default_rng(0).integers(0, 2**64, 100, np.uint64)
                )),
            ],
            ["k", "bits"],
        )
        want = _tree_bytes(t)
        h = cat.register("tbl", t)
        h.spill(to_disk=True)
        back = h.get()
        assert isinstance(back, Table) and back.names == t.names
        assert _tree_bytes(back) == want

    def test_pinned_never_spills(self):
        cat = memgov.BufferCatalog()
        h = cat.register("hot", jnp.zeros(100, jnp.float64), pinned=True)
        assert cat.spill_until(10**9) == 0
        assert h.tier == memgov.TIER_DEVICE
        with pytest.raises(ValueError):
            h.spill()
        h.unpin()
        assert cat.spill_until(1) == h.nbytes
        assert h.tier == memgov.TIER_HOST

    def test_lru_order_spills_coldest_first(self):
        cat = memgov.BufferCatalog()
        a = cat.register("a", jnp.zeros(100, jnp.float64))  # 800 B
        b = cat.register("b", jnp.zeros(100, jnp.float64))
        a.get()  # refresh a: b is now the LRU victim
        assert cat.spill_until(1) == 800
        assert b.tier == memgov.TIER_HOST and a.tier == memgov.TIER_DEVICE

    def test_spilled_bytes_and_respilled_counters_exact(self):
        cat = memgov.BufferCatalog()
        h = cat.register("x", jnp.zeros(500, jnp.float64))  # 4000 B
        before = _counter("memgov.spilled_bytes")
        h.spill()
        h.get()
        h.spill()
        assert _counter("memgov.spilled_bytes") == before + 8000
        assert _counter("memgov.respilled") >= 1
        assert _counter("memgov.rematerialized_bytes") >= 4000

    def test_host_budget_demotes_to_disk(self, tmp_path):
        cat = memgov.BufferCatalog(spill_dir=str(tmp_path), host_budget=1000)
        a = cat.register("a", jnp.zeros(100, jnp.float64))  # 800 B
        b = cat.register("b", jnp.zeros(100, jnp.float64))
        a.spill()
        assert a.tier == memgov.TIER_HOST  # under the host budget
        b.spill()  # host tier would be 1600 B: LRU host entry demotes
        assert b.tier == memgov.TIER_HOST
        assert a.tier == memgov.TIER_DISK
        assert cat.host_bytes() <= 1000
        assert _tree_bytes(a.get()) == _tree_bytes(jnp.zeros(100, jnp.float64))

    def test_spill_fail_injection_skips_entry(self):
        """The faultinj ``spill_fail`` kind (keyed on memgov.spill)
        makes a demotion fail: the entry stays resident, the failure is
        counted, the pressure loop keeps going."""
        cat = memgov.BufferCatalog()
        h = cat.register("x", jnp.zeros(100, jnp.float64))
        faultinj.configure(
            {"faults": {"memgov.spill": {"type": "spill_fail", "percent": 100}}}
        )
        before = _counter("memgov.spill_failures")
        assert cat.spill_until(1) == 0
        assert h.tier == memgov.TIER_DEVICE
        assert _counter("memgov.spill_failures") == before + 1
        faultinj.disable()
        assert cat.spill_until(1) == h.nbytes
        assert h.tier == memgov.TIER_HOST

    def test_accounting_only_arena_entries(self):
        cat = memgov.BufferCatalog()
        h = cat.register_host_bytes("sidecar.arena.c1", 1 << 20)
        assert cat.host_bytes() == 1 << 20
        snap = cat.snapshot()
        assert snap["arenas"] == 1 and snap["arena_bytes"] == 1 << 20
        with pytest.raises(ValueError):
            h.get()  # no payload to materialize
        assert cat.spill_until(10**9) == 0  # never a demotion victim
        assert cat.unregister("sidecar.arena.c1")
        assert cat.host_bytes() == 0

    def test_reregister_replaces(self):
        cat = memgov.BufferCatalog()
        cat.register("k", jnp.zeros(10, jnp.float64))
        cat.register("k", jnp.zeros(20, jnp.float64))
        assert cat.snapshot()["entries"] == 1
        assert cat.device_bytes() == 160


# ---------------------------------------------------------------------------
# pressure loop + admission integration
# ---------------------------------------------------------------------------


class TestPressure:
    def test_acquire_spills_cold_buffers_to_fit(self):
        ctl, cat = _new_pair(1000)
        cold = cat.register("cold", jnp.zeros(100, jnp.float64))  # 800 B
        before = _counter("memgov.spilled_bytes")
        adm = ctl.acquire(600, "hot")  # 800 + 600 > 1000: must spill
        assert cold.tier == memgov.TIER_HOST
        assert _counter("memgov.spilled_bytes") == before + 800
        adm.release()

    def test_pinned_residents_bound_the_budget(self):
        ctl, cat = _new_pair(1000)
        cat.register("pinned", jnp.zeros(100, jnp.float64), pinned=True)
        with pytest.raises(MemoryBudgetExceeded):
            ctl.acquire(600, "hot")  # 800 pinned + 600 can never fit
        ctl.acquire(150, "small").release()  # 800 + 150 fits fine

    def test_ensure_fits_grows_the_held_admission(self):
        """An in-op escalation RESERVES the escalated footprint: after
        ensure_fits, a concurrent admission can no longer slip into the
        bytes the doubled buffers are about to use."""
        ctl, _ = _new_pair(1000, max_wait_s=0.15)
        adm = ctl.acquire(100, "op")
        ctl.ensure_fits(600, "op.escalation", admission=adm)
        assert ctl.in_use() == 600 and adm.nbytes == 600
        with pytest.raises(MemoryBudgetExceeded):
            ctl.acquire(500, "rival")  # 600 + 500 > 1000 now
        adm.release()
        assert ctl.in_use() == 0
        # an escalation that cannot fit leaves the reservation as-is
        adm2 = ctl.acquire(100, "op2")
        with pytest.raises(MemoryBudgetExceeded):
            ctl.ensure_fits(2000, "op2.escalation", admission=adm2)
        assert ctl.in_use() == 100 and adm2.nbytes == 100
        adm2.release()

    def test_spill_survives_dead_disk_tier(self):
        """A sick disk tier (unwritable SRJT_SPILL_DIR under a host
        budget) degrades to an over-budget host tier — the device spill
        still lands and admission never sees the OSError."""
        cat = memgov.BufferCatalog(
            spill_dir="/proc/definitely-not-writable/spill", host_budget=100
        )
        a = cat.register("a", jnp.zeros(100, jnp.float64))  # 800 B
        before = _counter("memgov.spill_failures")
        assert cat.spill_until(1) == 800  # device spill freed its bytes
        assert a.tier == memgov.TIER_HOST  # host copy stands, disk failed
        assert _counter("memgov.spill_failures") == before + 1

    def test_smcache_drop_last_resort(self, monkeypatch):
        from spark_rapids_jni_tpu.parallel import _smcache

        monkeypatch.setenv("SRJT_MEMGOV_DROP_SMCACHE", "1")
        # preserve the real compiled-program cache across this test
        saved = dict(_smcache._CACHE)
        _smcache._CACHE.clear()
        try:
            _smcache.cached_sm(("memgov-test",), lambda: object())
            assert _smcache.entry_count() == 1
            ctl, _ = _new_pair(1000)
            before = _counter("memgov.smcache_dropped")
            with pytest.raises(MemoryBudgetExceeded):
                ctl.acquire(5000, "too_big")
            assert _smcache.entry_count() == 0
            assert _counter("memgov.smcache_dropped") == before + 1
        finally:
            _smcache._CACHE.clear()
            _smcache._CACHE.update(saved)


# ---------------------------------------------------------------------------
# op_boundary integration
# ---------------------------------------------------------------------------


@op_boundary("memgov_outer_op")
def _outer_op(t):
    return _inner_op(t)


@op_boundary("memgov_inner_op")
def _inner_op(t):
    return t


@op_boundary("memgov_failing_op")
def _failing_op(t):
    raise ValueError("op body failed")


class TestDispatch:
    def test_disabled_governor_never_touches_admission(self, monkeypatch):
        monkeypatch.setenv("SRJT_DEVICE_MEMORY_BUDGET", "10")
        memgov.disable()
        before = _counter("memgov.admitted")
        t = Table([Column(dt.INT64, data=jnp.arange(64))], ["x"])
        _inner_op(t)  # footprint estimate would be far over budget
        assert _counter("memgov.admitted") == before

    def test_outermost_boundary_owns_the_admission(self, monkeypatch):
        monkeypatch.setenv("SRJT_DEVICE_MEMORY_BUDGET", "100000")
        t = Table([Column(dt.INT64, data=jnp.arange(64))], ["x"])
        before = _counter("memgov.admitted")
        with memgov.enabled():
            _outer_op(t)  # dispatches the nested inner op
        assert _counter("memgov.admitted") == before + 1
        assert memgov.controller().in_use() == 0

    def test_memory_bytes_overrides_estimate(self, monkeypatch):
        monkeypatch.setenv("SRJT_DEVICE_MEMORY_BUDGET", "1000")
        t = Table([Column(dt.INT64, data=jnp.arange(10_000))], ["x"])
        with memgov.enabled():
            with pytest.raises(MemoryBudgetExceeded):
                _inner_op(t)  # default estimate: ~160 KB over a 1 KB budget
            _inner_op(t, memory_bytes=100)  # caller knows better
        assert memgov.controller().in_use() == 0

    def test_admission_released_on_op_failure(self, monkeypatch):
        monkeypatch.setenv("SRJT_DEVICE_MEMORY_BUDGET", "100000")
        t = Table([Column(dt.INT64, data=jnp.arange(16))], ["x"])
        with memgov.enabled():
            with pytest.raises(ValueError):
                _failing_op(t, memory_bytes=500)
            assert memgov.controller().in_use() == 0

    def test_admission_denial_engages_retry_split(self, monkeypatch):
        """An over-budget admission raises the retryable
        MemoryBudgetExceeded, which the orchestrator's split path
        halves until the batch fits — the acceptance loop."""
        monkeypatch.setenv("SRJT_DEVICE_MEMORY_BUDGET", "4000")
        calls = []

        @op_boundary("memgov_split_op")
        def proc(t):
            calls.append(t.num_rows)
            return t

        def run(t):
            return proc(t, memory_bytes=t.num_rows * 1000)

        t = Table([Column(dt.INT64, data=jnp.arange(16))], ["x"])
        pol = retry.RetryPolicy(max_attempts=1, split_depth=4)
        with memgov.enabled():
            out = retry.retry_with_split(run, t, op_name="memgov_split", policy=pol)
        assert out.num_rows == 16
        assert np.array_equal(np.asarray(out.column("x").data), np.arange(16))
        assert calls and max(calls) <= 4  # nothing bigger than 4 KB ran
        assert retry.stats()["splits"] >= 2


# ---------------------------------------------------------------------------
# pipeline build tables ride the catalog
# ---------------------------------------------------------------------------


def test_pipeline_registered_build_spills_and_rematerializes():
    from spark_rapids_jni_tpu.ops.expressions import col
    from spark_rapids_jni_tpu.pipeline import (
        Agg, JoinSpec, PlanSpec, compile_plan,
    )

    n = 64
    fact = Table(
        [
            Column(dt.INT64, data=jnp.arange(n) % 8),
            Column(dt.FLOAT64, data=jnp.asarray(
                np.frombuffer(np.arange(n, dtype=np.float64).tobytes(), np.uint64)
            )),
        ],
        ["k", "v"],
    )
    build = Table(
        [
            Column(dt.INT64, data=jnp.arange(8)),
            Column(dt.INT64, data=jnp.arange(8) * 10),
        ],
        ["bk", "payload"],
    )
    plan = PlanSpec(
        joins=(JoinSpec(build="dim", probe_key="k", build_key="bk",
                        num_keys=8, payload=("payload",)),),
        aggregates=(Agg("payload", "sum"),),
    )
    pipe = compile_plan(plan)
    want = pipe(fact, {"dim": build})

    pipe.register_build("dim", build)
    got = pipe(fact)  # no explicit builds: the catalog supplies it
    handle = pipe._build_handles["dim"]
    assert np.asarray(got.column("payload_sum").data).tobytes() == \
        np.asarray(want.column("payload_sum").data).tobytes()

    handle.spill()  # demote between batches, next call re-materializes
    assert handle.tier == memgov.TIER_HOST
    got2 = pipe(fact)
    assert np.asarray(got2.column("payload_sum").data).tobytes() == \
        np.asarray(want.column("payload_sum").data).tobytes()
    assert handle.tier == memgov.TIER_DEVICE
    pipe.unregister_builds()
    assert memgov.catalog().snapshot()["entries"] == 0
    _ = col  # quiet the linter: imported for parity with other tests


# ---------------------------------------------------------------------------
# shuffle capacity escalation routes through the governor
# ---------------------------------------------------------------------------


class TestShuffleEscalation:
    def test_escalation_that_cannot_fit_raises_retryable(self, mesh8, monkeypatch):
        """A capacity doubling whose exchange footprint exceeds the
        budget must surface the retryable MemoryBudgetExceeded (the
        split path), not grow buckets until XLA OOMs."""
        from spark_rapids_jni_tpu.parallel import mesh as mesh_mod, shuffle
        from spark_rapids_jni_tpu.utils.memory import exchange_bytes_estimate

        n = 512
        t = Table(
            [
                Column(dt.INT64, data=jnp.zeros(n, jnp.int64)),  # all -> shard 0
                Column(dt.INT64, data=jnp.arange(n)),
            ],
            ["k", "v"],
        )
        t_s = mesh_mod.shard_table_rows(t, mesh8)
        # budget: admits the op itself (inputs = 16 KB at headroom 1)
        # but refuses the exchange estimate at the per-shard ceiling
        # (17408 bytes) — the final doubling must be denied
        monkeypatch.setenv("SRJT_MEMGOV_HEADROOM", "1.0")
        rb = 17  # 2 int64 lanes + mask byte, the shuffle's own estimate
        ceiling_est = exchange_bytes_estimate(rb, 8, n // 8)
        monkeypatch.setenv("SRJT_DEVICE_MEMORY_BUDGET", str(ceiling_est - 400))
        with memgov.enabled():
            with pytest.raises(MemoryBudgetExceeded):
                shuffle.exchange_by_key(
                    t_s, ["k"], mesh8, capacity=1, on_overflow="retry"
                )
        assert memgov.controller().in_use() == 0

    def test_escalation_admitted_under_ample_budget(self, mesh8, monkeypatch):
        """Same skew, budget that fits: the governed escalation loop
        completes and lands every row."""
        from spark_rapids_jni_tpu.parallel import mesh as mesh_mod, shuffle

        n = 512
        t = Table(
            [
                Column(dt.INT64, data=jnp.asarray(np.arange(n) % 8, jnp.int64)),
                Column(dt.INT64, data=jnp.arange(n)),
            ],
            ["k", "v"],
        )
        t_s = mesh_mod.shard_table_rows(t, mesh8)
        monkeypatch.setenv("SRJT_DEVICE_MEMORY_BUDGET", str(64 << 20))
        before = retry.stats()["capacity_retries"]
        with memgov.enabled():
            pairs, mask, overflow = shuffle.exchange_by_key(
                t_s, ["k"], mesh8, capacity=2, on_overflow="retry"
            )
        assert not bool(np.asarray(overflow).any())
        assert retry.stats()["capacity_retries"] > before
        got = np.sort(np.asarray(pairs[1][0]).reshape(-1)[np.asarray(mask).reshape(-1)])
        np.testing.assert_array_equal(got, np.arange(n))


# ---------------------------------------------------------------------------
# squeeze acceptance: spills + splits interleave, results bit-identical
# ---------------------------------------------------------------------------


class TestSqueeze:
    def test_groupby_squeeze_spills_and_splits_interleave(self, mesh8, monkeypatch):
        """The ISSUE 4 chaos storm: a skewed distributed groupby under
        a pinched budget AND the spill_fail chaos profile — forced
        catalog spills and retry splits interleave, and the result is
        still exactly right."""
        from spark_rapids_jni_tpu.parallel.table_ops import distributed_groupby_table
        from spark_rapids_jni_tpu.utils import memory as mem

        # admission takes the op's 131 KB; the skewed key's buckets grow to a
        # whole shard (~180 KB a device) and exceed the budget, each half's
        # (~90 KB) fit: test_table_ops' sizes
        monkeypatch.setenv("SRJT_DEVICE_MEMORY_BUDGET", "160000")
        rng = np.random.default_rng(3)
        n = 4096
        keys = np.where(rng.integers(0, 10, n) < 9, 0, rng.integers(0, 50, n))
        vals = rng.integers(0, 100, n)
        t = Table(
            [
                Column(dt.INT64, data=jnp.asarray(keys)),
                Column(dt.INT64, data=jnp.asarray(vals)),
            ],
            ["k", "v"],
        )
        # cold decoys: ~120 KB device-resident, so admissions must spill
        decoys = [
            memgov.catalog().register(f"decoy{i}", jnp.zeros(7_500, jnp.float64))
            for i in range(2)
        ]
        faultinj.configure_from_file(_MEMGOV_CHAOS)
        splits_before = mem.split_retry_count()
        spilled_before = _counter("memgov.spilled_bytes")
        with memgov.enabled(), retry.enabled(
            max_attempts=10, base_delay_ms=1, max_delay_ms=8, seed=99
        ):
            out, ovf = distributed_groupby_table(
                t, ["k"], [("v", "sum", "v_sum"), ("v", "mean", "v_mean")], mesh8
            )
        assert not ovf
        assert mem.split_retry_count() > splits_before, "expected budget splits"
        assert _counter("memgov.spilled_bytes") > spilled_before, "expected spills"
        # pressure stops once the request fits, so at least the LRU
        # decoy demoted; the hotter one may legitimately stay resident
        assert any(d.tier != memgov.TIER_DEVICE for d in decoys)
        want, wc = {}, {}
        for k, v in zip(keys.tolist(), vals.tolist()):
            want[k] = want.get(k, 0) + v
            wc[k] = wc.get(k, 0) + 1
        got = dict(zip(out.column("k").to_pylist(), out.column("v_sum").to_pylist()))
        gotm = dict(zip(out.column("k").to_pylist(), out.column("v_mean").to_pylist()))
        assert got == want
        for k in want:
            assert abs(gotm[k] - want[k] / wc[k]) < 1e-9

    def test_q1_bit_identical_under_squeeze(self, monkeypatch):
        """TPC-H q1 with the budget pinched below its comfortable
        footprint: the governed run must spill (cold catalog decoys
        yield to the query) and produce byte-identical results."""
        from spark_rapids_jni_tpu.models.tpch import gen_lineitem, q1

        lineitem = gen_lineitem(1000, seed=7)
        baseline = q1(lineitem)
        want = [np.asarray(c.data).tobytes() for c in baseline.columns]

        est = memgov.estimate_call_bytes((lineitem,), {})
        monkeypatch.setenv("SRJT_DEVICE_MEMORY_BUDGET", str(int(est * 1.2)))
        decoy = memgov.catalog().register(
            "cold_cache", jnp.zeros(max(est // 16, 1024), jnp.float64)
        )
        spilled_before = _counter("memgov.spilled_bytes")
        with memgov.enabled():
            squeezed = q1(lineitem)
        got = [np.asarray(c.data).tobytes() for c in squeezed.columns]
        assert got == want, "squeezed q1 diverged from the unsqueezed run"
        assert _counter("memgov.spilled_bytes") > spilled_before
        assert decoy.tier != memgov.TIER_DEVICE


# ---------------------------------------------------------------------------
# sidecar arena registration surfaces in STATS
# ---------------------------------------------------------------------------


def test_sidecar_arena_registers_with_catalog(tmp_path):
    """OP_SET_ARENA makes the worker's mmap'd arena a host-tier pinned
    catalog entry, visible through the STATS verb (memgov section +
    arena gauges in the registry snapshot)."""
    import json
    import mmap
    import socket
    import struct
    import subprocess
    import sys

    from spark_rapids_jni_tpu.sidecar import (
        ARENA_FLAG,
        OP_SET_ARENA,
        OP_STATS,
        STATUS_OK,
        _recv_exact,
    )

    sock = str(tmp_path / "w.sock")
    proc = subprocess.Popen(
        [sys.executable, "-m", "spark_rapids_jni_tpu.sidecar", "--socket", sock]
    )
    conn = None
    try:
        for _ in range(600):
            if os.path.exists(sock):
                break
            time.sleep(0.1)
        conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        conn.connect(sock)

        size = 1 << 20
        afd = os.memfd_create("memgov-arena")
        os.ftruncate(afd, size)
        arena = mmap.mmap(afd, size)
        import array

        hdr = struct.pack("<IQ", OP_SET_ARENA, 8) + struct.pack("<Q", size)
        conn.sendmsg(
            [hdr],
            [(socket.SOL_SOCKET, socket.SCM_RIGHTS,
              array.array("i", [afd]).tobytes())],
        )
        os.close(afd)
        status, rlen = struct.unpack("<IQ", _recv_exact(conn, 12))
        assert status == STATUS_OK and rlen == 0

        conn.sendall(struct.pack("<IQ", OP_STATS, 0))
        status, rlen = struct.unpack("<IQ", _recv_exact(conn, 12))
        assert (status & ~ARENA_FLAG) == STATUS_OK
        # with an arena installed the response rides IT when it fits
        raw = (
            bytes(arena[:rlen])
            if status & ARENA_FLAG
            else _recv_exact(conn, rlen)
        )
        stats = json.loads(raw.decode())
        assert stats["memgov"]["catalog"]["arenas"] == 1
        assert stats["memgov"]["catalog"]["arena_bytes"] == size
        gauges = stats["snapshot"]["gauges"]
        assert gauges.get("memgov.arena_bytes") == size
        assert gauges.get("memgov.arenas") == 1
    finally:
        if conn is not None:
            conn.close()
        proc.terminate()
        proc.wait(timeout=10)
