"""srjt-cache tests (cache/, ISSUE 17): parameterized-fingerprint
properties (literals-only-differ share a key, structure-differ never
collide across the fuzz corpus), plan-cache hit/rebind/evict economics
with bit-exactness against uncached oracles, single-flight
attach/cancel/failure isolation, memgov-governed subresult
spill-then-rematerialize, table-generation invalidation, the serve
integration (bad-estimate normalization, forecast shedding, chaos
eviction with zero wrong answers), and the stats surfaces."""

import threading
import time

import numpy as np
import pytest

from spark_rapids_jni_tpu import cache, memgov
from spark_rapids_jni_tpu import plan as P
from spark_rapids_jni_tpu.cache import plancache, tablegen
from spark_rapids_jni_tpu.cache.flight import SingleFlight
from spark_rapids_jni_tpu.columnar import Table
from spark_rapids_jni_tpu.columnar.column import Column
from spark_rapids_jni_tpu.plan.rewrites import (
    parameterized_fingerprint,
    rebind_literals,
)
from spark_rapids_jni_tpu.serve.scheduler import Scheduler
from spark_rapids_jni_tpu.utils import deadline, faultinj, metrics
from spark_rapids_jni_tpu.utils.errors import DeadlineExceeded, Overloaded

_COUNTERS = (
    "hits", "misses", "rebinds", "rebind_fallbacks", "insert_verified",
    "insert_rejected", "evictions", "evict_injected", "share",
    "share_fallback", "sub_hits", "sub_misses", "sub_evictions",
    "sub_corrupt", "invalidations",
)


def _vals():
    reg = metrics.registry()
    return {n: reg.value(f"cache.{n}") for n in _COUNTERS}


def _delta(before, after):
    return {n: after[n] - before[n] for n in _COUNTERS}


@pytest.fixture(autouse=True)
def _caches_off(monkeypatch, clean_state):
    # every test starts from the shipped OFF posture, even when the CI
    # tier exports the cache knobs process-wide (the premerge cache
    # tier does) — tests that want the caches armed say so via `armed`
    monkeypatch.delenv("SRJT_PLAN_CACHE", raising=False)
    monkeypatch.delenv("SRJT_SUBRESULT_CACHE", raising=False)


@pytest.fixture
def armed(monkeypatch):
    monkeypatch.setenv("SRJT_PLAN_CACHE", "1")
    monkeypatch.setenv("SRJT_SUBRESULT_CACHE", "1")


def _tables(rows=120):
    rng = np.random.default_rng(11)
    return {
        "fact": Table(
            [Column.from_numpy(np.arange(rows, dtype=np.int64)),
             Column.from_numpy(rng.integers(0, 7, rows).astype(np.int64)),
             Column.from_numpy(rng.random(rows))],
            ["v", "k", "p"],
        ),
    }


def _mk(cut, factor=2.0):
    return P.Aggregate(
        P.Filter(P.Scan("fact"),
                 (P.pcol("v") < P.plit(cut)) & (P.pcol("p") < P.plit(factor))),
        keys=("k",), aggs=(P.AggSpec("v", "sum", "s"),),
    )


# ---------------------------------------------------------------------------
# parameterized fingerprint properties
# ---------------------------------------------------------------------------


class TestParamFingerprint:
    def test_literals_only_differ_same_key(self):
        a, b = parameterized_fingerprint(_mk(10)), parameterized_fingerprint(_mk(99))
        assert a.key == b.key
        assert a.values != b.values
        # but the FULL fingerprints differ — the param key is coarser
        from spark_rapids_jni_tpu.plan.rewrites import fingerprint
        assert fingerprint(_mk(10)) != fingerprint(_mk(99))

    def test_structure_differ_different_key(self):
        plain = parameterized_fingerprint(_mk(10))
        sorted_ = parameterized_fingerprint(
            P.Sort(_mk(10), keys=(("s", False),)))
        assert plain.key != sorted_.key

    def test_literal_type_tags_distinct(self):
        # int vs float vs np.int32 in the same slot = different
        # structures: _PLit.dtype() infers INT64/FLOAT64/INT32 and a
        # rebind across them would change the compiled plan's schema
        f = lambda lit: parameterized_fingerprint(
            P.Filter(P.Scan("fact"), P.pcol("v") < P.plit(lit)))
        keys = {f(10).key, f(10.0).key, f(np.int32(10)).key}
        assert len(keys) == 3

    def test_rebind_reproduces_fresh_plan(self):
        from spark_rapids_jni_tpu.plan.rewrites import fingerprint

        orig = _mk(1998, 0.5)
        orig_fp = fingerprint(orig)
        pf = parameterized_fingerprint(orig)
        mapping = {}
        for tag, value, d in pf.bindings:
            if tag == "int":
                mapping[(tag, value, d)] = 2001
        rebound = rebind_literals(orig, mapping)
        assert fingerprint(rebound) == fingerprint(_mk(2001, 0.5))
        # the original is untouched (frozen nodes, rebuilt not mutated)
        assert fingerprint(orig) == orig_fp

    def test_fuzz_corpus_keys_track_slotted_structure(self):
        """Across the planfuzz seed corpus: equal param keys <=> equal
        literal-slotted structures (no collisions, no spurious splits)."""
        from spark_rapids_jni_tpu.analysis import planfuzz
        from spark_rapids_jni_tpu.plan import rewrites as RW

        by_key = {}
        for seed in range(555, 579):
            p, _ = planfuzz.gen_plan(np.random.default_rng(seed))
            pf = parameterized_fingerprint(p)
            slotted = repr(RW._slot_literals(P.structure(p), []))
            assert by_key.setdefault(pf.key, slotted) == slotted, (
                f"seed {seed}: param-key collision across different "
                f"slotted structures"
            )
        # the corpus is diverse enough for the property to mean something
        assert len(by_key) > 3


# ---------------------------------------------------------------------------
# compiled-plan cache
# ---------------------------------------------------------------------------


class TestPlanCache:
    def test_off_knob_is_plain_compile(self):
        fn = cache.compile_cached(_mk(10), _tables(), name="off")
        assert not isinstance(fn, cache.CachedQuery)
        assert type(fn).__name__ == "CompiledPlan"

    def test_miss_then_exact_hit(self, armed):
        tabs = _tables()
        before = _vals()
        q1 = cache.compile_cached(_mk(10), tabs, name="q")
        q2 = cache.compile_cached(_mk(10), tabs, name="q")
        d = _delta(before, _vals())
        assert d["misses"] == 1 and d["hits"] == 1 and d["rebinds"] == 0
        assert d["insert_verified"] == 1
        assert q2.compiled is q1.compiled  # the retained artifact itself
        assert q1().to_pydict() == q2().to_pydict()

    def test_rebind_hit_bit_exact(self, armed):
        tabs = _tables()
        cache.compile_cached(_mk(10), tabs, name="q")
        before = _vals()
        q = cache.compile_cached(_mk(77), tabs, name="q")
        d = _delta(before, _vals())
        assert d["hits"] == 1 and d["rebinds"] == 1 and d["misses"] == 0
        oracle = P.compile_ir(_mk(77), tabs, name="oracle")
        assert q().to_pydict() == oracle().to_pydict()

    def test_verifier_gate_blocks_insert(self, armed, monkeypatch):
        # a red verifier verdict must keep the artifact OUT of the
        # cache (still returned to run once) — next submission misses
        monkeypatch.setattr(plancache, "verify_for_cache",
                            lambda *a, **k: ["simulated violation"])
        tabs = _tables()
        before = _vals()
        q1 = cache.compile_cached(_mk(10), tabs, name="q")
        q2 = cache.compile_cached(_mk(10), tabs, name="q")
        d = _delta(before, _vals())
        assert d["insert_rejected"] == 2 and d["misses"] == 2
        assert d["hits"] == 0 and d["insert_verified"] == 0
        assert q1().to_pydict() == q2().to_pydict()

    def test_lru_eviction_counts(self, armed, monkeypatch):
        monkeypatch.setenv("SRJT_CACHE_PLAN_ENTRIES", "2")
        tabs = _tables()
        before = _vals()
        cache.compile_cached(_mk(1), tabs, name="a")
        cache.compile_cached(P.Sort(_mk(1), keys=(("s", False),)), tabs,
                             name="b")
        cache.compile_cached(P.Limit(P.Sort(_mk(1), keys=(("s", False),)), 3),
                             tabs, name="c")
        d = _delta(before, _vals())
        assert d["evictions"] == 1
        assert cache.plan_cache().snapshot()["entries"] == 2

    def test_cost_ewma_feeds_predicted_cost(self, armed):
        tabs = _tables()
        q = cache.compile_cached(_mk(10), tabs, name="q")
        assert q.predicted_cost_s is None  # no evidence yet
        q()
        assert q.predicted_cost_s is not None and q.predicted_cost_s > 0

    def test_catalog_signature_splits_schemas(self, armed):
        # same plan over a schema with different dtypes = different entry
        tabs = _tables()
        other = {"fact": Table(
            [Column.from_numpy(np.arange(8, dtype=np.int32)),
             Column.from_numpy(np.zeros(8, dtype=np.int64)),
             Column.from_numpy(np.zeros(8))],
            ["v", "k", "p"],
        )}
        before = _vals()
        cache.compile_cached(_mk(5), tabs, name="q")
        cache.compile_cached(_mk(5), other, name="q")
        d = _delta(before, _vals())
        assert d["misses"] == 2 and d["hits"] == 0


# ---------------------------------------------------------------------------
# single-flight latch
# ---------------------------------------------------------------------------


class TestSingleFlight:
    def test_fan_out_computes_once(self):
        sf = SingleFlight("t")
        gate = threading.Event()
        calls = []

        def thunk():
            gate.wait(5)
            calls.append(1)
            return {"x": 1}

        results = [None] * 6
        def run(i):
            results[i] = sf.run("k", thunk)
        ts = [threading.Thread(target=run, args=(i,)) for i in range(6)]
        for t in ts:
            t.start()
        # let everyone reach the latch, then release the leader
        time.sleep(0.1)
        before = _vals()
        gate.set()
        for t in ts:
            t.join(10)
        assert len(calls) == 1, "exactly one computation per key"
        assert all(r == {"x": 1} for r in results)
        # the waiters shared the leader's leg
        assert metrics.registry().value("cache.share") >= 5

    def test_waiter_cancel_never_cancels_leader(self):
        sf = SingleFlight("t")
        gate = threading.Event()
        out = {}

        def thunk():
            gate.wait(10)
            return 42

        def leader():
            out["leader"] = sf.run("k", thunk)

        def waiter():
            try:
                with deadline.scope(0.1):
                    sf.run("k", thunk)
                out["waiter"] = "no-raise"
            except DeadlineExceeded:
                out["waiter"] = "expired"

        tl = threading.Thread(target=leader)
        tl.start()
        time.sleep(0.05)
        tw = threading.Thread(target=waiter)
        tw.start()
        tw.join(10)
        assert out["waiter"] == "expired"  # the waiter's budget, its exit
        gate.set()
        tl.join(10)
        assert out["leader"] == 42  # the shared leg survived the cancel

    def test_leader_failure_not_fanned_out(self):
        sf = SingleFlight("t")
        gate = threading.Event()
        calls = []

        def thunk():
            calls.append(1)
            if len(calls) == 1:
                gate.wait(5)
                raise RuntimeError("leader crashed")
            return "recomputed"

        out = {}
        def leader():
            try:
                sf.run("k", thunk)
            except RuntimeError:
                out["leader"] = "raised"

        def waiter():
            out["waiter"] = sf.run("k", thunk)

        before = metrics.registry().value("cache.share_fallback")
        tl = threading.Thread(target=leader)
        tl.start()
        time.sleep(0.05)
        tw = threading.Thread(target=waiter)
        tw.start()
        time.sleep(0.05)
        gate.set()
        tl.join(10)
        tw.join(10)
        assert out["leader"] == "raised"
        assert out["waiter"] == "recomputed"  # per-leg fault isolation
        assert metrics.registry().value("cache.share_fallback") == before + 1


# ---------------------------------------------------------------------------
# subresult cache (memgov-governed)
# ---------------------------------------------------------------------------


class TestSubresultCache:
    def test_spill_then_rematerialize_hit_bit_exact(self, armed):
        tabs = _tables()
        q = cache.compile_cached(_mk(50), tabs, name="q")
        first = q().to_pydict()
        sc = cache.subresult_cache()
        assert sc.snapshot()["entries"] > 0
        # demote every cached subresult host-ward, behind the cache's
        # back — exactly what governor pressure does
        with sc._lock:
            handles = [e.handle for e in sc._entries.values()]
        for h in handles:
            h.spill()
        before = _vals()
        again = cache.compile_cached(_mk(50), tabs, name="q")().to_pydict()
        d = _delta(before, _vals())
        assert again == first  # CRC-checked rematerialization, bit-exact
        assert d["sub_hits"] > 0 and d["sub_corrupt"] == 0

    def test_governed_bytes_ride_the_catalog(self, armed):
        tabs = _tables()
        cache.compile_cached(_mk(50), tabs, name="q")()
        entries, nbytes = memgov.catalog().kind_stats("cache")
        assert entries > 0 and nbytes > 0
        snap = memgov.catalog().snapshot()
        assert snap["cache_entries"] == entries
        cache.reset()
        entries, nbytes = memgov.catalog().kind_stats("cache")
        assert entries == 0 and nbytes == 0  # reset unregisters cleanly

    def test_corrupt_entry_degrades_to_recompute(self, armed):
        tabs = _tables()
        q = cache.compile_cached(_mk(50), tabs, name="q")
        first = q().to_pydict()
        sc = cache.subresult_cache()
        # yank the governed entries out from under the cache (the
        # closed-handle flavor of rot); hits must degrade to recompute
        with sc._lock:
            regkeys = [e.regkey for e in sc._entries.values()]
        for rk in regkeys:
            memgov.catalog().unregister(rk)
        before = _vals()
        again = cache.compile_cached(_mk(50), tabs, name="q")().to_pydict()
        d = _delta(before, _vals())
        assert again == first
        assert d["sub_corrupt"] > 0  # rot observed, answered by recompute

    def test_byte_cap_evicts_lru(self, armed, monkeypatch):
        monkeypatch.setenv("SRJT_CACHE_SUBRESULT_BYTES", "1")
        tabs = _tables()
        before = _vals()
        cache.compile_cached(_mk(50), tabs, name="q")()
        d = _delta(before, _vals())
        assert d["sub_evictions"] > 0
        assert cache.subresult_cache().snapshot()["entries"] <= 1

    def test_invalidate_table_drops_dependents(self, armed):
        tabs = _tables()
        q = cache.compile_cached(_mk(50), tabs, name="q")
        first = q().to_pydict()
        assert cache.subresult_cache().snapshot()["entries"] > 0
        before = _vals()
        cache.invalidate_table(tabs["fact"])
        d = _delta(before, _vals())
        assert d["invalidations"] > 0
        assert cache.subresult_cache().snapshot()["entries"] == 0
        # resubmission recomputes (new stamps -> new keys), same answer
        before = _vals()
        again = cache.compile_cached(_mk(50), tabs, name="q")().to_pydict()
        d = _delta(before, _vals())
        assert again == first and d["sub_hits"] == 0 and d["sub_misses"] > 0

    def test_new_table_object_never_aliases(self, armed):
        # a reloaded table (different object, same shape) must not hit
        # subresults computed over the old one — serial-based identity
        tabs1, tabs2 = _tables(), _tables()
        q1 = cache.compile_cached(_mk(50), tabs1, name="q")
        q1()
        before = _vals()
        cache.compile_cached(_mk(50), tabs2, name="q")()
        d = _delta(before, _vals())
        assert d["sub_hits"] == 0  # fresh serials, fresh keys


# ---------------------------------------------------------------------------
# serve integration
# ---------------------------------------------------------------------------


class TestServeIntegration:
    def test_cached_serving_bit_exact(self, armed):
        tabs = _tables()
        oracle = {
            cut: P.compile_ir(_mk(cut), tabs, name="oracle")().to_pydict()
            for cut in (10, 50, 90)
        }
        s = Scheduler(max_concurrent=2, queue_depth=32, name="csrv")
        try:
            handles = [
                (cut, s.submit(_mk(cut), tabs, tenant="t"))
                for cut in (10, 50, 90) for _ in range(4)
            ]
            for cut, h in handles:
                assert h.result(30).to_pydict() == oracle[cut]
        finally:
            assert s.shutdown(drain=False, timeout_s=30.0)
        v = _vals()
        assert v["hits"] >= 9  # 12 submissions, 3 structures-as-misses

    def test_bad_estimate_normalized(self, armed):
        reg = metrics.registry()
        s = Scheduler(max_concurrent=1, queue_depth=8, name="best")
        try:
            def fn():
                return 7
            fn.estimated_memory_bytes = 0  # "free query" lie
            before = reg.value("serve.bad_estimate")
            h = s.submit(fn, tenant="t")
            assert h.result(10) == 7
            assert reg.value("serve.bad_estimate") == before + 1
            assert h._memory_bytes is None  # normalized, not admitted as 0
            # explicit negative estimate normalizes the same way
            h2 = s.submit(lambda: 8, tenant="t", memory_bytes=-5)
            assert h2.result(10) == 8
            assert reg.value("serve.bad_estimate") == before + 2
        finally:
            assert s.shutdown(drain=False, timeout_s=30.0)

    def test_forecast_shed(self, monkeypatch):
        monkeypatch.setenv("SRJT_SERVE_FORECAST_BUDGET_SEC", "5")
        reg = metrics.registry()
        s = Scheduler(max_concurrent=1, queue_depth=8, name="fcst")
        try:
            ev = threading.Event()
            blocker = s.submit(ev.wait, 30, tenant="t")
            t0 = time.monotonic()
            while blocker.status() != "running":
                assert time.monotonic() - t0 < 5
                time.sleep(0.002)

            def pricey():
                return 1
            pricey.predicted_cost_s = 10.0  # EWMA says: 10s of work
            before = reg.value("serve.shed.forecast")
            with pytest.raises(Overloaded) as ei:
                s.submit(pricey, tenant="t")
            assert ei.value.cause == "forecast"
            assert reg.value("serve.shed.forecast") == before + 1
            # a query with NO cost evidence is never forecast-shed
            h = s.submit(lambda: 2, tenant="t")
            ev.set()
            assert h.result(10) == 2
        finally:
            ev.set()
            assert s.shutdown(drain=False, timeout_s=30.0)

    def test_chaos_cache_evict_zero_wrong_answers(self, armed):
        tabs = _tables()
        oracle = P.compile_ir(_mk(50), tabs, name="oracle")().to_pydict()
        faultinj.configure({
            "seed": 3,
            "faults": {"cache.*": {"type": "cache_evict", "percent": 100}},
        })
        before = _vals()
        try:
            for _ in range(5):
                got = cache.compile_cached(_mk(50), tabs, name="q")()
                assert got.to_pydict() == oracle
        finally:
            faultinj.disable()
        d = _delta(before, _vals())
        assert d["evict_injected"] > 0  # the storm really landed


# ---------------------------------------------------------------------------
# stats surfaces
# ---------------------------------------------------------------------------


class TestStats:
    def test_stats_report_cache_section(self, armed):
        from spark_rapids_jni_tpu import runtime

        cache.compile_cached(_mk(10), _tables(), name="q")()
        rep = runtime.stats_report()
        sec = rep["cache"]
        assert sec["enabled"]["plan"] is True
        assert sec["counters"]["misses"] >= 1
        assert sec["plan"]["entries"] >= 1
        assert "governed" in sec and sec["governed"]["entries"] >= 0
        # the pretty renderer walks the new section without choking
        assert "cache" in runtime.stats_report(pretty=True)

    def test_stage_report_cache_keys(self):
        rep = metrics.stage_report("s")
        assert set(rep["cache"]) == {
            "hits", "misses", "rebinds", "share", "sub_hits",
            "sub_misses", "evictions", "evict_injected",
        }

    def test_off_posture_stats_inert(self):
        sec = cache.stats_section()
        assert sec["enabled"]["plan"] is False
        assert "plan" not in sec  # no singleton was materialized
