"""Pallas kernel parity tests (interpret mode — hermetic on CPU)."""

import numpy as np
import pytest

import spark_rapids_jni_tpu  # noqa: F401
import jax.numpy as jnp
from spark_rapids_jni_tpu.columnar import Column
from spark_rapids_jni_tpu.columnar import dtype as dt
from spark_rapids_jni_tpu.ops.hashing import hash_partition_map
from spark_rapids_jni_tpu.ops.pallas_kernels import pallas_partition_map


@pytest.mark.parametrize("np_dt,col_dt", [(np.int64, dt.INT64), (np.int32, dt.INT32)])
@pytest.mark.parametrize("n", [1, 127, 1024, 5000])
def test_partition_map_parity(rng, np_dt, col_dt, n):
    # draw the full dtype range so the int64 high-word lane is exercised
    info = np.iinfo(np_dt)
    keys = rng.integers(info.min, info.max, n, dtype=np_dt)
    want = np.asarray(hash_partition_map([Column(col_dt, data=jnp.asarray(keys))], 16))
    got = np.asarray(pallas_partition_map(jnp.asarray(keys), 16, interpret=True))
    np.testing.assert_array_equal(got, want)


def test_partition_map_range(rng):
    keys = rng.integers(0, 10**9, 2048).astype(np.int64)
    p = np.asarray(pallas_partition_map(jnp.asarray(keys), 7, interpret=True))
    assert p.min() >= 0 and p.max() < 7


def test_rejects_narrow_keys():
    with pytest.raises(ValueError, match="4/8-byte"):
        pallas_partition_map(jnp.zeros((4,), jnp.int16), 4, interpret=True)


def test_groupby_sum_bounded_parity(rng):
    from spark_rapids_jni_tpu.ops.pallas_kernels import pallas_groupby_sum_bounded

    keys = rng.integers(0, 50, 5000).astype(np.int64)
    vals = rng.standard_normal(5000).astype(np.float32)
    got = np.asarray(
        pallas_groupby_sum_bounded(jnp.asarray(keys), jnp.asarray(vals), 50, interpret=True)
    )
    want = np.bincount(keys, weights=vals, minlength=50).astype(np.float32)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize(
    "n,num_keys", [(5000, 4096), (300, 7), (40000, 130), (2048, 16384), (3000, 65536)]
)
def test_groupby_sum_outer_parity(rng, n, num_keys):
    # dual-implementation cross-check: the MXU outer-product kernel must
    # agree with the host bincount oracle on sums AND counts, dropping
    # out-of-domain keys
    from spark_rapids_jni_tpu.ops.pallas_kernels import pallas_groupby_sum_outer

    keys = rng.integers(-5, num_keys + 5, n)
    vals = (rng.standard_normal(n) * 100).astype(np.float32)
    s, c = pallas_groupby_sum_outer(
        jnp.asarray(keys, jnp.int64), jnp.asarray(vals), num_keys, interpret=True
    )
    ind = (keys >= 0) & (keys < num_keys)
    want_s = np.bincount(keys[ind], weights=vals[ind].astype(np.float64), minlength=num_keys)
    want_c = np.bincount(keys[ind], minlength=num_keys)
    np.testing.assert_allclose(np.asarray(s), want_s, rtol=2e-6, atol=1e-3)
    np.testing.assert_array_equal(np.asarray(c), want_c)
    assert c.dtype == jnp.int64


def test_groupby_sum_outer_int64_overflow_keys_dropped():
    from spark_rapids_jni_tpu.ops.pallas_kernels import pallas_groupby_sum_outer

    keys = jnp.asarray([0, 1, 2**32, -3], jnp.int64)
    vals = jnp.asarray([1.0, 2.0, 100.0, 200.0], jnp.float32)
    s, c = pallas_groupby_sum_outer(keys, vals, 4, interpret=True)
    np.testing.assert_allclose(np.asarray(s), [1.0, 2.0, 0.0, 0.0])
    np.testing.assert_array_equal(np.asarray(c), [1, 1, 0, 0])


def test_groupby_sum_outer_limb_split_precision(rng):
    # values chosen so single-bf16 rounding would visibly corrupt sums:
    # the 3-limb split must keep f32-class accuracy
    keys = np.zeros(1000, np.int64)
    vals = (1.0 + rng.random(1000) * 1e-4).astype(np.float32)
    from spark_rapids_jni_tpu.ops.pallas_kernels import pallas_groupby_sum_outer

    s, c = pallas_groupby_sum_outer(jnp.asarray(keys), jnp.asarray(vals), 4, interpret=True)
    want = float(np.sum(vals.astype(np.float64)))
    assert abs(float(s[0]) - want) / want < 1e-6


def test_groupby_sum_bounded_rejects_large_domain():
    from spark_rapids_jni_tpu.ops.pallas_kernels import pallas_groupby_sum_bounded

    with pytest.raises(ValueError, match="num_keys"):
        pallas_groupby_sum_bounded(jnp.zeros((8,), jnp.int32), jnp.zeros((8,)), 100000)


def test_groupby_sum_bounded_int64_overflow_keys_dropped():
    # keys >= 2^32 must drop, not wrap into the domain via the i32 cast
    from spark_rapids_jni_tpu.ops.pallas_kernels import pallas_groupby_sum_bounded

    keys = jnp.asarray([0, 1, 2**32, 2**32 + 1], jnp.int64)
    vals = jnp.asarray([1.0, 2.0, 100.0, 200.0], jnp.float32)
    got = np.asarray(pallas_groupby_sum_bounded(keys, vals, 4, interpret=True))
    np.testing.assert_allclose(got, [1.0, 2.0, 0.0, 0.0])


def test_groupby_sum_bounded_empty_input():
    from spark_rapids_jni_tpu.ops.pallas_kernels import pallas_groupby_sum_bounded

    got = np.asarray(
        pallas_groupby_sum_bounded(
            jnp.zeros((0,), jnp.int64), jnp.zeros((0,), jnp.float32), 4, interpret=True
        )
    )
    np.testing.assert_array_equal(got, np.zeros(4, np.float32))


# ---------------------------------------------------------------------------
# paged hash join build/probe (ISSUE 13)
# ---------------------------------------------------------------------------

from spark_rapids_jni_tpu.columnar import Table
from spark_rapids_jni_tpu.ops import join as join_ops
from spark_rapids_jni_tpu.ops.pallas_kernels import (
    build_paged_table,
    pallas_probe_paged,
)
from spark_rapids_jni_tpu.utils import metrics


def _key_table(keys, col_dt, valid=None):
    v = None if valid is None else jnp.asarray(valid)
    return Table([Column(col_dt, data=jnp.asarray(keys), validity=v)], ["k"])


def _tier_count(tier):
    return metrics.registry().counter(f"dispatch.tier.{tier}").value


@pytest.mark.parametrize("np_dt,col_dt", [(np.int64, dt.INT64), (np.int32, dt.INT32)])
@pytest.mark.parametrize("how", ["inner", "left"])
def test_paged_join_parity_random(rng, np_dt, col_dt, how, monkeypatch):
    # interpret-mode pallas maps must be BIT-identical to the XLA
    # sort-probe formulation: same pairs, same order
    monkeypatch.setenv("SRJT_PALLAS_INTERPRET", "1")
    info = np.iinfo(np_dt)
    lk = rng.integers(info.min, info.max, 400, dtype=np_dt)
    rk = rng.integers(info.min, info.max, 300, dtype=np_dt)
    # plant guaranteed matches (full-range draws rarely collide)
    rk[:100] = lk[:100]
    lt, rt = _key_table(lk, col_dt), _key_table(rk, col_dt)
    got = join_ops.join_gather_maps(lt, rt, how)
    monkeypatch.setenv("SRJT_PALLAS_JOIN", "0")
    want = join_ops.join_gather_maps(lt, rt, how)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


@pytest.mark.parametrize("how", ["inner", "left"])
def test_paged_join_parity_null_heavy(rng, how, monkeypatch):
    monkeypatch.setenv("SRJT_PALLAS_INTERPRET", "1")
    lk = rng.integers(0, 8, 250).astype(np.int64)
    rk = rng.integers(0, 8, 200).astype(np.int64)
    lt = _key_table(lk, dt.INT64, valid=rng.random(250) > 0.6)
    rt = _key_table(rk, dt.INT64, valid=rng.random(200) > 0.6)
    got = join_ops.join_gather_maps(lt, rt, how)
    monkeypatch.setenv("SRJT_PALLAS_JOIN", "0")
    want = join_ops.join_gather_maps(lt, rt, how)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


def test_paged_join_parity_all_overflow_skew(rng, monkeypatch):
    # pathological key skew: EVERY build row in one bucket -> the
    # longest possible overflow chain; must stay correct, just slower
    monkeypatch.setenv("SRJT_PALLAS_INTERPRET", "1")
    lk = np.asarray([7] * 60 + [3] * 5, np.int64)
    rk = np.asarray([7] * 2000, np.int64)
    lt, rt = _key_table(lk, dt.INT64), _key_table(rk, dt.INT64)
    tab = build_paged_table(jnp.asarray(rk))
    assert tab is not None and tab.c_max >= 16  # chains actually engaged
    got = join_ops.join_gather_maps(lt, rt, "inner")
    assert got[0].shape[0] == 60 * 2000
    monkeypatch.setenv("SRJT_PALLAS_JOIN", "0")
    want = join_ops.join_gather_maps(lt, rt, "inner")
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


def test_paged_join_empty_sides_fall_back(monkeypatch):
    # empty probe/build sides gate out of the kernel tier and must take
    # the XLA path (counted as such), returning the XLA shapes
    monkeypatch.setenv("SRJT_PALLAS_INTERPRET", "1")
    empty = _key_table(np.zeros(0, np.int64), dt.INT64)
    some = _key_table(np.asarray([1, 2, 3], np.int64), dt.INT64)
    before = _tier_count("xla")
    lmap, rmap = join_ops.join_gather_maps(some, empty, "inner")
    assert lmap.shape[0] == 0 and rmap.shape[0] == 0
    lmap, rmap = join_ops.join_gather_maps(empty, some, "left")
    assert lmap.shape[0] == 0
    assert _tier_count("xla") == before + 2


def test_paged_join_probe_ranges_oracle(rng):
    # kernel-level contract: r_order[lo : lo+eq] lists exactly the
    # matching build rows in original order
    rk = rng.integers(-5, 5, 700).astype(np.int64)
    lk = rng.integers(-7, 7, 300).astype(np.int64)
    tab = build_paged_table(jnp.asarray(rk))
    lo, eq = pallas_probe_paged(jnp.asarray(lk), None, tab, interpret=True)
    lo, eq, r_order = np.asarray(lo), np.asarray(eq), np.asarray(tab.r_order)
    for i in range(300):
        want = [j for j in range(700) if rk[j] == lk[i]]
        got = list(r_order[lo[i] : lo[i] + eq[i]])
        assert got == want


def test_paged_join_build_gates():
    # over-cap and degenerate build sides return None (keep-XLA signal)
    assert build_paged_table(jnp.zeros((0,), jnp.int64)) is None
    allnull = jnp.zeros((5,), jnp.int64)
    assert build_paged_table(allnull, jnp.zeros((5,), bool)) is None
    big = jnp.zeros(((1 << 16) + 1,), jnp.int64)
    assert build_paged_table(big) is None


def test_paged_join_forced_fallback_mid_suite(rng, monkeypatch):
    # the satellite contract: disabling the tier mid-suite degrades
    # silently and bit-identically, and the tier counters prove which
    # path served each dispatch
    monkeypatch.setenv("SRJT_PALLAS_INTERPRET", "1")
    lk = rng.integers(0, 40, 200).astype(np.int64)
    rk = rng.integers(0, 40, 150).astype(np.int64)
    lt, rt = _key_table(lk, dt.INT64), _key_table(rk, dt.INT64)
    p0, x0 = _tier_count("pallas"), _tier_count("xla")
    a = join_ops.join_gather_maps(lt, rt, "inner")
    assert _tier_count("pallas") == p0 + 1
    monkeypatch.setenv("SRJT_PALLAS_JOIN", "0")
    b = join_ops.join_gather_maps(lt, rt, "inner")
    assert _tier_count("xla") == x0 + 1
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))
    monkeypatch.delenv("SRJT_PALLAS_JOIN")
    c = join_ops.join_gather_maps(lt, rt, "inner")
    assert _tier_count("pallas") == p0 + 2  # re-armed without restart
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(c[1]))


def test_paged_join_unsupported_dtype_keeps_xla(rng, monkeypatch):
    # multi-column and non-integer keys never enter the kernel tier
    monkeypatch.setenv("SRJT_PALLAS_INTERPRET", "1")
    n = 40
    two = Table(
        [
            Column(dt.INT64, data=jnp.asarray(rng.integers(0, 5, n))),
            Column(dt.INT64, data=jnp.asarray(rng.integers(0, 5, n))),
        ],
        ["a", "b"],
    )
    before = _tier_count("pallas")
    join_ops.join_gather_maps(two, two, "inner")
    assert _tier_count("pallas") == before


# ---------------------------------------------------------------------------
# fused ragged decode (ISSUE 13)
# ---------------------------------------------------------------------------

from spark_rapids_jni_tpu.ops.pallas_kernels import pallas_ragged_compact
from spark_rapids_jni_tpu.ops.ragged_bytes import (
    build_pool32,
    ragged_compact,
    ragged_compact_tiered,
)


def _ragged_case(rng, n, max_len, gap, null_frac=0.0):
    lens = rng.integers(0, max_len + 1, n).astype(np.int64) if max_len else np.zeros(n, np.int64)
    if null_frac:
        lens[rng.random(n) < null_frac] = 0  # null strings own no bytes
    gaps = rng.integers(0, gap + 1, n).astype(np.int64)
    base = np.cumsum(np.concatenate([[0], (lens + gaps)[:-1]]))
    plen = int(base[-1] + lens[-1] + gaps[-1]) + 5
    pool = rng.integers(1, 255, max(plen, 1)).astype(np.uint8)
    offs = np.concatenate([[0], np.cumsum(lens)])
    return jnp.asarray(pool), jnp.asarray(base), jnp.asarray(offs), int(offs[-1])


@pytest.mark.parametrize(
    "n,max_len,gap,null_frac",
    [
        (50, 13, 7, 0.0),
        (1, 37, 0, 0.0),
        (300, 32, 600, 0.4),  # big inter-row gaps, null-heavy
        (1000, 3, 0, 0.0),  # tiny strings: many rows per output block
        (20, 257, 11, 0.0),  # max-width rows
        (500, 16, 0, 0.9),  # almost-all-null
    ],
)
def test_fused_decode_parity(rng, n, max_len, gap, null_frac):
    pool, base, offs, total = _ragged_case(rng, n, max_len, gap, null_frac)
    want = np.asarray(ragged_compact(pool, base, offs, total))
    got = pallas_ragged_compact(pool, base, offs, total, interpret=True)
    assert got is not None
    np.testing.assert_array_equal(np.asarray(got), want)


def test_fused_decode_empty_and_all_null(rng):
    pool, base, offs, total = _ragged_case(rng, 64, 0, 5)
    assert total == 0
    got = pallas_ragged_compact(pool, base, offs, total, interpret=True)
    assert np.asarray(got).shape == (0,)


def test_fused_decode_padded_matrix_layout(rng):
    # the strings.py ragged_compact shape: base = r*W over a padded pool
    w, n = 24, 200
    lens = rng.integers(0, w + 1, n).astype(np.int64)
    pool = jnp.asarray(rng.integers(0, 255, n * w).astype(np.uint8))
    base = jnp.asarray((np.arange(n) * w).astype(np.int64))
    offs = jnp.asarray(np.concatenate([[0], np.cumsum(lens)]))
    total = int(offs[-1])
    want = np.asarray(ragged_compact(pool, base, offs, total))
    got = np.asarray(pallas_ragged_compact(pool, base, offs, total, interpret=True))
    np.testing.assert_array_equal(got, want)


def test_fused_decode_shared_pool32(rng):
    # multi-column callers build pool32 ONCE; results must not depend
    # on who built it
    pool, base, offs, total = _ragged_case(rng, 120, 20, 9)
    p32 = build_pool32(pool)
    a = np.asarray(pallas_ragged_compact(pool, base, offs, total, interpret=True))
    b = np.asarray(
        pallas_ragged_compact(pool, base, offs, total, pool32=p32, interpret=True)
    )
    np.testing.assert_array_equal(a, b)


def test_fused_decode_window_gate_returns_none(rng):
    # a hint past the VMEM caps is the keep-XLA signal, not an error
    pool, base, offs, total = _ragged_case(rng, 50, 9, 3)
    from spark_rapids_jni_tpu.ops import pallas_kernels as pk

    assert (
        pallas_ragged_compact(
            pool, base, offs, total, interpret=True,
            hint=(pk._PD_MAX_RW + 1, 128),
        )
        is None
    )
    assert (
        pallas_ragged_compact(
            pool, base, offs, total, interpret=True,
            hint=(8, pk._PD_MAX_WIN + 1),
        )
        is None
    )


def test_tiered_decode_forced_fallback_mid_suite(rng, monkeypatch):
    monkeypatch.setenv("SRJT_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("SRJT_PALLAS_DECODE", "1")
    pool, base, offs, total = _ragged_case(rng, 400, 16, 4, 0.2)
    p0, x0 = _tier_count("pallas"), _tier_count("xla")
    a = np.asarray(ragged_compact_tiered(pool, base, offs, total))
    assert _tier_count("pallas") == p0 + 1
    monkeypatch.setenv("SRJT_PALLAS_DECODE", "0")
    b = np.asarray(ragged_compact_tiered(pool, base, offs, total))
    assert _tier_count("xla") == x0 + 1
    np.testing.assert_array_equal(a, b)
    monkeypatch.setenv("SRJT_PALLAS_DECODE", "1")
    c = np.asarray(ragged_compact_tiered(pool, base, offs, total))
    assert _tier_count("pallas") == p0 + 2
    np.testing.assert_array_equal(a, c)


def test_string_decode_through_row_conversion(rng, monkeypatch):
    # end to end: convert_from_rows' string chars ride the fused kernel
    # when armed, bit-identical to the XLA decode program
    from spark_rapids_jni_tpu.models.datagen import Profile, create_random_table
    from spark_rapids_jni_tpu.ops import row_conversion as rc

    monkeypatch.setenv("SRJT_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("SRJT_PALLAS_DECODE", "1")
    dtypes = [dt.INT32, dt.STRING, dt.FLOAT64, dt.STRING]
    profiles = {1: Profile(min_length=0, max_length=24), 3: Profile(min_length=1, max_length=9)}
    table = create_random_table(dtypes, 1500, seed=77, profiles=profiles)
    rows = rc.convert_to_rows(table)[0]
    p0 = _tier_count("pallas")
    got = rc.convert_from_rows(rows, table.dtypes())
    assert _tier_count("pallas") > p0
    monkeypatch.setenv("SRJT_PALLAS_DECODE", "0")
    want = rc.convert_from_rows(rows, table.dtypes())
    for c1, c2 in zip(got.columns, want.columns):
        if c1.dtype.id == dt.STRING.id:
            np.testing.assert_array_equal(np.asarray(c1.chars), np.asarray(c2.chars))
            np.testing.assert_array_equal(np.asarray(c1.offsets), np.asarray(c2.offsets))
        else:
            np.testing.assert_array_equal(np.asarray(c1.data), np.asarray(c2.data))


# ---------------------------------------------------------------------------
# tier observability + memoized probes (ISSUE 13 satellites)
# ---------------------------------------------------------------------------


def test_note_tier_counts_registry_direct():
    # registry-direct: counts even with the SRJT_METRICS_ENABLED
    # hot-path gate explicitly OFF (the memory.split_retries
    # bookkeeping discipline)
    from spark_rapids_jni_tpu.utils.dispatch import note_tier

    with metrics.disabled():
        before = _tier_count("pallas")
        note_tier("pallas", "unit_test")
        assert _tier_count("pallas") == before + 1


def test_note_tier_annotates_span():
    from spark_rapids_jni_tpu.utils import tracing
    from spark_rapids_jni_tpu.utils.dispatch import note_tier

    with tracing.enabled():
        tr = tracing.start_trace("tier_probe")
        assert tr is not None
        with tr.activate():
            with tracing.span("op.probe"):
                note_tier("pallas", "unit_test")
                sp = tracing.current_span()
                assert sp is not None and sp.annotations.get("tier") == "pallas"
        tr.finish()


def test_backend_probes_memoized(monkeypatch):
    from spark_rapids_jni_tpu.ops import pallas_kernels as pk

    pk._reset_probe_cache()
    assert pk.on_tpu() is False  # hermetic tier runs on CPU
    # memoized: even a monkeypatched backend probe is not re-consulted
    monkeypatch.setattr(
        jax := __import__("jax"), "default_backend",
        lambda: (_ for _ in ()).throw(AssertionError("probe not memoized")),
    )
    assert pk.on_tpu() is False
    pk._reset_probe_cache()


def test_kernel_tier_mode_gates(monkeypatch):
    from spark_rapids_jni_tpu.ops import pallas_kernels as pk

    monkeypatch.delenv("SRJT_PALLAS_INTERPRET", raising=False)
    assert pk.kernel_tier_mode("SRJT_PALLAS_JOIN") == ""  # CPU, no force
    monkeypatch.setenv("SRJT_PALLAS_INTERPRET", "1")
    assert pk.kernel_tier_mode("SRJT_PALLAS_JOIN") == "interpret"
    monkeypatch.setenv("SRJT_PALLAS_JOIN", "0")
    assert pk.kernel_tier_mode("SRJT_PALLAS_JOIN") == ""


# ---------------------------------------------------------------------------
# a kernel that fails must be seen (ISSUE 22): no silent XLA answer
# ---------------------------------------------------------------------------


from spark_rapids_jni_tpu.utils import errors


class _MosaicRefusal(Exception):
    pass


def _refuse(*_a, **_k):
    raise _MosaicRefusal("injected kernel failure")


def _dispatch_join(rng):
    lk = rng.integers(0, 40, 200).astype(np.int64)
    rk = rng.integers(0, 40, 150).astype(np.int64)
    return join_ops.join_gather_maps(_key_table(lk, dt.INT64), _key_table(rk, dt.INT64), "inner")


def _dispatch_ragged(rng):
    return ragged_compact_tiered(*_ragged_case(rng, 100, 16, 4))


def _dispatch_string_decode(rng):
    from spark_rapids_jni_tpu.models.datagen import Profile, create_random_table
    from spark_rapids_jni_tpu.ops import row_conversion as rc

    table = create_random_table([dt.INT32, dt.STRING], 64, seed=5,
                                profiles={1: Profile(min_length=1, max_length=9)})
    with pytest.MonkeyPatch.context() as mp:  # encode on the XLA path
        mp.setenv("SRJT_PALLAS_DECODE", "0")
        rows = rc.convert_to_rows(table)[0]
    return rc.convert_from_rows(rows, table.dtypes())


_SITES = {
    # dispatch site -> (driver, module holding the kernel entry, its name)
    "join": (_dispatch_join, "spark_rapids_jni_tpu.ops.join", "_pallas_join_maps"),
    "ragged_compact": (_dispatch_ragged, "spark_rapids_jni_tpu.ops.pallas_kernels",
                       "pallas_ragged_compact"),
    "string_decode": (_dispatch_string_decode, "spark_rapids_jni_tpu.ops.row_conversion",
                      "_pallas_string_chars"),
}


@pytest.mark.parametrize("mode", ["tpu", "interpret"])
@pytest.mark.parametrize("site", sorted(_SITES))
def test_kernel_failure_propagates(rng, monkeypatch, site, mode):
    import importlib

    from spark_rapids_jni_tpu.ops import pallas_kernels as pk

    drive, module, entry = _SITES[site]
    monkeypatch.setattr(pk, "kernel_tier_mode", lambda _knob: mode)
    monkeypatch.setattr(importlib.import_module(module), entry, _refuse)
    x0 = _tier_count("xla")
    # op_boundary ops re-raise in the device-error taxonomy, cause kept
    with pytest.raises((_MosaicRefusal, errors.DeviceError)) as raised:
        drive(rng)
    e = raised.value
    assert isinstance(e, _MosaicRefusal) or isinstance(e.__cause__, _MosaicRefusal)
    assert _tier_count("xla") == x0  # nothing answered from the other path


@pytest.mark.parametrize("site", sorted(_SITES))
def test_none_from_kernel_selects_xla(rng, monkeypatch, site):
    """A shape outside the kernel's caps (None) is selection, not a
    fallback: XLA serves and is counted as such."""
    import importlib

    from spark_rapids_jni_tpu.ops import pallas_kernels as pk

    drive, module, entry = _SITES[site]
    monkeypatch.setattr(pk, "kernel_tier_mode", lambda _knob: "interpret")
    monkeypatch.setattr(importlib.import_module(module), entry, lambda *a, **k: None)
    p0, x0 = _tier_count("pallas"), _tier_count("xla")
    drive(rng)
    assert _tier_count("xla") > x0 and _tier_count("pallas") == p0
