"""A group-by over a row mask answers what it answers over the compacted
table, bit for bit (ISSUE 35).

``groupby_aggregate(keys, values, aggs, present=m)`` sorts the absent rows
last behind one more lane, counts the groups of the present rows only and
masks every aggregate with ``live`` (an iota under the mask's count: no
gather of the mask); ``count_all`` is the difference of the groups' starts,
with a mask or without. Every ``how`` x value type x key shape x mask here
has to equal ``groupby_aggregate`` over ``apply_boolean_mask(..., m)`` in
dtype, shape, validity and every lane. On the chip at q1's full size:
``benchmarks/calls/pr35_forms.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.columnar import dtype as dt
from spark_rapids_jni_tpu.columnar.dtype import TypeId
from spark_rapids_jni_tpu.ops import aggregate
from spark_rapids_jni_tpu.ops.aggregate import groupby_aggregate
from spark_rapids_jni_tpu.ops.copying import apply_boolean_mask
from spark_rapids_jni_tpu.ops.sort import sorted_order

N = 320
HOWS = ("sum", "mean", "count", "count_all", "min", "max", "var", "std", "nunique")
# what ``_agg_column`` takes of each type (a DECIMAL128 has no order key and is no number to var/std)
SUPPORTED = {"float64": HOWS, "int32": HOWS, "float32": HOWS,
             "decimal128": ("sum", "count", "count_all", "nunique")}
VALUES = tuple(SUPPORTED)
KEYS = ("int8_pair", "string", "nullable", "none")
MASKS = ("all", "none", "one_row", "half", "one_percent")


def _value_column(rng, kind, nulls=True):
    valid = jnp.asarray(rng.random(N) < 0.8) if nulls else None
    if kind == "float64":
        a = (rng.standard_normal(N) * 10.0 ** rng.integers(-3, 9, N)).round(3)
        return Column(dt.FLOAT64, data=Column.from_numpy(a, dt.FLOAT64).data, validity=valid)
    if kind == "int32":
        return Column(dt.INT32, data=jnp.asarray(rng.integers(-50, 50, N).astype(np.int32)), validity=valid)
    if kind == "float32":
        return Column(dt.FLOAT32, data=jnp.asarray(rng.integers(-400, 400, N).astype(np.float32) / 8), validity=valid)
    limbs = np.zeros((N, 4), np.uint32)
    limbs[:, 0] = rng.integers(0, 1 << 32, N, dtype=np.uint64)
    limbs[:, 1] = rng.integers(0, 1 << 20, N, dtype=np.uint64)
    neg = rng.random(N) < 0.4  # two's complement of the 128-bit value
    wide = (limbs[:, 0].astype(object) | (limbs[:, 1].astype(object) << 32))
    wide = np.where(neg, (1 << 128) - wide, wide)
    for k in range(4):
        limbs[:, k] = [(int(w) >> (32 * k)) & 0xFFFFFFFF for w in wide]
    return Column(dt.decimal128(2), data=jnp.asarray(limbs), validity=valid)


def _key_table(rng, kind):
    if kind == "int8_pair":
        return Table([Column.from_numpy(rng.integers(0, 3, N).astype(np.int8), dt.INT8),
                      Column.from_numpy(rng.integers(0, 2, N).astype(np.int8), dt.INT8)], ["flag", "status"])
    if kind == "string":
        brands = ["exportischolar #1", "exportischolar #2", "amalgimporto #1", "", "edu packscholar #2"]
        return Table([Column.from_pylist([brands[i] for i in rng.integers(0, len(brands), N)], dt.STRING)], ["brand"])
    if kind == "nullable":
        return Table([Column.from_numpy(rng.integers(0, 4, N).astype(np.int32), dt.INT32,
                                        validity=rng.random(N) < 0.85)], ["k"])
    return Table([Column(dt.INT32, data=jnp.zeros((N,), jnp.int32))], ["__g"])  # a global aggregate's one group


def _mask(kind):
    """One mask a kind, whatever the case: the compacted twin then has five
    row counts in all, and the eager programs behind it compile five times."""
    rng = np.random.default_rng(MASKS.index(kind))
    if kind == "all":
        return np.ones(N, bool)
    if kind == "none":
        return np.zeros(N, bool)
    if kind == "one_row":
        m = np.zeros(N, bool)
        m[int(rng.integers(0, N))] = True
        return m
    return rng.random(N) < (0.5 if kind == "half" else 0.01)


def _same_column(got: Column, want: Column, what):
    assert got.dtype == want.dtype, what
    if got.dtype.id == TypeId.STRING:
        assert got.to_pylist() == want.to_pylist(), what
        return
    assert got.data.dtype == want.data.dtype and got.data.shape == want.data.shape, what
    assert (got.validity is None) == (want.validity is None), what
    np.testing.assert_array_equal(np.asarray(got.valid_mask()), np.asarray(want.valid_mask()), err_msg=str(what))
    assert np.asarray(got.data).tobytes() == np.asarray(want.data).tobytes(), what


def _same_table(got: Table, want: Table, what):
    assert got.names == want.names and got.num_rows == want.num_rows, what
    for name, g, w in zip(got.names, got.columns, want.columns):
        _same_column(g, w, (what, name))


@pytest.mark.parametrize("mask", MASKS)
@pytest.mark.parametrize("keys", KEYS)
@pytest.mark.parametrize("values,how", [(v, how) for v in VALUES for how in SUPPORTED[v]])
def test_a_masked_groupby_is_the_groupby_of_the_compacted_table(values, how, keys, mask):
    rng = np.random.default_rng([VALUES.index(values), KEYS.index(keys), MASKS.index(mask)])
    k = _key_table(rng, keys)
    v = Table([_value_column(rng, values)], ["v"])
    m = jnp.asarray(_mask(mask))
    got = groupby_aggregate(k, v, [("v", how)], present=m)
    want = groupby_aggregate(apply_boolean_mask(k, m), apply_boolean_mask(v, m), [("v", how)])
    _same_table(got, want, (values, how, keys, mask))
    if mask == "none":
        assert got.num_rows == 0
    if mask == "all":  # and a mask that keeps every row is no mask
        _same_table(got, groupby_aggregate(k, v, [("v", how)]), "all present")


@pytest.mark.parametrize("mask", ["half", "one_percent"])
@pytest.mark.parametrize("nulls", ["some", "whole_groups", "none"])
def test_a_value_columns_own_nulls_stay_nulls_under_a_mask(nulls, mask):
    """Every aggregate of one call at once (q1's shape: one sort, many
    aggregates), over a value column whose validity is its own."""
    rng = np.random.default_rng([35, MASKS.index(mask)])
    k = _key_table(rng, "nullable")
    cols = []
    for kind in ("float64", "int32", "float32"):
        c = _value_column(rng, kind, nulls != "none")
        if nulls == "whole_groups":  # group 1 has no valid value at all
            c = Column(c.dtype, data=c.data, validity=c.validity & (k.column("k").data != 1))
        cols.append(c)
    v = Table(cols, ["d", "i", "f"])
    aggs = [(c, how) for c in v.names for how in HOWS]
    m = jnp.asarray(_mask(mask))
    got = groupby_aggregate(k, v, aggs, present=m)
    want = groupby_aggregate(apply_boolean_mask(k, m), apply_boolean_mask(v, m), aggs)
    _same_table(got, want, (nulls, mask))
    if nulls == "whole_groups" and mask == "half":
        group = np.asarray(got.column("k").data).tolist().index(1)
        assert not bool(got.column("d_sum").validity[group]) and int(got.column("d_count").data[group]) == 0


@pytest.mark.parametrize("mask", [None, "all", "half", "one_row", "none"])
@pytest.mark.parametrize("groups", [1, 4, 17, 100])  # 17: ``_static_groups`` compiles for 20
def test_count_all_by_start_differences_is_the_scatter_of_ones(groups, mask):
    rng = np.random.default_rng([groups, 0 if mask is None else 1 + MASKS.index(mask)])
    key = rng.integers(0, groups, N).astype(np.int32)
    key[:groups] = np.arange(groups)
    k = Table([Column.from_numpy(key, dt.INT32)], ["k"])
    v = Table([_value_column(rng, "float64")], ["v"])
    m = None if mask is None else _mask(mask)
    got = groupby_aggregate(k, v, [("v", "count_all"), ("v", "sum")],
                            present=None if m is None else jnp.asarray(m))
    # the form it replaces: a segment_sum of ones over the sorted rows' ids
    kept = key if m is None else key[m]
    ck = Table([Column.from_numpy(kept, dt.INT32)], ["k"])
    order = sorted_order(ck)
    seg, num = aggregate._segment_ids(ck, order)
    scatter = jax.ops.segment_sum(jnp.ones_like(seg, jnp.int64), seg, num)
    assert got.column("v_count_all").dtype == dt.INT64 and got.column("v_count_all").validity is None
    assert got.column("v_count_all").data.dtype == jnp.int64
    np.testing.assert_array_equal(np.asarray(got.column("v_count_all").data), np.asarray(scatter))
    np.testing.assert_array_equal(np.asarray(got.column("v_count_all").data), np.bincount(kept, minlength=0)[np.unique(kept)])
    assert int(np.asarray(got.column("v_count_all").data).sum()) == len(kept)
    if groups == 17 and mask in (None, "all"):
        assert aggregate._static_groups(num) == 20 and got.num_rows == 17


def test_the_absent_rows_sort_last_and_take_the_id_past_the_groups(rng):
    key = rng.integers(0, 5, N).astype(np.int8)
    m = rng.random(N) < 0.6
    k = Table([Column.from_numpy(key, dt.INT8)], ["k"])
    order = np.asarray(sorted_order(k, present=jnp.asarray(m)))
    kept = int(m.sum())
    want = np.flatnonzero(m)[np.argsort(key[m], kind="stable")]
    np.testing.assert_array_equal(order[:kept], want)  # the order they would have had alone
    np.testing.assert_array_equal(np.sort(order[kept:]), np.flatnonzero(~m))  # the absent rows trail
    live, count = aggregate._live_rows(jnp.asarray(m))
    assert int(count) == kept
    np.testing.assert_array_equal(np.asarray(live), np.arange(N) < kept)
    seg, num = aggregate._segment_ids(k, jnp.asarray(order), live)
    assert num == len(np.unique(key[m]))
    seg = np.asarray(seg)
    assert (seg[kept:] == num).all() and seg[:kept].max() == num - 1 and (np.diff(seg[:kept]) >= 0).all()
