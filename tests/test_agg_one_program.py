"""A FLOAT64 sum or mean of the op tier is ONE jitted program (ISSUE 29).

``ops/aggregate._f64_sum_mean`` has to hand back the u64 lanes and the
validity that the un-jitted chain over ``ops/f64acc`` hands back — here
on the CPU, and on the chip by ``benchmarks/calls/pr29_exact.py`` —
through ``groupby_aggregate`` and through ``ops/window``'s use of
``_agg_column``; and a second call at the same shapes compiles nothing,
``mean`` included (``_limb_divide``'s scan body is traced once).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.columnar import dtype as dt
from spark_rapids_jni_tpu.ops import aggregate, f64acc
from spark_rapids_jni_tpu.ops.aggregate import groupby_aggregate
from spark_rapids_jni_tpu.ops.sort import sorted_order
from spark_rapids_jni_tpu.ops.window import window_aggregate
from spark_rapids_jni_tpu.utils import metrics

N = 600


def _eager_chain(col, order, seg, num, how):
    """The branch of ``_agg_column`` as it stood before the one program:
    every ``jnp`` call a launch of its own."""
    valid = col.valid_mask()[order]
    bits = col.data[order]
    if how == "sum":
        out = f64acc.segment_sum_f64bits(bits, seg, num, valid=valid)
    else:
        out, _ = f64acc.segment_mean_f64bits(bits, seg, num, valid=valid)
    any_valid = jax.ops.segment_max(valid.astype(jnp.int32), seg, num) > 0
    return np.asarray(out), np.asarray(any_valid)


def _values(rng, n, kind):
    if kind == "plain":
        return (rng.standard_normal(n) * 1e4).round(2)
    vals = rng.standard_normal(n) * (10.0 ** rng.uniform(-300, 300, n))
    tiny = rng.random(n) < 0.05
    vals[tiny] = 5e-324 * rng.integers(1, 1 << 20, int(tiny.sum()))  # subnormal
    vals[rng.random(n) < 0.02] = np.inf
    vals[rng.random(n) < 0.02] = -np.inf
    vals[rng.random(n) < 0.02] = np.nan
    return vals


def _validity(rng, n, keys, nulls):
    if nulls == "none":
        return None
    valid = rng.random(n) < 0.8
    if nulls == "whole-groups":
        valid &= keys % 3 != 0  # every third group has no valid row
    return valid


def _table(rng, n, groups, kind, nulls):
    keys = rng.integers(0, groups, n).astype(np.int32)
    keys[:groups] = np.arange(groups)[: len(keys)]  # every group is there
    valid = _validity(rng, n, keys, nulls)
    col = Column(
        dt.FLOAT64,
        data=Column.from_numpy(_values(rng, n, kind)).data,
        validity=None if valid is None else jnp.asarray(valid),
    )
    return Table([Column(dt.INT32, data=jnp.asarray(keys)), col], ["k", "v"])


CASES = [
    # groups (the count compiled for), values, nulls
    (1, "plain", "none"),
    (1, "specials", "some"),
    (4, "plain", "none"),
    (4, "plain", "some"),
    (4, "specials", "whole-groups"),
    (17, "plain", "none"),  # compiled for 20
    (17, "specials", "whole-groups"),
    (100, "specials", "some"),  # compiled for 112
]


@pytest.mark.parametrize("how", ["sum", "mean"])
@pytest.mark.parametrize("groups,kind,nulls", CASES)
def test_groupby_returns_the_eager_chains_lanes(rng, groups, kind, nulls, how):
    t = _table(rng, N, groups, kind, nulls)
    keys = t.select(["k"])
    order = sorted_order(keys)
    seg, num = aggregate._segment_ids(keys, order)
    assert num == groups
    want_bits, want_valid = _eager_chain(t.column("v"), order, seg, num, how)
    got = groupby_aggregate(keys, t, [("v", how)]).column(f"v_{how}")
    assert got.dtype == dt.FLOAT64 and got.data.shape == (groups,)
    np.testing.assert_array_equal(np.asarray(got.data), want_bits)
    np.testing.assert_array_equal(np.asarray(got.validity), want_valid)
    if nulls == "whole-groups":
        assert not want_valid[0] and want_valid[1]


@pytest.mark.parametrize("how", ["sum", "mean"])
@pytest.mark.parametrize("groups,kind,nulls", [
    (1, "plain", "none"), (4, "specials", "whole-groups"), (17, "plain", "some")])
def test_window_returns_the_eager_chains_lanes(rng, groups, kind, nulls, how):
    t = _table(rng, N, groups, kind, nulls)
    keys = t.select(["k"])
    order = sorted_order(keys)
    seg, num = aggregate._segment_ids(keys, order)
    want_bits, want_valid = _eager_chain(t.column("v"), order, seg, num, how)
    got = window_aggregate(t, ["k"], [], [("v", how, "w")]).column("w")
    group_of_row = np.asarray(t.column("k").data)  # keys are 0..groups-1: the group's number
    np.testing.assert_array_equal(np.asarray(got.data), want_bits[group_of_row])
    np.testing.assert_array_equal(np.asarray(got.validity), want_valid[group_of_row])


@pytest.mark.parametrize("how", ["sum", "mean"])
def test_zero_rows_are_zero_groups(how):
    keys = Table([Column(dt.INT32, data=jnp.zeros((0,), jnp.int32))], ["k"])
    vals = Table([Column(dt.FLOAT64, data=jnp.zeros((0,), jnp.uint64))], ["v"])
    got = groupby_aggregate(keys, vals, [("v", how)]).column(f"v_{how}")
    assert got.dtype == dt.FLOAT64
    assert got.data.shape == (0,) and got.data.dtype == jnp.uint64
    assert got.validity.shape == (0,)


@pytest.mark.parametrize("num,padded", [
    (0, 0), (1, 1), (4, 4), (8, 8), (9, 10), (16, 16), (17, 20), (704, 768), (1000, 1024),
    (1 << 20, 1 << 20), ((1 << 20) + 1, (1 << 20) + (1 << 18))])
def test_the_group_count_compiled_for(num, padded):
    assert aggregate._static_groups(num) == padded


@pytest.mark.parametrize("how", ["sum", "mean"])
def test_a_second_call_at_the_same_shapes_compiles_nothing(rng, how):
    def call():
        t = _table(rng, 352, 5, "plain", "some")  # shapes no other test of this file has
        out = groupby_aggregate(t.select(["k"]), t, [("v", how)])
        jax.block_until_ready([c.data for c in out.columns])

    call()
    before = metrics.registry().value("xla.backend_compiles")
    programs = aggregate._f64_sum_mean._cache_size()
    call()
    assert metrics.registry().value("xla.backend_compiles") == before
    assert aggregate._f64_sum_mean._cache_size() == programs


def test_a_drifting_group_count_meets_one_program(rng):
    sizes = set()
    for groups in (33, 35, 36):  # all compiled for 40
        t = _table(rng, 480, groups, "plain", "none")
        got = groupby_aggregate(t.select(["k"]), t, [("v", "sum")]).column("v_sum")
        assert got.data.shape == (groups,) and got.validity.shape == (groups,)
        sizes.add(aggregate._f64_sum_mean._cache_size())
    assert len(sizes) == 1
