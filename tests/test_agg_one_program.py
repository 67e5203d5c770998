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
    return _eager_lanes(col.data, col.valid_mask(), order, seg, None, num, how)


def _eager_lanes(data, validity, order, seg, live, num, how):
    """``_eager_chain`` over ``_f64_sum_mean``'s own arguments: the rows
    through ``order`` (None: where they lie), ``live`` masking the absent
    ones, and "any row valid" asked of the rows by a ``segment_max``."""
    valid, bits = (validity, data) if order is None else (validity[order], data[order])
    if live is not None:
        valid = valid & live
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


LANE_CASES = [
    # groups (compiled for ``_static_groups`` of it), what the last group holds
    (1, "plain"), (4, "plain"), (16, "plain"), (17, "plain"), (64, "plain"),
    (4, "nulls only"), (17, "nulls only"),
    (4, "absent rows only"), (64, "absent rows only"),
    (9, "padded"), (17, "padded"),  # compiled for 10 and 20
    (1, "zero rows"), (17, "zero rows"),
    (4, "nan and infinities"), (17, "nan and infinities"),
    (4, "signed zeros"), (17, "signed zeros"),
    (4, "subnormals"), (17, "subnormals"),
]


def _lanes_input(rng, groups, holds, n=240):
    """``_f64_sum_mean``'s (data, validity, order, seg, live) over ``groups``
    groups, the last of which holds ``holds``. Up to 16 groups the rows lie
    where they are numbered (the dense form, ``order`` None); above, they
    are gathered through a permutation (the sorted form)."""
    n = 0 if holds == "zero rows" else n
    seg = rng.integers(0, groups, n).astype(np.int32)
    seg[:groups] = np.arange(groups)[:n]
    vals = _values(rng, n, "plain")
    valid = rng.random(n) < 0.9
    live = np.ones(n, bool)
    last = seg == groups - 1
    k = int(last.sum())
    if holds == "nulls only":
        valid[last] = False
    elif holds == "absent rows only":
        live[last] = False
    elif holds == "padded":  # the absent rows carry the id past the last group, as the dense form numbers them
        live[rng.random(n) < 0.2] = False
        seg[~live] = groups
    elif holds in ("nan and infinities", "signed zeros", "subnormals"):
        valid[last] = True
        if holds == "nan and infinities":
            vals[last] = rng.choice([np.nan, np.inf, -np.inf], k)
        elif holds == "signed zeros":
            vals[last] = rng.choice([0.0, -0.0], k)
        else:
            vals[last] = 5e-324 * rng.integers(-(1 << 20), 1 << 20, k)
    if groups <= 16:
        order, data, validity = None, vals, valid
    else:
        perm = rng.permutation(n).astype(np.int32)
        data, validity = np.empty_like(vals), np.empty_like(valid)
        data[perm], validity[perm] = vals, valid  # what ``order`` gathers is the rows above
        order = jnp.asarray(perm)
    return (jnp.asarray(data.view(np.uint64)), jnp.asarray(validity), order,
            jnp.asarray(seg), jnp.asarray(live))


@pytest.mark.parametrize("how", ["sum", "mean"])
@pytest.mark.parametrize("groups,holds", LANE_CASES)
def test_any_valid_comes_from_the_exponent_maxima_lane_for_lane(rng, groups, holds, how):
    data, validity, order, seg, live = _lanes_input(rng, groups, holds)
    num = aggregate._static_groups(groups)
    want_bits, want_valid = _eager_lanes(data, validity, order, seg, live, num, how)
    got_bits, got_valid = aggregate._f64_sum_mean(data, validity, order, seg, live, num=num, how=how)
    np.testing.assert_array_equal(np.asarray(got_bits), want_bits)
    np.testing.assert_array_equal(np.asarray(got_valid), want_valid)
    if holds in ("nulls only", "absent rows only", "zero rows"):
        assert not want_valid[groups - 1]
    elif holds in ("nan and infinities", "signed zeros", "subnormals"):
        assert want_valid[groups - 1]
    if holds == "padded":
        assert num > groups and not want_valid[groups:].any()


@pytest.mark.parametrize("how", ["sum", "mean"])
@pytest.mark.parametrize("groups", [1, 4, 16, 17, 64])
def test_no_scatter_asks_which_groups_hold_a_row(how, groups):
    """Up to 16 groups the program is masked reductions and a contraction:
    no scatter at all. Above, the exponent maxima are one ``segment_max``
    (and a mean's count one ``segment_sum``): none more."""
    n = 4096
    rows = lambda dtype: jax.ShapeDtypeStruct((n,), dtype)  # noqa: E731
    text = aggregate._f64_sum_mean.lower(
        rows(jnp.uint64), rows(jnp.bool_), None, rows(jnp.int32), rows(jnp.bool_),
        num=aggregate._static_groups(groups), how=how,
    ).as_text()
    want = 0 if groups <= 16 else (1 if how == "sum" else 2)
    assert text.count('"stablehlo.scatter"(') == want
