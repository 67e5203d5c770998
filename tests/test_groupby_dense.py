"""A group-by whose integer keys span a handful of values numbers its groups
from the keys' codes and answers what the sort path answers (ISSUE 37).

``groupby_aggregate`` reads the keys' domain with one probe program where
every key is a fixed-width integer or a BOOL8; where the product of the
keys' ranges is at most ``_DENSE_MAX_SLOTS`` no row is sorted and no column
gathered. Every ``how`` x value type x key shape x mask here has to equal the
sort path's answer (the same call with the constant patched to 0, which
shuts the gate behind the probe) in dtype, shape, validity, every lane that
holds a value and row ORDER. The variance family is compared on the chip's
branch (double-float32 deviations, exact sums: nothing depends on the rows'
order); on a backend with real float64 its plain sums do, so there the two
forms agree to a few units in the last place. On the chip at q1's full size:
``benchmarks/calls/pr37_dense.py``.
"""

import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.columnar import dtype as dt
from spark_rapids_jni_tpu.ops import aggregate, bitutils
from spark_rapids_jni_tpu.ops.aggregate import groupby_aggregate
from spark_rapids_jni_tpu.utils import metrics, trace_sink, tracing

pytestmark = pytest.mark.usefixtures("own_span_log")

N = 320
VARIANCES = ("var", "std", "var_pop", "stddev_pop")
HOWS = ("sum", "mean", "count", "count_all", "min", "max", "nunique") + VARIANCES
# what ``_agg_column`` takes of each type (a DECIMAL128 has no order key and is no number to var/std)
SUPPORTED = {"float64": HOWS, "int32": HOWS, "float32": HOWS,
             "decimal128": ("sum", "count", "count_all", "nunique")}
VALUES = tuple(SUPPORTED)
KEYS = ("int8_pair", "bool8", "int32_below_zero", "nullable_int8", "nullable_pair", "all_null", "zeros", "forty")
MASKS = ("no_mask", "half", "one_row", "one_percent")


def _value_column(rng, kind):
    valid = jnp.asarray(rng.random(N) < 0.8)
    if kind == "float64":
        a = (rng.standard_normal(N) * 10.0 ** rng.integers(-3, 9, N)).round(3)
        return Column(dt.FLOAT64, data=Column.from_numpy(a, dt.FLOAT64).data, validity=valid)
    if kind == "int32":
        return Column(dt.INT32, data=jnp.asarray(rng.integers(-50, 50, N).astype(np.int32)), validity=valid)
    if kind == "float32":  # eighths: a float32 sum of them is exact, so the rows' order cannot show
        return Column(dt.FLOAT32, data=jnp.asarray(rng.integers(-400, 400, N).astype(np.float32) / 8), validity=valid)
    limbs = np.zeros((N, 4), np.uint32)
    wide = rng.integers(0, 1 << 52, N, dtype=np.uint64).astype(object)
    wide = np.where(rng.random(N) < 0.4, (1 << 128) - wide, wide)  # two's complement of the 128-bit value
    for k in range(4):
        limbs[:, k] = [(int(w) >> (32 * k)) & 0xFFFFFFFF for w in wide]
    return Column(dt.decimal128(2), data=jnp.asarray(limbs), validity=valid)


def _key_table(rng, kind, under_nulls=1):
    """``under_nulls``: the one value that lies under every NULL key, or None
    for whatever the draw left there. The SORT path orders NULL rows by those
    bytes: with more than one value there it splits (NULL, b) groups of a key
    pair and counts a NULL group's equal values apart (ROADMAP F15), so the
    cases that compare the two forms keep one; the dense form reads none."""
    def int8(hi, validity=None):
        data = rng.integers(0, hi, N).astype(np.int8)
        if validity is not None and under_nulls is not None:
            data[~validity] = under_nulls
        return Column.from_numpy(data, dt.INT8, validity=validity)

    if kind == "int8_pair":  # q1's flags
        return Table([int8(3), int8(2)], ["flag", "status"])
    if kind == "bool8":
        return Table([Column.from_numpy(rng.integers(0, 2, N).astype(np.uint8), dt.BOOL8)], ["b"])
    if kind == "int32_below_zero":
        return Table([Column.from_numpy(rng.integers(-70_003, -69_998, N).astype(np.int32), dt.INT32)], ["k"])
    if kind == "nullable_int8":
        return Table([int8(4, rng.random(N) < 0.85)], ["k"])
    if kind == "nullable_pair":  # the NULL code of the second key sits between the first key's strides
        return Table([int8(2, rng.random(N) < 0.9), int8(3, rng.random(N) < 0.8)], ["a", "b"])
    if kind == "all_null":
        return Table([int8(100, np.zeros(N, bool))], ["k"])
    if kind == "forty":  # past 16 groups an exact sum's per-group reductions are scatters (``ops/f64acc``)
        return Table([Column.from_numpy((1000 + rng.integers(0, 40, N)).astype(np.int16), dt.INT16)], ["k"])
    return Table([Column(dt.INT32, data=jnp.zeros((N,), jnp.int32))], ["__g"])  # a global aggregate's one group


def _mask(kind):
    rng = np.random.default_rng(MASKS.index(kind))
    if kind == "no_mask":
        return None
    if kind == "one_row":
        m = np.zeros(N, bool)
        m[int(rng.integers(0, N))] = True
        return jnp.asarray(m)
    return jnp.asarray(rng.random(N) < (0.5 if kind == "half" else 0.01))


def _moved(before=None):
    reg = metrics.registry()
    now = {k: reg.value(f"groupby.{k}") for k in ("dense", "sorted")}
    return now if before is None else {k: v - before[k] for k, v in now.items()}


def _sorted_form(*args, **kwargs):
    """The sort path's answer: the gate shut behind the probe."""
    before = _moved()
    with mock.patch.object(aggregate, "_DENSE_MAX_SLOTS", 0):
        out = groupby_aggregate(*args, **kwargs)
    assert _moved(before) == {"dense": 0, "sorted": 1}
    return out


def _dense_form(*args, **kwargs):
    before = _moved()
    out = groupby_aggregate(*args, **kwargs)
    assert _moved(before) == {"dense": 1, "sorted": 0}
    return out


def _same_column(got: Column, want: Column, what, rtol=None):
    assert got.dtype == want.dtype, what
    assert got.data.dtype == want.data.dtype and got.data.shape == want.data.shape, what
    assert (got.validity is None) == (want.validity is None), what
    valid = np.asarray(want.valid_mask())
    np.testing.assert_array_equal(np.asarray(got.valid_mask()), valid, err_msg=str(what))
    # what lies under a NULL is nobody's value: the sort path hands on the first row's, the dense form 0
    a, b = np.asarray(got.data)[valid], np.asarray(want.data)[valid]
    if rtol is None:
        assert a.tobytes() == b.tobytes(), what
    else:
        np.testing.assert_allclose(a.view(np.float64), b.view(np.float64), rtol=rtol, atol=0, err_msg=str(what))


@functools.lru_cache(maxsize=None)
def _both_forms(values, keys, mask):
    """(keys, dense, sorted): both forms' answers to every ``how`` the value
    type takes, one call a form, shared by the cases of one (values, keys, mask)."""
    rng = np.random.default_rng([37, VALUES.index(values), KEYS.index(keys), MASKS.index(mask)])
    k = _key_table(rng, keys)
    v = Table([_value_column(rng, values)], ["v"])
    m = _mask(mask)
    aggs = [("v", how) for how in SUPPORTED[values]]
    return k, _dense_form(k, v, aggs, present=m), _sorted_form(k, v, aggs, present=m)


@pytest.mark.parametrize("mask", MASKS)
@pytest.mark.parametrize("keys", KEYS)
@pytest.mark.parametrize("values,how", [(v, how) for v in VALUES for how in SUPPORTED[v]])
def test_the_dense_form_answers_what_the_sort_path_answers(values, how, keys, mask):
    k, dense, sorted_ = _both_forms(values, keys, mask)
    assert dense.names == sorted_.names and dense.num_rows == sorted_.num_rows > 0
    for name in k.names:  # the keys too: the groups' ORDER is the sort path's
        _same_column(dense.column(name), sorted_.column(name), (values, how, keys, mask, name))
    # with real float64 a variance is plain sums of deviations, and the two forms add a group's rows in
    # another order where a NULL key's rows are sorted by what lies under them: the last places may differ
    # there; the chip's branch is exact, bit for bit (the test below)
    loose = how in VARIANCES and bitutils.backend_has_f64()
    _same_column(dense.column(f"v_{how}"), sorted_.column(f"v_{how}"), (values, how, keys, mask),
                 rtol=1e-12 if loose else None)


@pytest.mark.parametrize("mask", ["no_mask", "half"])
@pytest.mark.parametrize("keys", ["int8_pair", "nullable_pair"])
@pytest.mark.parametrize("values,how", [("float64", "var"), ("float64", "stddev_pop"), ("int32", "std"), ("float32", "var_pop")])
def test_on_the_chips_branch_a_variance_is_the_sort_paths_bit_for_bit(values, how, keys, mask, monkeypatch):
    """Double-float32 deviations and exact sums of their squares: nothing
    depends on the rows' order."""
    monkeypatch.setattr(bitutils, "backend_has_f64", lambda: False)
    rng = np.random.default_rng([37, KEYS.index(keys), MASKS.index(mask)])
    k, v, m = _key_table(rng, keys), Table([_value_column(rng, values)], ["v"]), _mask(mask)
    got, want = _dense_form(k, v, [("v", how)], present=m), _sorted_form(k, v, [("v", how)], present=m)
    _same_column(got.column(f"v_{how}"), want.column(f"v_{how}"), (values, how, keys, mask))


@pytest.mark.parametrize("keys", ["nullable_int8", "nullable_pair", "all_null"])
def test_the_dense_form_reads_no_byte_under_a_null_key(keys):
    aggs = [("v", how) for how in HOWS]
    answers = []
    for under_nulls in (1, None):
        rng = np.random.default_rng([37, KEYS.index(keys)])
        k = _key_table(rng, keys, under_nulls)
        answers.append(_dense_form(k, Table([_value_column(rng, "float64")], ["v"]), aggs, present=_mask("half")))
    assert answers[0].names == answers[1].names
    for name in answers[0].names:
        _same_column(answers[0].column(name), answers[1].column(name), (keys, name))


def test_every_aggregate_of_one_call_reads_the_rows_where_they_lie(monkeypatch):
    """q1's shape: many aggregates over one set of group ids, and neither a
    sort of the rows nor a gather of a column among them."""
    rng = np.random.default_rng(37)
    k = _key_table(rng, "int8_pair")
    v = Table([_value_column(rng, kind) for kind in ("float64", "int32", "float32")], ["d", "i", "f"])
    aggs = [(c, how) for c in v.names for how in HOWS if how not in VARIANCES + ("nunique",)]
    m = _mask("half")
    want = _sorted_form(k, v, aggs, present=m)

    def no_sort(*a, **kw):
        raise AssertionError("the dense form sorted its rows")

    monkeypatch.setattr(aggregate, "sorted_order", no_sort)
    monkeypatch.setattr(aggregate, "gather", no_sort)
    got = _dense_form(k, v, aggs, present=m)
    assert got.names == want.names
    for name in got.names:
        _same_column(got.column(name), want.column(name), name)


# ---------------------------------------------------------------------------
# the gate
# ---------------------------------------------------------------------------


def _waits(spans):
    return [s["annotations"]["what"] for s in spans if s["name"] == "device.wait"]


def _traced(fn, *args, **kwargs):
    trace_sink.reset_for_tests()
    with tracing.enabled():
        qt = tracing.start_trace("dense.test")
        with qt.activate():
            out = fn(*args, **kwargs)
        qt.finish("ok")
    return trace_sink.recorder().last(1)[0]["spans"], out


def _segments(spans):
    return [s["annotations"] for s in spans if s["name"] == "groupby.segments"]


@pytest.mark.parametrize("kind", ["string", "float64", "float32", "decimal128", "timestamp", "string_beside_int8"])
def test_a_key_whose_order_is_not_its_integers_launches_no_probe(kind, monkeypatch):
    rng = np.random.default_rng(3)
    cols = {
        "string": [Column.from_pylist([["a", "b", ""][i] for i in rng.integers(0, 3, N)], dt.STRING)],
        "float64": [Column.from_numpy(rng.integers(0, 3, N).astype(np.float64), dt.FLOAT64)],
        "float32": [Column.from_numpy(rng.integers(0, 3, N).astype(np.float32), dt.FLOAT32)],
        "decimal128": [_value_column(rng, "decimal128")],
        "timestamp": [Column.from_numpy(rng.integers(0, 3, N).astype(np.int32), dt.TIMESTAMP_DAYS)],
    }
    cols["string_beside_int8"] = [Column.from_numpy(rng.integers(0, 2, N).astype(np.int8), dt.INT8)] + cols["string"]
    k = Table(cols[kind], [f"k{i}" for i in range(len(cols[kind]))])
    v = Table([_value_column(rng, "float64")], ["v"])

    def no_probe(*a, **kw):
        raise AssertionError("a key that is no integer was probed")

    monkeypatch.setattr(aggregate, "_key_domain", no_probe)
    before = _moved()
    spans, out = _traced(groupby_aggregate, k, v, [("v", "sum"), ("v", "count_all")])
    assert _moved(before) == {"dense": 0, "sorted": 1}
    assert "key_domain" not in _waits(spans) and _waits(spans).count("group_count") == 1
    assert _segments(spans) == [{"groups": out.num_rows}]  # the span the sort path always had, and no other


@pytest.mark.parametrize("extra,dense", [(0, True), (1, False)])
def test_the_bound_is_the_domain_not_the_groups(extra, dense):
    """A domain of exactly ``_DENSE_MAX_SLOTS`` is dense and one more is
    sorted, however few of its slots are groups."""
    width = aggregate._DENSE_MAX_SLOTS + extra
    key = np.where(np.arange(N) % 2 == 0, 5, 5 + width - 1).astype(np.int16)  # two groups, a domain of ``width``
    k = Table([Column.from_numpy(key, dt.INT16)], ["k"])
    v = Table([Column.from_numpy(np.arange(N, dtype=np.float64), dt.FLOAT64)], ["v"])
    before = _moved()
    spans, out = _traced(groupby_aggregate, k, v, [("v", "sum"), ("v", "count_all")])
    assert _moved(before) == {"dense": int(dense), "sorted": int(not dense)}
    assert np.asarray(out.column("k").data).tolist() == [5, 5 + width - 1]
    assert np.asarray(out.column("v_count_all").data).tolist() == [N // 2, N // 2]
    assert np.asarray(out.column("v_sum").data).view(np.float64).tolist() == [float(sum(range(0, N, 2))), float(sum(range(1, N, 2)))]
    first = {"dense": dense, "domain": width}
    if dense:
        assert _segments(spans) == [{**first, "groups": 2}]
        assert _waits(spans) == ["key_domain", "group_count"]
        assert not [s for s in spans if s["name"] == "groupby.sort"]
    else:  # the probe's span, then the sort path's own
        assert _segments(spans) == [first, {"groups": 2}]
        assert _waits(spans) == ["key_domain", "sort_order", "group_count"]
        assert len([s for s in spans if s["name"] == "groupby.sort"]) == 1


@pytest.mark.parametrize("kind,lo,hi", [(dt.INT64, np.iinfo(np.int64).min, np.iinfo(np.int64).max),
                                        (dt.UINT64, 0, np.iinfo(np.uint64).max),
                                        (dt.INT8, -128, 127)])
def test_a_key_that_spans_its_whole_type_does_not_overflow_the_hosts_arithmetic(kind, lo, hi):
    key = np.array([hi, lo, hi, lo, lo], kind.np_dtype)
    k = Table([Column.from_numpy(key, kind)], ["k"])
    v = Table([Column.from_numpy(np.array([1.0, 2.0, 4.0, 8.0, 16.0]), dt.FLOAT64)], ["v"])
    before = _moved()
    spans, out = _traced(groupby_aggregate, k, v, [("v", "sum")])
    assert _moved(before) == {"dense": 0, "sorted": 1}
    assert _segments(spans)[0] == {"dense": False, "domain": int(hi) - int(lo) + 1}
    assert np.asarray(out.column("k").data).tolist() == [lo, hi]
    assert np.asarray(out.column("v_sum").data).view(np.float64).tolist() == [26.0, 5.0]


@pytest.mark.parametrize("kind,lo", [(dt.INT64, np.iinfo(np.int64).min), (dt.INT64, np.iinfo(np.int64).max - 3),
                                     (dt.UINT64, np.iinfo(np.uint64).max - 3), (dt.INT8, -128), (dt.UINT8, 252)])
def test_a_narrow_range_at_the_edge_of_its_type_is_dense(kind, lo):
    key = np.array([int(lo) + d for d in (3, 0, 3, 2, 0)], dtype=object).astype(kind.np_dtype)
    k = Table([Column.from_numpy(key, kind, validity=np.array([1, 1, 1, 1, 0], bool))], ["k"])
    v = Table([Column.from_numpy(np.array([1.0, 2.0, 4.0, 8.0, 16.0]), dt.FLOAT64)], ["v"])
    got, want = _dense_form(k, v, [("v", "sum"), ("v", "count_all")]), _sorted_form(k, v, [("v", "sum"), ("v", "count_all")])
    for name in got.names:
        _same_column(got.column(name), want.column(name), name)
    assert [int(x) for x in np.asarray(got.column("k").data)[1:]] == [int(lo), int(lo) + 2, int(lo) + 3]


@pytest.mark.parametrize("rows", ["no_row_present", "no_rows"])
def test_nothing_to_group_gives_the_sort_paths_empty_answer(rows):
    rng = np.random.default_rng(5)
    if rows == "no_rows":
        k = Table([Column(dt.INT8, data=jnp.zeros((0,), jnp.int8))], ["k"])
        v = Table([Column(dt.FLOAT64, data=jnp.zeros((0,), jnp.uint64))], ["v"])
        m = None
    else:
        k, v, m = _key_table(rng, "int8_pair"), Table([_value_column(rng, "float64")], ["v"]), jnp.zeros((N,), bool)
    aggs = [("v", how) for how in ("sum", "mean", "count", "count_all", "min", "nunique")]
    before = _moved()
    got = groupby_aggregate(k, v, aggs, present=m)
    assert _moved(before) == {"dense": 0, "sorted": 1}
    want = _sorted_form(k, v, aggs, present=m)
    assert got.num_rows == want.num_rows == 0 and got.names == want.names
    for g, w in zip(got.columns, want.columns):
        assert g.dtype == w.dtype and g.data.shape == w.data.shape and g.data.dtype == w.data.dtype


def test_a_second_batch_with_other_minima_compiles_nothing():
    """Minima, strides and the NULL shifts go in as device scalars; only the
    domain and the group count are shapes."""
    reg = metrics.registry()

    def batch(lo, seed):
        rng = np.random.default_rng(seed)
        key = (lo + rng.integers(0, 3, N)).astype(np.int32)
        key[:3] = lo + np.arange(3)  # every slot a group, whatever the draw
        return (Table([Column.from_numpy(key, dt.INT32)], ["k"]),
                Table([Column.from_numpy(rng.standard_normal(N), dt.FLOAT64)], ["v"]))

    aggs = [("v", "sum"), ("v", "mean"), ("v", "count_all"), ("v", "max")]
    _dense_form(*batch(10, 1), aggs)
    compiles = reg.value("xla.backend_compiles")
    out = _dense_form(*batch(-2_000_000_000, 2), aggs)
    jax.block_until_ready([c.data for c in out.columns])
    assert reg.value("xla.backend_compiles") == compiles
    assert np.asarray(out.column("k").data).tolist() == [-2_000_000_000, -1_999_999_999, -1_999_999_998]


def test_the_programs_take_arrays_that_live_on_several_devices():
    """Under a mesh the local tier's tables are copies on every chip
    (q95-x4's last aggregates): the dense programs and the rebuilt keys
    follow their inputs."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    if jax.device_count() < 4:
        pytest.skip("needs four devices")
    everywhere = NamedSharding(Mesh(np.array(jax.devices()[:4]), ("data",)), PartitionSpec())
    rng = np.random.default_rng(9)
    k, v = _key_table(rng, "nullable_int8"), Table([_value_column(rng, "float64")], ["v"])
    m = _mask("half")

    def copies(t):
        return Table([Column(c.dtype, data=jax.device_put(c.data, everywhere),
                             validity=None if c.validity is None else jax.device_put(c.validity, everywhere))
                      for c in t.columns], list(t.names))

    aggs = [("v", "sum"), ("v", "count_all"), ("v", "min")]
    got = _dense_form(copies(k), copies(v), aggs, present=jax.device_put(m, everywhere))
    want = _dense_form(k, v, aggs, present=m)
    for name in got.names:
        _same_column(got.column(name), want.column(name), name)
        assert len(got.column(name).data.sharding.device_set) == 4, name
