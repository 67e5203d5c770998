"""A Sort is one program: ``sort_by_key`` answers, to the bit, what
``gather(values, sorted_order(keys, ...))`` answers.

``sort_by_key`` builds its key lanes, sorts and gathers every fixed-width
value column inside ``_sort_gather``; a STRING key's lanes are made
before it and a STRING or LIST value column is gathered after it,
through the order. Every key type here — float keys with -0.0, NaN of
two payloads and both infinities, DECIMAL64 and DECIMAL128, BOOL8,
TIMESTAMP, a STRING longer than one lane — in both directions, with
NULLs first and last, at 0, 1, 4 and 37 rows, has to give the old form's
columns: dtype, every byte of the data, the validity (``None`` kept
``None``), a STRING's offsets and characters, a LIST's offsets and child.
On the chip: ``benchmarks/calls/pr43_sort.py``.
"""

import jax
import numpy as np
import pytest

from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.columnar import dtype as dt
from spark_rapids_jni_tpu.columnar.dtype import TypeId
from spark_rapids_jni_tpu.ops import sort
from spark_rapids_jni_tpu.ops.copying import gather
from spark_rapids_jni_tpu.utils import metrics, trace_sink, tracing

pytestmark = pytest.mark.usefixtures("own_span_log")

KINDS = ("int8", "int32", "int64", "float32", "float64", "decimal64", "decimal128", "bool8", "timestamp", "string")
ROWS = (0, 1, 4, 37)
ORDERS = ((True, True), (True, False), (False, True), (False, False))  # (ascending, nulls_first)

_NEG_NAN = np.array([0xFFF8000000000001], np.uint64).view(np.float64)[0]  # a NaN of another sign and payload
_STRINGS = ("", "a", "ab", "abc", "brand#12", "brand#1", "a" * 20, "a" * 19 + "b", "été")


def _column(kind, rng, n, nulls):
    valid = (rng.random(n) < 0.7) if nulls else None
    if kind == "string":
        vals = [_STRINGS[i] for i in rng.integers(0, len(_STRINGS), n)]
        col = Column.from_pylist(vals, dt.STRING)
        return Column(dt.STRING, validity=None if valid is None else jax.numpy.asarray(valid),
                      offsets=col.offsets, chars=col.chars)
    if kind == "decimal128":
        wide = [int(v) for v in rng.choice([0, 1, 7, 1 << 70, (1 << 127) - 1], n)]
        wide = [(1 << 128) - w if s else w for w, s in zip(wide, rng.random(n) < 0.4)]  # two's complement
        limbs = np.array([[(w >> (32 * k)) & 0xFFFFFFFF for k in range(4)] for w in wide], np.uint32).reshape(n, 4)
        return Column(dt.decimal128(2), data=jax.numpy.asarray(limbs),
                      validity=None if valid is None else jax.numpy.asarray(valid))
    if kind in ("float32", "float64"):
        a = rng.choice([-np.inf, -1.5, -0.0, 0.0, 1.5, np.inf, np.nan, _NEG_NAN], n)
        d = dt.FLOAT32 if kind == "float32" else dt.FLOAT64
    else:
        d, a = {
            "int8": (dt.INT8, rng.integers(-3, 3, n)),
            "int32": (dt.INT32, rng.integers(-5, 5, n)),
            "int64": (dt.INT64, rng.choice([np.iinfo(np.int64).min, -1, 0, 1, np.iinfo(np.int64).max], n)),
            "decimal64": (dt.decimal64(2), rng.integers(-1000, 1000, n)),
            "bool8": (dt.BOOL8, rng.integers(0, 2, n)),
            "timestamp": (dt.TIMESTAMP_MICROSECONDS, rng.integers(-2, 2, n) * 86_400_000_000),
        }[kind]
    return Column.from_numpy(np.asarray(a), d, validity=valid)


def _list_column(rng, n):
    lens = rng.integers(0, 3, n)
    offsets = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    child = Column.from_numpy(rng.integers(0, 9, int(offsets[-1])).astype(np.int32), dt.INT32)
    return Column.list_from_parts(offsets, child, validity=jax.numpy.asarray(rng.random(n) < 0.8))


def _bytes(a):
    return None if a is None else np.asarray(a).tobytes()


def _assert_same_column(got, want):
    assert got.dtype == want.dtype
    assert (got.validity is None) == (want.validity is None)
    for part in ("data", "validity", "offsets", "chars"):
        g, w = getattr(got, part), getattr(want, part)
        assert _bytes(g) == _bytes(w), part
        if g is not None:
            assert (g.shape, g.dtype) == (w.shape, w.dtype), part
    if want.child is not None:
        _assert_same_column(got.child, want.child)


def _assert_same_table(got, want):
    assert got.names == want.names and got.num_columns == want.num_columns
    for g, w in zip(got.columns, want.columns):
        _assert_same_column(g, w)


def _counts():
    reg = metrics.registry()
    return reg.value("sort.one_program"), reg.value("sort.columns_outside")


def _traced(fn, *args, **kwargs):
    """(spans, result) of ``fn(*args)`` under a trace of its own; compiled by an untraced call first."""
    fn(*args, **kwargs)
    trace_sink.reset_for_tests()
    with tracing.enabled():
        qt = tracing.start_trace("sort.test")
        with qt.activate():
            out = fn(*args, **kwargs)
        qt.finish("ok")
    return trace_sink.recorder().last(1)[0]["spans"], out


def _under_sort(spans, name, key):
    """[annotation ``key``] of the ``name`` spans whose parent is ``op.sort_by_key``."""
    sorts = {s["span"] for s in spans if s["name"] == "op.sort_by_key"}
    return [s["annotations"][key] for s in sorted(spans, key=lambda s: s["ts"])
            if s["name"] == name and s["parent"] in sorts]


def _sort_annotations(spans):
    (one,) = [s for s in spans if s["name"] == "op.sort_by_key"]
    return one["annotations"]


@pytest.mark.parametrize("ascending,nulls_first", ORDERS, ids=("asc-nf", "asc-nl", "desc-nf", "desc-nl"))
@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize("kind", KINDS)
def test_the_one_program_sorts_as_gather_over_sorted_order(kind, rows, ascending, nulls_first):
    rng = np.random.default_rng([43, KINDS.index(kind), rows])
    major = _column(kind, rng, rows, nulls=True)
    minor = _column("int32", rng, rows, nulls=False)
    values = Table(
        [major, minor]
        + [_column(k, rng, rows, nulls=i % 2 == 0) for i, k in enumerate(KINDS)]
        + [_list_column(rng, rows)],
        ["major", "minor"] + list(KINDS) + ["list"])
    keys = Table([major, minor], ["k0", "k1"])
    asc, nf = [ascending, True], [nulls_first, False]
    want = gather(values, sort.sorted_order(keys, asc, nf))
    before = _counts()
    got = sort.sort_by_key(values, keys, ascending=asc, nulls_first=nf)
    _assert_same_table(got, want)
    ran = rows > 0  # an empty table takes the old form: nothing to order
    outside = sum(c.dtype.id in (TypeId.STRING, TypeId.LIST) for c in values.columns)  # gathered after the program
    assert _counts() == (before[0] + ran, before[1] + outside * ran)
    if ran:  # the span says what the old form said of its lanes
        spans, _ = _traced(sort.sort_by_key, values, keys, ascending=asc, nulls_first=nf)
        assert _sort_annotations(spans) == _sort_annotations_of_old(keys, asc, nf)


def _sort_annotations_of_old(keys, asc, nf):
    trace_sink.reset_for_tests()
    with tracing.enabled():
        qt = tracing.start_trace("sort.test")
        with qt.activate(), tracing.span("old"):
            sort.sorted_order(keys, asc, nf)
        qt.finish("ok")
    (old,) = [s for s in trace_sink.recorder().last(1)[0]["spans"] if s["name"] == "old"]
    return old["annotations"]


def test_thirteen_lanes_sort_in_two_chunks_inside_the_program():
    """Five INT8 keys and a DECIMAL128 are 13 lanes, one past ``_LEXSORT_LANES``."""
    rng = np.random.default_rng(4313)
    cols = [_column("int8", rng, 37, nulls=True) for _ in range(5)] + [_column("decimal128", rng, 37, nulls=True)]
    keys = Table(cols, [f"k{i}" for i in range(6)])
    values = Table(cols + [_column("float64", rng, 37, nulls=True), _column("string", rng, 37, nulls=True)],
                   [f"k{i}" for i in range(6)] + ["v", "s"])
    asc = [True, False, True, False, True, False]
    spans, got = _traced(sort.sort_by_key, values, keys, ascending=asc)
    _assert_same_table(got, gather(values, sort.sorted_order(keys, asc)))
    assert _sort_annotations(spans) == {"key_lanes": 13, "string_keys": 0}
    assert 13 > sort._LEXSORT_LANES


def _q1_result(rng, decimal):
    """Four result rows, as q1's Sort gets them: two INT8 keys, the sums and means, the count."""
    value = "decimal128" if decimal else "float64"
    flag = Column.from_numpy(np.array([2, 0, 1, 1], np.int8), dt.INT8)
    status = Column.from_numpy(np.array([1, 0, 1, 0], np.int8), dt.INT8)
    aggs = [_column(value, rng, 4, nulls=False) for _ in range(7)]
    aggs = [Column(c.dtype, data=c.data, validity=jax.numpy.ones((4,), bool)) for c in aggs]
    count = Column.from_numpy(np.array([5, 6, 7, 8], np.int64), dt.INT64)
    return Table([flag, status] + aggs + [count], ["flag", "status"] + [f"a{i}" for i in range(7)] + ["count"])


@pytest.mark.parametrize("decimal", (False, True), ids=("float64", "decimal128"))
def test_a_q1_shaped_sort_is_one_launch(decimal):
    values = _q1_result(np.random.default_rng(43), decimal)
    keys = values.select(["flag", "status"])
    spans, got = _traced(sort.sort_by_key, values, keys, ascending=[True, True])
    _assert_same_table(got, gather(values, sort.sorted_order(keys, [True, True])))
    assert np.asarray(got.columns[0].data).tolist() == [0, 1, 1, 2]
    assert _under_sort(spans, "device.launch", "program") == ["_sort_gather"]
    assert _under_sort(spans, "device.wait", "what") == ["sort_input"]
    before = _counts()
    sort.sort_by_key(values, keys, ascending=[True, True])
    assert _counts() == (before[0] + 1, before[1])


def test_a_store_star_shaped_sort_gathers_its_string_after_the_program():
    """816 rows ordered by a FLOAT64 sum descending and an INT32 brand id; the brand's name rides along."""
    rng = np.random.default_rng(55)
    n = 816
    price = Column.from_numpy(rng.uniform(0, 1e6, n).round(2), dt.FLOAT64)
    brand_id = Column.from_numpy(rng.integers(1_001_001, 10_016_017, n).astype(np.int32), dt.INT32)
    brand = _column("string", rng, n, nulls=True)
    values = Table([brand_id, brand, price], ["brand_id", "brand", "ext_price"])
    keys = Table([price, brand_id], ["k0", "k1"])
    asc = [False, True]
    spans, got = _traced(sort.sort_by_key, values, keys, ascending=asc)
    _assert_same_table(got, gather(values, sort.sorted_order(keys, asc)))
    assert _under_sort(spans, "device.launch", "program") == ["_sort_gather"]
    assert _under_sort(spans, "device.wait", "what") == ["sort_input", "string_chars"]
    before = _counts()
    sort.sort_by_key(values, keys, ascending=asc)
    assert _counts() == (before[0] + 1, before[1] + 1)


def test_a_string_key_enters_the_program_as_its_lanes():
    rng = np.random.default_rng(4343)
    name = _column("string", rng, 37, nulls=True)
    values = Table([name, _column("int64", rng, 37, nulls=True)], ["name", "v"])
    keys = Table([name], ["k0"])
    spans, got = _traced(sort.sort_by_key, values, keys, ascending=[False], nulls_first=[False])
    _assert_same_table(got, gather(values, sort.sorted_order(keys, [False], [False])))
    assert _under_sort(spans, "device.launch", "program") == ["_string_lanes", "_sort_gather"]
    assert _sort_annotations(spans) == {"key_lanes": 1 + 3 + 1, "string_keys": 1}  # 20 bytes: three u64 lanes
