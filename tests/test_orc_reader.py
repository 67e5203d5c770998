"""ORC decode tests: pyarrow.orc-written files as the oracle."""

import io

import numpy as np
import pyarrow as pa
import pytest

orc = pytest.importorskip("pyarrow.orc")

import spark_rapids_jni_tpu  # noqa: F401
from spark_rapids_jni_tpu.io.orc_reader import OrcReadError, read_table


def write(table, **kw):
    buf = io.BytesIO()
    orc.write_table(table, buf, **kw)
    return buf.getvalue()


def check_roundtrip(pa_table, **kw):
    data = write(pa_table, **kw)
    got = read_table(data)
    for name in pa_table.column_names:
        expected = pa_table.column(name).to_pylist()
        actual = got.column(name).to_pylist()
        typ = pa_table.schema.field(name).type
        if pa.types.is_floating(typ):
            for e, a in zip(expected, actual):
                assert (e is None) == (a is None)
                if e is not None:
                    assert a == e or abs(e - a) < 1e-6
        elif pa.types.is_date(typ):
            import datetime

            epoch = datetime.date(1970, 1, 1)
            for e, a in zip(expected, actual):
                assert (e is None) == (a is None)
                if e is not None:
                    assert a == (e - epoch).days
        else:
            assert actual == expected, f"column {name}"


BASIC = pa.table({
    "i32": pa.array([1, -2, 3, None, 5], pa.int32()),
    "i64": pa.array([2**40, None, -7, 0, 9], pa.int64()),
    "i8": pa.array([1, None, -8, 127, -128], pa.int8()),
    "f32": pa.array([1.5, 2.5, None, -0.25, 0.0], pa.float32()),
    "f64": pa.array([1e300, None, -2.25, 0.5, 3.125], pa.float64()),
    "s": pa.array(["hello", "", None, "spark", "tpu"], pa.string()),
    "b": pa.array([True, False, None, True, False], pa.bool_()),
})


@pytest.mark.parametrize("codec", ["uncompressed", "zlib", "snappy", "zstd"])
def test_roundtrip_codecs(codec):
    check_roundtrip(BASIC, compression=codec)


def test_large_int_runs_and_literals(rng):
    n = 20000
    t = pa.table({
        # monotonic -> delta encoding; repeats -> short-repeat; random -> direct/patched
        "mono": pa.array(np.arange(n, dtype=np.int64) * 3 + 7),
        "rep": pa.array(np.repeat(rng.integers(-50, 50, 200), 100).astype(np.int32)),
        "rand": pa.array(rng.integers(-(2**40), 2**40, n).astype(np.int64)),
        "skew": pa.array(
            np.where(rng.integers(0, 100, n) == 0,
                     rng.integers(0, 2**50, n),
                     rng.integers(0, 100, n)).astype(np.int64)
        ),  # outliers force PATCHED_BASE
    })
    check_roundtrip(t)


def test_int64_extremes():
    """Values with |v| >= 2^62 exercise zigzag decode at the unsigned
    64-bit boundary (advisor round-2 high finding: an arithmetic shift
    on the signed reinterpretation silently corrupted these)."""
    ext = [
        -(2**63),  # Long.MIN_VALUE (real Spark sentinel)
        2**63 - 1,  # Long.MAX_VALUE
        2**62 + 7,
        -(2**62 + 7),
        -1,
        0,
        1,
        None,
    ]
    t = pa.table({"v": pa.array(ext, pa.int64())})
    check_roundtrip(t)


def test_int64_extreme_runs():
    """A RUN of Long.MIN_VALUE hits RLEv2 short-repeat with an 8-byte
    value whose top bit is set (advisor round-2: np.int64() raised
    OverflowError instead of decoding)."""
    t = pa.table({
        "minrun": pa.array([-(2**63)] * 64, pa.int64()),
        "maxrun": pa.array([2**63 - 1] * 64, pa.int64()),
        "neg62": pa.array([-(2**62 + 13)] * 64, pa.int64()),
    })
    check_roundtrip(t)


def test_strings_direct_and_dictionary(rng):
    n = 5000
    # low-cardinality -> dictionary encoding; high-cardinality -> direct
    t = pa.table({
        "dict": pa.array([f"cat_{int(x)}" for x in rng.integers(0, 20, n)]),
        "direct": pa.array([f"row_{i}_{int(rng.integers(0, 1 << 30))}" for i in range(n)]),
    })
    check_roundtrip(t)


def test_multiple_stripes(rng):
    n = 150000
    t = pa.table({
        "x": pa.array(rng.integers(0, 1000, n).astype(np.int64)),
        "y": pa.array([f"k{int(v) % 37}" for v in rng.integers(0, 1000, n)]),
    })
    data = write(t, stripe_size=64 * 1024)
    got = read_table(data)
    assert got.column("x").to_pylist() == t.column("x").to_pylist()
    assert got.column("y").to_pylist() == t.column("y").to_pylist()


def test_date_column():
    import datetime

    d = datetime.date
    t = pa.table({"d": pa.array([d(1970, 1, 1), d(2024, 2, 29), None, d(1969, 12, 31)])})
    check_roundtrip(t)


def test_column_selection():
    got = read_table(write(BASIC), columns=["s", "i32"])
    assert got.names == ["i32", "s"]
    assert got.column("s").to_pylist() == BASIC.column("s").to_pylist()


def test_all_nulls_and_empty():
    t = pa.table({"n": pa.array([None, None, None], pa.int32())})
    got = read_table(write(t))
    assert got.column("n").to_pylist() == [None, None, None]
    t2 = pa.table({"a": pa.array([], pa.int64())})
    got2 = read_table(write(t2))
    assert got2.num_rows == 0


def test_nested_supported():
    # nested schemas decode since round 3 (full battery: test_orc_nested.py)
    t = pa.table({"l": pa.array([[1, 2]], pa.list_(pa.int64()))})
    assert read_table(write(t)).column("l").to_pylist() == [[1, 2]]


def test_lz4_codec_native(native):
    check_roundtrip(BASIC, compression="lz4")


def test_timestamps_vs_pyarrow():
    """ORC TIMESTAMP: 2015-epoch seconds + trailing-zero-packed nanos,
    incl. pre-2015 and pre-1970 values with fractional parts."""
    import datetime

    vals = [
        datetime.datetime(2020, 6, 1, 12, 34, 56, 789012),
        datetime.datetime(2015, 1, 1, 0, 0, 0),
        datetime.datetime(2014, 12, 31, 23, 59, 59, 500000),
        datetime.datetime(1969, 12, 31, 23, 59, 59, 123456),
        datetime.datetime(1960, 2, 29, 1, 2, 3),
        None,
        datetime.datetime(2038, 1, 19, 3, 14, 7, 999999),
    ]
    t = pa.table({"ts": pa.array(vals, pa.timestamp("ns"))})
    data = write(t)
    got = read_table(data)
    want = [None if v is None else pa.scalar(v, pa.timestamp("ns")).value for v in vals]
    assert got.column("ts").to_pylist() == want


def test_decimals_vs_pyarrow():
    """ORC DECIMAL: unbounded varint magnitudes + per-value scales,
    through both the 64-bit and 128-bit output widths."""
    import decimal

    d = decimal.Decimal
    small = [d("1.23"), d("-45.60"), d("0.01"), None, d("99999.99"), d("-0.05")]
    t = pa.table({"v": pa.array(small, pa.decimal128(7, 2))})
    got = read_table(write(t))
    assert got.column("v").dtype.scale == -2
    assert got.column("v").to_pylist() == [
        None if v is None else int(v.scaleb(2)) for v in small
    ]

    big = [d("12345678901234567890123456.789"), d("-0.999"), None, d("1e20")]
    t = pa.table({"v": pa.array(big, pa.decimal128(38, 3))})
    got = read_table(write(t))
    assert got.column("v").dtype.scale == -3
    ctx = decimal.Context(prec=50)  # default 28-digit context would round
    assert got.column("v").to_pylist() == [
        None if v is None else int(v.scaleb(3, ctx)) for v in big
    ]


def test_union_as_tagged_struct():
    """ORC UNION decodes as STRUCT<tag, f0, f1> (sparse dense-union
    mapping; cudf has no union type)."""
    import numpy as np

    tags = pa.array([0, 1, 0, 1, 0], pa.int8())
    offsets = pa.array([0, 0, 1, 1, 2], pa.int32())
    ints = pa.array([7, 9, -3], pa.int64())
    strs = pa.array(["x", "yy"], pa.string())
    arr = pa.UnionArray.from_dense(tags, offsets, [ints, strs])
    data = write(pa.table({"u": arr}))

    from spark_rapids_jni_tpu.io.orc_reader import read_table

    t = read_table(data)
    u = t.column(0)
    vals = u.to_pylist()
    assert [v["tag"] for v in vals] == [0, 1, 0, 1, 0]
    assert [v["f0"] for v in vals] == [7, None, 9, None, -3]
    assert [v["f1"] for v in vals] == [None, "x", None, "yy", None]


def test_union_multi_stripe_and_nested_child():
    import numpy as np

    n = 3000
    rng = np.random.default_rng(8)
    tags_np = rng.integers(0, 2, n).astype(np.int8)
    n0 = int((tags_np == 0).sum())
    n1 = n - n0
    offs_np = np.zeros(n, np.int32)
    offs_np[tags_np == 0] = np.arange(n0)
    offs_np[tags_np == 1] = np.arange(n1)
    ints_np = rng.integers(-(2**40), 2**40, n0)
    strs_py = [f"s{i % 13}" for i in range(n1)]
    arr = pa.UnionArray.from_dense(
        pa.array(tags_np, pa.int8()),
        pa.array(offs_np, pa.int32()),
        [pa.array(ints_np, pa.int64()), pa.array(strs_py, pa.string())],
    )
    data = write(pa.table({"u": arr}), stripe_size=64 * 1024)

    from spark_rapids_jni_tpu.io.orc_reader import read_table

    t = read_table(data)
    vals = t.column(0).to_pylist()
    i0 = i1 = 0
    for r in range(n):
        if tags_np[r] == 0:
            assert vals[r]["f0"] == int(ints_np[i0]) and vals[r]["f1"] is None
            i0 += 1
        else:
            assert vals[r]["f1"] == strs_py[i1] and vals[r]["f0"] is None
            i1 += 1
