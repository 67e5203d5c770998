"""Native columnar engine tests: the JVM-facing contract driven through
the C ABI via ctypes (no JDK needed), mirroring the reference's Java
JUnit tier:

- RowConversionTest.java:30-94 round-trips (wide mixed-type tables with
  nulls incl. decimal32/64) through convertToRows/convertFromRows,
- CastStringsTest.java:35-99 toInteger non-ANSI null-on-garbage and
  ANSI CastException row/string assertions,
- plus the dual-implementation cross-check the reference applies to row
  conversion (row_conversion.cpp:43-60): native output must be
  BYTE-IDENTICAL to the Python/XLA op tier.
"""

import numpy as np
import pytest

import spark_rapids_jni_tpu  # noqa: F401
import jax.numpy as jnp
from spark_rapids_jni_tpu import runtime
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.columnar import dtype as dt
from spark_rapids_jni_tpu.ops import row_conversion as rc
from spark_rapids_jni_tpu.ops import zorder as zo
from spark_rapids_jni_tpu.ops.cast_decimal import string_to_decimal
from spark_rapids_jni_tpu.ops.cast_string import string_to_integer

pytestmark = pytest.mark.usefixtures("native")


def col_from(vals, d):
    return Column.from_pylist(vals, d)


def roundtrip_native(table: Table):
    with runtime.NativeTable.from_python(table) as nt:
        with runtime.native_convert_to_rows(nt) as rows:
            with runtime.native_convert_from_rows(rows, table.dtypes()) as back:
                assert back.num_rows == table.num_rows
                assert back.num_columns == table.num_columns
                for i, c in enumerate(table.columns):
                    with back.column(i) as nc:
                        got = nc.to_python(c.dtype)
                    assert got.to_pylist() == c.to_pylist(), f"column {i}"


def test_fixed_width_rows_round_trip_wide():
    # RowConversionTest.fixedWidthRowsRoundTripWide: 8 column patterns
    # repeated 10x, nulls in every column
    cols, names = [], []
    for rep in range(10):
        pat = [
            col_from([3, 9, 4, 2, 20, None], dt.INT64),
            col_from([5.0, 9.5, 0.9, 7.23, 2.8, None], dt.FLOAT64),
            col_from([5, 1, 0, 2, 7, None], dt.INT32),
            col_from([True, False, False, True, False, None], dt.BOOL8),
            col_from([1.0, 3.5, 5.9, 7.1, 9.8, None], dt.FLOAT32),
            col_from([2, 3, 4, 5, 9, None], dt.INT8),
            col_from([5000, 9500, 900, 7230, 2800, None], dt.decimal32(-3)),
            col_from([3, 9, 4, 2, 20, None], dt.decimal64(-8)),
        ]
        for i, c in enumerate(pat):
            cols.append(c)
            names.append(f"c{rep}_{i}")
    roundtrip_native(Table(cols, names))


def test_string_rows_round_trip():
    t = Table(
        [
            col_from(["hello", "", None, "a much longer string value", "x"], dt.STRING),
            col_from([1, 2, 3, 4, 5], dt.INT64),
            col_from([None, "y", "zz", "", None], dt.STRING),
        ],
        ["s1", "v", "s2"],
    )
    roundtrip_native(t)


def test_native_rows_byte_identical_with_python(rng):
    # dual-implementation cross-check: same blob bytes as the XLA op
    kinds = [dt.INT8, dt.INT16, dt.INT32, dt.INT64, dt.FLOAT32, dt.FLOAT64, dt.BOOL8]
    cols = []
    for i in range(23):
        d = kinds[i % len(kinds)]
        vals = rng.integers(0, 100, 37).tolist()
        if d in (dt.FLOAT32, dt.FLOAT64):
            vals = [float(v) for v in vals]
        elif d == dt.BOOL8:
            vals = [bool(v & 1) for v in vals]
        vals = [v if j % 7 else None for j, v in enumerate(vals)]
        cols.append(col_from(vals, d))
    t = Table(cols, [f"c{i}" for i in range(len(cols))])

    py_rows = rc.convert_to_rows(t)
    assert len(py_rows) == 1
    py_blob = np.asarray(py_rows[0].child.data).view(np.uint8).tobytes()
    py_offs = np.asarray(py_rows[0].offsets).tolist()

    with runtime.NativeTable.from_python(t) as nt:
        with runtime.native_convert_to_rows(nt) as rows:
            got = rows.to_python(dt.LIST)
    got_blob = np.asarray(got.child.data).view(np.uint8).tobytes()
    assert np.asarray(got.offsets).tolist() == py_offs
    assert got_blob == py_blob


def _native_to_integer(strings, ansi, d):
    with runtime.NativeColumn.from_python(col_from(strings, dt.STRING)) as sc:
        with runtime.native_cast_string_to_integer(sc, ansi, d) as out:
            return out.to_python(d).to_pylist()


def test_cast_to_integer():
    # CastStringsTest.castToIntegerTest
    assert _native_to_integer(["3", "9", "4", "2", "20", None, "7.6asd"], False, dt.INT64) == [
        3, 9, 4, 2, 20, None, None,
    ]
    assert _native_to_integer(["5", "1", "0", "2", "7", None, "asdf"], False, dt.INT32) == [
        5, 1, 0, 2, 7, None, None,
    ]
    assert _native_to_integer(["2", "3", "4", "5", "9", None, "7.8.3"], False, dt.INT8) == [
        2, 3, 4, 5, 9, None, None,
    ]


def test_cast_to_integer_ansi():
    # CastStringsTest.castToIntegerAnsiTest
    assert _native_to_integer(["3", "9", "4", "2", "20"], True, dt.INT64) == [3, 9, 4, 2, 20]
    with pytest.raises(runtime.NativeCastError) as ei:
        _native_to_integer(["asdf", "9.0.2", "- 4e", "b2", "20-fe"], True, dt.INT64)
    assert ei.value.string_with_error == "asdf"
    assert ei.value.row_with_error == 0


def test_cast_to_integer_matches_python_op(rng):
    corpus = [
        "42", " 42 ", "+7", "-7", "007", "", " ", ".", "1.", "1.99", "-1.5",
        "2147483647", "2147483648", "-2147483648", "-2147483649",
        "127", "128", "-128", "-129", "9" * 25, "x", "4x", "x4", "4 4",
        "\t13\n", "+", "-", "--4", "1e4", None, "18446744073709551615",
    ]
    for d in (dt.INT8, dt.INT16, dt.INT32, dt.INT64, dt.UINT8, dt.UINT64):
        want = string_to_integer(col_from(corpus, dt.STRING), False, d).to_pylist()
        got = _native_to_integer(corpus, False, d)
        assert got == want, d


def _native_to_decimal(strings, precision, scale, ansi=False):
    from spark_rapids_jni_tpu.columnar.dtype import decimal32, decimal64, decimal128

    d = decimal32(scale) if precision <= 9 else (
        decimal64(scale) if precision <= 18 else decimal128(scale)
    )
    with runtime.NativeColumn.from_python(col_from(strings, dt.STRING)) as sc:
        with runtime.native_cast_string_to_decimal(sc, ansi, precision, scale) as out:
            assert out._lib.srjt_column_type(out.handle) == int(d.id)
            assert out._lib.srjt_column_scale(out.handle) == scale
            return out.to_python(d).to_pylist()


def test_cast_to_decimal_goldens():
    """Reference StringToDecimalTests shapes (cast_string.cu battery,
    :245-541): simple/rounding/exponent/overprecision/positive scale."""
    assert _native_to_decimal(["1.23", "-2.5", "0.05", None, "x"], 5, -2) == [
        123, -250, 5, None, None,
    ]
    assert _native_to_decimal(["1.255", "1.254", "-1.255"], 5, -2) == [126, 125, -126]
    assert _native_to_decimal(["1.5e2", "-12E-1", "3e0"], 7, -1) == [1500, -12, 30]
    assert _native_to_decimal(["12345.67"], 4, -2) == [None]  # overprecise
    # positive scale 1: unscaled value excludes the scaled-away digit
    assert _native_to_decimal(["1234", "12345", "150"], 3, 1) == [123, None, 15]
    assert _native_to_decimal(["99999999999999999999", "1"], 20, 0) == [
        99999999999999999999, 1,
    ]


def test_cast_to_decimal_ansi():
    assert _native_to_decimal(["1.5", "2.5"], 4, -1, ansi=True) == [15, 25]
    with pytest.raises(runtime.NativeCastError) as ei:
        _native_to_decimal(["1.5", "bad7", "2"], 4, -1, ansi=True)
    assert ei.value.row_with_error == 1
    assert ei.value.string_with_error == "bad7"


def test_cast_to_decimal_matches_python_op():
    corpus = [
        "0", "1", "-1", "1.5", "-1.5", "1.25", "-1.25", "0.05", ".5", "5.",
        " 42.42 ", "+7.001", "007.900", "", " ", ".", "..", "1..2",
        "1e3", "1E-3", "-1.5e2", "1e", "1e+", "1e99999999999999999999",
        "9" * 40, "0." + "9" * 40, "123456789012345678901234567890123456789",
        "0.000000000000000000000000000000000000001", None,
        "\t1.5\n", "1.5 x", "x1.5", "- 1", "1 1", "nan", "inf",
        "99999999999999999.99", "-99999999999999999.99",
    ]
    for precision, scale in [(5, -2), (9, 0), (18, -6), (38, -10), (10, 2), (3, 1), (38, 0)]:
        want = string_to_decimal(
            col_from(corpus, dt.STRING), False, precision, scale
        ).to_pylist()
        got = _native_to_decimal(corpus, precision, scale)
        assert got == want, (precision, scale)


def test_zorder_matches_python(rng):
    cols = [
        Column(dt.INT32, data=jnp.asarray(rng.integers(-1000, 1000, 50), jnp.int32)),
        Column(dt.INT32, data=jnp.asarray(rng.integers(-1000, 1000, 50), jnp.int32)),
        Column(dt.INT32, data=jnp.asarray(rng.integers(-1000, 1000, 50), jnp.int32)),
    ]
    want = zo.interleave_bits(50, *cols)
    t = Table(cols, ["a", "b", "c"])
    with runtime.NativeTable.from_python(t) as nt:
        with runtime.native_zorder_interleave_bits(nt) as out:
            got = out.to_python(dt.LIST)
    want_bytes = np.asarray(want.child.data).view(np.uint8).tobytes()
    got_bytes = np.asarray(got.child.data).view(np.uint8).tobytes()
    assert got_bytes == want_bytes
    assert np.asarray(got.offsets).tolist() == np.asarray(want.offsets).tolist()


def test_handle_leak_accounting():
    base = runtime.live_columnar_handles()
    t = Table([col_from([1, 2, 3], dt.INT32)], ["a"])
    nt = runtime.NativeTable.from_python(t)
    rows = runtime.native_convert_to_rows(nt)
    assert runtime.live_columnar_handles() > base
    rows.close()
    nt.close()
    assert runtime.live_columnar_handles() == base


def test_invalid_handle_is_error_not_crash():
    lib = runtime.native_lib()
    assert lib.srjt_column_size(987654321) == -1
    assert b"invalid" in lib.srjt_last_error()


# ---------------------------------------------------------------------------
# DecimalUtils through the C ABI, cross-checked against the Python op
# ---------------------------------------------------------------------------


def _dec_col(unscaled_vals, scale):
    return Column.from_pylist(unscaled_vals, dt.decimal128(scale))


def _native_dec_op(op, a, b, scale):
    with runtime.NativeColumn.from_python(a) as na:
        with runtime.NativeColumn.from_python(b) as nb:
            fn = (
                runtime.native_multiply_decimal128
                if op == "mul"
                else runtime.native_divide_decimal128
            )
            with fn(na, nb, scale) as t:
                with t.column(0) as c0, t.column(1) as c1:
                    return (
                        c0.to_python(dt.BOOL8).to_pylist(),
                        c1.to_python(dt.decimal128(scale)).to_pylist(),
                    )


@pytest.mark.parametrize("op,scale", [
    ("mul", -6), ("mul", -1), ("mul", -20),
    ("div", -6), ("div", 2), ("div", -45),
])
def test_decimal128_native_matches_python(rng, op, scale):
    from spark_rapids_jni_tpu.ops.decimal_utils import divide128, multiply128

    vals_a, vals_b = [], []
    for _ in range(60):
        bits_a = int(rng.integers(1, 120))
        bits_b = int(rng.integers(1, 120))
        va = int(rng.integers(0, 2**62)) * (2 ** max(bits_a - 62, 0)) + int(rng.integers(0, 2**30))
        vb = int(rng.integers(0, 2**62)) * (2 ** max(bits_b - 62, 0)) + int(rng.integers(0, 2**30))
        va = min(va, 2**126)
        vb = min(vb, 2**126)
        if rng.random() < 0.5:
            va = -va
        if rng.random() < 0.5:
            vb = -vb
        if rng.random() < 0.1:
            vb = 0
        vals_a.append(va)
        vals_b.append(vb)
    a = _dec_col(vals_a, -10)
    b = _dec_col(vals_b, -4)
    py_op = multiply128 if op == "mul" else divide128
    want = py_op(a, b, scale)
    want_ovf = want.columns[0].to_pylist()
    want_res = want.columns[1].to_pylist()
    got_ovf, got_res = _native_dec_op(op, a, b, scale)
    assert [bool(o) for o in got_ovf] == [bool(o) for o in want_ovf]
    for i, (g, w, ov) in enumerate(zip(got_res, want_res, want_ovf)):
        if not ov:
            assert g == w, f"row {i}: native {g} != python {w}"


def test_decimal128_native_spark40129_case():
    # the pinned SPARK-40129 double-rounding battery (DecimalUtilsTest.java:151)
    import decimal

    decimal.getcontext().prec = 100
    def dec(v, scale):
        return int(decimal.Decimal(v).scaleb(-scale))

    a = _dec_col([dec("3358377338823096511784947656.4650294583", -10),
                  dec("7161021785186010157110137546.5940777916", -10),
                  dec("9173594185998001607642838421.5479932913", -10)], -10)
    b = _dec_col([dec("-12.0000000000", -10)] * 3, -10)
    got_ovf, got_res = _native_dec_op("mul", a, b, -6)
    assert got_ovf == [False, False, False]
    assert got_res == [
        dec("-40300528065877158141419371877.580354", -6),
        dec("-85932261422232121885321650559.128933", -6),
        dec("-110083130231976019291714061058.575920", -6),
    ]


def test_decimal128_native_null_and_divzero():
    a = _dec_col([10**20, None, 5], -2)
    b = _dec_col([0, 7, 2], -2)
    got_ovf, got_res = _native_dec_op("div", a, b, -4)
    assert got_ovf[0] is True        # div-by-zero -> overflow
    assert got_res[0] == 0
    assert got_ovf[1] is None and got_res[1] is None  # null propagates
    assert got_ovf[2] is False


def test_convert_from_rows_rejects_corrupt_blob():
    import ctypes

    lib = runtime.native_lib()
    # a "row" of 4 bytes for a schema needing 13+ (INT64 + validity):
    # must error, not read out of bounds
    offs = np.asarray([0, 4], np.int32)
    blob = np.zeros(4, np.uint8)
    h = lib.srjt_column_create(
        int(dt.LIST.id), 0, 1, None, 0, None,
        offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        blob.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), 4,
    )
    assert h != 0
    ids = (ctypes.c_int32 * 1)(int(dt.INT64.id))
    scales = (ctypes.c_int32 * 1)(0)
    out = lib.srjt_convert_from_rows(h, ids, scales, 1)
    assert out == 0
    assert b"shorter than" in lib.srjt_last_error()

    # a string slot pointing outside its row must error too
    row = np.zeros(16, np.uint8)
    row[0:4] = np.frombuffer(np.uint32(9).tobytes(), np.uint8)     # offset
    row[4:8] = np.frombuffer(np.uint32(4096).tobytes(), np.uint8)  # len: way past row end
    row[8] |= 1  # valid
    offs2 = np.asarray([0, 16], np.int32)
    h2 = lib.srjt_column_create(
        int(dt.LIST.id), 0, 1, None, 0, None,
        offs2.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        row.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), 16,
    )
    ids2 = (ctypes.c_int32 * 1)(int(dt.STRING.id))
    out2 = lib.srjt_convert_from_rows(h2, ids2, scales, 1)
    assert out2 == 0
    assert b"outside its row" in lib.srjt_last_error()
    lib.srjt_column_close(h)
    lib.srjt_column_close(h2)


def test_convert_to_rows_internal_batch_split():
    """convertToRows splits internally against the batch byte ceiling
    (reference build_batches, row_conversion.cu:1465-1543) — exercised
    with an injected limit so the test doesn't need 2 GiB of rows."""
    n = 1000
    t = Table(
        [
            col_from(list(range(n)), dt.INT64),
            col_from([f"s{i % 13}" * (i % 5) for i in range(n)], dt.STRING),
        ],
        ["v", "s"],
    )
    with runtime.NativeTable.from_python(t) as nt:
        # default limit: one batch, identical to the single-batch entry
        batches = runtime.native_convert_to_rows_batched(nt)
        assert len(batches) == 1
        with runtime.native_convert_to_rows(nt) as single:
            a = single.to_python(dt.LIST)
        b = batches[0].to_python(dt.LIST)
        np.testing.assert_array_equal(np.asarray(a.child.data), np.asarray(b.child.data))
        for c in batches:
            c.close()

        # injected 4 KiB limit: many batches, concatenation reproduces
        # the single blob and every batch respects the ceiling
        batches = runtime.native_convert_to_rows_batched(nt, max_batch_bytes=4096)
        assert len(batches) > 1
        blobs, nrows = [], 0
        for c in batches:
            pc = c.to_python(dt.LIST)
            blob = np.asarray(pc.child.data)
            assert blob.size <= 4096
            blobs.append(blob)
            nrows += len(pc)
            c.close()
        assert nrows == n
        np.testing.assert_array_equal(np.concatenate(blobs), np.asarray(a.child.data))

        # decode side: each batch converts back and the rows concatenate
        batches = runtime.native_convert_to_rows_batched(nt, max_batch_bytes=4096)
        vals, strs = [], []
        for c in batches:
            with runtime.native_convert_from_rows(c, t.dtypes()) as back:
                with back.column(0) as c0:
                    vals.extend(c0.to_python(dt.INT64).to_pylist())
                with back.column(1) as c1:
                    strs.extend(c1.to_python(dt.STRING).to_pylist())
            c.close()
        assert vals == t.column("v").to_pylist()
        assert strs == t.column("s").to_pylist()
