"""Parquet data decode tests: pyarrow-written files as the oracle."""

import io

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import spark_rapids_jni_tpu  # noqa: F401
from spark_rapids_jni_tpu.io.parquet_reader import read_table


def write(table, **kw):
    buf = io.BytesIO()
    pq.write_table(table, buf, **kw)
    return buf.getvalue()


def check_roundtrip(pa_table, **kw):
    data = write(pa_table, **kw)
    got = read_table(data)
    for name in pa_table.column_names:
        expected = pa_table.column(name).to_pylist()
        actual = got.column(name).to_pylist()
        if pa.types.is_floating(pa_table.schema.field(name).type):
            for e, a in zip(expected, actual):
                assert (e is None) == (a is None)
                if e is not None:
                    assert abs(e - a) < 1e-6 or e == a
        else:
            assert actual == expected, f"column {name}"


BASIC = pa.table({
    "i32": pa.array([1, -2, 3, None, 5], pa.int32()),
    "i64": pa.array([2**40, None, -7, 0, 9], pa.int64()),
    "f32": pa.array([1.5, 2.5, None, -0.25, 0.0], pa.float32()),
    "f64": pa.array([1e300, None, -2.25, 0.5, 3.125], pa.float64()),
    "s": pa.array(["hello", "", None, "spark", "tpu"], pa.string()),
    "b": pa.array([True, False, None, True, False], pa.bool_()),
})


@pytest.mark.parametrize("codec", ["NONE", "snappy", "zstd", "gzip"])
def test_roundtrip_codecs(codec):
    check_roundtrip(BASIC, compression=codec)


def test_roundtrip_plain_encoding():
    check_roundtrip(BASIC, use_dictionary=False, compression="NONE")


def test_roundtrip_dictionary_encoding():
    check_roundtrip(BASIC, use_dictionary=True)


def test_roundtrip_v2_pages():
    check_roundtrip(BASIC, data_page_version="2.0")
    check_roundtrip(BASIC, data_page_version="2.0", use_dictionary=False)


def test_multiple_row_groups(rng):
    t = pa.table({
        "x": pa.array([int(v) for v in rng.integers(0, 1000, 5000)], pa.int64()),
        "y": pa.array([f"k{int(v) % 50}" for v in rng.integers(0, 1000, 5000)]),
    })
    data = write(t, row_group_size=750)
    got = read_table(data)
    assert got.column("x").to_pylist() == t.column("x").to_pylist()
    assert got.column("y").to_pylist() == t.column("y").to_pylist()


def test_column_selection():
    got = read_table(write(BASIC), columns=["s", "i32"])
    assert got.names == ["i32", "s"]
    assert got.column("i32").to_pylist() == BASIC.column("i32").to_pylist()


def test_all_nulls_column():
    t = pa.table({"n": pa.array([None, None, None], pa.int32())})
    got = read_table(write(t))
    assert got.column("n").to_pylist() == [None, None, None]


def test_empty_table():
    t = pa.table({"a": pa.array([], pa.int32())})
    got = read_table(write(t))
    assert got.num_rows == 0


# ---------------------------------------------------------------------------
# nested schemas (lists / structs / maps) vs the pyarrow oracle
# ---------------------------------------------------------------------------


def test_list_of_int():
    t = pa.table({
        "l": pa.array([[1, 2, 3], [], None, [4], [None, 5]], pa.list_(pa.int64())),
    })
    check_roundtrip(t)
    check_roundtrip(t, use_dictionary=False)
    check_roundtrip(t, data_page_version="2.0")


def test_list_of_strings():
    t = pa.table({
        "l": pa.array([["a", "bb"], None, [], ["", None, "ccc"]], pa.list_(pa.string())),
    })
    check_roundtrip(t)


def test_struct_flat():
    t = pa.table({
        "s": pa.array(
            [{"a": 1, "b": "x"}, None, {"a": None, "b": "z"}, {"a": 4, "b": None}],
            pa.struct([("a", pa.int32()), ("b", pa.string())]),
        ),
    })
    check_roundtrip(t)


def test_struct_of_list():
    t = pa.table({
        "s": pa.array(
            [{"v": [1, 2]}, {"v": None}, None, {"v": []}, {"v": [None, 3]}],
            pa.struct([("v", pa.list_(pa.int64()))]),
        ),
    })
    check_roundtrip(t)


def test_list_of_struct():
    t = pa.table({
        "l": pa.array(
            [[{"a": 1}, {"a": None}], [], None, [{"a": 7}]],
            pa.list_(pa.struct([("a", pa.int64())])),
        ),
    })
    check_roundtrip(t)


def test_list_of_list():
    t = pa.table({
        "ll": pa.array(
            [[[1], [2, 3]], [], None, [None, [4, None]], [[]]],
            pa.list_(pa.list_(pa.int32())),
        ),
    })
    check_roundtrip(t)


def test_map_column():
    t = pa.table({
        "m": pa.array(
            [[("k1", 1), ("k2", 2)], [], None, [("k3", None)]],
            pa.map_(pa.string(), pa.int64()),
        ),
    })
    got = read_table(write(t))
    # maps land as LIST<STRUCT<key, value>> (the cudf representation)
    want = [
        None if row is None else [{"key": k, "value": v} for k, v in row]
        for row in t.column("m").to_pylist()
    ]
    assert got.column("m").to_pylist() == want


def test_deep_nesting_row_groups(rng):
    rows = []
    for i in range(700):
        r = int(rng.integers(0, 6))
        if r == 0:
            rows.append(None)
        else:
            rows.append(
                [
                    {
                        "tags": None if rng.integers(0, 5) == 0 else [
                            f"t{int(x)}" for x in rng.integers(0, 9, int(rng.integers(0, 3)))
                        ],
                        "n": None if rng.integers(0, 5) == 0 else int(rng.integers(0, 100)),
                    }
                    for _ in range(int(rng.integers(0, 3)))
                ]
            )
    typ = pa.list_(pa.struct([("tags", pa.list_(pa.string())), ("n", pa.int64())]))
    t = pa.table({"events": pa.array(rows, typ), "id": pa.array(range(700), pa.int64())})
    data = write(t, row_group_size=128)
    got = read_table(data)
    assert got.column("events").to_pylist() == t.column("events").to_pylist()
    assert got.column("id").to_pylist() == t.column("id").to_pylist()


def test_nested_next_to_flat_selection():
    t = pa.table({
        "flat": pa.array([1, 2, 3], pa.int32()),
        "l": pa.array([[1], [], [2, 3]], pa.list_(pa.int32())),
    })
    got = read_table(write(t), columns=["l"])
    assert got.names == ["l"]
    assert got.column("l").to_pylist() == t.column("l").to_pylist()


def test_lz4_raw_codec():
    check_roundtrip(BASIC, compression="lz4")  # pyarrow writes LZ4_RAW


def test_lz4_hadoop_framing():
    """Legacy codec 5 pages use Hadoop block framing: repeated
    [u32 BE usize][u32 BE csize][raw LZ4 block] (advisor round-2 low
    finding: these were fed whole to the LZ4 *frame* decoder)."""
    import struct

    import pyarrow as pa_mod

    from spark_rapids_jni_tpu.io.parquet_reader import _lz4_hadoop

    plain = b"spark-rapids-jni-tpu hadoop lz4 framing " * 40
    half = len(plain) // 2
    blocks = []
    for part in (plain[:half], plain[half:]):
        comp = pa_mod.Codec("lz4_raw").compress(part).to_pybytes()
        blocks.append(struct.pack(">II", len(part), len(comp)) + comp)
    framed = b"".join(blocks)
    assert _lz4_hadoop(framed, len(plain)) == plain
    # LZ4-frame payloads (non-Hadoop writers) must be rejected -> None
    frame = pa_mod.Codec("lz4").compress(plain).to_pybytes()
    assert _lz4_hadoop(frame, len(plain)) is None


def test_zstd_decodes_through_native_tier(native, monkeypatch):
    """The zstd path must run on the native codec (nvcomp analog), not
    the pyarrow fallback."""
    import pyarrow as pa_mod

    def _boom(*a, **k):
        raise AssertionError("pyarrow codec used for zstd")

    monkeypatch.setattr(pa_mod, "Codec", _boom)
    check_roundtrip(BASIC, compression="zstd")
