"""Native runtime tests: C++ footer service vs the pure-Python oracle
(the dual-implementation cross-check pattern the reference uses for its
row kernels, row_conversion.cpp:43-60, applied across languages), plus
handle/leak accounting and host buffers.

tests/conftest.py builds native/build/libsrjt.so before collection; its
``native`` fixture skips these where there is no toolchain.
"""

import io

import pyarrow as pa
import pyarrow.parquet as pq
import pytest


def make_parquet(table: pa.Table, row_group_size=None) -> bytes:
    buf = io.BytesIO()
    pq.write_table(table, buf, row_group_size=row_group_size, compression="snappy")
    return buf.getvalue()


@pytest.fixture
def flat_file():
    t = pa.table({
        "a": pa.array(range(100), pa.int32()),
        "b": pa.array([f"s{i}" for i in range(100)]),
        "c": pa.array([i * 0.5 for i in range(100)]),
    })
    return make_parquet(t, row_group_size=30)


@pytest.fixture
def nested_file():
    t = pa.table({
        "s": pa.array([{"x": i, "y": f"v{i}"} for i in range(50)],
                      pa.struct([("x", pa.int64()), ("y", pa.string())])),
        "l": pa.array([[i, i + 1] for i in range(50)], pa.list_(pa.int32())),
        "m": pa.array([[(f"k{i}", i)] for i in range(50)],
                      pa.map_(pa.string(), pa.int64())),
        "plain": pa.array(range(50), pa.int64()),
    })
    return make_parquet(t)


def _schema(*specs):
    from spark_rapids_jni_tpu.io.parquet_footer import (
        ListElement, MapElement, StructElement, ValueElement,
    )

    root = StructElement()
    for name, kind in specs:
        if kind == "v":
            root.add_child(name, ValueElement())
        elif kind == "l":
            root.add_child(name, ListElement(ValueElement()))
        elif kind == "m":
            root.add_child(name, MapElement(ValueElement(), ValueElement()))
        elif isinstance(kind, tuple):
            s = StructElement()
            for n2 in kind:
                s.add_child(n2, ValueElement())
            root.add_child(name, s)
    return root


def test_native_matches_python_flat(native, flat_file):
    from spark_rapids_jni_tpu.io.parquet_footer import read_and_filter

    schema = _schema(("a", "v"), ("c", "v"))
    py = read_and_filter(flat_file, 0, len(flat_file), schema)
    with native.NativeParquetFooter.read_and_filter(flat_file, 0, len(flat_file), schema) as nat:
        assert nat.get_num_rows() == py.get_num_rows() == 100
        assert nat.get_num_columns() == py.get_num_columns() == 2
        # byte-identical serialization: both writers emit ascending fids
        assert nat.serialize_thrift_file() == py.serialize_thrift_file()


def test_native_serialized_readable_by_pyarrow(native, flat_file):
    schema = _schema(("a", "v"), ("b", "v"))
    with native.NativeParquetFooter.read_and_filter(flat_file, 0, len(flat_file), schema) as nat:
        md = pq.read_metadata(io.BytesIO(nat.serialize_thrift_file()))
    assert md.num_columns == 2
    assert [md.schema.column(i).name for i in range(2)] == ["a", "b"]


def test_native_nested_pruning_matches_python(native, nested_file):
    from spark_rapids_jni_tpu.io.parquet_footer import read_and_filter

    schema = _schema(("s", ("x",)), ("l", "l"), ("m", "m"))
    py = read_and_filter(nested_file, 0, len(nested_file), schema)
    with native.NativeParquetFooter.read_and_filter(
        nested_file, 0, len(nested_file), schema
    ) as nat:
        assert nat.serialize_thrift_file() == py.serialize_thrift_file()


def test_native_row_group_split(native, flat_file):
    from spark_rapids_jni_tpu.io.parquet_footer import read_and_filter

    schema = _schema(("a", "v"))
    full = read_and_filter(flat_file, 0, len(flat_file), schema)
    assert full.get_num_rows() == 100
    # an empty split keeps no groups — both impls agree
    with native.NativeParquetFooter.read_and_filter(flat_file, 0, 1, schema) as nat:
        py = read_and_filter(flat_file, 0, 1, schema)
        assert nat.get_num_rows() == py.get_num_rows()


def test_native_case_insensitive(native, flat_file):
    schema = _schema(("A", "v"))
    with native.NativeParquetFooter.read_and_filter(
        flat_file, 0, len(flat_file), schema, ignore_case=True
    ) as nat:
        assert nat.get_num_columns() == 1
    with native.NativeParquetFooter.read_and_filter(
        flat_file, 0, len(flat_file), schema, ignore_case=False
    ) as nat:
        assert nat.get_num_columns() == 0


def test_native_error_translation(native):
    with pytest.raises(RuntimeError, match="native runtime error"):
        native.NativeParquetFooter.read_and_filter(b"not thrift", 0, 10, _schema(("a", "v")))


def test_handle_leak_accounting(native, flat_file):
    base = native.live_handles()
    schema = _schema(("a", "v"))
    f = native.NativeParquetFooter.read_and_filter(flat_file, 0, len(flat_file), schema)
    assert native.live_handles() == base + 1
    f.close()
    assert native.live_handles() == base
    f.close()  # double close is safe


def test_host_buffer_roundtrip(native):
    before = native.NativeHostBuffer.bytes_in_use()
    with native.NativeHostBuffer(1024) as b:
        assert native.NativeHostBuffer.bytes_in_use() == before + 1024
        assert b.address % 64 == 0
        b.write(b"hello parquet", 100)
        assert b.read(13, 100) == b"hello parquet"
        with pytest.raises(ValueError):
            b.write(b"x" * 2000)
    assert native.NativeHostBuffer.bytes_in_use() == before


def test_host_buffer_rejects_bad_alignment(native):
    with pytest.raises(RuntimeError):
        native.NativeHostBuffer(16, alignment=3)


def test_snappy_roundtrip_vs_pyarrow(native):
    # pyarrow's compressor produces the stream; the native decoder must
    # invert it — including overlapping back-references from repeats
    payloads = [
        b"",
        b"a",
        b"hello world " * 500,  # long repeats -> copies with small offsets
        bytes(range(256)) * 40,  # literals
        b"\x00" * 100_000,  # long runs
    ]
    for want in payloads:
        comp = pa.Codec("snappy").compress(want).to_pybytes()
        assert native.snappy_uncompress(comp) == want


def test_snappy_rejects_garbage(native):
    with pytest.raises(RuntimeError):
        native.snappy_uncompress(b"\xff\xff\xff\xff\xff\x00garbage")


def test_parquet_reader_uses_native_snappy(native, flat_file):
    # flat_file is written with compression='snappy'; decode through the
    # reader and cross-check values against pyarrow
    from spark_rapids_jni_tpu.io.parquet_reader import read_table

    t = read_table(flat_file, columns=["a", "c"])
    import numpy as np

    assert np.asarray(t.column("a").data).tolist() == list(range(100))
