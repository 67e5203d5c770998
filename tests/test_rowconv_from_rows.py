"""The row-to-column transcode against a plain reference (ISSUE 38):
``convert_from_rows`` and the sidecar's ``_op_convert_from_rows`` behind
it, on seeded tables, against ``bench/references/jcudf_from_rows.py``
(numpy only, no import of the program): every validity flag, and the
value bytes of every valid row. Both forms of the decode (``uniform``,
``gathered``), three producers of the rows column (the worker's host
offsets, a device column with no stride, ``convert_to_rows``' batches),
the reply's pieces, and the spans and counters the decode adds.
"""

import importlib.util
import os
import struct
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu import sidecar
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.columnar import dtype as dt
from spark_rapids_jni_tpu.columnar.dtype import DType, TypeId
from spark_rapids_jni_tpu.ops import row_conversion as rc
from spark_rapids_jni_tpu.utils import metrics, trace_sink, tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(REPO, "bench")
CYCLE = ["INT8", "INT32", "INT16", "INT64", "INT32", "BOOL8", "UINT16", "UINT8", "UINT64"]
COUNTERS = ("calls", "rows", "bytes_in", "uniform", "gathered", "stride_probes")

pytestmark = pytest.mark.usefixtures("clean_state")


def _bench_module(kind, name):
    if BENCH_DIR not in sys.path:
        sys.path.insert(0, BENCH_DIR)  # the driver imports benchlib
    spec = importlib.util.spec_from_file_location(f"rowconv_from_{kind}_{name}",
                                                  os.path.join(BENCH_DIR, kind, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _bench_module("references", "jcudf_from_rows")


@pytest.fixture(scope="module")
def encode():
    return _bench_module("references", "jcudf_rows").rows


@pytest.fixture(scope="module")
def driver():
    return _bench_module("drivers", "sidecar_from")


def _cycle_table(rows, seed):
    """The cell's own table: the nine-type cycle over 212 columns, ~10%
    nulls in every fourth column."""
    cfg = {"tables": {"table": {"columns": 212, "types": CYCLE}}}
    return _bench_module("data", "rowconv_fixed_width").host_tables(cfg, seed, rows)["table"]


def _small_table(rows, seed):
    """INT8, INT64 with nulls, INT16, UINT32 with nulls: 32-byte rows."""
    rng = np.random.default_rng(seed)
    cols = []
    for name, code, nulls in (("INT8", "i1", False), ("INT64", "i8", True), ("INT16", "i2", False),
                              ("UINT32", "u4", True)):
        info = np.iinfo(np.dtype(code))
        data = rng.integers(info.min, info.max, rows, dtype=code, endpoint=True)
        cols.append((name, data, rng.random(rows) >= 0.3 if nulls else None))
    return cols


def _np_dtype(name):
    return np.dtype(DType(TypeId[name]).np_dtype)


def _schema(host):
    return [DType(TypeId[name]) for name, _, _ in host]


def _device_table(host) -> Table:
    return Table([Column(DType(TypeId[name]), data=jnp.asarray(data.view(_np_dtype(name))),
                         validity=None if validity is None else jnp.asarray(validity))
                  for name, data, validity in host])


def _ragged(rows2d, seed):
    """The same rows with 0, 8 or 16 bytes of padding after each: offsets
    that no stride describes (the ``gathered`` form)."""
    pad = np.random.default_rng(seed).integers(0, 3, len(rows2d)) * 8
    sizes = rows2d.shape[1] + pad
    offsets = np.zeros(len(rows2d) + 1, np.int32)
    np.cumsum(sizes, out=offsets[1:])
    blob = np.zeros(int(offsets[-1]), np.uint8)
    for r, row in enumerate(rows2d):
        blob[offsets[r]:offsets[r] + len(row)] = row
    return offsets, blob


def _uniform(rows2d):
    n, size = rows2d.shape
    return (np.arange(n + 1, dtype=np.int64) * size).astype(np.int32), rows2d.reshape(-1)


def _rows_column(producer, offsets, blob):
    """A rows column as each producer hands it to ``convert_from_rows``."""
    if producer == "host":  # the sidecar's worker: host offsets, the stride read there
        return rc.rows_from_host(offsets, blob)
    # another producer's column on the device: INT8 bytes, no stride attached
    return Column(dt.LIST, offsets=jnp.asarray(offsets),
                  child=Column(dt.INT8, data=jnp.asarray(blob.view(np.int8))))


def _assert_columns_are_the_reference(table, host, rows2d, ref):
    dtypes = [_np_dtype(name) for name, _, _ in host]
    want = ref.columns(rows2d, dtypes)
    assert len(table.columns) == len(host)
    for col, (name, _, _), d, (values, valid) in zip(table.columns, host, dtypes, want):
        assert col.dtype == DType(TypeId[name]) and len(col) == len(rows2d)
        got_valid = np.ones(len(rows2d), bool) if col.validity is None else np.asarray(col.validity)
        np.testing.assert_array_equal(got_valid, valid)
        got = np.asarray(col.data).view(d)
        np.testing.assert_array_equal(got[valid], values[valid])


def _moved(before):
    reg = metrics.registry()
    return {k: reg.value(f"rowconv.from_rows.{k}") - v for k, v in before.items()}


def _counters():
    reg = metrics.registry()
    return {k: reg.value(f"rowconv.from_rows.{k}") for k in COUNTERS}


TABLES = {
    "cycle_212x4096": (lambda: _cycle_table(4096, 3_800_000_011), "uniform"),
    "small_999": (lambda: _small_table(999, 38), "uniform"),
    "small_999_ragged": (lambda: _small_table(999, 39), "gathered"),
    "one_row": (lambda: _small_table(1, 40), "uniform"),
    "zero_rows": (lambda: _small_table(0, 41), None),
}


@pytest.mark.parametrize("producer", ["host", "device_int8"])
@pytest.mark.parametrize("case", sorted(TABLES))
def test_convert_from_rows_is_the_reference(case, producer, ref, encode):
    build, form = TABLES[case]
    host = build()
    rows2d, _ = encode(host)
    offsets, blob = _ragged(rows2d, 7) if case.endswith("_ragged") else _uniform(rows2d)
    before = _counters()
    table = rc.convert_from_rows(_rows_column(producer, offsets, blob), _schema(host))
    _assert_columns_are_the_reference(table, host, rows2d, ref)
    moved = _moved(before)
    # a device column with no stride is probed once where its bytes could be uniform
    probes = int(producer == "device_int8" and form == "uniform")
    assert moved == {"calls": 1, "rows": len(rows2d), "bytes_in": len(blob), "uniform": int(form == "uniform"),
                     "gathered": int(form == "gathered"), "stride_probes": probes}


def test_ragged_rows_of_a_uniform_total_are_probed_and_gathered(ref, encode):
    """Rows of 4 and 12 bytes in turn hold as many bytes as rows of 8: only
    the offsets tell them apart. The host reads that from its own copy; a
    device column is probed once, and both decode in the gathered form."""
    host = [(name, np.arange(20, dtype=np.int8) * k, None) for k, name in enumerate(["INT8"] * 3, start=1)]
    rows2d, row_size = encode(host)
    assert row_size == 8
    sizes = np.where(np.arange(20) % 2 == 0, 4, 12)
    offsets = np.zeros(21, np.int32)
    np.cumsum(sizes, out=offsets[1:])
    blob = np.zeros(int(offsets[-1]), np.uint8)
    for r in range(20):
        blob[offsets[r]:offsets[r] + 4] = rows2d[r, :4]  # the fixed section: three values, one validity byte
    for producer, probes in (("host", 0), ("device_int8", 1)):
        before = _counters()
        table = rc.convert_from_rows(_rows_column(producer, offsets, blob), _schema(host))
        assert _moved(before) == {"calls": 1, "rows": 20, "bytes_in": 160, "uniform": 0, "gathered": 1,
                                  "stride_probes": probes}
        _assert_columns_are_the_reference(table, host, rows2d, ref)


@pytest.mark.parametrize("stride,want", [(None, 0), (0, 0), (8, 8)])
def test_the_host_reads_the_stride_from_its_offsets(stride, want):
    if stride is None:
        offsets = np.array([0, 4, 12, 16], np.int32)  # ragged
    else:
        offsets = np.arange(4, dtype=np.int32) * stride
    col = rc.rows_from_host(offsets, np.zeros(int(offsets[-1]), np.uint8))
    assert col._uniform_stride == want
    assert col.child.data.dtype == jnp.uint8 and col.child.dtype == dt.UINT8  # put as it came


@pytest.mark.parametrize("case", ["cycle_212x4096", "small_999"])
def test_round_trip_through_convert_to_rows(case, encode):
    """convert_to_rows' batches carry their stride: decoded with no probe,
    they give the table back at every valid row."""
    host = TABLES[case][0]()
    (batch,) = rc.convert_to_rows(_device_table(host))
    np.testing.assert_array_equal(np.asarray(batch.child.data).view(np.uint8), encode(host)[0].reshape(-1))
    before = _counters()
    table = rc.convert_from_rows(batch, _schema(host))
    assert _moved(before)["stride_probes"] == 0
    for col, (name, data, validity) in zip(table.columns, host):
        valid = np.ones(len(data), bool) if validity is None else validity
        np.testing.assert_array_equal(np.asarray(col.validity), valid)
        np.testing.assert_array_equal(np.asarray(col.data).view(_np_dtype(name))[valid],
                                      data.view(_np_dtype(name))[valid])


def _payload(host, offsets, blob):
    tids = np.asarray([int(TypeId[name].value) for name, _, _ in host], "<i4")
    return b"".join([struct.pack("<I", len(host)), tids.tobytes(), np.zeros(len(host), "<i4").tobytes(),
                     struct.pack("<Q", len(offsets) - 1), offsets.astype("<i4").tobytes(),
                     struct.pack("<Q", len(blob)), blob.tobytes()])


@pytest.mark.parametrize("case", sorted(TABLES))
def test_the_worker_op_answers_the_reference_in_the_walker_layout(case, ref, encode, driver):
    """The native client's wire layout in, the table's pieces out: what
    ``_op_convert_from_rows`` answers parses to the schema, column for
    column, and is the reference's."""
    build, form = TABLES[case]
    host = build()
    rows2d, _ = encode(host)
    offsets, blob = _ragged(rows2d, 8) if case.endswith("_ragged") else _uniform(rows2d)
    before = _counters()
    reply = sidecar._op_convert_from_rows(_payload(host, offsets, blob))
    assert isinstance(reply, sidecar.ReplyPieces)
    got = driver.parse_reply(sidecar.as_bytes(reply))
    assert len(got) == len(host)
    dtypes = [_np_dtype(name) for name, _, _ in host]
    for (tid, scale, n, validity, data), (name, _, _), d, (values, valid) in zip(
            got, host, dtypes, ref.columns(rows2d, dtypes)):
        assert (tid, scale, n, len(data)) == (int(TypeId[name].value), 0, len(rows2d), len(rows2d) * d.itemsize)
        np.testing.assert_array_equal(validity != 0, valid)  # every column comes with its validity
        np.testing.assert_array_equal(data.view(d)[valid], values[valid])
    assert _moved(before)["stride_probes"] == 0  # the worker's path never asks the device for the stride
    assert _moved(before)["gathered"] == int(form == "gathered")


def test_the_reply_is_pieces_of_the_cells_frame(encode, driver):
    """A reply of the 212-column table is one header, then per column its
    header, flag, validity, length and values: 1 + 212 x 5 pieces."""
    host = _cycle_table(512, 5)
    rows2d, _ = encode(host)
    reply = sidecar._op_convert_from_rows(_payload(host, *_uniform(rows2d)))
    assert len(reply.pieces) == 1 + 212 * 5
    assert len(reply) == 4 + 212 * (25 + 512) + 512 * 732  # the configuration's 732 value bytes a row


def test_a_malformed_reply_does_not_parse(encode, driver):
    host = _small_table(10, 6)
    good = sidecar.as_bytes(sidecar._op_convert_from_rows(_payload(host, *_uniform(encode(host)[0]))))
    assert len(driver.parse_reply(good)) == 4
    for bad in (good[:-1], good + b"\0", good[:40]):
        with pytest.raises(ValueError):
            driver.parse_reply(bad)


def _traced(fn):
    trace_sink.reset_for_tests()
    with tracing.enabled():
        qt = tracing.start_trace("rowconv.test")
        with qt.activate():
            out = fn()
        qt.finish("ok")
    return trace_sink.recorder().last(1)[0]["spans"], out


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


@pytest.fixture(scope="module")
def worker_spans():
    """The spans of one traced CONVERT_FROM_ROWS through the worker's
    dispatch (the second call: the first compiled everything), and the
    counters it moved."""
    host = _small_table(300, 9)
    rows2d, _ = _bench_module("references", "jcudf_rows").rows(host)
    payload = _payload(host, *_uniform(rows2d))
    sidecar._dispatch(sidecar.OP_CONVERT_FROM_ROWS, payload, "cpu")
    before = _counters()
    spans, _ = _traced(lambda: sidecar._dispatch(sidecar.OP_CONVERT_FROM_ROWS, payload, "cpu"))
    return spans, _moved(before), len(payload)


def test_decode_table_and_the_decode_sit_side_by_side_under_the_request(worker_spans):
    spans, _, nbytes = worker_spans
    (root,) = _named(spans, "rowconv.test")
    (decode_table,) = _named(spans, "sidecar.worker.decode_table")
    (op,) = _named(spans, "op.convert_from_rows")
    (decode,) = _named(spans, "rowconv.decode")
    (d2h,) = _named(spans, "sidecar.worker.d2h")
    assert decode_table["parent"] == op["parent"] == d2h["parent"] == root["span"]
    assert decode["parent"] == op["span"]
    assert decode_table["ts"] + decode_table["dur_us"] / 1e6 <= op["ts"] + 1e-6 and op["ts"] <= d2h["ts"]
    assert decode_table["annotations"] == {"bytes": nbytes, "rows": 300, "cols": 4}
    assert decode["annotations"] == {"form": "uniform", "rows": 300, "cols": 4, "bytes": 300 * 32}


def test_the_decode_launches_one_program_and_waits_for_nothing(worker_spans):
    """The worker's rows are bytes already and their stride is known: under
    the operator one launch (the decode), no bitcast, no probe, no wait."""
    spans, moved, _ = worker_spans
    (op,) = _named(spans, "op.convert_from_rows")
    by_id = {s["span"]: s for s in spans}

    def under_op(s):
        while s is not None:
            if s["parent"] == op["span"]:
                return True
            s = by_id.get(s["parent"])
        return False

    launches = [s["annotations"]["program"] for s in _named(spans, "device.launch") if under_op(s)]
    assert launches == ["_decode_fixed_uniform"]
    assert not [s for s in _named(spans, "device.wait") if under_op(s)]
    # the rows' put is waited for in its own phase, the decode in the reply's copy
    (decode_table,) = _named(spans, "sidecar.worker.decode_table")
    (d2h,) = _named(spans, "sidecar.worker.d2h")
    waits = {s["annotations"]["what"]: s["parent"] for s in _named(spans, "device.wait")}
    assert waits == {"rows_put": decode_table["span"], "result": d2h["span"]}
    assert moved == {"calls": 1, "rows": 300, "bytes_in": 300 * 32, "uniform": 1, "gathered": 0,
                     "stride_probes": 0}


@pytest.mark.parametrize("ragged", [False, True])
def test_a_device_column_with_no_stride_is_probed_under_the_decode(ragged, encode):
    """Another producer's rows: one bitcast, then the probe and its wait
    (``row_stride``) inside ``rowconv.decode``; ragged rows also launch the
    row gather. Counted in ``stride_probes`` with tracing on or off."""
    host = [(name, np.arange(64, dtype=np.int8) * k, None) for k, name in enumerate(["INT8"] * 3, start=1)]
    rows2d, _ = encode(host)
    if ragged:
        sizes = np.where(np.arange(64) % 2 == 0, 4, 12)
        offsets = np.zeros(65, np.int32)
        np.cumsum(sizes, out=offsets[1:])
        blob = np.zeros(int(offsets[-1]), np.uint8)
        for r in range(64):
            blob[offsets[r]:offsets[r] + 4] = rows2d[r, :4]
    else:
        offsets, blob = _uniform(rows2d)
    rows = _rows_column("device_int8", offsets, blob)
    rc.convert_from_rows(rows, _schema(host))  # compiled before the traced call
    before = _counters()
    spans, _ = _traced(lambda: rc.convert_from_rows(rows, _schema(host)))
    (decode,) = _named(spans, "rowconv.decode")
    assert decode["annotations"]["form"] == ("gathered" if ragged else "uniform")
    (wait,) = _named(spans, "device.wait")
    assert wait["annotations"] == {"what": "row_stride"} and wait["parent"] == decode["span"]
    programs = [s["annotations"]["program"] for s in _named(spans, "device.launch")]
    assert programs == ["bitcast_convert_type", "_offsets_uniform_probe"] + (
        ["_jit_gather_fixed_impl", "_decode_fixed_cols"] if ragged else ["_decode_fixed_uniform"])
    assert _moved(before)["stride_probes"] == 1


def test_the_counters_show_in_the_stats_report(encode):
    from spark_rapids_jni_tpu import runtime

    assert not tracing.is_enabled()  # registry-direct: counted with tracing off
    host = _small_table(100, 10)
    before = _counters()
    rc.convert_from_rows(rc.rows_from_host(*_uniform(encode(host)[0])), _schema(host))
    moved = _moved(before)
    assert moved == {"calls": 1, "rows": 100, "bytes_in": 3200, "uniform": 1, "gathered": 0, "stride_probes": 0}
    counters = runtime.stats_report()["metrics"]["counters"]
    assert all(counters[f"rowconv.from_rows.{k}"] >= moved[k] for k in COUNTERS)
