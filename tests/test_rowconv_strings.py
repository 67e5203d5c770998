"""The variable-width transcode against a plain reference, byte for byte
(ISSUE 34): ``convert_to_rows`` over tables with STRING columns against
``bench/references/jcudf_rows_var.py`` (numpy only, no import of the
program), the same table through the sidecar's ``_op_convert_to_rows`` in
the legacy walker layout, the one wait that sizes the encode, and an
encode that fails loudly and leaves no process state behind.
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
from spark_rapids_jni_tpu.columnar.dtype import DType, TypeId
from spark_rapids_jni_tpu.ops import row_conversion as rc
from spark_rapids_jni_tpu.utils import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(REPO, "bench")

pytestmark = pytest.mark.usefixtures("clean_state")


def _bench_module(kind, name):
    if BENCH_DIR not in sys.path:
        sys.path.insert(0, BENCH_DIR)  # the driver imports benchlib
    spec = importlib.util.spec_from_file_location(f"rowconv_strings_{kind}_{name}",
                                                  os.path.join(BENCH_DIR, kind, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _bench_module("references", "jcudf_rows_var")


@pytest.fixture(scope="module")
def driver():
    return _bench_module("drivers", "sidecar_var")


def _strings(lens, rng):
    offsets = np.zeros(len(lens) + 1, np.int32)
    np.cumsum(lens, out=offsets[1:])
    return offsets, rng.integers(0x20, 0x7F, int(offsets[-1]), dtype=np.uint8)


def _cycle_table(rows, seed):
    """The cell's own table: the ten-type cycle over 155 columns."""
    cfg = {"tables": {"table": {"columns": 155, "types": ["INT8", "INT32", "INT16", "INT64", "INT32", "BOOL8",
                                                           "UINT16", "UINT8", "UINT64", "STRING"]}}}
    return _bench_module("data", "rowconv_var_width").host_tables(cfg, seed, rows)["table"]


def _small_table(rows, seed, lens_of, null_strings=None):
    """INT32, STRING, INT64, STRING, INT8, STRING: ``lens_of(k, rows, rng)``
    draws the k-th STRING column's lengths; ``null_strings`` names the
    STRING columns that carry nulls (all NULL where the value is "all")."""
    rng = np.random.default_rng(seed)
    cols, k = [], 0
    for name, code in (("INT32", "i4"), ("STRING", None), ("INT64", "i8"), ("STRING", None), ("INT8", "i1"),
                       ("STRING", None)):
        if code is not None:
            info = np.iinfo(np.dtype(code))
            validity = rng.random(rows) >= 0.2 if name == "INT64" else None
            cols.append((name, rng.integers(info.min, info.max, rows, dtype=code, endpoint=True), validity))
            continue
        lens = np.asarray(lens_of(k, rows, rng), np.int32)
        validity = None
        how = (null_strings or {}).get(k)
        if how is not None:
            validity = np.zeros(rows, bool) if how == "all" else rng.random(rows) >= 0.3
            lens = np.where(validity, lens, 0).astype(np.int32)  # a NULL string has no characters
        cols.append((name, _strings(lens, rng), validity))
        k += 1
    return cols


def _device_table(host) -> Table:
    """As ``sidecar._decode_table`` builds them: no memo of the longest string."""
    cols = []
    for name, data, validity in host:
        d = DType(TypeId[name])
        v = None if validity is None else jnp.asarray(validity)
        if isinstance(data, tuple):
            cols.append(Column(d, validity=v, offsets=jnp.asarray(data[0]), chars=jnp.asarray(data[1])))
        else:
            cols.append(Column(d, data=jnp.asarray(data.view(np.dtype(d.np_dtype))), validity=v))
    return Table(cols)


def _assert_batches_equal(got, want):
    assert len(got) == len(want)
    for col, (offsets, blob) in zip(got, want):
        assert col.offsets.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(col.offsets), offsets)
        np.testing.assert_array_equal(np.asarray(col.child.data).view(np.uint8), blob)


def _uniform(lo, hi):
    return lambda k, rows, rng: rng.integers(lo, hi + 1, rows)


CASES = {
    # name: (host table, MAX_BATCH_BYTES or None, padded budget or None, batches expected, form expected)
    "ten_type_cycle_155x4096": (lambda: _cycle_table(4096, 34), None, None, 1, "padded"),
    "empty_strings": (lambda: _small_table(300, 1, lambda k, rows, rng: np.where(rng.random(rows) < 0.5, 0,
                                                                                 rng.integers(0, 20, rows))),
                      None, None, 1, "padded"),
    "every_string_empty": (lambda: _small_table(64, 2, _uniform(0, 0)), None, None, 1, "padded"),
    "null_strings": (lambda: _small_table(300, 3, _uniform(0, 32), {0: "some", 2: "some"}), None, None, 1, "padded"),
    "all_null_string_column": (lambda: _small_table(200, 4, _uniform(0, 32), {1: "all"}), None, None, 1, "padded"),
    "no_rows": (lambda: _small_table(0, 5, _uniform(0, 32)), None, None, 1, None),
    "one_row": (lambda: _small_table(1, 6, _uniform(1, 32)), None, None, 1, "padded"),
    "a_row_all_at_the_maximum": (lambda: _small_table(
        129, 7, lambda k, rows, rng: np.where(np.arange(rows) == 77, 32, rng.integers(0, 9, rows))),
        None, None, 1, "padded"),
    "several_batches": (lambda: _small_table(1000, 8, _uniform(0, 32)), 16384, None, None, "padded"),
    "scatter_forced": (lambda: _small_table(300, 9, _uniform(0, 32)), None, 1024, 1, "scatter"),
    "several_batches_scattered": (lambda: _small_table(500, 10, _uniform(0, 32)), 16384, 1024, None, "scatter"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_convert_to_rows_is_the_reference_byte_for_byte(case, ref, monkeypatch):
    build, batch_limit, budget, batches, form = CASES[case]
    host = build()
    if batch_limit is not None:
        monkeypatch.setattr(rc, "MAX_BATCH_BYTES", batch_limit)
    if budget is not None:
        monkeypatch.setattr(rc, "_PADDED_ROWS_BYTE_BUDGET", budget)
    want = ref.rows(host, max_batch_bytes=batch_limit or ref.MAX_BATCH_BYTES)
    reg = metrics.registry()
    before = {k: reg.value(f"rowconv.to_rows.{k}") for k in ("padded", "scatter", "batches", "bytes_out")}
    got = rc.convert_to_rows(_device_table(host))
    _assert_batches_equal(got, want)
    if batches is None:
        assert len(want) > 2  # the limit was small enough to split the table
    else:
        assert len(want) == batches
    moved = {k: reg.value(f"rowconv.to_rows.{k}") - v for k, v in before.items()}
    assert moved["batches"] == len(want) and moved["bytes_out"] == sum(len(b) for _, b in want)
    assert (moved["padded"], moved["scatter"]) == {"padded": (1, 0), "scatter": (0, 1), None: (0, 0)}[form]


def test_the_control_differs_from_the_reference_in_the_validity_bytes_alone(ref):
    host = _small_table(200, 11, _uniform(0, 32), {0: "some"})
    (_, want), (_, control) = ref.rows(host)[0], ref.rows(host, honour_nulls=False)[0]
    differ = np.flatnonzero(want != control)
    assert len(differ) > 0
    sizes = ref.row_sizes(host)
    starts = np.concatenate([[0], np.cumsum(sizes)])[:-1]
    within = differ - starts[np.searchsorted(starts, differ, side="right") - 1]
    _, validity_off, fixed_end = ref.layout(host)
    assert np.all((within >= validity_off) & (within < fixed_end))


@pytest.mark.parametrize("rows", [0, 1, 777])
def test_string_table_through_the_worker_op_in_the_walker_layout(rows, ref, driver):
    """The native client's wire layout in, the reply's batches out: what
    ``_op_convert_to_rows`` answers is the reference's bytes and frame."""
    host = _small_table(rows, 12, _uniform(0, 32), {1: "some"})
    payload = driver._encode_table(host, [int(TypeId[name].value) for name, _, _ in host])
    reply = sidecar.as_bytes(sidecar._op_convert_to_rows(payload))
    got = driver.parse_reply(reply)
    want = ref.rows(host)
    assert struct.unpack_from("<I", reply, 0) == (len(want),)
    assert len(got) == len(want)
    for (n, offsets, blob), (want_offsets, want_blob) in zip(got, want):
        assert n == rows
        np.testing.assert_array_equal(offsets, want_offsets)
        np.testing.assert_array_equal(blob, want_blob)


def test_one_wait_sizes_the_encode_of_fifteen_string_columns(ref, monkeypatch):
    """A single-batch call waits for the device once before its encode is
    launched, and asks no column for its longest string."""

    def refuse(self):
        raise AssertionError("Column.max_char_len reached from convert_to_rows")

    host = _cycle_table(512, 13)
    table = _device_table(host)
    monkeypatch.setattr(Column, "max_char_len", property(refuse))
    reg = metrics.registry()
    names = ("calls", "rows", "bytes_out", "batches", "string_cols", "size_waits", "padded", "scatter")
    before = {k: reg.value(f"rowconv.to_rows.{k}") for k in names}
    got = rc.convert_to_rows(table)
    moved = {k: reg.value(f"rowconv.to_rows.{k}") - v for k, v in before.items()}
    want = ref.rows(host)
    _assert_batches_equal(got, want)
    assert moved == {"calls": 1, "rows": 512, "bytes_out": len(want[0][1]), "batches": 1, "string_cols": 15,
                     "size_waits": 1, "padded": 1, "scatter": 0}


def test_a_second_pull_of_the_sizes_is_counted_when_the_table_spans_batches(monkeypatch):
    monkeypatch.setattr(rc, "MAX_BATCH_BYTES", 16384)
    reg = metrics.registry()
    before = reg.value("rowconv.to_rows.size_waits")
    rc.convert_to_rows(_device_table(_small_table(1000, 14, _uniform(0, 32))))
    assert reg.value("rowconv.to_rows.size_waits") - before == 2


def test_fixed_width_call_waits_for_no_sizes():
    reg = metrics.registry()
    names = ("calls", "string_cols", "size_waits", "padded", "scatter")
    before = {k: reg.value(f"rowconv.to_rows.{k}") for k in names}
    rc.convert_to_rows(Table([Column.from_numpy(np.arange(100, dtype=np.int32))]))
    moved = {k: reg.value(f"rowconv.to_rows.{k}") - v for k, v in before.items()}
    assert moved == {"calls": 1, "string_cols": 0, "size_waits": 0, "padded": 0, "scatter": 0}


def test_a_failing_encode_raises_and_leaves_no_process_state_behind(ref, monkeypatch):
    """One form of the encode: where it fails the operator raises, as any
    operator does, and the next call is not demoted to another path."""
    host = _small_table(100, 15, _uniform(0, 32))

    def broken(*args, **kwargs):
        raise RuntimeError("INTERNAL: the encode's program failed")

    with monkeypatch.context() as m:
        m.setattr(rc, "_jit_encode_strings_fused", broken)
        with pytest.raises(Exception, match="the encode's program failed"):
            rc.convert_to_rows(_device_table(host))
    assert not hasattr(rc, "_FUSED_ENCODE_BROKEN")
    assert not [name for name in vars(rc) if name.endswith("_BROKEN")]
    reg = metrics.registry()
    before = reg.value("rowconv.to_rows.padded")
    _assert_batches_equal(rc.convert_to_rows(_device_table(host)), ref.rows(host))
    assert reg.value("rowconv.to_rows.padded") - before == 1


def test_a_failing_encode_is_the_workers_error_reply(driver, monkeypatch):
    """Through the worker's dispatch the failure is the reply's status and
    message, not a silent answer by another path."""
    host = _small_table(50, 16, _uniform(0, 32))
    payload = driver._encode_table(host, [int(TypeId[name].value) for name, _, _ in host])

    def broken(*args, **kwargs):
        raise RuntimeError("INTERNAL: the encode's program failed")

    monkeypatch.setattr(rc, "_jit_encode_strings_fused", broken)
    with pytest.raises(Exception, match="the encode's program failed"):
        sidecar._dispatch(sidecar.OP_CONVERT_TO_ROWS, payload, "cpu")
