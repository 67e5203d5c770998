"""The row-to-column transcode's comparison has been shown to fail (ISSUE
38): the control and three replies broken underneath the timed path, each
in one way that only a check of every flag, every valid value and the
frame can see, come out not correct; the program's own answer comes out
correct. And the plain decode is the inverse of the plain encode.

    JAX_PLATFORMS=cpu python -m pytest bench/tests/test_rowconv_from.py -q -p no:cacheprovider
"""

from __future__ import annotations

import os
import struct
import sys

import numpy as np
import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

import control  # noqa: E402
from benchlib import compare, loader  # noqa: E402

CELL = "rowconv-212x1m-from.from-rows"
SEED = 2_147_483_931


def verdict(numbers: dict) -> bool:
    return all(c["ok"] for c in compare.judge(numbers, loader.cell(CELL)["config"]["limits"]))


def _columns(reply):
    """(writable copy of the reply, [(position of the validity bytes or
    None, position of the values, rows, value size)] a column)."""
    out, pos, where = bytearray(reply), 4, []
    (ncols,) = struct.unpack_from("<I", out, 0)
    for _ in range(ncols):
        _, _, n = struct.unpack_from("<iiQ", out, pos)
        pos += 16
        vpos = pos + 1 if out[pos] else None
        pos += 1 + (n if vpos is not None else 0)
        (dlen,) = struct.unpack_from("<Q", out, pos)
        where.append((vpos, pos + 8, n, dlen // max(n, 1)))
        pos += 8 + dlen
    return out, where


def _clear_one_validity_flag(session, reply):
    out, where = _columns(reply)
    vpos, _, n, _ = where[0]
    row = next(r for r in range(n) if out[vpos + r])
    out[vpos + row] = 0
    return bytes(out)


def _alter_one_value_byte(session, reply):
    """A value byte of a column with no nulls (the second): every row of it
    is valid, so the check has to see it."""
    out, where = _columns(reply)
    _, dpos, _, size = where[1]
    out[dpos + 5 * size] ^= 0x40
    return bytes(out)


def _a_column_short(session, reply):
    out, where = _columns(reply)
    vpos, _, _, _ = where[-1]
    end = vpos - 1 - 16  # the last column's header starts here
    out[0:4] = struct.pack("<I", len(where) - 1)
    return bytes(out[:end])


def _breaking(alter):
    def prepare(session):
        inner = session.issue

        def issue(i):
            rows, (ncols, nrows, reply) = inner(i)
            return rows, (ncols, nrows, alter(session, reply))

        session.issue = issue

    return prepare


def test_the_plain_decode_gives_the_encoded_table_back():
    cfg = loader.cell(CELL)["config"]
    cols = loader.module("data", cfg["data"]).host_tables(cfg, SEED, 512)["table"]
    rows, row_size = loader.module("references", "jcudf_rows").rows(cols)
    assert row_size == cfg["tables"]["table"]["row_bytes_jcudf"]
    got = loader.module("references", cfg["reference"]).columns(rows, [d.dtype for _, d, _ in cols])
    for (values, valid), (_, data, validity) in zip(got, cols):
        np.testing.assert_array_equal(values, data)
        np.testing.assert_array_equal(valid, np.ones(len(data), bool) if validity is None else validity)


def test_the_programs_answer_is_correct_and_the_control_is_not():
    r = control.readings(CELL, seed=SEED, seconds=0.2, rehearse=True, control=True)
    assert verdict(r["program"]), r["program"]
    assert r["control"]["from_rows.validity_diff"] > 0 and not verdict(r["control"]), r["control"]


@pytest.mark.parametrize("alter,reading", [(_clear_one_validity_flag, "from_rows.validity_diff"),
                                           (_alter_one_value_byte, "from_rows.bytes_diff"),
                                           (_a_column_short, "from_rows.frame_diff")])
def test_a_reply_broken_in_one_way_is_not_correct(alter, reading):
    r = control.readings(CELL, seed=SEED, seconds=0.2, rehearse=True, control=False, prepare=_breaking(alter))
    assert r["program"][reading] > 0 and not verdict(r["program"]), r["program"]


def test_a_decode_in_the_gathered_form_is_not_correct():
    """The worker's own counter is one of the readings (tests/test_rowconv_from_rows.py
    shows it move): a window with a gathered decode is not the cell's."""
    r = control.readings(CELL, seed=SEED, seconds=0.2, rehearse=True, control=False)
    assert r["program"]["worker.gathered_decodes"] == 0 and verdict(r["program"])
    assert not verdict(dict(r["program"], **{"worker.gathered_decodes": 1.0}))
