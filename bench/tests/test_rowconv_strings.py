"""The variable-width transcode's comparison has been shown to fail
(ISSUE 34): the control and three replies broken underneath the timed
path, each in one way that only a check of every byte and of the frame
can see, come out not correct; the program's own answer comes out correct.

    JAX_PLATFORMS=cpu python -m pytest bench/tests/test_rowconv_strings.py -q -p no:cacheprovider
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

CELL = "rowconv-155x1m-strings.to-rows"
SEED = 2_147_483_929


def verdict(numbers: dict) -> bool:
    return all(c["ok"] for c in compare.judge(numbers, loader.cell(CELL)["config"]["limits"]))


def _rows_of(session, reply):
    """(writable copy of the reply, position of the row bytes in it, the
    one batch's offsets, the slot starts of the table's STRING columns)."""
    (_, offsets, blob), = loader.module("drivers", "sidecar_var").parse_reply(reply)
    at = len(reply) - len(blob)
    starts, _, _ = session.jcudf.layout(session.cols)
    slots = [s for s, col in zip(starts, session.cols) if session.jcudf.is_string(col)]
    return bytearray(reply), at, offsets, slots


def _swap_two_strings(session, reply):
    """The first two strings of one row change places: the same characters,
    the same row size, another order than the columns'."""
    out, at, offsets, slots = _rows_of(session, reply)
    for r in range(len(offsets) - 1):
        row = at + int(offsets[r])
        (o1, n1), (o2, n2) = (struct.unpack_from("<II", out, row + s) for s in slots[:2])
        a, b = bytes(out[row + o1:row + o1 + n1]), bytes(out[row + o2:row + o2 + n2])
        if n1 and n2 and a + b != b + a:
            assert o2 == o1 + n1  # column order: the second string follows the first
            out[row + o1:row + o1 + n1 + n2] = b + a
            return bytes(out)
    raise AssertionError("no row with two different strings")


def _set_a_padding_byte(session, reply):
    out, at, offsets, slots = _rows_of(session, reply)
    for r in range(len(offsets) - 1):
        row, end = at + int(offsets[r]), at + int(offsets[r + 1])
        o, n = struct.unpack_from("<II", out, row + slots[-1])
        if row + o + n < end:  # the row's last string ends before the row does
            assert out[end - 1] == 0
            out[end - 1] = 1
            return bytes(out)
    raise AssertionError("no row with padding")


def _uniform_offsets(session, reply):
    """The frame of a fixed-width reply: offsets at multiples of one row
    size (the mean), the bytes untouched."""
    out, at, offsets, _ = _rows_of(session, reply)
    n = len(offsets) - 1
    uniform = (np.arange(n + 1, dtype=np.int64) * (int(offsets[-1]) // n)).astype("<i4")
    out[12:12 + uniform.nbytes] = uniform.tobytes()
    return bytes(out)


def _breaking(alter):
    def prepare(session):
        inner = session.issue

        def issue(i):
            rows, (nbatches, nrows, reply) = inner(i)
            return rows, (nbatches, nrows, alter(session, reply))

        session.issue = issue

    return prepare


def test_the_programs_answer_is_correct_and_the_control_is_not():
    r = control.readings(CELL, seed=SEED, seconds=0.2, rehearse=True, control=True)
    assert verdict(r["program"]), r["program"]
    assert r["control"]["to_rows.bytes_diff"] > 0 and not verdict(r["control"]), r["control"]


@pytest.mark.parametrize("alter,reading", [(_swap_two_strings, "to_rows.bytes_diff"),
                                           (_set_a_padding_byte, "to_rows.bytes_diff"),
                                           (_uniform_offsets, "to_rows.frame_diff")])
def test_a_reply_broken_in_one_way_is_not_correct(alter, reading):
    r = control.readings(CELL, seed=SEED, seconds=0.2, rehearse=True, control=False, prepare=_breaking(alter))
    assert r["program"][reading] > 0 and not verdict(r["program"]), r["program"]


def test_a_call_on_the_scatter_path_is_not_correct():
    """The worker's own counter is one of the readings (tests/test_rowconv_strings.py
    shows it move): a window with a call off the padded form is not the cell's."""
    r = control.readings(CELL, seed=SEED, seconds=0.2, rehearse=True, control=False)
    assert r["program"]["worker.scatter_encodes"] == 0 and verdict(r["program"])
    assert not verdict(dict(r["program"], **{"worker.scatter_encodes": 1.0}))
