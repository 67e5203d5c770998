"""The comparison has been shown to fail: the control comes out as not
correct, and so does a run whose timed path is broken underneath.

Run by hand on the CPU (no chip needed, tiny sizes):

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q -p no:cacheprovider

The benchmark's own runs never run these. Each case skips the harness's
look for a chip (``rehearse``) and drives the rest of a run: set-up, a
short window through the cell's own entry, release, the comparison.
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

import control  # noqa: E402
from benchlib import compare, loader  # noqa: E402

CELLS = [w["name"] for w in loader.benchmark()["workloads"]]


def verdict(cell: str, numbers: dict) -> bool:
    return all(c["ok"] for c in compare.judge(numbers, loader.cell(cell)["config"]["limits"]))


@pytest.mark.parametrize("cell", CELLS)
def test_program_is_correct_and_control_is_not(cell):
    r = control.readings(cell, seed=2_147_483_659, seconds=0.2, rehearse=True, control=True)
    assert verdict(cell, r["program"]), r["program"]
    assert not verdict(cell, r["control"]), r["control"]


def _alter_answer(session):
    """An answer altered where it is produced: one value of each result, by
    one part in a million (plan cells); one byte of the reply (sidecar)."""
    inner = session.issue

    def issue(i):
        rows, handle = inner(i)
        if isinstance(handle, tuple):  # sidecar: (nbatches, nrows, reply bytes)
            reply = bytearray(handle[2])
            reply[len(reply) // 2] ^= 0x01
            return rows, (handle[0], handle[1], bytes(reply))
        from spark_rapids_jni_tpu.columnar import Column, Table
        from spark_rapids_jni_tpu.columnar.dtype import TypeId

        outs = []
        for t in handle:
            cols = list(t.columns)
            k = next(j for j, c in enumerate(cols) if c.dtype.id == TypeId.FLOAT64)
            v = np.asarray(cols[k].data).view(np.float64).copy()
            v[0] *= 1 + 1e-6
            cols[k] = Column.from_numpy(v)
            outs.append(Table(cols, list(t.names)))
        return rows, outs

    session.issue = issue


def _half_the_rows(session):
    """Half of the batch left out: the plans run over the first half of
    every fact table, the reference over all of it."""
    from spark_rapids_jni_tpu import plan as P
    from spark_rapids_jni_tpu.columnar import Column, Table

    name = session.config["scaled_table"]
    t = session.tables[name]
    half = t.num_rows // 2
    session.tables[name] = Table([Column(c.dtype, data=c.data[:half],
                                         validity=None if c.validity is None else c.validity[:half])
                                  for c in t.columns], list(t.names))
    session.compiled = [P.compile_ir(q.plan(P), {n: session.tables[n] for n in q.TABLES}, name=qn)
                        for qn, q in session.queries]


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answer_is_not_correct(cell):
    r = control.readings(cell, seed=2_147_483_777, seconds=0.2, rehearse=True, control=False,
                         prepare=_alter_answer)
    assert not verdict(cell, r["program"]), r["program"]


@pytest.mark.parametrize("cell", [c for c in CELLS if loader.cell(c)["traffic"]["driver"] == "plan_serve"])
def test_half_the_rows_is_not_correct(cell):
    r = control.readings(cell, seed=2_147_483_777, seconds=0.2, rehearse=True, control=False,
                         prepare=_half_the_rows)
    assert not verdict(cell, r["program"]), r["program"]
