"""The store star's comparison has been shown to fail (PR 32): through
the harness's own judge the float32 control, an answer with one brand's
name altered, an answer with two brands' rows merged into one and an
answer short of a row all come out NOT correct, and the rehearsal of the
cell that PR 32 adds passes (``tpcds-sf10-web.q95`` on one chip was measured
and left out: its traffic file waits, PERF.md section 7).

    JAX_PLATFORMS=cpu python -m pytest bench/tests/test_store_star.py -q -p no:cacheprovider
"""

from __future__ import annotations

import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

import pytest  # noqa: E402

import control  # noqa: E402
from benchlib import compare, loader  # noqa: E402

STAR = "tpcds-sf1-store.q3-q55"


def verdict(cell: str, numbers: dict) -> bool:
    return all(c["ok"] for c in compare.judge(numbers, loader.cell(cell)["config"]["limits"]))


def _edited(edit):
    """``prepare`` for ``control.readings``: every answer of the window goes
    through ``edit(columns)``, a dict of column name -> list of host values
    (a NULL is None), before the harness keeps it."""

    def prepare(session):
        from spark_rapids_jni_tpu.columnar import Column, Table

        inner = session.issue

        def issue(i):
            rows, outs = inner(i)
            altered = []
            for t in outs:
                cols = {n: c.to_pylist() for n, c in zip(t.names, t.columns)}
                edit(cols)
                altered.append(Table([Column.from_pylist(cols[n], c.dtype) for n, c in zip(t.names, t.columns)],
                                     list(t.names)))
            return rows, altered

        session.issue = issue

    return prepare


def _one_name_altered(cols):
    cols["i_brand"][1] = cols["i_brand"][1][:-1] + "~"  # the LAST byte: past the 16 that used to be compared


def _two_brands_merged(cols):
    total = next(n for n in cols if n in ("sum_agg", "ext_price"))
    cols[total][0] = (cols[total][0] or 0.0) + (cols[total][1] or 0.0)
    for values in cols.values():
        del values[1]


def _a_row_short(cols):
    for values in cols.values():
        del values[-1]


def _same(cols):
    pass


def test_float32_control_is_not_correct():
    r = control.readings(STAR, seed=3_200_000_033, seconds=0.2, rehearse=True, control=True)
    assert verdict(STAR, r["program"]), r["program"]
    assert not verdict(STAR, r["control"]), r["control"]
    # by the sums alone: the float32 reference keeps every key and row
    assert all(v == 0 for k, v in r["control"].items() if not k.endswith("rel_gap")), r["control"]


@pytest.mark.parametrize("edit,reading", [(_one_name_altered, "exact_diff"), (_two_brands_merged, "shape_diff"),
                                          (_a_row_short, "shape_diff")],
                         ids=["one_name_altered", "two_brands_merged", "a_row_short"])
def test_altered_answer_is_not_correct(edit, reading):
    r = control.readings(STAR, seed=3_200_104_759, seconds=0.2, rehearse=True, control=False, prepare=_edited(edit))
    assert not verdict(STAR, r["program"]), r["program"]
    assert r["program"][f"tpcds_q3.{reading}"] > 0 and r["program"][f"tpcds_q55.{reading}"] > 0, r["program"]


def test_an_answer_through_the_same_round_trip_unaltered_is_correct():
    r = control.readings(STAR, seed=3_200_104_759, seconds=0.2, rehearse=True, control=False, prepare=_edited(_same))
    assert verdict(STAR, r["program"]), r["program"]


def test_rehearsal_passes():
    r = control.readings(STAR, seed=3_200_209_489, seconds=0.2, rehearse=True, control=False)
    assert r["requests"] >= 1 and verdict(STAR, r["program"]), r
