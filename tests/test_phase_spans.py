"""Phase spans down the two served paths (ISSUE 26).

The group-by and the plan stages name their phases, the sidecar request
names its passes over the payload (worker and client, one spawned worker
so the spans cross a real process boundary), compiles are counted and
show as ``xla.compile`` spans, the span log is written per request and
not per span, ``STATS`` answers for the worker's own device, and the ten
per-layer readers under ``bench/readers/`` reduce a synthetic span list
to the numbers worked out by hand here.
"""

import glob
import importlib.util
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu import plan as P
from spark_rapids_jni_tpu import serve, sidecar, sidecar_pool
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.columnar import dtype as dt
from spark_rapids_jni_tpu.ops.aggregate import groupby_aggregate
from spark_rapids_jni_tpu.utils import metrics, trace_sink, tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(REPO, "bench")


pytestmark = pytest.mark.usefixtures("own_span_log")


def _read_logs(pattern):
    out = []
    for path in sorted(glob.glob(pattern)):
        with open(path) as f:
            out += [json.loads(line) for line in f if line.strip()]
    return out


def _log_spans():
    path = trace_sink.resolved_log_path()
    return [r for r in _read_logs(path) if r.get("kind") == "span"]


def _counter(name):
    return metrics.registry().value(name)


# ---------------------------------------------------------------------------
# execution: plan.<kind> and groupby.* on a q1-shaped plan
# ---------------------------------------------------------------------------

N_ROWS = 4000


def _lineitem(rng, rows=None):
    rows = N_ROWS if rows is None else rows

    def f64(a):
        return Column.from_numpy(np.ascontiguousarray(a, np.float64), dt.FLOAT64)

    return Table(
        [
            Column.from_numpy(rng.integers(0, 3, rows).astype(np.int8), dt.INT8),
            Column.from_numpy(rng.integers(0, 2, rows).astype(np.int8), dt.INT8),
            f64(rng.integers(1, 51, rows)),
            f64(rng.uniform(900.0, 105000.0, rows).round(2)),
            f64(rng.integers(0, 11, rows) / 100.0),
            Column.from_numpy(rng.integers(0, 2600, rows).astype(np.int32), dt.INT32),
        ],
        ["flag", "status", "qty", "price", "disc", "shipdate"],
    )


def _q1_shaped_plan(cutoff=2436):
    disc_price = P.pcol("price") * (P.plit(1.0) - P.pcol("disc"))
    x = P.Filter(P.Scan("lineitem"), P.pcol("shipdate") <= P.plit(np.int32(cutoff)))
    x = P.Project(x, (
        ("flag", P.pcol("flag")),
        ("status", P.pcol("status")),
        ("qty", P.pcol("qty")),
        ("disc_price", disc_price),
    ))
    agg = P.Aggregate(x, keys=("flag", "status"), aggs=(
        P.AggSpec("qty", "sum", "sum_qty"),
        P.AggSpec("disc_price", "sum", "sum_disc_price"),
        P.AggSpec("qty", "mean", "avg_qty"),
        P.AggSpec(None, "count_all", "count_order"),
    ))
    return P.Sort(agg, (("flag", True), ("status", True)))


@pytest.fixture(scope="module")
def q1_spans():
    return _spans_of_a_q1_shaped_query()


@pytest.fixture(scope="module")
def q1_sorted_spans():
    """The same query down the sort path: the dense form's bound shut."""
    return _spans_of_a_q1_shaped_query(dense_max_slots=0)


@pytest.fixture
def q1_form_spans(request):
    """``q1_spans`` (``dense``: what the query takes) or ``q1_sorted_spans`` (``sorted``), by the test's parameter."""
    return request.getfixturevalue({"dense": "q1_spans", "sorted": "q1_sorted_spans"}[request.param])


both_forms = pytest.mark.parametrize("q1_form_spans", ["sorted", "dense"], indirect=True)


def _spans_of_a_q1_shaped_query(cutoff=2436, dense_max_slots=None, rows=None):
    """The span tree of one traced q1-shaped query through the scheduler
    (the second run: the first has compiled everything). ``dense_max_slots``
    0 shuts the group-by's dense form behind its probe (ISSUE 37): the two
    int8 keys span six values, so as it is the query never sorts."""
    from spark_rapids_jni_tpu.ops import aggregate

    table = _lineitem(np.random.default_rng(26), rows)
    cp = P.compile_ir(_q1_shaped_plan(cutoff), {"lineitem": table}, name="q1_shaped")
    sched = serve.Scheduler(max_concurrent=1, name="phase-spans")
    prev = tracing.is_enabled()
    bound = aggregate._DENSE_MAX_SLOTS
    try:
        if dense_max_slots is not None:
            aggregate._DENSE_MAX_SLOTS = dense_max_slots
        sched.submit(cp).result()
        trace_sink.reset_for_tests()
        tracing.set_enabled(True)
        out = sched.submit(cp).result()
        jax.block_until_ready([c.data for c in out.columns])
    finally:
        aggregate._DENSE_MAX_SLOTS = bound
        tracing.set_enabled(prev)
        sched.shutdown()
    rec = trace_sink.recorder().last(1)[0]
    assert rec["name"] == "serve.query" and rec["dropped_spans"] == 0
    return rec["spans"]


def _by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s["name"], []).append(s)
    return out


def _one(spans, name):
    hits = _by_name(spans)[name]
    assert len(hits) == 1, (name, len(hits))
    return hits[0]


PLAN_PARENTS = [
    ("plan.sort", "serve.run"),
    ("plan.aggregate", "plan.sort"),
    ("plan.project", "plan.aggregate"),
    ("plan.filter", "plan.project"),
    ("plan.scan", "plan.filter"),
    ("op.groupby_aggregate", "plan.aggregate"),
]


@pytest.mark.parametrize("child,parent", PLAN_PARENTS)
def test_plan_stage_spans_nest_as_the_plan_does(q1_spans, child, parent):
    assert _one(q1_spans, child)["parent"] == _one(q1_spans, parent)["span"]


def test_plan_stage_spans_say_their_rows_out(q1_spans):
    assert _one(q1_spans, "plan.scan")["annotations"] == {"rows_out": N_ROWS}
    # ISSUE 35: the Filter keeps 94% of its rows and an Aggregate reads them
    # through one jitted Project, so it hands its mask on: ``rows_out`` is the
    # slot count (as a mesh stage's), ``kept`` the rows that are present
    notes = _one(q1_spans, "plan.filter")["annotations"]
    assert notes["deferred"] is True and 0 < notes["kept"] < N_ROWS
    assert notes["rows_out"] == N_ROWS
    assert _one(q1_spans, "plan.project")["annotations"]["rows_out"] == N_ROWS
    assert _one(q1_spans, "plan.aggregate")["annotations"]["rows_out"] == 6


@pytest.mark.parametrize("stage,jit,exprs", [
    ("plan.filter", True, 1),      # the predicate, its validity folded in: one program
    ("plan.project", True, 1),     # disc_price; the three references are handed on
    ("plan.aggregate", False, 0),  # FLOAT64 sums and a count: nothing to normalise, no launch
])
def test_stage_spans_say_what_went_into_the_stages_one_program(q1_spans, stage, jit, exprs):
    """ISSUE 33: a Filter's or a Project's expressions, and an aggregate's
    float64 normalisation, are ONE jitted program a stage."""
    notes = _one(q1_spans, stage)["annotations"]
    assert notes["jit"] is jit and notes["exprs"] == exprs
    own = {"deferred", "kept"} if stage == "plan.filter" else set()  # ISSUE 35
    assert set(notes) == {"rows_out", "jit", "exprs"} | own
    assert "jit" not in _one(q1_spans, "plan.scan")["annotations"]


@pytest.mark.parametrize("case,jitted,eager", [
    ("computed_project", 1, 0), ("references_only", 0, 0), ("like_filter", 0, 1),
    ("like_and_integer_filter", 0, 1), ("integer_filter", 1, 0), ("normalised_min_max", 1, 0)])
def test_expr_counters_move_as_the_stages_run(case, jitted, eager):
    from spark_rapids_jni_tpu import runtime

    assert not tracing.is_enabled()  # registry-direct: counted with tracing off
    t = Table([Column.from_numpy(np.arange(12, dtype=np.int32) % 4, dt.INT32),
               Column.from_numpy(np.arange(12, dtype=np.float64) / 4.0, dt.FLOAT64),
               Column.from_pylist(["pri", "able", "prime"] * 4, dt.STRING)], ["k", "x", "s"])
    scan = P.Scan("t")
    plan = {
        "computed_project": P.Project(scan, (("s", P.pcol("s")), ("y", P.pcol("x") * P.plit(2.0)), ("z", P.pcol("k") + P.plit(1)))),
        "references_only": P.Project(scan, (("s", P.pcol("s")), ("x", P.pcol("x")))),
        "like_filter": P.Filter(scan, P.plike(P.pcol("s"), "pri%")),
        "like_and_integer_filter": P.Filter(scan, P.plike(P.pcol("s"), "pri%") & (P.pcol("k") < P.plit(np.int32(3)))),
        "integer_filter": P.Filter(scan, P.pcol("k") != P.plit(np.int32(3))),
        "normalised_min_max": P.Aggregate(P.Aggregate(scan, keys=("k", "x"), aggs=()), keys=("x",), aggs=(
            P.AggSpec("k", "min", "lo"), P.AggSpec("k", "max", "hi"), P.AggSpec("k", "count", "n"))),
    }[case]
    cp = P.compile_ir(plan, {"t": t}, name=case)
    was = _counter("plan.expr.jitted"), _counter("plan.expr.eager")
    cp()
    assert (_counter("plan.expr.jitted") - was[0], _counter("plan.expr.eager") - was[1]) == (jitted, eager)
    cp()  # one a stage run, every run
    assert (_counter("plan.expr.jitted") - was[0], _counter("plan.expr.eager") - was[1]) == (2 * jitted, 2 * eager)
    counters = runtime.stats_report()["metrics"]["counters"]
    assert counters.get("plan.expr.jitted", 0) >= 2 * jitted and counters.get("plan.expr.eager", 0) >= 2 * eager


@pytest.mark.parametrize("form", ["dense", "sorted"])
@pytest.mark.parametrize("share,deferred", [("most", True), ("half", True), ("a_row_under_half", False), ("none", False)])
def test_filter_counters_and_spans_say_which_form_ran(share, deferred, form):
    """ISSUE 35: ``plan.filter.deferred`` a Filter run that handed its mask
    on, ``plan.filter.compacted`` one that ran ``apply_boolean_mask``
    (registry-direct: counted with tracing off, shown by ``stats_report``);
    the span says ``deferred`` and ``kept``, ``groupby.sort`` says ``masked``.
    ISSUE 37: the group-by behind either form of the Filter is dense (two
    int8 keys over six values: ``groupby.dense``, no ``groupby.sort`` span,
    ``groupby.segments`` says ``dense``, ``domain`` and ``groups``) unless
    its bound is shut or no row is left."""
    from spark_rapids_jni_tpu import runtime

    assert not tracing.is_enabled()
    ship = np.asarray(_lineitem(np.random.default_rng(26)).column("shipdate").data)
    middle = int(np.sort(ship)[N_ROWS // 2 - 1])  # ``<= middle`` keeps half the rows or a few more
    cutoff = {"most": 2436, "half": middle, "a_row_under_half": middle - 1, "none": -1}[share]
    was = _counter("plan.filter.deferred"), _counter("plan.filter.compacted")
    forms = _counter("groupby.dense"), _counter("groupby.sorted")
    spans = _spans_of_a_q1_shaped_query(cutoff, 0 if form == "sorted" else None)  # two runs: one untraced, one traced
    moved = _counter("plan.filter.deferred") - was[0], _counter("plan.filter.compacted") - was[1]
    assert moved == ((2, 0) if deferred else (0, 2))
    kept = int((ship <= cutoff).sum())
    assert (2 * kept >= N_ROWS) is deferred
    notes = _one(spans, "plan.filter")["annotations"]
    assert notes["deferred"] is deferred and notes["kept"] == kept
    assert notes["rows_out"] == (N_ROWS if deferred else kept)
    dense = form == "dense" and kept > 0
    assert (_counter("groupby.dense") - forms[0], _counter("groupby.sorted") - forms[1]) == ((2, 0) if dense else (0, 2))
    segments = [s["annotations"] for s in _by_name(spans)["groupby.segments"]]
    if dense:
        assert "groupby.sort" not in _by_name(spans)
        assert segments == [{"dense": True, "domain": 6, "groups": 6}]
    else:
        sort = _one(spans, "groupby.sort")["annotations"]
        assert sort["masked"] is deferred and sort["rows"] == notes["rows_out"]
        assert sort["key_lanes"] == (5 if deferred else 4)
        # the probe's span says why it sorts; no row at all is refused before the probe
        assert segments == ([{"dense": False, "domain": 6}] if kept else []) + [{"groups": 6 if kept else 0}]
    counters = runtime.stats_report()["metrics"]["counters"]
    assert counters.get("plan.filter.deferred", 0) >= moved[0]
    assert counters.get("plan.filter.compacted", 0) >= moved[1]


def _sorted_forms_segments(spans):
    """The sort path's ``groupby.segments`` span, past the one the refused probe left in front of the sort
    (ISSUE 37: the form is known only when the probe is back, inside a span of this name)."""
    refused, segments = sorted(_by_name(spans)["groupby.segments"], key=lambda s: s["ts"])
    assert refused["annotations"] == {"dense": False, "domain": 6} and refused["parent"] == segments["parent"]
    assert refused["ts"] < _one(spans, "groupby.sort")["ts"] < segments["ts"]
    return segments


def _agg_spans_are_children_of(spans, op):
    names = _by_name(spans)
    aggs = sorted(
        (s["name"], s["annotations"]["col"], s["annotations"]["dtype"])
        for n, ss in names.items() if n.startswith("groupby.agg.") for s in ss
    )
    assert aggs == [
        ("groupby.agg.count_all", "flag", "INT8"),
        ("groupby.agg.mean", "qty", "FLOAT64"),
        ("groupby.agg.sum", "disc_price", "FLOAT64"),
        ("groupby.agg.sum", "qty", "FLOAT64"),
    ]
    assert all(s["parent"] == op for n, ss in names.items()
               if n.startswith("groupby.agg.") for s in ss)


def test_groupby_phase_spans_are_children_of_the_operator(q1_sorted_spans):
    spans = q1_sorted_spans
    op = _one(spans, "op.groupby_aggregate")["span"]
    for phase in ("groupby.sort", "groupby.keys"):
        assert _one(spans, phase)["parent"] == op
    assert _sorted_forms_segments(spans)["parent"] == op
    assert _one(spans, "groupby.sort")["annotations"] == {"rows": _one(
        spans, "plan.project")["annotations"]["rows_out"], "keys": 2,
        # two int8 keys: a null rank and one lane each (PR 32), no STRING;
        # and in front of them the deferred Filter's mask (ISSUE 35)
        "key_lanes": 5, "string_keys": 0, "masked": True}
    assert _sorted_forms_segments(spans)["annotations"] == {"groups": 6}
    _agg_spans_are_children_of(spans, op)


def test_the_dense_groupbys_phase_spans_are_children_of_the_operator(q1_spans):
    """ISSUE 37: two int8 keys over six values: the groups are numbered from
    the codes, inside ``groupby.segments``, and nothing sorts."""
    op = _one(q1_spans, "op.groupby_aggregate")["span"]
    for phase in ("groupby.segments", "groupby.keys"):
        assert _one(q1_spans, phase)["parent"] == op
    assert "groupby.sort" not in _by_name(q1_spans)
    assert _one(q1_spans, "groupby.segments")["annotations"] == {"dense": True, "domain": 6, "groups": 6}
    _agg_spans_are_children_of(q1_spans, op)


@both_forms
def test_agg_spans_say_which_went_through_the_one_program(q1_form_spans):
    """ISSUE 29: a FLOAT64 sum or mean is one jitted program, said by the
    span's ``jit``; and nothing compiles in a request after the first
    (``_limb_divide``'s scan body was a new closure a call before)."""
    jit = sorted((s["name"], s["annotations"]["col"], s["annotations"]["jit"])
                 for s in q1_form_spans if s["name"].startswith("groupby.agg."))
    assert jit == [
        ("groupby.agg.count_all", "flag", False),
        ("groupby.agg.mean", "qty", True),
        ("groupby.agg.sum", "disc_price", True),
        ("groupby.agg.sum", "qty", True),
    ]
    assert [s for s in q1_form_spans if s["name"] == "xla.compile"] == []


def test_agg_counters_tell_the_one_program_from_the_eager_branches():
    from spark_rapids_jni_tpu import runtime

    assert not tracing.is_enabled()  # registry-direct: counted with tracing off
    keys = Table([Column.from_numpy(np.array([1, 1, 2], np.int32), dt.INT32)], ["k"])
    vals = Table([Column.from_numpy(np.array([1.5, 2.25, 3.0]), dt.FLOAT64),
                  Column.from_numpy(np.array([1, 2, 3], np.int64), dt.INT64),
                  Column.from_numpy(np.array([1, 2, 3], np.float32), dt.FLOAT32)], ["f", "i", "g"])
    jitted, eager = _counter("groupby.agg.jitted"), _counter("groupby.agg.eager")
    out = groupby_aggregate(keys, vals, [("f", "sum"), ("f", "mean"), ("f", "min"), ("i", "sum"),
                                         ("g", "mean"), ("f", "count"), ("f", "std")])
    assert np.asarray(out.column("f_sum").data).view(np.float64).tolist() == [3.75, 3.0]
    assert np.asarray(out.column("f_mean").data).view(np.float64).tolist() == [1.875, 3.0]
    assert _counter("groupby.agg.jitted") == jitted + 2
    assert _counter("groupby.agg.eager") == eager + 5
    counters = runtime.stats_report()["metrics"]["counters"]
    assert counters["groupby.agg.jitted"] >= 2 and counters["groupby.agg.eager"] >= 5


# ---------------------------------------------------------------------------
# execution: join.* under op.*_join, and the key lanes of a STRING key (ISSUE 32)
# ---------------------------------------------------------------------------

N_FACT, N_DIM = 3000, 40
STAR_COUNTERS = ("join.calls", "join.rows_probed", "join.rows_out", "keys.string.columns", "keys.string.lanes",
                 "groupby.dense", "groupby.sorted")


def _star_tables(rng):
    brands = [f"exportischolar #{i}" for i in range(1, N_DIM + 1)]  # 17 or 18 bytes, alike in the first 16
    return {
        "fact": Table([Column.from_numpy(rng.integers(1, N_DIM + 11, N_FACT).astype(np.int32), dt.INT32),
                       Column.from_numpy(rng.integers(1, 100, N_FACT).astype(np.int64), dt.INT64)], ["f_dim", "f_v"]),
        "dim": Table([Column.from_numpy(np.arange(1, N_DIM + 1, dtype=np.int32), dt.INT32),
                      Column.from_pylist(brands, dt.STRING)], ["d_sk", "d_brand"]),
    }


def _star_plan():
    x = P.Join(P.Scan("fact"), P.Scan("dim"), on=(("f_dim", "d_sk"),), bounded=None)
    agg = P.Aggregate(x, keys=("d_brand",), aggs=(P.AggSpec("f_v", "sum", "total"),))
    return P.Sort(agg, (("d_brand", False),))


@pytest.fixture(scope="module")
def star_spans():
    """(spans, counters moved, answer) of one traced star request: the
    second run, and the five counters over an UNTRACED third."""
    tables = _star_tables(np.random.default_rng(32))
    cp = P.compile_ir(_star_plan(), tables, name="star_shaped")
    sched = serve.Scheduler(max_concurrent=1, name="phase-spans-star")
    prev = tracing.is_enabled()
    try:
        sched.submit(cp).result()
        trace_sink.reset_for_tests()
        tracing.set_enabled(True)
        out = sched.submit(cp).result()
        jax.block_until_ready([c.data for c in out.columns if c.data is not None])
        rec = trace_sink.recorder().last(1)[0]
        tracing.set_enabled(False)
        before = {k: _counter(k) for k in STAR_COUNTERS}
        sched.submit(cp).result()
        moved = {k: _counter(k) - v for k, v in before.items()}
    finally:
        tracing.set_enabled(prev)
        sched.shutdown()
    assert rec["name"] == "serve.query" and rec["dropped_spans"] == 0
    return rec["spans"], moved, out


def test_join_phase_spans_lie_side_by_side_under_the_operator(star_spans):
    spans, _, out = star_spans
    op = _one(spans, "op.inner_join")
    phases = [s for s in spans if s["name"].startswith("join.")]
    assert sorted(s["name"] for s in phases) == ["join.expand", "join.factorize", "join.gather", "join.probe"]
    assert all(s["parent"] == op["span"] for s in phases)  # none nests in another
    joined = _one(spans, "join.expand")["annotations"]["rows_out"]
    assert 0 < joined < N_FACT  # ten of the fact's fifty keys meet no dimension row
    assert _one(spans, "join.factorize")["annotations"] == {
        "rows_left": N_FACT, "rows_right": N_DIM, "keys": 1, "string_keys": 0, "key_lanes": 2}
    assert _one(spans, "join.probe")["annotations"] == {"rows_probed": N_FACT, "tier": "xla"}
    assert _one(spans, "join.gather")["annotations"] == {"cols": 3}
    assert sum(s["dur_us"] for s in phases) <= op["dur_us"]
    assert out.column("d_brand").to_pylist() == sorted((f"exportischolar #{i}" for i in range(1, N_DIM + 1)),
                                                       reverse=True)


def test_sort_spans_say_their_key_lanes(star_spans):
    spans, _, _ = star_spans
    joined = _one(spans, "join.expand")["annotations"]["rows_out"]
    # an 18-byte brand: a null rank, three 8-byte lanes and the length
    assert _one(spans, "groupby.sort")["annotations"] == {"rows": joined, "keys": 1, "key_lanes": 5, "string_keys": 1,
                                                             "masked": False}
    assert _one(spans, "op.sort_by_key")["annotations"] == {"key_lanes": 5, "string_keys": 1}


def test_join_and_string_key_counters_count_with_tracing_off(star_spans):
    spans, moved, _ = star_spans
    joined = _one(spans, "join.expand")["annotations"]["rows_out"]
    # the brand's lanes are made three times a request: the group-by's sort, its boundaries, the Sort
    # ISSUE 37: a STRING key is refused by its dtype: the group-by sorts, and probes nothing (the waits below)
    assert moved == {"join.calls": 1, "join.rows_probed": N_FACT, "join.rows_out": joined,
                     "keys.string.columns": 3, "keys.string.lanes": 12, "groupby.dense": 0, "groupby.sorted": 1}


@pytest.fixture(scope="module")
def q1_dense_spans_at_size():
    """The dense form where its operator is the ~12 ms of host that the sorted one is at ``N_ROWS``: 600,000 rows.
    At ``N_ROWS`` it is ~3 ms, and the ~0.4 ms no phase can hold (the boundary's preamble, six spans closed and
    recorded, the same in both forms) is a seventh of that, where it is a thirtieth of the sorted operator."""
    return _spans_of_a_q1_shaped_query(rows=600_000)


@pytest.mark.parametrize("spans,whole,parts", [
    ("q1_sorted_spans", "op.groupby_aggregate", "groupby."),
    ("q1_sorted_spans", "serve.run", "plan."),
    ("q1_dense_spans_at_size", "op.groupby_aggregate", "groupby."),  # ISSUE 37
    ("q1_dense_spans_at_size", "serve.run", "plan."),
])
def test_phase_spans_cover_nine_tenths_of_their_parent(request, spans, whole, parts):
    spans = request.getfixturevalue(spans)
    parent = _one(spans, whole)
    covered = sum(s["dur_us"] for s in spans
                  if s["parent"] == parent["span"] and s["name"].startswith(parts))
    assert covered >= 0.9 * parent["dur_us"], (covered, parent["dur_us"])
    assert covered <= parent["dur_us"]


# ---------------------------------------------------------------------------
# kernels: rowconv.sizes and rowconv.encode under op.convert_to_rows (ISSUE 34)
# ---------------------------------------------------------------------------

ROWCONV_ROWS = 600


def _mixed_table(rng):
    words = ["", "a", "spark", "tpu-native", "x" * 31, "yz"]
    return Table([
        Column.from_numpy(rng.integers(-9, 9, ROWCONV_ROWS).astype(np.int32), dt.INT32),
        Column.from_pylist([words[i % len(words)] for i in range(ROWCONV_ROWS)], dt.STRING),
        Column.from_numpy(rng.integers(0, 1 << 40, ROWCONV_ROWS).astype(np.int64), dt.INT64),
        Column.from_pylist([None if i % 7 == 0 else words[(i * 3) % len(words)] for i in range(ROWCONV_ROWS)],
                           dt.STRING),
    ], ["a", "s1", "b", "s2"])


def _traced_convert_to_rows(table):
    from spark_rapids_jni_tpu.ops import row_conversion as rc

    rc.convert_to_rows(table)  # compiled before the traced call
    trace_sink.reset_for_tests()
    with tracing.enabled():
        qt = tracing.start_trace("rowconv.test")
        with qt.activate():
            out = rc.convert_to_rows(table)
        qt.finish("ok")
    return trace_sink.recorder().last(1)[0]["spans"], out


@pytest.fixture(scope="module")
def rowconv_spans():
    """{case: (spans, batches)} of one traced convert_to_rows each: a mixed
    table in one batch, the same table with the batch limit patched small,
    the same with the padded form's budget patched small."""
    from spark_rapids_jni_tpu.ops import row_conversion as rc

    table = _mixed_table(np.random.default_rng(34))
    out = {"one_batch": _traced_convert_to_rows(table)}
    limit, budget = rc.MAX_BATCH_BYTES, rc._PADDED_ROWS_BYTE_BUDGET
    try:
        rc.MAX_BATCH_BYTES = 8192
        out["several_batches"] = _traced_convert_to_rows(table)
        rc.MAX_BATCH_BYTES, rc._PADDED_ROWS_BYTE_BUDGET = limit, 1024
        out["scatter"] = _traced_convert_to_rows(table)
    finally:
        rc.MAX_BATCH_BYTES, rc._PADDED_ROWS_BYTE_BUDGET = limit, budget
    return out


@pytest.mark.parametrize("case", ["one_batch", "several_batches", "scatter"])
def test_rowconv_spans_are_side_by_side_under_the_operator(rowconv_spans, case):
    spans, batches = rowconv_spans[case]
    op = _one(spans, "op.convert_to_rows")
    sizes = _one(spans, "rowconv.sizes")  # the one span that waits, whatever the batches
    encodes = sorted(_by_name(spans)["rowconv.encode"], key=lambda s: s["ts"])
    assert len(encodes) == len(batches)
    assert all(s["parent"] == op["span"] for s in [sizes] + encodes)  # never nested in each other
    assert all(sizes["ts"] <= e["ts"] for e in encodes)
    assert [e["annotations"]["batch"] for e in encodes] == list(range(len(batches)))


def test_rowconv_sizes_says_what_the_one_transfer_told_the_host(rowconv_spans):
    spans, batches = rowconv_spans["one_batch"]
    blob = int(batches[0].child.data.shape[0])
    sizes = np.diff(np.asarray(batches[0].offsets))
    assert _one(spans, "rowconv.sizes")["annotations"] == {
        "rows": ROWCONV_ROWS, "string_cols": 2, "total_bytes": blob, "max_row": int(sizes.max())}


@pytest.mark.parametrize("case,form", [("one_batch", "padded"), ("several_batches", "padded"), ("scatter", "scatter")])
def test_rowconv_encode_says_its_form_and_its_batch(rowconv_spans, case, form):
    spans, batches = rowconv_spans[case]
    assert (len(batches) > 2) if case == "several_batches" else (len(batches) == 1)
    for e, b in zip(sorted(_by_name(spans)["rowconv.encode"], key=lambda s: s["ts"]), batches):
        notes = e["annotations"]
        assert set(notes) == {"form", "batch", "rows", "bytes", "maxvar"}
        assert notes["form"] == form and notes["rows"] == len(b) and notes["bytes"] == int(b.child.data.shape[0])
        assert notes["maxvar"] % 64 == 0 and notes["maxvar"] >= 64


def test_rowconv_counters_show_in_the_stats_report():
    from spark_rapids_jni_tpu import runtime
    from spark_rapids_jni_tpu.ops import row_conversion as rc

    assert not tracing.is_enabled()  # registry-direct: counted with tracing off
    names = ("calls", "rows", "bytes_out", "batches", "string_cols", "size_waits", "padded", "scatter")
    before = {k: _counter(f"rowconv.to_rows.{k}") for k in names}
    (batch,) = rc.convert_to_rows(_mixed_table(np.random.default_rng(35)))
    moved = {k: _counter(f"rowconv.to_rows.{k}") - v for k, v in before.items()}
    assert moved == {"calls": 1, "rows": ROWCONV_ROWS, "bytes_out": int(batch.child.data.shape[0]), "batches": 1,
                     "string_cols": 2, "size_waits": 1, "padded": 1, "scatter": 0}
    counters = runtime.stats_report()["metrics"]["counters"]
    assert all(counters[f"rowconv.to_rows.{k}"] >= moved[k] for k in names)


# ---------------------------------------------------------------------------
# compiles: the counters and the xla.compile span
# ---------------------------------------------------------------------------


def test_fresh_jit_is_one_compile_and_one_span_a_second_call_neither():
    @jax.jit
    def fresh(x):
        return (x * 3 + 1).sum()

    x = jnp.arange(37, dtype=jnp.int32)  # made before counting: arange compiles too
    jax.block_until_ready(x)
    with tracing.enabled():
        qt = tracing.start_trace("q")
        with qt.activate():
            with tracing.span("caller") as caller:
                before = _counter("xla.backend_compiles")
                seconds = _counter("xla.backend_compile_s")
                fresh(x).block_until_ready()
                assert _counter("xla.backend_compiles") == before + 1
                assert _counter("xla.backend_compile_s") > seconds
                fresh(x).block_until_ready()
                assert _counter("xla.backend_compiles") == before + 1
        qt.finish("ok")
    compiles = [s for s in _log_spans() if s["name"] == "xla.compile"]
    assert len(compiles) == 1
    assert compiles[0]["parent"] == f"{caller.span_id:016x}"
    assert "fresh" in compiles[0]["annotations"]["fun"]
    assert compiles[0]["dur_us"] > 0


def test_compile_counters_are_in_the_snapshot_with_tracing_off():
    assert not tracing.is_enabled()
    before = _counter("xla.backend_compiles")
    jax.jit(lambda x: x - 41)(jnp.ones((3,), jnp.float32)).block_until_ready()
    assert _counter("xla.backend_compiles") > before
    counters = metrics.snapshot()["counters"]
    assert {"xla.backend_compiles", "xla.backend_compile_s"} <= set(counters)
    assert _log_spans() == []


# ---------------------------------------------------------------------------
# tracing off: no record, the shared null span at every new site
# ---------------------------------------------------------------------------


def test_tracing_off_makes_no_record_and_hands_out_the_null_span():
    assert not tracing.is_enabled()
    spans_before = _counter("trace.spans")
    for name in ("plan.aggregate", "groupby.sort", "groupby.agg.sum", "integrity.crc",
                 "sidecar.worker.decode_table", "sidecar.worker.d2h",
                 "sidecar.client.send", "sidecar.client.wait"):
        with tracing.span(name, bytes=1) as sp:
            assert sp is tracing._NULL_SPAN
    keys = Table([Column.from_numpy(np.array([1, 1, 2], np.int32), dt.INT32)], ["k"])
    vals = Table([Column.from_numpy(np.array([1, 2, 3], np.int64), dt.INT64)], ["v"])
    out = groupby_aggregate(keys, vals, [("v", "sum")])
    assert np.asarray(out.columns[1].data).tolist() == [3, 3]
    table = Table([Column(dt.INT32, data=jnp.arange(16, dtype=jnp.int32))], ["a"])
    sidecar._dispatch(sidecar.OP_CONVERT_TO_ROWS, sidecar.as_bytes(sidecar._write_table(table, framed=False)), "cpu")
    assert _counter("trace.spans") == spans_before
    assert trace_sink.recorder().last(5) == []
    trace_sink.close_log()
    assert not os.path.exists(trace_sink.resolved_log_path())


# ---------------------------------------------------------------------------
# the sink: nothing before a request ends, everything after
# ---------------------------------------------------------------------------


def test_sink_writes_nothing_before_the_root_finishes_and_everything_after():
    with tracing.enabled():
        qt = tracing.start_trace("q")
        with qt.activate():
            for i in range(5):
                with tracing.span(f"s{i}"):
                    pass
            assert _log_spans() == []
        assert _log_spans() == []
        qt.finish("ok")
    assert [s["name"] for s in _log_spans()] == ["s0", "s1", "s2", "s3", "s4", "q"]


def test_close_log_writes_what_is_buffered():
    with tracing.enabled():
        qt = tracing.start_trace("q")
        with qt.activate():
            with tracing.span("early"):
                pass
            assert _log_spans() == []
            trace_sink.close_log()
            assert [s["name"] for s in _log_spans()] == ["early"]
        qt.finish("ok")
    assert [s["name"] for s in _log_spans()] == ["early", "q"]


def test_set_log_path_writes_the_buffer_to_the_old_path(tmp_path):
    old = trace_sink.resolved_log_path()
    with tracing.enabled():
        qt = tracing.start_trace("q")
        with qt.activate():
            with tracing.span("before_the_move"):
                pass
            trace_sink.set_log_path(str(tmp_path / "moved.jsonl"))
        qt.finish("ok")
    assert [r["name"] for r in _read_logs(old)] == ["before_the_move"]
    assert [s["name"] for s in _log_spans()] == ["q"]


def test_the_length_cap_bounds_a_process_that_never_finishes_a_root(monkeypatch):
    monkeypatch.setattr(trace_sink, "_BUFFER_MAX", 8)
    with tracing.enabled():
        qt = tracing.start_trace("q")
        with qt.activate():
            for i in range(19):
                with tracing.span(f"s{i}"):
                    pass
            assert len(_log_spans()) == 16  # two full buffers; three spans wait
            assert len(trace_sink._buffer) == 3
        qt.finish("ok")
    assert len(_log_spans()) == 20


def test_the_stats_verb_is_a_flush_point():
    with tracing.enabled():
        qt = tracing.start_trace("q")
        with qt.activate():
            with tracing.span("held"):
                pass
            assert _log_spans() == []
            sidecar._dispatch(sidecar.OP_STATS, b"", "cpu")
            assert [s["name"] for s in _log_spans()] == ["held"]
        qt.finish("ok")


def test_a_remote_scope_writes_its_spans_when_it_exits():
    with tracing.enabled():
        with tracing.remote_scope(0xABC, 0xDEF, True):
            with tracing.span("worker_side"):
                pass
            assert _log_spans() == []
        spans = _log_spans()
    assert [s["name"] for s in spans] == ["worker_side"]
    assert spans[0]["parent"] == f"{0xDEF:016x}" and spans[0]["trace"] == f"{0xABC:016x}"


def test_a_straggler_past_its_root_reaches_the_log_on_its_own():
    import contextvars

    with tracing.enabled():
        qt = tracing.start_trace("q")
        with qt.activate():
            late = contextvars.copy_context()
        qt.finish("ok")
        assert [s["name"] for s in _log_spans()] == ["q"]

        def straggle():
            with tracing.span("hedge_loser"):
                pass

        late.run(straggle)
    assert [s["name"] for s in _log_spans()] == ["q", "hedge_loser"]


def test_a_flushed_trace_record_follows_its_spans_in_the_log():
    with tracing.enabled():
        qt = tracing.start_trace("q")
        with qt.activate():
            with tracing.span("inner"):
                pass
        qt.finish("failed")
    recs = _read_logs(trace_sink.resolved_log_path())
    assert [(r["kind"], r["name"]) for r in recs] == [
        ("span", "inner"), ("span", "q"), ("trace", "q")]
    assert recs[-1]["flushed"] and len(recs[-1]["spans"]) == 2


# ---------------------------------------------------------------------------
# the sidecar request: one spawned worker, CONVERT_TO_ROWS through a region
# ---------------------------------------------------------------------------

WORKER_SPANS = ("sidecar.worker.payload_read", "sidecar.worker.decode_table", "sidecar.worker.d2h",
                "sidecar.worker.encode_reply", "sidecar.worker.reply_write")
CLIENT_SPANS = ("sidecar.client.send", "sidecar.client.wait", "sidecar.client.reply_read")


@pytest.fixture(scope="module")
def traced_convert(tmp_path_factory):
    """One traced CONVERT_TO_ROWS through SidecarPool(1) with a real worker
    process: the spans of both processes, the frame sizes, and the worker's
    STATS reply (polled after the request: it flushes the worker's log)."""
    base = str(tmp_path_factory.mktemp("phase") / "spans")
    rng = np.random.default_rng(7)
    table = Table(
        [
            Column.from_numpy(rng.integers(-9, 9, 500).astype(np.int32), dt.INT32,
                              validity=rng.random(500) > 0.1),
            Column.from_numpy(rng.integers(0, 1 << 40, 500).astype(np.int64), dt.INT64),
            Column.from_numpy(rng.integers(0, 100, 500).astype(np.int8), dt.INT8),
        ],
        ["a", "b", "c"],
    )
    payload = sidecar.as_bytes(sidecar._write_table(table, framed=False))
    want = sidecar.as_bytes(sidecar._dispatch(sidecar.OP_CONVERT_TO_ROWS, payload, "cpu"))
    prev_base, prev_enabled = trace_sink.log_path(), tracing.is_enabled()
    pool = sidecar_pool.SidecarPool(
        size=1, deadline_s=120, heartbeat_s=1e9, startup_timeout_s=120.0, slab_bytes=1 << 20,
        env={"SRJT_TRACE_ENABLED": "1", "SRJT_TRACE_LOG": base},
    )
    try:
        assert pool.call(sidecar.OP_PING).decode() == "cpu"  # untraced: no span of it
        trace_sink.set_log_path(base)
        tracing.set_enabled(True)
        region = pool.lease(max(len(payload), len(want)))
        try:
            region.write(payload)
            qt = tracing.start_trace("test.request")
            with qt.activate():
                reply = pool.call(sidecar.OP_CONVERT_TO_ROWS, region=region)
            qt.finish("ok")
        finally:
            region.release()
        tracing.set_enabled(False)
        trace_sink.close_log()
        stats = pool.worker_stats(fold=False)["w0"]
        # the worker's handler thread closes its last span (the reply's write)
        # and writes its log AFTER the client has the reply: wait for it while
        # the worker lives (shutdown's SIGTERM takes an unwritten log with it)
        end = time.monotonic() + 10.0
        while True:
            spans = [r for r in _read_logs(base + ".*.jsonl") if r.get("kind") == "span"]
            if any(s["name"] == "sidecar.worker.reply_write" for s in spans) or time.monotonic() > end:
                break
            time.sleep(0.02)
    finally:
        tracing.set_enabled(prev_enabled)
        trace_sink.set_log_path(prev_base)
        pool.shutdown()
    assert reply == want
    return {"spans": spans, "request_bytes": len(payload), "reply_bytes": len(want),
            "stats": stats, "cols": 3}


def test_the_workers_stats_hold_the_transcodes_counters(traced_convert):
    """ISSUE 34: registry-direct, so they count in a worker whether or not
    its tracing is on, and ride the STATS verb's snapshot."""
    counters = traced_convert["stats"]["snapshot"]["counters"]
    got = {k[len("rowconv.to_rows."):]: v for k, v in counters.items() if k.startswith("rowconv.to_rows.")}
    assert got == {"calls": 1, "rows": 500, "bytes_out": 500 * 24, "batches": 1, "string_cols": 0,
                   "size_waits": 0, "padded": 0, "scatter": 0}
    encode = _one(traced_convert["spans"], "rowconv.encode")
    assert encode["parent"] == _one(traced_convert["spans"], "op.convert_to_rows")["span"]
    assert encode["annotations"] == {"form": "fixed", "batch": 0, "rows": 500, "bytes": 500 * 24, "maxvar": 0}
    assert "rowconv.sizes" not in _by_name(traced_convert["spans"])  # a fixed-width table waits for no sizes


def _descends_from(span, ancestor_id, by_id):
    while span is not None:
        if span["parent"] == ancestor_id:
            return True
        span = by_id.get(span["parent"])
    return False


@pytest.mark.parametrize("name", WORKER_SPANS + CLIENT_SPANS + ("sidecar.worker_op", "op.convert_to_rows"))
def test_every_phase_of_the_request_is_one_span_under_the_clients_request(traced_convert, name):
    spans = traced_convert["spans"]
    request = _one(spans, "sidecar.request")
    span = _one(spans, name)
    assert _descends_from(span, request["span"], {s["span"]: s for s in spans})
    assert span["trace"] == request["trace"]
    # two processes, one tree: the worker's spans carry another pid
    assert (span["pid"] != request["pid"]) == (not name.startswith("sidecar.client."))


def test_worker_handling_spans_are_siblings_of_the_worker_op(traced_convert):
    spans = traced_convert["spans"]
    request = _one(spans, "sidecar.request")["span"]
    for name in ("sidecar.worker_op", "sidecar.worker.payload_read", "sidecar.worker.reply_write") + CLIENT_SPANS:
        assert _one(spans, name)["parent"] == request, name
    op = _one(spans, "sidecar.worker_op")["span"]
    for name in ("sidecar.worker.decode_table", "op.convert_to_rows", "sidecar.worker.d2h",
                 "sidecar.worker.encode_reply"):
        assert _one(spans, name)["parent"] == op, name


def test_the_four_crc_passes_are_named_and_sized_by_their_frames(traced_convert):
    crcs = {s["annotations"]["where"]: s for s in traced_convert["spans"] if s["name"] == "integrity.crc"}
    assert sorted(crcs) == ["reply", "request", "verify_reply", "verify_request"]
    req, rep = traced_convert["request_bytes"], traced_convert["reply_bytes"]
    assert {w: s["annotations"]["bytes"] for w, s in crcs.items()} == {
        "request": req, "verify_request": req, "reply": rep, "verify_reply": rep}
    request = _one(traced_convert["spans"], "sidecar.request")
    by_id = {s["span"]: s for s in traced_convert["spans"]}
    assert all(_descends_from(s, request["span"], by_id) for s in crcs.values())
    assert crcs["request"]["pid"] == crcs["verify_reply"]["pid"] == request["pid"]
    assert crcs["verify_request"]["pid"] == crcs["reply"]["pid"] != request["pid"]


def test_bytes_annotations_equal_the_frame_sizes(traced_convert):
    spans, req, rep = traced_convert["spans"], traced_convert["request_bytes"], traced_convert["reply_bytes"]
    ann = {name: _one(spans, name).get("annotations") for name in WORKER_SPANS + CLIENT_SPANS}
    assert ann["sidecar.client.send"] == {"bytes": req}
    assert ann["sidecar.worker.payload_read"] == {"bytes": req, "via": "region"}
    assert ann["sidecar.worker.decode_table"] == {"bytes": req, "cols": traced_convert["cols"]}
    # one batch: count, rows, offsets, blob length, blob
    assert ann["sidecar.worker.encode_reply"] == {"bytes": rep, "pieces": 5}
    assert ann["sidecar.worker.reply_write"] == {"bytes": rep, "via": "region"}
    assert ann["sidecar.client.reply_read"] == {"bytes": rep, "via": "region"}
    # offsets and blob, less the reply's 20 bytes of counts and lengths
    assert ann["sidecar.worker.d2h"] == {"bytes": rep - 20}
    assert ann["sidecar.client.wait"] is None


def test_the_request_is_covered_by_its_phases(traced_convert):
    """By parentage and by the clock, not by ratios of what two processes
    were given of a loaded machine: the client's phases are the request's
    children, lie inside it one after the other and leave it no tenth of
    its time; the worker's phases are its children in the other process,
    all begun between the client's send and the end of its wait, and they
    cover what the worker did between its first and its last."""
    spans = traced_convert["spans"]
    request = _one(spans, "sidecar.request")
    tol = 2e-3  # a span's start is read on the wall clock, its length on the monotonic one

    def end(s):
        return s["ts"] + s["dur_us"] / 1e6

    phases = [_one(spans, n) for n in CLIENT_SPANS]
    assert all(s["parent"] == request["span"] and s["pid"] == request["pid"] for s in phases)
    assert request["ts"] - tol <= phases[0]["ts"] and end(phases[-1]) <= end(request) + tol
    assert all(end(a) <= b["ts"] + tol for a, b in zip(phases, phases[1:]))
    # the three are timed back to back on one thread: what they leave out is a few lines of code
    assert sum(s["dur_us"] for s in phases) >= 0.9 * request["dur_us"]

    wait = _one(spans, "sidecar.client.wait")
    worker = sorted((s for s in spans if s["parent"] == request["span"] and s["pid"] != request["pid"]),
                    key=lambda s: s["ts"])
    assert [s["name"] for s in worker if s["name"] != "integrity.crc"] == [
        "sidecar.worker.payload_read", "sidecar.worker_op", "sidecar.worker.reply_write"]
    # the worker has the request once sendall has returned: on a loaded machine that is before
    # the client's thread gets to open its wait, so the worker's first span is held to the send
    assert all(phases[0]["ts"] - tol <= s["ts"] <= end(wait) + tol for s in worker)
    # the reply's write is closed after the client has the reply; every other phase ends inside the wait
    assert all(end(s) <= end(wait) + tol for s in worker[:-1])
    assert all(end(a) <= b["ts"] + tol for a, b in zip(worker, worker[1:]))
    # the wake-up of the worker's thread belongs to no phase; from its first span on, four fifths are spanned
    did = end(worker[-1]) - worker[0]["ts"]
    assert sum(s["dur_us"] for s in worker) / 1e6 >= 0.8 * did


def test_stats_carries_the_workers_device_and_memory(traced_convert):
    stats = traced_convert["stats"]
    assert stats["device"] == {"platform": "cpu", "kind": jax.devices()[0].device_kind,
                               "count": len(jax.devices())}
    assert sorted(stats["memory"]) == sorted(str(d.id) for d in jax.devices())
    assert all(m == {} for m in stats["memory"].values())  # the CPU reports none
    assert {"backend", "snapshot", "memgov"} <= set(stats)
    counters = stats["snapshot"]["counters"]
    assert counters["xla.backend_compiles"] >= 1 and counters["xla.backend_compile_s"] > 0


def test_device_section_reads_memory_stats_where_the_backend_gives_them(monkeypatch):
    class Chip:
        id, platform, device_kind = 0, "tpu", "TPU v5 lite"

        def memory_stats(self):
            return {"bytes_in_use": 5, "peak_bytes_in_use": 9, "bytes_limit": 16, "num_allocs": 3}

    monkeypatch.setattr(jax, "devices", lambda: [Chip()])
    assert sidecar._device_section() == {
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "memory": {"0": {"bytes_in_use": 5, "peak_bytes_in_use": 9, "bytes_limit": 16}},
    }


# ---------------------------------------------------------------------------
# the ten readers, loaded by path, on a synthetic span list
# ---------------------------------------------------------------------------


def _reader(name):
    if BENCH_DIR not in sys.path:
        sys.path.insert(0, BENCH_DIR)  # the readers import benchlib.tracered
    spec = importlib.util.spec_from_file_location(
        f"phase_reader_{name}", os.path.join(BENCH_DIR, "readers", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _s(name, dur_ms, span=None, parent=None):
    return {"name": name, "dur_us": dur_ms * 1e3, "ts": 1.0, "span": span or name, "parent": parent}


# two requests; names that no reader may count stand beside those it must
SYNTHETIC = [
    _s("sidecar.worker.payload_read", 200), _s("sidecar.worker.payload_read", 220),
    _s("sidecar.worker.decode_table", 1500), _s("sidecar.worker.decode_table", 1700),
    _s("sidecar.worker.d2h", 900), _s("sidecar.worker.d2h", 1100),
    _s("sidecar.worker.encode_reply", 1000), _s("sidecar.worker.encode_reply", 1400),
    _s("sidecar.worker.reply_write", 300), _s("sidecar.worker.reply_write", 500),
    _s("sidecar.client.reply_read", 250), _s("sidecar.client.reply_read", 350),
    _s("sidecar.client.send", 7), _s("sidecar.client.wait", 8000), _s("sidecar.worker_op", 5000),
    *[_s("integrity.crc", ms) for ms in (100, 110, 150, 160, 100, 110, 150, 160)],
    _s("groupby.sort", 400), _s("groupby.sort", 600), _s("groupby.segments", 30), _s("groupby.segments", 50),
    _s("groupby.keys", 8), _s("groupby.keys", 12), _s("op.groupby_aggregate", 9999),
    _s("groupby.agg.sum", 1000), _s("groupby.agg.sum", 1200), _s("groupby.agg.mean", 1500),
    _s("groupby.agg.count_all", 300), _s("groupby.aggregate_not_an_agg", 77),
    _s("rowconv.sizes", 3), _s("rowconv.sizes", 4), _s("rowconv.sizes", 5), _s("rowconv.encode", 40),
]
EXPECTED = {
    "sidecar_payload_read_ms": 210.0,
    "sidecar_decode_table_ms": 1600.0,
    "sidecar_d2h_ms": 1000.0,
    "sidecar_encode_reply_ms": 1200.0,
    "sidecar_reply_write_ms": 400.0,
    "sidecar_reply_read_ms": 300.0,
    "sidecar_crc_ms": 520.0,
    "groupby_order_ms": 550.0,
    "groupby_agg_ms": 2000.0,
    "rowconv_size_wait_ms": 6.0,
}
ALL_READERS = sorted(EXPECTED) + ["plan_stage_self_ms"]


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reduces_the_synthetic_spans_to_the_mean_per_request(name):
    got = _reader(name)({"spans": SYNTHETIC, "requests": [object(), object()]})
    assert got == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", ALL_READERS)
def test_reader_finds_nothing_to_read_on_the_parents_spans(name):
    """The parent commit has none of these spans: the reader returns None
    (the line then leaves the metric out) and does not raise."""
    parents = [_s("serve.run", 11000), _s("op.groupby_aggregate", 9000), _s("sidecar.worker_op", 5000),
               _s("sidecar.request", 8000)]
    read = _reader(name)
    assert read({"spans": parents, "requests": [object()]}) is None
    assert read({"spans": [], "requests": []}) is None


def test_plan_stage_self_time_on_a_three_deep_nest():
    # plan.sort 100 > plan.aggregate 90 > plan.project 30 > plan.filter 20 (an op.* of 5 inside);
    # op.groupby_aggregate 50 inside plan.aggregate, op.sort_by_key 4 inside plan.sort;
    # a groupby.* child of the operator and a serve.run above are not plan stages' children
    spans = [
        _s("serve.run", 101, "run"),
        _s("plan.sort", 100, "sort", "run"),
        _s("op.sort_by_key", 4, "sbk", "sort"),
        _s("plan.aggregate", 90, "agg", "sort"),
        _s("op.groupby_aggregate", 50, "gb", "agg"),
        _s("groupby.sort", 20, "gbs", "gb"),
        _s("plan.project", 30, "proj", "agg"),
        _s("plan.filter", 20, "filt", "proj"),
        _s("op.apply_boolean_mask", 5, "abm", "filt"),
        _s("xla.compile", 3, "xc", "filt"),
    ]
    # self: sort 100-4-90 = 6, aggregate 90-50-30 = 10, project 30-20 = 10, filter 20-5 = 15
    read = _reader("plan_stage_self_ms")
    assert read({"spans": spans, "requests": [object()]}) == pytest.approx(41.0)
    again = [dict(s, span=s["span"] + "2", parent=s["parent"] and s["parent"] + "2") for s in spans]
    assert read({"spans": spans + again, "requests": [object(), object()]}) == pytest.approx(41.0)


# ---------------------------------------------------------------------------
# device time under the span that asked for it (ISSUE 36): a ``device.wait``
# span at every host sync, a ``device.launch`` event at every program the
# system builds itself
# ---------------------------------------------------------------------------


def _under(spans, name, key):
    """[(annotation ``key``, parent span's name)] of the spans called ``name``, in start order."""
    by_id = {s["span"]: s["name"] for s in spans}
    hits = sorted((s for s in spans if s["name"] == name), key=lambda s: s["ts"])
    return [(s["annotations"][key], by_id.get(s["parent"])) for s in hits]


def _waits(spans):
    return _under(spans, "device.wait", "what")


def _launches(spans):
    return _under(spans, "device.launch", "program")


def _traced(fn, *args, **kwargs):
    """(spans, result) of ``fn(*args)`` under a trace of its own; compiled by an untraced call first."""
    fn(*args, **kwargs)
    trace_sink.reset_for_tests()
    with tracing.enabled():
        qt = tracing.start_trace("device.test")
        with qt.activate():
            out = fn(*args, **kwargs)
        qt.finish("ok")
    return trace_sink.recorder().last(1)[0]["spans"], out


@pytest.mark.parametrize("what,parent", [
    ("mask_popcount", "plan.filter"),      # a Filter that may defer reads its mask's count
    ("key_domain", "groupby.segments"),    # ISSUE 37: integer keys are probed first: six values, refused by the bound
    ("sort_order", "groupby.segments"),    # the phase's dispatch stalls behind the sort: named before it starts
    ("group_count", "groupby.segments"),   # the group-by's one read of a value
    ("sort_input", "op.sort_by_key"),      # the Sort waits for what the aggregates left in the queue
])
def test_each_sync_site_of_a_q1_is_one_device_wait_under_its_span(q1_sorted_spans, what, parent):
    assert _waits(q1_sorted_spans).count((what, parent)) == 1
    assert len(_waits(q1_sorted_spans)) == 5  # and the request has no other


@pytest.mark.parametrize("what,parent", [
    ("mask_popcount", "plan.filter"),
    ("key_domain", "groupby.segments"),    # the probe's handful of scalars: the keys span six values
    ("group_count", "groupby.segments"),   # the slots' counts: which are groups, and ``count_all``
    ("sort_input", "op.sort_by_key"),
])
def test_each_sync_site_of_a_dense_q1_is_one_device_wait_under_its_span(q1_spans, what, parent):
    assert _waits(q1_spans).count((what, parent)) == 1
    assert len(_waits(q1_spans)) == 4  # no ``sort_order``: nothing sorts


def test_a_q1s_programs_are_launched_under_the_spans_that_built_them(q1_sorted_spans):
    assert _launches(q1_sorted_spans) == [
        ("_body", "plan.filter"), ("_body", "plan.project"), ("_key_domain", "groupby.segments"),
        ("lexsort", "groupby.sort"),
        ("_f64_sum_mean", "groupby.agg.sum"), ("_f64_sum_mean", "groupby.agg.sum"),
        ("_f64_sum_mean", "groupby.agg.mean"), ("lexsort", "op.sort_by_key")]


def test_a_dense_q1s_programs_are_launched_under_the_spans_that_built_them(q1_spans):
    assert _launches(q1_spans) == [
        ("_body", "plan.filter"), ("_body", "plan.project"), ("_key_domain", "groupby.segments"),
        ("_slot_counts", "groupby.segments"), ("_slot_group_ids", "groupby.segments"),
        ("_f64_sum_mean", "groupby.agg.sum"), ("_f64_sum_mean", "groupby.agg.sum"),
        ("_f64_sum_mean", "groupby.agg.mean"), ("lexsort", "op.sort_by_key")]


def test_a_compacting_filter_waits_for_its_mask_before_nonzero_reads_its_size():
    spans = _spans_of_a_q1_shaped_query(cutoff=260)  # keeps a tenth: it compacts
    assert ("mask_popcount", "plan.filter") in _waits(spans) and ("mask_nonzero", "plan.filter") in _waits(spans)


@pytest.mark.parametrize("what,parent,count", [
    ("group_count", "join.factorize", 1),   # the XLA tier's dense ids
    ("join_size", "join.expand", 1),        # the output-size wait
    ("string_chars", "join.gather", 1),     # the brand's characters through the map
    ("max_char_len", "groupby.sort", 1),    # a gathered STRING key has lost its memo
    ("sort_order", "groupby.segments", 1),
    ("group_count", "groupby.segments", 1),
    ("string_chars", "groupby.keys", 1),
    ("sort_input", "op.sort_by_key", 1),
    ("string_chars", "op.sort_by_key", 1),
])
def test_each_sync_site_of_a_join_and_a_string_key_is_one_device_wait(star_spans, what, parent, count):
    spans, _, _ = star_spans
    assert _waits(spans).count((what, parent)) == count, _waits(spans)


def test_a_star_requests_waits_all_have_a_site_and_its_programs_a_layer(star_spans):
    spans, _, _ = star_spans
    assert len(_waits(spans)) == 10, _waits(spans)  # the nine above and the Sort's key's longest string
    # the INT64 sum is eager pieces (no program of the system's own under ``groupby.agg.sum``); its
    # normalisation to FLOAT64 is the aggregate stage's one program
    assert _launches(spans) == [
        ("lexsort", "join.factorize"), ("_string_lanes", "groupby.sort"), ("lexsort", "groupby.sort"),
        ("_string_lanes", "groupby.segments"), ("_to_float64_program", "plan.aggregate"),
        ("_string_lanes", "op.sort_by_key"), ("lexsort", "op.sort_by_key")]


def test_the_paged_hash_join_waits_for_its_table_and_launches_its_probe(monkeypatch):
    from spark_rapids_jni_tpu.ops import join as join_ops

    monkeypatch.setenv("SRJT_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(36)
    left = Table([Column.from_numpy(rng.integers(0, 50, 600).astype(np.int32), dt.INT32)], ["k"])
    right = Table([Column.from_numpy(np.arange(40, dtype=np.int32), dt.INT32),
                   Column.from_numpy(np.arange(40, dtype=np.int64), dt.INT64)], ["k", "v"])
    spans, out = _traced(join_ops.inner_join, left, right, on=["k"])
    assert _one(spans, "join.probe")["annotations"]["tier"] == "pallas" and out.num_rows > 0
    assert _waits(spans) == [("paged_table", "join.factorize"), ("join_size", "join.expand")]
    assert _launches(spans) == [("_probe_impl", "join.probe")]


@pytest.mark.parametrize("case,waits", [
    ("one_batch", [("row_sizes", "rowconv.sizes")]),
    ("scatter", [("row_sizes", "rowconv.sizes")]),
])
def test_convert_to_rows_with_strings_waits_once_and_launches_three_programs(rowconv_spans, case, waits):
    spans, _ = rowconv_spans[case]
    assert _waits(spans) == waits
    want = [("_jit_row_size_stats", "rowconv.sizes"), ("bitcast_convert_type", "op.convert_to_rows")]
    if case == "one_batch":  # the scatter form is eager pieces: no program of the system's own
        want.insert(1, ("_jit_encode_strings_fused", "rowconv.encode"))
    assert _launches(spans) == want


def test_a_table_that_spans_batches_waits_for_its_sizes_twice_and_once_a_sliced_string(rowconv_spans):
    spans, batches = rowconv_spans["several_batches"]
    waits = _waits(spans)
    assert waits[:2] == [("row_sizes", "rowconv.sizes"), ("row_sizes_full", "rowconv.sizes")]
    assert set(waits[2:]) == {("string_chars", "op.convert_to_rows")}
    assert len(waits[2:]) <= 2 * len(batches)  # two STRING columns a batch; a batch that is the whole column slices nothing
    assert _launches(spans).count(("_jit_encode_strings_fused", "rowconv.encode")) == len(batches)


def test_a_fixed_width_encode_waits_for_nothing_and_launches_the_encode_and_the_bitcast():
    from spark_rapids_jni_tpu.ops import row_conversion as rc

    table = Table([Column.from_numpy(np.arange(50, dtype=np.int32), dt.INT32),
                   Column.from_numpy(np.arange(50, dtype=np.int64), dt.INT64)], ["a", "b"])
    spans, _ = _traced(rc.convert_to_rows, table)
    assert _waits(spans) == []
    assert _launches(spans) == [("_jit_to_rows_fixed_static", "rowconv.encode"),
                                ("bitcast_convert_type", "op.convert_to_rows")]


def test_a_sharded_exchange_and_its_gather_wait_once_each_and_launch_the_mesh_programs():
    from spark_rapids_jni_tpu.parallel import table_ops
    from spark_rapids_jni_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"data": 4}, devices=jax.devices()[:4])
    keys = np.arange(4000, dtype=np.int64) * 7 + 1
    table = Table([Column.from_numpy(keys, dt.INT64), Column.from_numpy(np.arange(4000, dtype=np.int64), dt.INT64)],
                  ["k", "v"])

    def there_and_back():
        return table_ops.gather_table(table_ops.exchange_sharded(table_ops.shard_table(table, mesh), ["k"]))

    spans, out = _traced(there_and_back)
    assert out.num_rows == 4000
    assert _waits(spans) == [("exchange_counts", "exchange.table"), ("overflow_flags", "exchange.gather")]
    assert _launches(spans) == [("count_program", "exchange.table"), ("exchange_program", "exchange.table")]


def _module_of(lowered) -> str:
    import re

    return re.search(r"module @(\S+)", lowered.as_text()).group(1)


def _launch_case(case):
    """(the wrapped program, its arguments, its keywords) of one of the programs the system wraps."""
    from jax.sharding import PartitionSpec

    from spark_rapids_jni_tpu.ops import aggregate, pallas_kernels, row_conversion as rc, sort
    from spark_rapids_jni_tpu.parallel._smcache import cached_sm, shard_map
    from spark_rapids_jni_tpu.parallel.mesh import make_mesh
    from spark_rapids_jni_tpu.plan import compiler

    n = 64
    order, seg = jnp.arange(n, dtype=jnp.int32), jnp.zeros((n,), jnp.int32)
    x = Column.from_numpy(np.arange(n, dtype=np.int32), dt.INT32)
    if case == "f64_sum_mean":
        return aggregate._f64_sum_mean, (jnp.arange(n, dtype=jnp.uint64), None, order, seg, None), {"num": 1, "how": "sum"}
    if case == "lexsort":
        return sort._launch_lexsort, ((order, seg),), {}
    if case in ("string_lanes", "row_size_stats"):
        strings = Column.from_pylist(["a", "bcd", "", "efghijklm"], dt.STRING)
        if case == "string_lanes":
            return sort._string_lanes, (strings.offsets, strings.chars), {"lanes": 2}
        return rc._jit_row_size_stats, (rc.compute_row_layout([dt.INT32, dt.STRING]), (strings.offsets,)), {}
    if case == "stage_program":
        stage = compiler._StageProgram([(P.pcol("x") + P.plit(np.int32(1)), dt.INT32)], {"x": dt.INT32})
        return stage._program, (n, (x,), None), {}
    if case == "to_float64_program":
        return compiler._to_float64_program, ((x,),), {}
    if case in ("to_rows_fixed_static", "to_rows_fixed_sliced"):
        return getattr(rc, "_jit_" + case), (rc.compute_row_layout([dt.INT32]), (x,), 0, n), {}
    if case == "mesh_program":
        mesh, spec = make_mesh({"data": 4}, devices=jax.devices()[:4]), PartitionSpec("data")

        def count_program(a):
            return a + 1

        program = cached_sm(("test_phase_spans", mesh),
                            lambda: jax.jit(shard_map(count_program, mesh=mesh, in_specs=(spec,), out_specs=spec)))
        return program, (jnp.arange(8, dtype=jnp.int32),), {}
    assert case == "probe_impl"
    table = pallas_kernels.build_paged_table(jnp.arange(40, dtype=jnp.int32), None)
    u = pallas_kernels._order_map_u(jnp.arange(n, dtype=jnp.int32))
    return pallas_kernels._probe_impl, (u, jnp.ones((n,), bool), table.limbs, table.meta, table.num_buckets,
                                        table.n_pages, table.nlimb, table.c_max, True), {}


@pytest.mark.parametrize("case", ["f64_sum_mean", "string_lanes", "lexsort", "stage_program", "to_float64_program",
                                  "row_size_stats", "to_rows_fixed_static", "to_rows_fixed_sliced", "mesh_program",
                                  "probe_impl"])
def test_a_wrapped_program_says_one_launch_a_call_by_the_name_its_call_lowers_to(case):
    fn, args, kwargs = _launch_case(case)
    spans, _ = _traced(fn, *args, **kwargs)
    (launch,) = [s for s in spans if s["name"] == "device.launch"]
    assert "jit_" + launch["annotations"]["program"] == _module_of(fn.lower(*args, **kwargs))
    assert launch["dur_us"] == 0 and launch["parent"] == _one(spans, "device.test")["span"]


def test_the_fused_encode_and_the_eager_bitcast_are_named_as_the_device_names_them(rowconv_spans):
    from jax import lax

    from spark_rapids_jni_tpu.ops import row_conversion as rc

    # an eager primitive's program is ``jit_`` + the primitive's own name
    assert rc._launch_bitcast.__name__ == lax.bitcast_convert_type_p.name == "bitcast_convert_type"
    assert rc._launch_bitcast.__wrapped__ is lax.bitcast_convert_type
    programs = {p for p, _ in _launches(rowconv_spans["one_batch"][0])}
    assert rc._jit_encode_strings_fused.__name__ in programs


def test_no_launch_while_a_program_is_traced_into_an_enclosing_one():
    from spark_rapids_jni_tpu.ops import aggregate, sort

    n = 32
    bits, order, seg = jnp.arange(n, dtype=jnp.uint64), jnp.arange(n, dtype=jnp.int32), jnp.zeros((n,), jnp.int32)

    def enclosing(bits, order, seg):
        again = sort._launch_lexsort((order, seg))
        return aggregate._f64_sum_mean(bits, None, again.astype(jnp.int32), seg, None, num=1, how="sum")

    trace_sink.reset_for_tests()
    with tracing.enabled():
        qt = tracing.start_trace("device.test")
        with qt.activate():
            jax.block_until_ready(jax.jit(enclosing)(bits, order, seg))  # traced and compiled under the trace
        qt.finish("ok")
    spans = trace_sink.recorder().last(1)[0]["spans"]
    assert [s for s in spans if s["name"] == "device.launch"] == []


def test_the_wrapper_keeps_the_jitted_objects_own_handles():
    from spark_rapids_jni_tpu.ops import aggregate

    fn = aggregate._f64_sum_mean
    assert fn.__name__ == "_f64_sum_mean" and callable(fn.lower) and callable(fn.clear_cache)
    assert isinstance(fn._cache_size(), int) and fn.__wrapped__.lower == fn.lower
    nameless = object()  # what a test of the mesh programs' memo puts there: nothing to name, nothing to wrap
    assert tracing.launches(nameless) is nameless


def test_tracing_off_no_wait_no_launch_and_no_block_until_ready(monkeypatch):
    from spark_rapids_jni_tpu.ops import row_conversion as rc

    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready", lambda x: calls.append(1) or real(x))
    table = _lineitem(np.random.default_rng(26))
    cp = P.compile_ir(_q1_shaped_plan(), {"lineitem": table}, name="q1_off")
    sched = serve.Scheduler(max_concurrent=1, name="phase-spans-off")
    trace_sink.reset_for_tests()
    assert not tracing.is_enabled()
    try:
        out = sched.submit(cp).result()
        rc.convert_to_rows(_mixed_table(np.random.default_rng(34)))
        x = jnp.arange(3)
        assert tracing.device_wait(x, "anything") is x
    finally:
        sched.shutdown()
    assert out.num_rows > 0 and calls == []
    assert trace_sink.recorder().last(1) == []
    # armed but with no trace active there is nobody to tell: still no wait
    with tracing.enabled():
        assert tracing.device_wait(x, "anything") is x
        assert calls == []
        qt = tracing.start_trace("device.test")
        with qt.activate():
            assert tracing.device_wait(x, "anything") is x
        qt.finish("ok")
    assert calls == [1]
    assert _waits(trace_sink.recorder().last(1)[0]["spans"]) == [("anything", "device.test")]


def test_the_rendered_tree_says_what_a_span_waited_for_and_the_share_it_took(q1_spans):
    rec = {"trace": "00", "name": "serve.query", "status": "ok", "duration_s": 0.9, "spans": [
        {"span": "a", "parent": None, "name": "op.exchange_sharded", "ts": 1.0, "dur_us": 850e3, "pid": 7},
        {"span": "b", "parent": "a", "name": "exchange.table", "ts": 1.1, "dur_us": 846e3, "pid": 7,
         "annotations": {"capacity": 65536}},
        {"span": "c", "parent": "b", "name": "device.launch", "ts": 1.2, "dur_us": 0.0, "pid": 7,
         "annotations": {"program": "count_program"}},
        {"span": "d", "parent": "b", "name": "device.wait", "ts": 1.3, "dur_us": 843e3, "pid": 7,
         "annotations": {"what": "exchange_counts"}},
    ]}
    text = trace_sink.render_trace(rec)
    assert "- exchange.table 846.00ms (pid 7) capacity=65536" in text
    assert "- device.wait(exchange_counts) 843.00ms = 99.6% of exchange.table (pid 7)" in text
    assert "- device.launch(count_program) 0.00ms (pid 7)" in text
    # and on a real request: the group-by's one sync under the span that asked for it
    import re

    real = trace_sink.render_trace({"name": "serve.query", "spans": q1_spans})
    assert re.search(r"- device\.wait\(group_count\) [\d.]+ms = [\d.]+% of groupby\.segments \(pid \d+\)$", real, re.M)
