"""``benchlib/attribution.py`` on a synthetic reduced trace and span list:
device time goes to the layer of the span that launched it, by name and
order, and nothing is guessed where the counts differ.

    python -m pytest bench/tests/test_attribution.py -q -p no:cacheprovider

No JAX: the helper works on plain dicts.
"""

from __future__ import annotations

import os
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

from benchlib import attribution, loader  # noqa: E402

MS = 1e6  # ns


def span(name, sid, parent, ts, dur_ms=0.0, **notes):
    return {"name": name, "span": sid, "parent": parent, "ts": ts, "dur_us": dur_ms * 1e3, "annotations": notes}


def launch(sid, parent, ts, program):
    return span(attribution.LAUNCH, sid, parent, ts, program=program)


def wait(sid, parent, ts, dur_ms, what):
    return span(attribution.WAIT, sid, parent, ts, dur_ms, what=what)


def ctx_of(spans, devices, requests=1, busy_ms=None):
    """``devices``: a list of program lists ``[(name, start_ms, dur_ms)]``;
    every program is one operation of its own length, so busy time is
    their sum unless given."""
    devs = {}
    for d, programs in enumerate(devices):
        rows = [[n, s * MS, u * MS] for n, s, u in programs]
        devs[f"/device:TPU:{d}"] = {"programs": rows, "ops": rows}
    if busy_ms is None:
        busy_ms = sum(u for programs in devices for _, _, u in programs) / max(len(devices), 1)
    return {"spans": spans, "trace": {"devices": devs}, "w0_ns": 0.0, "w1_ns": 1000 * MS,
            "requests": [object()] * requests, "busy_s": busy_ms / 1e3}


# one request: a Filter's and a Project's one program share the name ``_body``; the group-by's
# sort and one aggregate; an eager gather that nobody launched
TREE = [
    span("serve.run", "run", None, 0.0, 100),
    span("plan.aggregate", "agg", "run", 0.1, 90),
    span("plan.project", "proj", "agg", 0.2, 3),
    span("plan.filter", "filt", "proj", 0.3, 2),
    launch("l1", "filt", 0.31, "_body"),
    wait("w1", "filt", 0.32, 1.5, "mask_popcount"),
    launch("l2", "proj", 0.5, "_body"),
    span("op.groupby_aggregate", "gb", "agg", 1.0, 80),
    span("groupby.sort", "gbs", "gb", 1.1, 5),
    launch("l3", "gbs", 1.2, "lexsort"),
    span("groupby.segments", "seg", "gb", 2.0, 40),
    wait("w2", "seg", 2.1, 38.0, "group_count"),
    span("groupby.agg.sum", "sum", "gb", 50.0, 1),
    launch("l4", "sum", 50.1, "_f64_sum_mean"),
]
PROGRAMS = [
    ("jit__body(111)", 1.0, 0.25),          # the Filter's
    ("jit__body(222)", 2.0, 6.0),           # the Project's: another fingerprint, the same name
    ("jit_lexsort(333)", 9.0, 30.0),
    ("jit_gather(444)", 40.0, 12.0),        # eager: matched to no launch
    ("jit__f64_sum_mean(555)", 60.0, 140.0),
]


def test_two_layers_sharing_one_program_name_are_told_apart_by_order():
    ctx = ctx_of(TREE, [PROGRAMS])
    m = attribution.match(ctx)
    assert [s["span"] for s in m["launches"]] == ["l1", "l2", "l3", "l4"]
    assert [row[0] / MS for row in m["ns"]] == [0.25, 6.0, 30.0, 140.0]
    assert m["mismatched"] == {}
    nearest = attribution.prefix("plan.", "op.")  # the Filter's span lies inside the Project's
    assert attribution.device_ms(ctx, "plan.filter".__eq__, stop=nearest) == pytest.approx(0.25)
    assert attribution.device_ms(ctx, "plan.project".__eq__, stop=nearest) == pytest.approx(6.0)
    # the plan stages' own programs: under a plan.* span with no op.* between
    stage = attribution.device_ms(ctx, attribution.prefix("plan."), stop=nearest)
    assert stage == pytest.approx(6.25)
    assert attribution.device_ms(ctx, attribution.GROUPBY_ORDER.__contains__) == pytest.approx(30.0)
    assert attribution.device_ms(ctx, attribution.prefix("groupby.agg.")) == pytest.approx(140.0)
    assert attribution.device_ms(ctx, attribution.prefix("exchange.")) is None  # launched nothing


def test_unattributed_share_is_the_eager_programs_busy_time():
    ctx = ctx_of(TREE, [PROGRAMS])
    total = 0.25 + 6.0 + 30.0 + 12.0 + 140.0
    assert attribution.unattributed_share(ctx) == pytest.approx(100.0 * 12.0 / total)
    assert attribution.match(ctx)["unmatched_names"] == {"jit_gather(444)": 12.0 * MS}
    # the layers and the unattributed share close on the busy time
    layers = 6.25 + 30.0 + 140.0
    assert layers + attribution.unattributed_share(ctx) / 100.0 * total == pytest.approx(total)


def test_a_count_mismatch_gives_none_and_guesses_nothing():
    programs = PROGRAMS + [("jit__body(111)", 70.0, 0.25)]  # a third ``_body`` that no launch stands for
    ctx = ctx_of(TREE, [programs])
    assert attribution.match(ctx)["mismatched"] == {"_body": [2, [3]]}
    assert attribution.device_ms(ctx, "plan.filter".__eq__) is None
    assert attribution.device_ms(ctx, "plan.project".__eq__) is None
    assert attribution.unattributed_share(ctx) is None
    # a layer that launched no ``_body`` still reads
    assert attribution.device_ms(ctx, attribution.prefix("groupby.agg.")) == pytest.approx(140.0)


def test_a_launch_whose_program_never_ran_is_a_mismatch():
    ctx = ctx_of(TREE, [[p for p in PROGRAMS if "lexsort" not in p[0]]])
    assert attribution.match(ctx)["mismatched"] == {"lexsort": [1, [0]]}
    assert attribution.device_ms(ctx, attribution.GROUPBY_ORDER.__contains__) is None


def test_programs_outside_the_window_are_not_counted():
    ctx = ctx_of(TREE, [PROGRAMS + [("jit__body(111)", -50.0, 0.25), ("jit__body(111)", 1050.0, 0.25)]])
    assert attribution.match(ctx)["mismatched"] == {}
    assert attribution.device_ms(ctx, "plan.filter".__eq__) == pytest.approx(0.25)


def test_a_program_a_millisecond_before_the_window_is_the_windows():
    """The anchor's clock is good to about a millisecond: the first request's first program may
    read as started before the window opened."""
    programs = [("jit__body(111)", -0.19, 0.25)] + PROGRAMS[1:]
    ctx = ctx_of(TREE, [programs])
    assert attribution.match(ctx)["mismatched"] == {}
    assert attribution.device_ms(ctx, "plan.filter".__eq__) == pytest.approx(0.25)


def test_two_devices_are_averaged_and_a_device_that_ran_none_gives_nothing():
    spans = [
        span("exchange.table", "xt", None, 0.0, 20),
        launch("a", "xt", 0.1, "count_program"),
        launch("b", "xt", 0.2, "exchange_program"),
        span("plan.filter", "pf", None, 1.0, 1),
        launch("c", "pf", 1.1, "_body"),  # placed on the first chip alone
    ]
    dev0 = [("jit_count_program(1)", 1.0, 2.0), ("jit_exchange_program(2)", 4.0, 100.0), ("jit__body(3)", 200.0, 8.0)]
    dev1 = [("jit_count_program(1)", 1.0, 4.0), ("jit_exchange_program(2)", 6.0, 120.0)]
    ctx = ctx_of(spans, [dev0, dev1], requests=2)
    assert attribution.match(ctx)["mismatched"] == {}
    # (2 + 100 + 4 + 120) / 2 devices / 2 requests
    assert attribution.device_ms(ctx, attribution.prefix("exchange.")) == pytest.approx(56.5)
    assert attribution.device_ms(ctx, attribution.prefix("plan.")) == pytest.approx(2.0)  # 8 / 2 / 2
    assert attribution.unattributed_share(ctx) == pytest.approx(0.0)


def test_a_waits_layer_is_found_through_a_grandparent():
    spans = [
        span("op.inner_join", "j", None, 0.0, 300),
        span("join.factorize", "jf", "j", 0.1, 200),
        wait("w1", "jf", 0.2, 150.0, "paged_table"),
        span("join.gather", "jg", "j", 250.0, 40),
        span("op.gather", "g", "jg", 250.1, 30),          # an operator between: still the join's
        wait("w2", "g", 250.2, 20.0, "string_chars"),
        span("op.groupby_aggregate", "gb", None, 400.0, 100),
        span("groupby.segments", "seg", "gb", 400.1, 90),
        wait("w3", "seg", 400.2, 80.0, "group_count"),
        span("sidecar.worker.d2h", "d2h", None, 600.0, 2000),
        wait("w4", "d2h", 600.1, 640.0, "result"),
    ]
    ctx = ctx_of(spans, [[]], requests=2)
    assert attribution.waits_ms(ctx) == pytest.approx((150 + 20 + 80 + 640) / 2)
    assert attribution.waits_ms(ctx, attribution.JOINS.__contains__) == pytest.approx(85.0)
    assert attribution.waits_ms(ctx, "sidecar.worker.d2h".__eq__) == pytest.approx(320.0)
    assert attribution.waits_ms(ctx, attribution.prefix("exchange.")) is None


def test_a_program_without_the_records_reads_none_everywhere():
    """The parent commit records neither: every reader leaves its metric out."""
    old = [s for s in TREE if s["name"] not in (attribution.WAIT, attribution.LAUNCH)]
    ctx = ctx_of(old, [PROGRAMS])
    assert attribution.waits_ms(ctx) is None
    assert attribution.device_ms(ctx, attribution.prefix("plan.")) is None
    assert attribution.unattributed_share(ctx) is None


NEW = ["host_wait_ms", "exchange_wait_ms", "join_wait_ms", "sidecar_d2h_wait_ms", "groupby_agg_device_ms",
       "groupby_order_device_ms", "plan_stage_device_ms", "exchange_device_ms", "join_device_ms",
       "rowconv_encode_device_ms", "device_unattributed_share"]


@pytest.mark.parametrize("name", NEW)
def test_every_new_metric_resolves_to_a_reader_that_reads_nothing_on_old_spans(name):
    entry = next(m for m in loader.benchmark()["per_layer"] if m["name"] == name)
    assert entry["workloads"] and entry["moves"] == "latency_p50_ms"
    read = loader.module("readers", loader.read_json("metrics", f"{name}.json")["reader"]).read
    old = [s for s in TREE if s["name"] not in (attribution.WAIT, attribution.LAUNCH)]
    assert read(ctx_of(old, [PROGRAMS])) is None
    assert read(ctx_of([], [[]], requests=0)) is None
