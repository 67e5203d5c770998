"""A compiled plan over a mesh (``plan.compile_ir(..., mesh=P.MeshBinding)``):
the stages over row-sharded tables run as ``shard_map`` programs, an
Exchange stage as an all-to-all, and one exchange serves every keyed stage
after it. TPC-DS q95 as the benchmark sends it (``bench/queries/tpcds_q95.py``
over ``bench/data/tpcds_web.py``'s tables, at a small size) is the subject:
on a mesh it equals the same plan at world 1 and the pandas reference.
"""

import importlib.util
import os

import jax
import numpy as np
import pandas as pd
import pytest

from spark_rapids_jni_tpu import plan as P
from spark_rapids_jni_tpu import serve
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.columnar import dtype as dt
from spark_rapids_jni_tpu.parallel import table_ops
from spark_rapids_jni_tpu.parallel.mesh import make_mesh
from spark_rapids_jni_tpu.plan import nodes as pn
from spark_rapids_jni_tpu.utils import metrics, trace_sink, tracing

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench")
SHARDED = ("web_sales", "web_returns")
CONFIG = {"tables": {"date_dim": {"rows": 73049}, "customer_address": {"rows": 2000}, "web_site": {"rows": 42}}}
ROWS = 120_000
TYPES = {"web_sales": {"ws_order_number": dt.INT64, "ws_warehouse_sk": dt.INT32, "ws_ship_date_sk": dt.INT32,
                       "ws_ship_addr_sk": dt.INT32, "ws_web_site_sk": dt.INT32,
                       "ws_ext_ship_cost": dt.FLOAT64, "ws_net_profit": dt.FLOAT64},
         "web_returns": {"wr_order_number": dt.INT64},
         "date_dim": {"d_date_sk": dt.INT32, "d_date": dt.INT32},
         "customer_address": {"ca_address_sk": dt.INT32, "ca_state": dt.STRING},
         "web_site": {"web_site_sk": dt.INT32, "web_company_name": dt.STRING}}


def _bench_module(kind, name):
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", os.path.join(BENCH, kind, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


Q95 = _bench_module("queries", "tpcds_q95")
WEB = _bench_module("data", "tpcds_web")


def _tables(host):
    def column(a, d):
        a, valid = a if isinstance(a, tuple) else (a, None)
        if d.id == dt.STRING.id:
            return Column.from_pylist(list(a), dt.STRING)
        return Column.from_numpy(np.ascontiguousarray(a), d, validity=valid)

    return {n: Table([column(a, TYPES[n][c]) for c, a in cols.items()], list(cols)) for n, cols in host.items()}


def _frames(host):
    def series(a):
        return pd.Series(a[0].astype(np.float64)).where(a[1]) if isinstance(a, tuple) else pd.Series(a)

    return {n: pd.DataFrame({c: series(a) for c, a in cols.items()}) for n, cols in host.items()}


def _answer(t):
    return (int(np.asarray(t.column("order count").data)[0]),
            float(np.asarray(t.column("total shipping cost").data).view(np.float64)[0]),
            float(np.asarray(t.column("total net profit").data).view(np.float64)[0]))


def _mesh(world):
    return make_mesh({"data": world}, devices=jax.devices()[:world])


def _run(cp):
    sched = serve.Scheduler(max_concurrent=1, name="mesh-test")
    try:
        out = sched.submit(cp).result()
        jax.block_until_ready([c.data for c in out.columns])
        return out
    finally:
        sched.shutdown()


def _exchanges(node, seen=None):
    seen = {} if seen is None else seen
    if isinstance(node, pn.Exchange):
        seen[id(node)] = node
    for i in node.inputs():
        _exchanges(i, seen)
    return list(seen.values())


@pytest.mark.parametrize("world", [2, 4])
@pytest.mark.parametrize("seed", [7, 2_300_000_011, 2_147_483_659])
def test_q95_on_a_mesh_equals_one_chip_equals_pandas(seed, world):
    host = WEB.host_tables(CONFIG, seed, ROWS)
    tables = _tables(host)
    want = Q95.reference(_frames(host), np.float64).iloc[0]
    assert want["order count"] > 0  # an empty answer would prove nothing
    plan = P.insert_exchanges(Q95.plan(P), world, sharded=SHARDED)
    one = _answer(_run(P.compile_ir(plan, tables, name="q95-one")))  # no mesh: every Exchange is the identity
    cp = P.compile_ir(plan, tables, name="q95-mesh", mesh=P.MeshBinding(_mesh(world), SHARDED))
    assert cp.mesh.world == world
    got = _answer(_run(cp))
    assert got[0] == one[0] == int(want["order count"])
    for g, o, w in zip(got[1:], one[1:], (want["total shipping cost"], want["total net profit"])):
        assert abs(g - w) <= 1e-9 * abs(w) and abs(o - w) <= 1e-9 * abs(w)
    # the rows stayed on the mesh from the scans to the per-order aggregate
    kinds = [type(s).__name__ for s in cp.stages]
    assert kinds.count("_MeshExchangeExec") == 3 and kinds.count("_GatherExec") == 1
    assert kinds.count("_MeshJoinExec") == 6 and kinds.count("_MeshAggExec") == 2


def test_insert_exchanges_shuffles_each_lineage_of_q95_once():
    plan = P.insert_exchanges(Q95.plan(P), 4, sharded=SHARDED)
    found = _exchanges(plan)
    assert sorted(e.keys for e in found) == [("wr_order_number",), ("ws_order_number",), ("ws_order_number",)]
    assert all(e.world == 4 for e in found)
    # web_sales for the per-order warehouses; ws1 after the three dimension joins; web_returns
    under = sorted(type(e.input).__name__ for e in found)
    assert under == ["Join", "Scan", "Scan"]
    # the co-keyed stages read rows that are in place: no exchange directly
    # under the per-order aggregate, under the right side of a semi join
    # (ws_wh, shared by both), or under the second semi join's left side
    per_order = plan.input
    assert isinstance(per_order, pn.Aggregate) and isinstance(per_order.input, pn.Join)
    j2 = per_order.input
    j1, returned = j2.left, j2.right
    assert isinstance(j1, pn.Join) and isinstance(j1.left, pn.Exchange) and not isinstance(j1.right, pn.Exchange)
    assert isinstance(returned.left, pn.Exchange) and returned.right is j1.right  # ws_wh: computed and shuffled once
    # and none under a broadcast join: the three dimension joins sit under ws1's one exchange
    chain = j1.left.input
    for _ in range(3):
        assert isinstance(chain, pn.Join) and not _exchanges(chain.right) and not isinstance(chain.left, pn.Exchange)
        chain = chain.left
    assert isinstance(chain, pn.Scan)
    # without ``sharded`` (the cross-process fabric: every table but the fact replicated) every join
    # is a broadcast join: one exchange under each of the two keyed aggregates, none under a join
    legacy = _exchanges(P.insert_exchanges(Q95.plan(P), 4))
    assert sorted(type(e.input).__name__ for e in legacy) == ["Join", "Scan"]


def _skewed_tables(n=6000):
    """One order holds a third of the rows: its bucket is twice the even
    share of a shard's rows."""
    rng = np.random.default_rng(5)
    order = np.sort(np.where(np.arange(n) < n // 3, 17, rng.integers(100, 1100, n))).astype(np.int64)
    wh = rng.integers(1, 11, n).astype(np.int32)
    cost = rng.integers(0, 100_000, n) / 100.0
    fact = Table([Column.from_numpy(order, dt.INT64), Column.from_numpy(wh, dt.INT32),
                  Column.from_numpy(cost, dt.FLOAT64)], ["o", "w", "c"])
    return fact, pd.DataFrame({"o": order, "w": wh, "c": cost})


_EXCHANGE_COUNTERS = ("programs", "rows_in", "bytes_offered", "slots_out", "overflows", "capacity_retries")


def _exchange_counters():
    reg = metrics.registry()
    return {k: reg.value(f"exchange.{k}") for k in _EXCHANGE_COUNTERS}


def test_a_skewed_key_is_sized_on_the_first_try():
    """The exchange counts before it moves: the skewed order's bucket gets
    the capacity it needs from ONE all-to-all program, where a guessed
    capacity overflowed and ran again at four times the size."""
    fact, df = _skewed_tables()
    plan = pn.Aggregate(pn.Scan("fact"), keys=("o",), aggs=(pn.AggSpec("w", "max", "hi"), pn.AggSpec("c", "sum", "s"),
                                                           pn.AggSpec(None, "count_all", "n")))
    plan = P.insert_exchanges(pn.Sort(plan, (("o", True),)), 4, sharded=("fact",))
    before = _exchange_counters()
    out = P.compile_ir(plan, {"fact": fact}, name="skew", mesh=P.MeshBinding(_mesh(4), ("fact",)))()
    moved = {k: v - before[k] for k, v in _exchange_counters().items()}
    assert moved["overflows"] == 0 and moved["capacity_retries"] == 0
    assert moved["programs"] == 1 and moved["rows_in"] == 6000
    want = df.groupby("o").agg(hi=("w", "max"), s=("c", "sum"), n=("c", "size")).reset_index()
    assert np.asarray(out.column("o").data).tolist() == want.o.tolist()  # no row lost, none doubled
    assert np.asarray(out.column("n").data).tolist() == want.n.tolist()
    np.testing.assert_allclose(np.asarray(out.column("s").data).view(np.float64), want.s.to_numpy(), rtol=1e-12)
    np.testing.assert_array_equal(np.asarray(out.column("hi").data).view(np.float64), want.hi.to_numpy(float))


def _exchanged(table, key, keep=None):
    """``table`` placed over four shards, all rows but ``keep``'s
    filtered out as a mesh Filter does it (the slots stay), and exchanged
    on ``key`` under a trace: the result, the ``exchange.table`` span's
    annotations and what the exchange's counters moved by."""
    st = table_ops.shard_table(table, _mesh(4))
    if keep is not None:
        pad = np.zeros(st.num_rows - keep.size, bool)
        st = st.replace(present=st.present & jax.device_put(np.concatenate([keep, pad]), st.present.sharding))
    before = _exchange_counters()
    with tracing.enabled():
        trace = tracing.start_trace("test.exchange")
        with trace.activate():
            out = table_ops.exchange_sharded(st, [key])
        trace.finish()
    (span,) = [s for s in trace.ctx.seal()[0] if s["name"] == "exchange.table"]
    return out, span["annotations"], {k: v - before[k] for k, v in _exchange_counters().items()}


def _keyed(keys, kind=dt.INT64):
    keys = np.asarray(keys)
    return Table([Column.from_numpy(keys.astype(kind.np_dtype), kind),
                  Column.from_numpy(np.arange(keys.size, dtype=np.int64), dt.INT64)], ["k", "v"])


def _rows_of(st, *names):
    """The present rows of a ShardedTable as sorted tuples."""
    there = np.asarray(st.present)
    return sorted(zip(*(np.asarray(st.column(n).data)[there].tolist() for n in names)))


_ORDERS, _ITEMS = np.arange(3000, dtype=np.int64) * 7 + 1, np.arange(3000) % 9 + 8  # 3,000 orders of 8..16 rows


def _one_destination():
    """Every key hashes to one shard: the fullest bucket is a whole shard's rows."""
    lone = _exchanged(_keyed(np.arange(40_000)), "k")[0]
    keys = np.asarray(lone.column("k").data)[:lone.num_rows // 4][np.asarray(lone.present)[:lone.num_rows // 4]]
    return np.resize(keys, 6000)


@pytest.mark.parametrize("case", ["one_destination", "sparse", "empty", "dense"] + [f"shuffling{i}" for i in range(6)])
def test_an_exchange_sizes_its_buckets_from_the_rows_it_counted(case):
    """capacity = min(slots a shard, max(1,024, the power of two at or above
    the fullest bucket)): what the slots hold decides, not how many there are."""
    keep = None
    if case == "one_destination":  # 1,500 slots a shard, all bound for shard 0: no step above a shard's slots
        keys, capacity = _one_destination(), 1500
    elif case == "sparse":  # a filter left 24 rows in 200,000 slots (0.012%): the floor, not 75,000
        keys = np.arange(200_000) % 50_021
        keep = np.zeros(200_000, bool)
        keep[::8_500] = True
        capacity = 1024
    elif case == "empty":  # no row: one absent slot a shard
        keys, capacity = np.zeros(0, np.int64), 1
    elif case == "dense":  # 10,000 slots a shard, ~2,500 a bucket: the next step, where the guess was 3,750
        keys, capacity = np.arange(40_000), 4096
    else:  # one multiset of keys (orders of 8..16 rows lying together, as web_sales') dealt six ways, as a
        # benchmark's seeds deal it: one step, so one program
        deal = np.random.default_rng(int(case[-1])).permutation(_ORDERS.size)
        keys, capacity = np.repeat(_ORDERS[deal], _ITEMS[deal]), 4096
    table = _keyed(keys)
    rows = int(keys.size if keep is None else keep.sum())
    out, span, moved = _exchanged(table, "k", keep)
    assert span["capacity"] == capacity and out.num_rows == 16 * capacity
    assert moved == {"programs": 1, "rows_in": rows, "bytes_offered": 16 * rows, "slots_out": 16 * capacity,
                     "overflows": 0, "capacity_retries": 0}
    # the span says what the counted rows were: the fullest bucket by the routing of the rows that came out
    there, shard = np.asarray(out.present), np.repeat(np.arange(4), 4 * capacity)
    source = np.asarray(out.column("v").data)[there] // max(-(-keys.size // 4), 1)  # the shard a row was placed on
    buckets = np.zeros((4, 4), int)
    np.add.at(buckets, (source, shard[there]), 1)
    assert span["rows_in"] == rows and span["max_bucket"] == buckets.max()
    assert span["fill"] == pytest.approx(rows / (16 * capacity))
    # no row lost, none doubled, and equal keys on one shard
    want = np.flatnonzero(keep) if keep is not None else np.arange(keys.size)
    assert _rows_of(out, "k", "v") == sorted(zip(np.asarray(keys)[want].tolist(), want.tolist()))
    got_k = np.asarray(out.column("k").data)[there]
    assert len({(k, s) for k, s in zip(got_k.tolist(), shard[there].tolist())}) == np.unique(got_k).size
    assert table_ops.gather_table(out).num_rows == rows


@pytest.mark.parametrize("how", ["semi", "anti", "inner"])
def test_the_stages_behind_a_sparse_exchange_answer_with_sides_of_other_slot_counts(how):
    """q95's ``ws1`` in small: a filter keeps 30 rows of 200,000 slots, so
    its exchange hands on 4,096 slots a shard where the dense side's hands
    on 16,384. The probe, the group-by and the gather behind them answer as
    pandas does; an INT32 key meets the INT64 key of equal value."""
    rng = np.random.default_rng(23)
    n = 200_000
    lk, lv = rng.integers(0, 60_000, n).astype(np.int32), rng.integers(0, 1000, n)
    keep = np.zeros(n, bool)
    keep[rng.choice(n, 30, replace=False)] = True
    rk = rng.choice(60_000, 40_000, replace=False).astype(np.int64)  # unique: a dimension's key, or a group-by's
    rp = rng.integers(0, 100, rk.size).astype(np.int32)
    left = Table([Column.from_numpy(lk, dt.INT32), Column.from_numpy(lv, dt.INT64)], ["k", "v"])
    right = Table([Column.from_numpy(rk, dt.INT64), Column.from_numpy(rp, dt.INT32)], ["rk", "p"])
    sparse, s_span, _ = _exchanged(left, "k", keep)
    dense, d_span, _ = _exchanged(right, "rk")
    assert (s_span["capacity"], d_span["capacity"]) == (1024, 4096) and sparse.num_rows != dense.num_rows
    joined = table_ops.join_sharded(sparse, dense, ("k", "rk"), how, payload=("p",))
    assert joined.num_rows == sparse.num_rows  # the left's slots
    ldf = pd.DataFrame({"k": lk.astype(np.int64), "v": lv})[keep]
    hit = ldf.k.isin(rk)
    assert 0 < hit.sum() < len(ldf)
    if how == "inner":
        want = ldf.merge(pd.DataFrame({"k": rk, "p": rp}), on="k")
        assert _rows_of(joined, "k", "v", "p") == sorted(zip(want.k.tolist(), want.v.tolist(), want.p.tolist()))
    else:
        want = ldf[hit if how == "semi" else ~hit]
        assert _rows_of(joined, "k", "v") == sorted(zip(want.k.tolist(), want.v.tolist()))
    grouped = table_ops.gather_table(table_ops.groupby_sharded(joined, ["k"], [("v", "sum", "s"), (None, "count_all", "n")]))
    by_key = want.groupby("k").agg(s=("v", "sum"), n=("v", "size")).reset_index()
    got = sorted(zip(*(np.asarray(grouped.column(c).data).tolist() for c in ("k", "s", "n"))))
    assert got == sorted(zip(by_key.k.tolist(), by_key.s.tolist(), by_key.n.tolist()))


def test_int32_and_int64_keys_of_equal_value_are_counted_and_sent_alike():
    """The count and the all-to-all route by one hash of the key widened
    to int64: a sparse INT32 side at the floor and a dense INT64 side on a
    higher step still bring equal values to one shard."""
    values = np.arange(0, 80_000, 2)
    keep = np.zeros(values.size, bool)
    keep[::400] = True
    narrow, n_span, _ = _exchanged(_keyed(values, dt.INT32), "k", keep)
    wide, w_span, _ = _exchanged(_keyed(values), "k")
    assert n_span["capacity"] < w_span["capacity"]
    shard_of = {}
    for st in (wide, narrow):
        there, shard = np.asarray(st.present), np.repeat(np.arange(4), st.num_rows // 4)
        for value, s in zip(np.asarray(st.column("k").data)[there].tolist(), shard[there].tolist()):
            assert shard_of.setdefault(value, s) == s, value
    assert len(shard_of) == values.size


def test_a_bucket_that_overflows_fails_the_gather_and_is_counted(monkeypatch):
    """No counted capacity can overflow; if the all-to-all program says
    one did all the same, the rows do not leave the mesh."""
    monkeypatch.setattr(table_ops, "_counted_capacity", lambda max_bucket, per_shard: max_bucket - 1)
    before = _exchange_counters()["overflows"]
    out, _span, _ = _exchanged(_keyed(np.arange(4000)), "k")
    behind = table_ops.groupby_sharded(out, ["k"], [("v", "sum", "s")])  # the flag rides through the stages behind
    with pytest.raises(table_ops.ExchangeOverflow, match=r"exchange on \['k'\]"):
        table_ops.gather_table(behind)
    assert _exchange_counters()["overflows"] == before + 1


@pytest.mark.parametrize("world", [None, 4])
def test_a_string_predicate_and_a_string_projection_pass_a_plan(world):
    """ROADMAP F2, the part q95 meets: a STRING column of a replicated
    table goes through a Filter (LIKE without a wildcard) and through the
    pass-through Project that pruning or the author puts around it."""
    n = 40
    dim = Table([Column.from_numpy(np.arange(n, dtype=np.int32), dt.INT32),
                 Column.from_pylist([("IL" if i % 4 == 0 else "TX") for i in range(n)], dt.STRING)], ["k", "state"])
    fact = Table([Column.from_numpy((np.arange(400) % n).astype(np.int32), dt.INT32),
                  Column.from_numpy(np.arange(400, dtype=np.int64), dt.INT64)], ["fk", "v"])
    picked = pn.Project(pn.Filter(pn.Project(pn.Scan("dim"), (("k", P.pcol("k")), ("state", P.pcol("state")))),
                                  P.plike(P.pcol("state"), "IL")),
                        (("state", P.pcol("state")), ("k", P.pcol("k"))))
    plan = pn.Sort(pn.Join(pn.Scan("fact"), picked, on=(("fk", "k"),), how="inner"), (("v", True),))
    plan = P.insert_exchanges(plan, world or 1, sharded=("fact",))
    mesh = None if world is None else P.MeshBinding(_mesh(world), ("fact",))
    out = P.compile_ir(plan, {"fact": fact, "dim": dim}, name="strings", mesh=mesh)()
    assert np.asarray(out.column("v").data).tolist() == [v for v in range(400) if (v % n) % 4 == 0]
    assert set(out.column("state").to_pylist()) == {"IL"}


def _kinds(cp):
    return [type(s).__name__ for s in cp.stages]


@pytest.mark.parametrize("widths", [(np.int32, np.int64), (np.int64, np.int32), (np.int64, np.int64)])
@pytest.mark.parametrize("how", ["semi", "anti"])
def test_keys_of_two_widths_join_right_on_a_mesh(how, widths):
    """Each side of a shuffled join is routed by a hash of its own key. An
    INT32 hashes as one block where an INT64 hashes as two, so an exchange
    widens an integer key before it hashes it: equal VALUES of two widths
    share a chip, and the co-partitioned join loses no match."""
    rng = np.random.default_rng(11)
    lk, rk = rng.integers(-200, 500, 4000).astype(widths[0]), rng.integers(250, 750, 3000).astype(widths[1])
    kinds = {np.int32: dt.INT32, np.int64: dt.INT64}
    left = Table([Column.from_numpy(lk, kinds[widths[0]]), Column.from_numpy(np.arange(4000, dtype=np.int64), dt.INT64)],
                 ["k", "v"])
    right = Table([Column.from_numpy(rk, kinds[widths[1]])], ["rk"])
    plan = pn.Sort(pn.Join(pn.Scan("l"), pn.Scan("r"), on=(("k", "rk"),), how=how), (("v", True),))
    plan = P.insert_exchanges(plan, 4, sharded=("l", "r"))
    assert sorted(e.keys for e in _exchanges(plan)) == [("k",), ("rk",)]
    cp = P.compile_ir(plan, {"l": left, "r": right}, name="widths", mesh=P.MeshBinding(_mesh(4), ("l", "r")))
    hit = np.isin(lk.astype(np.int64), rk.astype(np.int64))
    assert 0 < hit.sum() < hit.size
    assert np.asarray(cp().column("v").data).tolist() == np.flatnonzero(hit if how == "semi" else ~hit).tolist()
    assert _kinds(cp).count("_MeshExchangeExec") == 2 and _kinds(cp).count("_MeshJoinExec") == 1
    # the layer itself: a value lies on one shard whichever side and width it came in
    sides = [table_ops.exchange_sharded(table_ops.shard_table(t, _mesh(4)), [k]) for t, k in ((left, "k"), (right, "rk"))]
    shard_of = {}
    for st, k in zip(sides, ("k", "rk")):
        vals, there = np.asarray(st.column(k).data), np.asarray(st.present)
        for shard, (v, p) in enumerate(zip(np.split(vals, 4), np.split(there, 4))):
            for value in np.unique(v[p]).tolist():
                assert shard_of.setdefault(value, shard) == shard, value


def test_a_uint64_key_meets_no_signed_key_on_the_mesh():
    """int64 holds every integer but a UINT64: 2**64 - 5 and -5 are one bit
    pattern there. The layer refuses the pair, and the plan does not ask."""
    big = Table([Column.from_numpy(np.array([2**64 - 5, 7], dtype=np.uint64), dt.UINT64)], ["k"])
    neg = Table([Column.from_numpy(np.array([-5, 7], dtype=np.int64), dt.INT64)], ["rk"])
    sides = [table_ops.exchange_sharded(table_ops.shard_table(t, _mesh(2)), [k]) for t, k in ((big, "k"), (neg, "rk"))]
    with pytest.raises(ValueError, match="do not meet in int64"):
        table_ops.join_sharded(sides[0], sides[1], ("k", "rk"), "semi")
    for sharded in (("l", "r"), ("l",)):  # against a sharded and against a broadcast right side
        plan = P.insert_exchanges(pn.Join(pn.Scan("l"), pn.Scan("r"), on=(("k", "rk"),), how="semi"), 2, sharded=sharded)
        cp = P.compile_ir(plan, {"l": big, "r": neg}, name="u64", mesh=P.MeshBinding(_mesh(2), sharded))
        assert "_MeshJoinExec" not in _kinds(cp)


def test_a_key_that_is_no_integer_groups_behind_a_gather():
    """The shard-local group-by sorts integer key lanes: a FLOAT64 key (or
    one beside an integer key) goes to the local tier, and answers."""
    rng = np.random.default_rng(3)
    k, j, v = rng.integers(0, 50, 3000) / 4.0, rng.integers(0, 3, 3000).astype(np.int32), rng.integers(0, 1000, 3000)
    fact = Table([Column.from_numpy(k, dt.FLOAT64), Column.from_numpy(j, dt.INT32), Column.from_numpy(v, dt.INT64)],
                 ["k", "j", "v"])
    df = pd.DataFrame({"k": k, "j": j, "v": v})
    for keys in (("k",), ("j", "k")):
        plan = pn.Aggregate(pn.Scan("fact"), keys=keys, aggs=(pn.AggSpec("v", "sum", "s"), pn.AggSpec(None, "count_all", "n")))
        plan = P.insert_exchanges(pn.Sort(plan, tuple((c, True) for c in keys)), 4, sharded=("fact",))
        cp = P.compile_ir(plan, {"fact": fact}, name="floatkey", mesh=P.MeshBinding(_mesh(4), ("fact",)))
        out = cp()
        want = df.groupby(list(keys)).agg(s=("v", "sum"), n=("v", "size")).reset_index()
        np.testing.assert_array_equal(np.asarray(out.column("k").data).view(np.float64), want.k.to_numpy())
        assert np.asarray(out.column("s").data).view(np.float64).tolist() == want.s.tolist()  # a plan's sums are FLOAT64
        assert np.asarray(out.column("n").data).tolist() == want.n.tolist()
        assert "_MeshAggExec" not in _kinds(cp) and "_GatherExec" in _kinds(cp)
    # the layer itself takes a key whose lane is an integer array (a FLOAT64's stored bits, as the
    # operator before it did), and refuses the others
    real = Table([Column.from_numpy(k.astype(np.float32), dt.FLOAT32), fact.column("v")], ["k", "v"])
    st = table_ops.exchange_sharded(table_ops.shard_table(real, _mesh(4)), ["k"])
    with pytest.raises(ValueError, match="integer key lanes"):
        table_ops.groupby_sharded(st, ["k"], [("v", "sum", "s")])


def _spans_of_a_run(tmp_path, enabled):
    fact, _ = _skewed_tables(1200)
    plan = pn.Aggregate(pn.Scan("fact"), keys=("o",), aggs=(pn.AggSpec("c", "sum", "s"),))
    plan = P.insert_exchanges(pn.Join(plan, pn.Scan("fact"), on=(("o", "o"),), how="semi"), 2, sharded=("fact",))
    base = str(tmp_path / ("on" if enabled else "off"))
    was = tracing.is_enabled()
    tracing.set_enabled(enabled)
    trace_sink.set_log_path(base)
    try:
        cp = P.compile_ir(plan, {"fact": fact}, name="spans", mesh=P.MeshBinding(_mesh(2), ("fact",)))
        _run(cp)
    finally:
        trace_sink.close_log()
        trace_sink.set_log_path(None)
        tracing.set_enabled(was)
    import glob
    import json

    return [json.loads(line) for path in glob.glob(base + ".*.jsonl") for line in open(path)
            if '"kind": "span"' in line]


def test_exchange_spans_and_counters_appear_and_vanish_with_tracing(tmp_path):
    reg = metrics.registry()
    before = {k: reg.value(f"exchange.{k}") for k in ("programs", "rows_in", "bytes_offered")}
    spans = _spans_of_a_run(tmp_path, True)
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    for name in ("exchange.place", "exchange.table", "exchange.groupby", "exchange.join", "exchange.gather"):
        assert name in by_name, sorted(by_name)
    for name in ("exchange.table", "exchange.groupby", "exchange.join"):
        for s in by_name[name]:
            assert {"rows_in", "keys", "capacity", "parts"} <= set(s["annotations"]), s
            assert s["annotations"]["parts"] == 2
    assert all({"max_bucket", "fill"} <= set(s["annotations"]) for s in by_name["exchange.table"])
    assert {s["annotations"]["how"] for s in by_name["exchange.place"]} == {"sharded"}
    # they sit under operator spans, so a plan stage's self time does not count them
    ids = {s["span"]: s["name"] for s in spans}
    assert all(ids.get(s["parent"], "").startswith("op.") for n in by_name if n.startswith("exchange.")
               for s in by_name[n])
    # two exchanges (the aggregate's side carries o and c, the semi join's other side o alone),
    # one all-to-all program each, skew or none: 1,200 rows enter each
    after = {k: reg.value(f"exchange.{k}") for k in before}
    assert after["programs"] - before["programs"] == 2
    assert after["rows_in"] - before["rows_in"] == 2400
    assert after["bytes_offered"] - before["bytes_offered"] == 1200 * 16 + 1200 * 8
    from spark_rapids_jni_tpu import runtime

    assert runtime.stats_report()["metrics"]["counters"]["exchange.programs"] == after["programs"]
    # tracing off: no span; the counters (registry-direct, like the plan tier's) still count
    assert _spans_of_a_run(tmp_path, False) == []
    assert reg.value("exchange.programs") == after["programs"] + 2


# ---------------------------------------------------------------------------
# the stage programs over a mesh (ISSUE 33): one jitted program a Filter, a
# Project and an aggregate's float64 normalisation, over row-sharded arrays
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def ws_wh_on_a_mesh():
    """q95's ``ws_wh`` as the cell plans it, on four virtual devices: the
    shard-local group-by's ``wh_lo`` / ``wh_hi`` as they come (integers),
    what the aggregate stage's one program makes of them, and the mesh
    Filter's ``present``."""
    from spark_rapids_jni_tpu.plan import compiler

    host = WEB.host_tables(CONFIG, 33, 30_000)
    wh, valid = host["web_sales"]["ws_warehouse_sk"]
    wh = np.where(host["web_sales"]["ws_order_number"] % 3 == 0, 4, wh).astype(np.int32)  # a third of the orders: one warehouse
    fact = Table([Column.from_numpy(host["web_sales"]["ws_order_number"], dt.INT64),
                  Column.from_numpy(wh, dt.INT32, validity=valid)], ["ws_order_number", "ws_warehouse_sk"])
    agg = pn.Aggregate(pn.Scan("web_sales"), keys=("ws_order_number",),
                       aggs=(pn.AggSpec("ws_warehouse_sk", "min", "wh_lo"), pn.AggSpec("ws_warehouse_sk", "max", "wh_hi")))
    plan = pn.Project(pn.Filter(agg, P.pcol("wh_lo") != P.pcol("wh_hi")), (("ws_order_number", P.pcol("ws_order_number")),))
    cp = P.compile_ir(P.insert_exchanges(plan, 4, sharded=("web_sales",)), {"web_sales": fact}, name="ws_wh",
                      mesh=P.MeshBinding(_mesh(4), ("web_sales",)))
    [agg_stage] = [s for s in cp.stages if type(s).__name__ == "_MeshAggExec"]
    [flt] = [s for s in cp.stages if type(s).__name__ == "_MeshFilterExec"]
    ctx = compiler._RunContext(cp._tables)
    before = {k: metrics.registry().value(f"plan.expr.{k}") for k in ("jitted", "eager")}
    raw = table_ops.groupby_sharded(agg_stage.inputs[0].run(ctx), agg_stage.keys,
                                    [(a.source, a.how, a.name) for a in agg_stage.aggs])
    st = agg_stage.run(ctx)
    out = flt.run(ctx)
    moved = {k: metrics.registry().value(f"plan.expr.{k}") - v for k, v in before.items()}
    eager = {n: compiler._to_float64(raw.column(n)) for n in ("wh_lo", "wh_hi")}
    mask = (P.pcol("wh_lo") != P.pcol("wh_hi")).lower().evaluate(Table(list(eager.values()), list(eager)))
    eager["present"] = Column(dt.BOOL8, data=compiler._keep(mask, st.present))
    jitted = {"wh_lo": st.column("wh_lo"), "wh_hi": st.column("wh_hi"), "present": Column(dt.BOOL8, data=out.present)}
    laid = {"wh_lo": raw.column("wh_lo").data.sharding, "wh_hi": raw.column("wh_hi").data.sharding,
            "present": st.present.sharding}
    return jitted, eager, laid, moved, cp


@pytest.mark.parametrize("what", ["wh_lo", "wh_hi", "present"])
def test_a_mesh_stages_program_gives_the_eager_lanes_laid_out_as_its_inputs(ws_wh_on_a_mesh, what):
    jitted, eager, laid, moved, _ = ws_wh_on_a_mesh
    got, want = jitted[what], eager[what]
    assert got.dtype.id == want.dtype.id and got.data.dtype == want.data.dtype
    np.testing.assert_array_equal(np.asarray(got.data), np.asarray(want.data))
    assert (got.validity is None) == (want.validity is None)
    if want.validity is not None:
        np.testing.assert_array_equal(np.asarray(got.validity), np.asarray(want.validity))
        assert got.validity.sharding.is_equivalent_to(laid[what], 1)
    assert got.data.sharding.is_equivalent_to(laid[what], 1) and len(got.data.sharding.device_set) == 4
    if what == "present":
        kept = int(np.asarray(got.data).sum())
        assert 0 < kept < int(np.asarray(eager["wh_lo"].validity).sum())  # some orders name one warehouse only
    # one program for both columns of the aggregate stage, one for the Filter; nothing eager
    assert moved == {"jitted": 2, "eager": 0}


@pytest.mark.parametrize("kind", ["literal", "null_literal", "computed", "cast", "passthrough"])
def test_a_mesh_projects_outputs_lie_as_its_inputs_do(kind):
    from spark_rapids_jni_tpu.plan import compiler

    n = 1000
    rng = np.random.default_rng(9)
    fact = Table([Column.from_numpy(np.arange(n, dtype=np.int64), dt.INT64),
                  Column.from_numpy(rng.uniform(0, 9, n).round(2), dt.FLOAT64, validity=rng.random(n) > 0.1)], ["k", "x"])
    exprs = {"literal": P.plit(2.5), "null_literal": P.plit(None, dt.FLOAT64), "computed": P.pcol("x") * (P.plit(1.0) - P.pcol("x")),
             "cast": P.pcol("k").cast(dt.FLOAT64), "passthrough": P.pcol("x")}
    plan = pn.Project(pn.Scan("fact"), (("k", P.pcol("k")), ("out", exprs[kind])))
    cp = P.compile_ir(plan, {"fact": fact}, name="meshproj", mesh=P.MeshBinding(_mesh(4), ("fact",)))
    [proj] = [s for s in cp.stages if type(s).__name__ == "_MeshProjectExec"]
    ctx = compiler._RunContext(cp._tables)
    st_in = proj.inputs[0].run(ctx)
    st = proj.run(ctx)
    got = st.column("out")
    want = compiler._materialize(None if kind == "null_literal" else exprs[kind].lower(), st_in.table,
                                 dt.FLOAT64, st_in.num_rows)
    np.testing.assert_array_equal(np.asarray(got.data), np.asarray(want.data))
    assert (got.validity is None) == (want.validity is None)
    if want.validity is not None:
        np.testing.assert_array_equal(np.asarray(got.validity), np.asarray(want.validity))
    rows = st_in.present.sharding
    assert got.data.sharding.is_equivalent_to(rows, 1) and len(got.data.sharding.device_set) == 4
    assert st.column("k").data is st_in.column("k").data  # a reference is handed on as it is
    if kind == "passthrough":
        assert got.data is st_in.column("x").data and not proj.program.trees
    # and the plan answers with the rows it was given
    out = cp()
    assert np.asarray(out.column("k").data).tolist() == list(range(n))


@pytest.mark.parametrize("world", [1, 2])
def test_q95s_local_filters_compact_and_its_mesh_filter_keeps_present(world):
    """ISSUE 35: the one-chip deferral is the op tier's alone. q95's local
    Filters feed joins (every run of one is counted ``compacted``); the
    Filter over the mesh never compacted and is counted as neither."""
    from spark_rapids_jni_tpu.plan.compiler import _FilterExec, _MeshFilterExec

    tables = _tables(WEB.host_tables(CONFIG, 7, ROWS))
    plan = P.insert_exchanges(Q95.plan(P), max(world, 2), sharded=SHARDED)
    mesh = P.MeshBinding(_mesh(world), SHARDED) if world > 1 else None
    cp = P.compile_ir(plan, tables, name="q95-filters", mesh=mesh)
    filters = [s for s in cp.stages if isinstance(s, _FilterExec)]
    local = [s for s in filters if type(s) is _FilterExec]
    assert not any(s.deferrable for s in filters)
    assert len(filters) - len(local) == (1 if world > 1 else 0)  # ``wh_lo <> wh_hi`` over the mesh
    assert all(isinstance(s, _MeshFilterExec) for s in filters if s not in local)
    reg = metrics.registry()
    was = reg.value("plan.filter.deferred"), reg.value("plan.filter.compacted")
    assert _answer(_run(cp))[0] > 0
    assert (reg.value("plan.filter.deferred") - was[0], reg.value("plan.filter.compacted") - was[1]) == (0, len(local))
