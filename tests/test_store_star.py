"""The TPC-DS store star as the benchmark sends it (ISSUE 32): the cell
``tpcds-sf1-store.q3-q55``'s own query files over its own data builder at
the configuration's ``rehearse_rows``, through ``plan.compile_ir`` and
``serve.Scheduler``, against the queries' pandas references — and the same
two plans with ``i_brand_id`` taken OUT of the group keys, so that the
brand's NAME alone (a STRING of 17 to 22 bytes that differs in its last
one) tells two brands apart: the variant that merged brands until PR 32.
"""

import importlib.util
import json
import os
from unittest import mock

import jax
import numpy as np
import pandas as pd
import pytest

from spark_rapids_jni_tpu import plan as P
from spark_rapids_jni_tpu import serve
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.columnar import dtype as dt
from spark_rapids_jni_tpu.columnar.dtype import TypeId
from spark_rapids_jni_tpu.utils import metrics

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench")
SEEDS = (7, 3200000033, 2200007920)
TYPES = {"int32": dt.INT32, "float64": dt.FLOAT64}


def _bench_module(kind, name):
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", os.path.join(BENCH, kind, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


with open(os.path.join(BENCH, "configs", "tpcds-sf1-store.json")) as _f:
    CONFIG = json.load(_f)
STORE = _bench_module("data", CONFIG["data"])
COMPARE = _bench_module("benchlib", "compare")
Q3, Q55 = _bench_module("queries", "tpcds_q3"), _bench_module("queries", "tpcds_q55")


class _ByNameAlone:
    """A query of the star grouped by the brand's name without its id:
    the same joins and filters (the query's own plan, its Aggregate, Sort
    and Limit rebuilt), the same pandas reference regrouped."""

    def __init__(self, q, lead, total):
        self.q, self.lead, self.total = q, lead, total  # leading group keys, the sum's name
        self.TABLES, self.EXACT = q.TABLES, lead + ("i_brand",)

    def plan(self, P):
        joined = self.q.plan(P).input.input.input  # Limit(Sort(Aggregate(joined)))
        agg = P.Aggregate(joined, keys=self.EXACT, aggs=(P.AggSpec("ss_ext_sales_price", "sum", self.total),))
        order = tuple((k, True) for k in self.lead) + ((self.total, False), ("i_brand", True))
        return P.Limit(P.Sort(agg, order), self.q.LIMIT)

    def reference(self, frames, real=np.float64):
        it = frames["item"]
        names = it.drop_duplicates("i_brand_id").set_index("i_brand_id").i_brand
        assert names.is_unique  # brand and brand id determine each other (the configuration's `assumed`)
        # the query's own reference up to its LIMIT, then regrouped: ids and names are one to one
        limit = self.q.LIMIT
        with mock.patch.object(self.q, "LIMIT", 10 ** 9):
            g = self.q.reference(frames, real)
        g = g.drop(columns="i_brand_id")
        g = g.sort_values("i_brand").sort_values(self.total, ascending=False, na_position="first", kind="stable")
        for k in reversed(self.lead):
            g = g.sort_values(k, kind="stable")
        return g[list(self.EXACT) + [self.total]].head(limit)


QUERIES = {"q3": Q3, "q55": Q55,
           "q3_by_name_alone": _ByNameAlone(Q3, ("d_year",), "sum_agg"),
           "q55_by_name_alone": _ByNameAlone(Q55, (), "ext_price")}


@pytest.fixture(scope="module")
def star():
    """seed -> (device tables, pandas frames), made once a seed."""
    made = {}

    def column(a, kind):
        a, valid = a if isinstance(a, tuple) else (a, None)
        if kind == "string":
            return Column.from_pylist(list(a), dt.STRING)
        return Column.from_numpy(np.ascontiguousarray(a), TYPES[kind], validity=valid)

    def series(a):
        return pd.Series(a[0].astype(np.float64)).where(a[1]) if isinstance(a, tuple) else pd.Series(a)

    def get(seed):
        if seed not in made:
            host = STORE.host_tables(CONFIG, seed, CONFIG["tables"]["store_sales"]["rehearse_rows"])
            tables = {n: Table([column(a, CONFIG["tables"][n]["columns"][c]) for c, a in cols.items()], list(cols))
                      for n, cols in host.items()}
            frames = {n: pd.DataFrame({c: series(a) for c, a in cols.items()}) for n, cols in host.items()}
            made[seed] = tables, frames
        return made[seed]

    return get


def _host(out, exact):
    """The answer as ``bench/drivers/plan_serve.py::check`` reads it."""
    got, nulls = {}, 0
    for n, c in zip(out.names, out.columns):
        if c.dtype.id == TypeId.STRING:
            got[n] = np.array(c.to_pylist(), dtype=object)
        else:
            a = np.asarray(c.data)
            got[n] = a.view(np.float64).copy() if c.dtype.id == TypeId.FLOAT64 else a
        if c.validity is not None:
            invalid = ~np.asarray(c.validity)
            if n in exact:
                nulls += int(np.count_nonzero(invalid))
            else:
                got[n][invalid] = np.nan
    return got, nulls


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", list(QUERIES))
def test_the_star_equals_its_pandas_reference(star, name, seed):
    q = QUERIES[name]
    tables, frames = star(seed)
    cp = P.compile_ir(q.plan(P), {t: tables[t] for t in q.TABLES}, name=name)
    sched = serve.Scheduler(max_concurrent=1, name="store-star")
    try:
        out = sched.submit(cp).result()
        jax.block_until_ready([x for c in out.columns for x in (c.data, c.validity) if x is not None])
    finally:
        sched.shutdown()
    want = q.reference(frames, np.float64)
    assert len(want) > 10  # the parameters select something at this size
    got, nulls = _host(out, q.EXACT)
    numbers = COMPARE.table_numbers(got, nulls, want, q.EXACT)
    assert numbers["shape_diff"] == 0 and numbers["exact_diff"] == 0 and numbers["rel_gap"] <= 1e-9, numbers


@pytest.mark.parametrize("name", ["q3", "q55"])
def test_the_stars_filters_feed_joins_and_keep_their_compaction(star, name):
    """ISSUE 35: a Filter hands its mask on only where an Aggregate reads it
    through Projects alone. The star's filters are dimension filters under
    Joins: every one compacts, and the STRING-keyed group-by runs unmasked."""
    q = QUERIES[name]
    tables, _ = star(SEEDS[0])
    cp = P.compile_ir(q.plan(P), {t: tables[t] for t in q.TABLES}, name=name)
    filters = [s for s in cp.stages if s.kind == "filter"]
    assert len(filters) == 2 and not any(s.deferrable for s in filters)
    reg = metrics.registry()
    was = reg.value("plan.filter.deferred"), reg.value("plan.filter.compacted")
    cp()
    assert (reg.value("plan.filter.deferred") - was[0], reg.value("plan.filter.compacted") - was[1]) == (0, 2)
