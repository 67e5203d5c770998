"""TPC-DS query 3 over the store star, as the spec's template with its
qualification parameters (MANUFACT 128, MONTH 11):

    select dt.d_year, item.i_brand_id brand_id, item.i_brand brand, sum(ss_ext_sales_price) sum_agg
    from date_dim dt, store_sales, item
    where dt.d_date_sk = store_sales.ss_sold_date_sk and store_sales.ss_item_sk = item.i_item_sk
      and item.i_manufact_id = 128 and dt.d_moy = 11
    group by dt.d_year, item.i_brand, item.i_brand_id
    order by dt.d_year, sum_agg desc, brand_id
    limit 100

The scans name the columns the query reads, as a Spark plan that has
pruned its columns hands them to the plugin; how to join is left to the
program (``bounded=None``: its optimizer decides).
"""

import numpy as np

TABLES = ("store_sales", "date_dim", "item")
READS = {"store_sales": ("ss_sold_date_sk", "ss_item_sk", "ss_ext_sales_price"),
         "date_dim": ("d_date_sk", "d_year", "d_moy"),
         "item": ("i_item_sk", "i_manufact_id", "i_brand_id", "i_brand")}
EXACT = ("d_year", "i_brand_id", "i_brand")
MANUFACT, MONTH, LIMIT = 128, 11, 100


def plan(P):
    x = P.Scan("store_sales", columns=READS["store_sales"])
    x = P.Join(x, P.Filter(P.Scan("date_dim", columns=READS["date_dim"]), P.pcol("d_moy") == P.plit(MONTH)),
               on=(("ss_sold_date_sk", "d_date_sk"),), bounded=None)
    x = P.Join(x, P.Filter(P.Scan("item", columns=READS["item"]), P.pcol("i_manufact_id") == P.plit(MANUFACT)),
               on=(("ss_item_sk", "i_item_sk"),), bounded=None)
    agg = P.Aggregate(x, keys=("d_year", "i_brand_id", "i_brand"),
                      aggs=(P.AggSpec("ss_ext_sales_price", "sum", "sum_agg"),))
    return P.Limit(P.Sort(agg, (("d_year", True), ("sum_agg", False), ("i_brand_id", True))), LIMIT)


def reference(frames, real=np.float64):
    ss, dd, it = frames["store_sales"], frames["date_dim"], frames["item"]
    ss = ss[ss.ss_sold_date_sk.notna()]  # a NULL key joins nothing
    ss = ss.assign(ss_sold_date_sk=ss.ss_sold_date_sk.astype(np.int64))
    j = ss.merge(dd[dd.d_moy == MONTH], left_on="ss_sold_date_sk", right_on="d_date_sk")
    j = j.merge(it[it.i_manufact_id == MANUFACT], left_on="ss_item_sk", right_on="i_item_sk")
    j = j.assign(ss_ext_sales_price=j.ss_ext_sales_price.astype(real))  # NaN is NULL: left out of a sum; a sum over none is NULL
    g = j.groupby(["d_year", "i_brand_id", "i_brand"])["ss_ext_sales_price"].sum(min_count=1).reset_index()
    g = g.rename(columns={"ss_ext_sales_price": "sum_agg"})
    # a NULL sum sorts first, the program's one order (the plan's Sort names none; the spec leaves it to the system)
    g = g.sort_values("i_brand_id").sort_values("sum_agg", ascending=False, na_position="first", kind="stable")
    return g.sort_values("d_year", kind="stable").head(LIMIT)
