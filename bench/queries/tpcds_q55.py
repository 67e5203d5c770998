"""TPC-DS query 55 over the store star, as the spec's template with its
qualification parameters (MANAGER 28, MONTH 11, YEAR 1999):

    select i_brand_id brand_id, i_brand brand, sum(ss_ext_sales_price) ext_price
    from date_dim, store_sales, item
    where d_date_sk = ss_sold_date_sk and ss_item_sk = i_item_sk
      and i_manager_id = 28 and d_moy = 11 and d_year = 1999
    group by i_brand, i_brand_id
    order by ext_price desc, i_brand_id
    limit 100

Columns pruned in the scans and the join left to the program, as in q3.
"""

import numpy as np

TABLES = ("store_sales", "date_dim", "item")
READS = {"store_sales": ("ss_sold_date_sk", "ss_item_sk", "ss_ext_sales_price"),
         "date_dim": ("d_date_sk", "d_year", "d_moy"),
         "item": ("i_item_sk", "i_manager_id", "i_brand_id", "i_brand")}
EXACT = ("i_brand_id", "i_brand")
MANAGER, MONTH, YEAR, LIMIT = 28, 11, 1999, 100


def plan(P):
    x = P.Scan("store_sales", columns=READS["store_sales"])
    x = P.Join(x, P.Filter(P.Scan("date_dim", columns=READS["date_dim"]),
                           (P.pcol("d_moy") == P.plit(MONTH)) & (P.pcol("d_year") == P.plit(YEAR))),
               on=(("ss_sold_date_sk", "d_date_sk"),), bounded=None)
    x = P.Join(x, P.Filter(P.Scan("item", columns=READS["item"]), P.pcol("i_manager_id") == P.plit(MANAGER)),
               on=(("ss_item_sk", "i_item_sk"),), bounded=None)
    agg = P.Aggregate(x, keys=("i_brand_id", "i_brand"),
                      aggs=(P.AggSpec("ss_ext_sales_price", "sum", "ext_price"),))
    return P.Limit(P.Sort(agg, (("ext_price", False), ("i_brand_id", True))), LIMIT)


def reference(frames, real=np.float64):
    ss, dd, it = frames["store_sales"], frames["date_dim"], frames["item"]
    ss = ss[ss.ss_sold_date_sk.notna()]  # a NULL key joins nothing
    ss = ss.assign(ss_sold_date_sk=ss.ss_sold_date_sk.astype(np.int64))
    j = ss.merge(dd[(dd.d_moy == MONTH) & (dd.d_year == YEAR)], left_on="ss_sold_date_sk", right_on="d_date_sk")
    j = j.merge(it[it.i_manager_id == MANAGER], left_on="ss_item_sk", right_on="i_item_sk")
    j = j.assign(ss_ext_sales_price=j.ss_ext_sales_price.astype(real))  # NaN is NULL: left out of a sum; a sum over none is NULL
    g = j.groupby(["i_brand_id", "i_brand"])["ss_ext_sales_price"].sum(min_count=1).reset_index()
    g = g.rename(columns={"ss_ext_sales_price": "ext_price"})
    # a NULL sum sorts first, the program's one order (the plan's Sort names none; the spec leaves it to the system)
    g = g.sort_values("i_brand_id").sort_values("ext_price", ascending=False, na_position="first", kind="stable")
    return g.head(LIMIT)
