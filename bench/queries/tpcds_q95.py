"""TPC-DS query 95 over the web channel, the template whole, with its
qualification parameters (DATE 1999-02-01, STATE IL, COMPANY pri):

    with ws_wh as (select ws1.ws_order_number, ws1.ws_warehouse_sk wh1, ws2.ws_warehouse_sk wh2
                   from web_sales ws1, web_sales ws2
                   where ws1.ws_order_number = ws2.ws_order_number
                     and ws1.ws_warehouse_sk <> ws2.ws_warehouse_sk)
    select count(distinct ws_order_number) as "order count",
           sum(ws_ext_ship_cost) as "total shipping cost", sum(ws_net_profit) as "total net profit"
    from web_sales ws1, date_dim, customer_address, web_site
    where d_date between '1999-02-01' and (cast('1999-02-01' as date) + 60 days)
      and ws1.ws_ship_date_sk = d_date_sk
      and ws1.ws_ship_addr_sk = ca_address_sk and ca_state = 'IL'
      and ws1.ws_web_site_sk = web_site_sk and web_company_name = 'pri'
      and ws1.ws_order_number in (select ws_order_number from ws_wh)
      and ws1.ws_order_number in (select wr_order_number from web_returns, ws_wh
                                  where wr_order_number = ws_wh.ws_order_number)
    order by count(distinct ws_order_number) limit 100

The plan, where it is not the SQL word for word, and why that is fair:

- ``ws_wh`` is read only through ``in (select ws_order_number ...)``, a
  set: an order is in it when two of its line items name different
  warehouses, which is min <> max of ``ws_warehouse_sk`` over the order
  (a NULL warehouse equals and differs from nothing, and is in neither).
  One group-by in place of the self-join, as Spark's plan too reads each
  side of the self-join from one shuffle.
- both ``in`` subqueries are semi joins (Spark's plan for IN), and inside
  the second ``web_returns, ws_wh`` is a semi join as well: only
  ``wr_order_number`` is read from it, as a set.
- the three dimension joins are inner joins on primary keys, as written.
- ``count(distinct ws_order_number)`` is the number of groups of a
  per-order aggregate; the sums are sums of its per-order sums. The one
  result row makes ``order by`` and ``limit`` idle.
- no exchange is written here: the driver places them for the cell's
  mesh (``P.insert_exchanges`` over the configuration's ``sharded``
  tables). Compiled with no mesh they are the identity, and the same
  plan runs on one chip.
"""

import numpy as np

TABLES = ("web_sales", "web_returns", "date_dim", "customer_address", "web_site")
READS = {"web_sales": ("ws_order_number", "ws_warehouse_sk", "ws_ship_date_sk", "ws_ship_addr_sk",
                       "ws_web_site_sk", "ws_ext_ship_cost", "ws_net_profit"),
         "web_returns": ("wr_order_number",),
         "date_dim": ("d_date_sk", "d_date"),
         "customer_address": ("ca_address_sk", "ca_state"),
         "web_site": ("web_site_sk", "web_company_name")}
EXACT = ("order count",)
D_1999_02_01 = 10623  # days since 1970-01-01
DAYS, STATE, COMPANY = 60, "IL", "pri"


def plan(P):
    ws_wh = P.Aggregate(P.Scan("web_sales", columns=("ws_order_number", "ws_warehouse_sk")),
                        keys=("ws_order_number",),
                        aggs=(P.AggSpec("ws_warehouse_sk", "min", "wh_lo"), P.AggSpec("ws_warehouse_sk", "max", "wh_hi")))
    ws_wh = P.Project(P.Filter(ws_wh, P.pcol("wh_lo") != P.pcol("wh_hi")),
                      (("ws_order_number", P.pcol("ws_order_number")),))
    x = P.Scan("web_sales", columns=READS["web_sales"])
    x = P.Join(x, P.Filter(P.Scan("date_dim", columns=READS["date_dim"]),
                           (P.pcol("d_date") >= P.plit(np.int32(D_1999_02_01)))
                           & (P.pcol("d_date") <= P.plit(np.int32(D_1999_02_01 + DAYS)))),
               on=(("ws_ship_date_sk", "d_date_sk"),))
    x = P.Join(x, P.Filter(P.Scan("customer_address", columns=READS["customer_address"]),
                           P.plike(P.pcol("ca_state"), STATE)),
               on=(("ws_ship_addr_sk", "ca_address_sk"),))
    x = P.Join(x, P.Filter(P.Scan("web_site", columns=READS["web_site"]),
                           P.plike(P.pcol("web_company_name"), COMPANY)),
               on=(("ws_web_site_sk", "web_site_sk"),))
    x = P.Join(x, ws_wh, on=(("ws_order_number", "ws_order_number"),), how="semi")
    returned = P.Join(P.Scan("web_returns", columns=READS["web_returns"]), ws_wh,
                      on=(("wr_order_number", "ws_order_number"),), how="semi")
    x = P.Join(x, returned, on=(("ws_order_number", "wr_order_number"),), how="semi")
    per_order = P.Aggregate(x, keys=("ws_order_number",),
                            aggs=(P.AggSpec("ws_ext_ship_cost", "sum", "ship_cost"),
                                  P.AggSpec("ws_net_profit", "sum", "net_profit")))
    return P.Aggregate(per_order, keys=(), aggs=(P.AggSpec("ws_order_number", "count", "order count"),
                                                 P.AggSpec("ship_cost", "sum", "total shipping cost"),
                                                 P.AggSpec("net_profit", "sum", "total net profit")))


def _ws1(frames):
    """The rows of web_sales that pass the three dimension joins (a NULL
    key joins nothing)."""
    ws, dd = frames["web_sales"], frames["date_dim"]
    ca, site = frames["customer_address"], frames["web_site"]
    days = dd[(dd.d_date >= D_1999_02_01) & (dd.d_date <= D_1999_02_01 + DAYS)].d_date_sk
    addrs = ca[ca.ca_state == STATE].ca_address_sk
    sites = site[site.web_company_name == COMPANY].web_site_sk
    return ws[ws.ws_ship_date_sk.isin(days) & ws.ws_ship_addr_sk.isin(addrs) & ws.ws_web_site_sk.isin(sites)]


def reference(frames, real=np.float64):
    """pandas, from the SQL; ``real`` is the type the money is held and
    summed in (float64 as the configuration states; float32 is the control)."""
    import pandas as pd

    ws, wr = frames["web_sales"], frames["web_returns"]
    wh = ws.dropna(subset=["ws_warehouse_sk"]).groupby("ws_order_number").ws_warehouse_sk.nunique()
    ws_wh = wh[wh > 1].index  # orders with two line items from different warehouses
    ws1 = _ws1(frames)
    ws1 = ws1[ws1.ws_order_number.isin(ws_wh)]
    ws1 = ws1[ws1.ws_order_number.isin(wr.wr_order_number[wr.wr_order_number.isin(ws_wh)])]
    return pd.DataFrame({"order count": [ws1.ws_order_number.nunique()],
                         "total shipping cost": [ws1.ws_ext_ship_cost.astype(real).sum(min_count=1)],
                         "total net profit": [ws1.ws_net_profit.astype(real).sum(min_count=1)]})


def exchanges(frames):
    """What q95 has to shuffle on four executors whatever implements it:
    (rows entering, table, columns carried) for each exchange on the order
    number. ``web_sales`` for the per-order warehouses, the rows of ``ws1``
    that are left after the dimension joins, and ``web_returns``."""
    return [(len(frames["web_sales"]), "web_sales", ("ws_order_number", "ws_warehouse_sk")),
            (len(_ws1(frames)), "web_sales", ("ws_order_number", "ws_ext_ship_cost", "ws_net_profit")),
            (len(frames["web_returns"]), "web_returns", ("wr_order_number",))]
