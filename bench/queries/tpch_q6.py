"""TPC-H q6 (forecasting revenue change): 1994, discount 0.06 +- 0.01, quantity < 24."""

import numpy as np

TABLES = ("lineitem",)
READS = {"lineitem": ("l_shipdate", "l_discount", "l_quantity", "l_extendedprice")}
EXACT = ()
D_1994_01_01, D_1995_01_01 = 731, 1096


def plan(P):
    pred = (
        (P.pcol("l_shipdate") >= P.plit(np.int32(D_1994_01_01)))
        & (P.pcol("l_shipdate") < P.plit(np.int32(D_1995_01_01)))
        & (P.pcol("l_discount") >= P.plit(0.05))
        & (P.pcol("l_discount") <= P.plit(0.07))
        & (P.pcol("l_quantity") < P.plit(24.0))
    )
    x = P.Project(P.Filter(P.Scan("lineitem"), pred),
                  (("rev", P.pcol("l_extendedprice") * P.pcol("l_discount")),))
    return P.Aggregate(x, keys=(), aggs=(P.AggSpec("rev", "sum", "revenue"),))


def reference(frames, real=np.float64):
    import pandas as pd

    df = frames["lineitem"]
    disc, qty = df.l_discount.astype(real), df.l_quantity.astype(real)
    m = ((df.l_shipdate >= D_1994_01_01) & (df.l_shipdate < D_1995_01_01)
         & (disc >= real(0.05)) & (disc <= real(0.07)) & (qty < real(24)))
    rev = (df.l_extendedprice.astype(real)[m] * disc[m]).sum()
    return pd.DataFrame({"revenue": [rev]})
