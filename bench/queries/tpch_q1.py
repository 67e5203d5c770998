"""TPC-H q1 (pricing summary report), DELTA 90: all eight aggregates."""

import numpy as np

TABLES = ("lineitem",)
READS = {"lineitem": ("l_quantity", "l_extendedprice", "l_discount", "l_tax",
                      "l_returnflag", "l_linestatus", "l_shipdate")}
EXACT = ("l_returnflag", "l_linestatus", "count_order")
D_1998_12_01 = 2526
CUTOFF = D_1998_12_01 - 90


def plan(P):
    one = P.plit(1.0)
    disc_price = P.pcol("l_extendedprice") * (one - P.pcol("l_discount"))
    x = P.Filter(P.Scan("lineitem"), P.pcol("l_shipdate") <= P.plit(np.int32(CUTOFF)))
    x = P.Project(x, (
        ("l_returnflag", P.pcol("l_returnflag")),
        ("l_linestatus", P.pcol("l_linestatus")),
        ("qty", P.pcol("l_quantity")),
        ("price", P.pcol("l_extendedprice")),
        ("disc", P.pcol("l_discount")),
        ("disc_price", disc_price),
        ("charge", disc_price * (one + P.pcol("l_tax"))),
    ))
    agg = P.Aggregate(x, keys=("l_returnflag", "l_linestatus"), aggs=(
        P.AggSpec("qty", "sum", "sum_qty"),
        P.AggSpec("price", "sum", "sum_base_price"),
        P.AggSpec("disc_price", "sum", "sum_disc_price"),
        P.AggSpec("charge", "sum", "sum_charge"),
        P.AggSpec("qty", "mean", "avg_qty"),
        P.AggSpec("price", "mean", "avg_price"),
        P.AggSpec("disc", "mean", "avg_disc"),
        P.AggSpec(None, "count_all", "count_order"),
    ))
    return P.Sort(agg, (("l_returnflag", True), ("l_linestatus", True)))


def reference(frames, real=np.float64):
    """pandas twin; ``real`` is the type every measure is held and summed
    in (float64 as the configuration states; float32 is the control)."""
    df = frames["lineitem"]
    df = df[df.l_shipdate <= CUTOFF]
    qty, price = df.l_quantity.astype(real), df.l_extendedprice.astype(real)
    disc, tax = df.l_discount.astype(real), df.l_tax.astype(real)
    disc_price = price * (real(1) - disc)
    work = df[["l_returnflag", "l_linestatus"]].assign(
        qty=qty, price=price, disc=disc, disc_price=disc_price, charge=disc_price * (real(1) + tax))
    return work.groupby(["l_returnflag", "l_linestatus"]).agg(
        sum_qty=("qty", "sum"),
        sum_base_price=("price", "sum"),
        sum_disc_price=("disc_price", "sum"),
        sum_charge=("charge", "sum"),
        avg_qty=("qty", "mean"),
        avg_price=("price", "mean"),
        avg_disc=("disc", "mean"),
        count_order=("qty", "size"),
    ).reset_index().sort_values(["l_returnflag", "l_linestatus"])
