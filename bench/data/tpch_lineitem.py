"""Seeded ``lineitem`` for the TPC-H cells: host numpy arrays only.

Every seed gives the SAME multiset in every column, in another order:
the column values are drawn once from a fixed stream, and ``--seed`` only
permutes rows (dates and flags together, each measure column on its
own). So the number of rows q1's filter keeps, the four flag groups and
every intermediate shape are the same for every seed (no seed compiles
anew, no seed does other work) while every sum differs.

Distributions follow the TPC-H specification's clause 4.2.3 where a
seven-column look-alike can: ship date = order date (uniform over
1992-01-01 .. 1998-08-02) + 1..121 days; ``l_linestatus`` is 'O' after
1995-06-17 and 'F' up to it; ``l_returnflag`` is 'N' where the receipt
date (ship date + 1..30) is after 1995-06-17 and 'R' or 'A' otherwise.
Quantity 1..50, discount 0.00..0.10, tax 0.00..0.08 in whole cents as in
the spec; extended price uniform 900..105,000 to the cent (the spec
derives it from part keys, which this table does not carry).
Dates are days since 1992-01-01; flags are dictionary codes
(returnflag 0='A' 1='N' 2='R', linestatus 0='F' 1='O').
"""

from __future__ import annotations

import numpy as np

BASE_STREAM = 19920101  # the fixed stream every seed's multiset comes from
D_1995_06_17 = 1263
D_1998_08_02 = 2405
D_1998_12_01 = 2526


def host_tables(config: dict, seed: int, rows: int) -> dict:
    base = np.random.default_rng(BASE_STREAM)
    order = base.integers(0, D_1998_08_02 + 1, rows)
    ship = (order + base.integers(1, 122, rows)).astype(np.int32)
    receipt = ship + base.integers(1, 31, rows)
    returnflag = np.where(receipt > D_1995_06_17, 1, np.where(base.random(rows) < 0.5, 2, 0)).astype(np.int8)
    linestatus = (ship > D_1995_06_17).astype(np.int8)
    quantity = base.integers(1, 51, rows).astype(np.float64)
    price = base.integers(90_000, 10_500_001, rows) / 100.0
    discount = base.integers(0, 11, rows) / 100.0
    tax = base.integers(0, 9, rows) / 100.0

    rng = np.random.default_rng(seed)
    p = rng.permutation(rows)
    return {"lineitem": {
        "l_quantity": quantity[rng.permutation(rows)],
        "l_extendedprice": price[rng.permutation(rows)],
        "l_discount": discount[rng.permutation(rows)],
        "l_tax": tax[rng.permutation(rows)],
        "l_returnflag": returnflag[p],
        "l_linestatus": linestatus[p],
        "l_shipdate": ship[p],
    }}
