"""Seeded web channel for the TPC-DS q95 cell: host numpy arrays only.

The five tables q95 reads at the spec's shapes (specification v3.2.0,
table 3-2 row counts and the column definitions of 2.3/2.4) from a seeded
generator in place of dsdgen; no network here, so what follows is written
from memory of dsdgen and the configuration lists it under ``assumed``:

- ``web_sales``: an order's 8 .. 16 line items lie together, and orders in
  order-number order (1, 2, ...), as dsdgen writes them. Of an order:
  ``ws_ship_addr_sk`` (1 .. customer_address rows) and ``ws_web_site_sk``
  (1 .. web_site rows). Of a line item: ``ws_warehouse_sk`` (1 .. 10),
  ``ws_ship_date_sk`` (sold on a day of 1998-01-02 .. 2003-01-02, shipped
  1 .. 120 days later), ``ws_ext_ship_cost`` (0.00 .. 5,000.00) and
  ``ws_net_profit`` (-10,000.00 .. 10,000.00), to the cent. The four
  foreign keys and the two money columns are NULL in 4.5% of the rows
  each; ``ws_order_number`` never.
- ``web_returns``: a tenth of the line items come back; a returned line
  item is one row, ``wr_order_number`` its order.
- ``date_dim``: all 73,049 days 1900-01-02 .. 2100-01-01, ``d_date_sk`` the
  Julian day number, ``d_date`` the day as days since 1970-01-01.
- ``customer_address``: ``ca_state`` 'IL' in 102 of 3,143 rows (dsdgen
  draws a county and takes its state; Illinois has 102 of the 3,143), the
  other 50 codes even over the rest.
- ``web_site``: ``web_company_name`` is the syllable word of company
  1 .. 6 in turn: ought, able, pri, ese, anti, cally.

Steady from seed to seed: everything above is one fixed draw. ``--seed``
deals the orders (each with its line items, its keys and its returns)
onto other order numbers and, apart from them, deals the money values
onto other rows: every seed holds the same multisets, so it scans,
exchanges, joins and groups exactly as many rows, the same orders'
worth qualify, and every sum differs.

A column is a numpy array, or ``(array, valid)`` where it carries nulls;
a string column is an object array of ``str``.
"""

from __future__ import annotations

import numpy as np

BASE_STREAM = 19980102
JULIAN_1900_01_02 = 2415022
EPOCH_1900_01_02 = -25566  # 1900-01-02 in days since 1970-01-01
NULL_SHARE = 0.045
WAREHOUSES = 10
RETURNED = 719_217 / 7_197_566  # table 3-2 at SF10: web_returns over web_sales
COMPANIES = ("ought", "able", "pri", "ese", "anti", "cally")
STATES = ("AK", "AL", "AR", "AZ", "CA", "CO", "CT", "DC", "DE", "FL", "GA", "HI", "IA", "ID", "IN", "KS", "KY",
          "LA", "MA", "MD", "ME", "MI", "MN", "MO", "MS", "MT", "NC", "ND", "NE", "NH", "NJ", "NM", "NV", "NY",
          "OH", "OK", "OR", "PA", "RI", "SC", "SD", "TN", "TX", "UT", "VA", "VT", "WA", "WI", "WV", "WY")
IL_SHARE = 102 / 3143


def date_dim(rows: int) -> dict:
    return {"d_date_sk": (JULIAN_1900_01_02 + np.arange(rows)).astype(np.int32),
            "d_date": (EPOCH_1900_01_02 + np.arange(rows)).astype(np.int32)}


def customer_address(rows: int, base: np.random.Generator) -> dict:
    il = base.random(rows) < IL_SHARE
    other = np.array(STATES, dtype=object)[base.integers(0, len(STATES), rows)]
    return {"ca_address_sk": np.arange(1, rows + 1, dtype=np.int32),
            "ca_state": np.where(il, "IL", other).astype(object)}


def web_site(rows: int) -> dict:
    return {"web_site_sk": np.arange(1, rows + 1, dtype=np.int32),
            "web_company_name": np.array([COMPANIES[i % len(COMPANIES)] for i in range(rows)], dtype=object)}


def host_tables(config: dict, seed: int, rows: int) -> dict:
    base = np.random.default_rng(BASE_STREAM)
    t = config["tables"]
    n_addr, n_site = int(t["customer_address"]["rows"]), int(t["web_site"]["rows"])
    dd = date_dim(int(t["date_dim"]["rows"]))

    # the fixed draw: orders of 8..16 line items until the rows are full
    items = base.integers(8, 17, rows // 8 + 1)
    n_orders = int(np.searchsorted(np.cumsum(items), rows, side="left")) + 1
    items = items[:n_orders]
    items[-1] -= int(items.sum()) - rows
    start = np.cumsum(items) - items
    addr = base.integers(1, n_addr + 1, n_orders, dtype=np.int32)
    site = base.integers(1, n_site + 1, n_orders, dtype=np.int32)
    warehouse = base.integers(1, WAREHOUSES + 1, rows, dtype=np.int32)
    sold = JULIAN_1900_01_02 + 35794 + base.integers(0, 1827, rows)  # 1998-01-02 .. 2003-01-02
    ship = (sold + base.integers(1, 121, rows)).astype(np.int32)
    cost = base.integers(0, 500_001, rows) / 100.0      # decimal(7,2), to the cent
    profit = base.integers(-1_000_000, 1_000_001, rows) / 100.0
    valid = {c: base.random(rows) >= NULL_SHARE for c in ("warehouse", "ship", "addr", "site", "cost", "profit")}
    returned = base.choice(rows, size=int(round(rows * RETURNED)), replace=False)  # line items that come back
    order_of_row = np.repeat(np.arange(n_orders), items)

    # the seed: orders dealt onto other order numbers, money onto other rows
    rng = np.random.default_rng(seed)
    deal = rng.permutation(n_orders)            # the order at number p + 1 is the fixed draw's order deal[p]
    number_of = np.empty(n_orders, np.int64)
    number_of[deal] = np.arange(1, n_orders + 1)
    took = items[deal]
    new_start = np.cumsum(took) - took
    row = np.repeat(start[deal], took) + (np.arange(rows) - np.repeat(new_start, took))  # old row at each new row
    order_number = np.repeat(np.arange(1, n_orders + 1, dtype=np.int64), took)
    money = rng.permutation(rows)
    back = rng.permutation(len(returned))
    return {
        "date_dim": dd,
        "customer_address": customer_address(n_addr, base),
        "web_site": web_site(n_site),
        "web_sales": {
            "ws_order_number": order_number,
            "ws_warehouse_sk": (warehouse[row], valid["warehouse"][row]),
            "ws_ship_date_sk": (ship[row], valid["ship"][row]),
            "ws_ship_addr_sk": (np.repeat(addr[deal], took), valid["addr"][row]),
            "ws_web_site_sk": (np.repeat(site[deal], took), valid["site"][row]),
            "ws_ext_ship_cost": (cost[money], valid["cost"][money]),
            "ws_net_profit": (profit[money], valid["profit"][money]),
        },
        "web_returns": {"wr_order_number": number_of[order_of_row[returned[back]]]},
    }
