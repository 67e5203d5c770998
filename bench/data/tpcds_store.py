"""Seeded store-channel star for the TPC-DS cell: host numpy arrays only.

The TPC-DS store channel at the spec's SF1 shapes (specification v3.2.0,
tables 3-1/3-2 and the column definitions of 2.3/2.4), from a seeded
generator in place of dsdgen (the configuration file lists what that
changes under ``assumed``):

- ``date_dim``: all 73,049 days, 1900-01-02 .. 2100-01-01, ``d_date_sk``
  the Julian day number 2415022 .. 2488070, ``d_year`` and ``d_moy`` of the
  real calendar.
- ``item``: 18,000 rows, ``i_item_sk`` 1 .. 18000, ``i_manufact_id``
  1 .. 1000, ``i_manager_id`` 1 .. 100, ``i_brand_id`` the seven-or-eight
  digit id dsdgen composes (category 1..10, class 1..16, number 1..17:
  category * 1,000,000 + class * 1,000 + number) and ``i_brand`` its name
  (syllables of class and category, `` #`` and the number).
- ``store_sales``: 2,880,404 rows. ``ss_sold_date_sk`` falls on the five
  years 1998-01-02 .. 2003-01-02 with dsdgen's seasonal weights (August to
  October twice, November and December three times a day of the other
  months) and is NULL in 4.5% of the rows, as dsdgen leaves a nullable
  foreign key; ``ss_item_sk`` is never NULL (primary key) and even over the
  items; ``ss_ext_sales_price`` is NULL in 4.5% of the rows.

Steady from seed to seed: keys, nulls, prices and the whole of ``item``
are one fixed draw; ``--seed`` permutes the fact's rows and, apart from
them, its prices. So every seed joins, groups and sorts exactly as many
rows (the plans' intermediate shapes depend on those counts: a seed that
changed them would compile anew and do other work) while every sum differs.

A column is a numpy array, or ``(array, valid)`` where it carries nulls;
a string column is an object array of ``str``.
"""

from __future__ import annotations

import numpy as np

BASE_STREAM = 19980102
JULIAN_1900_01_02 = 2415022
SYLLABLE = ("univ", "amalg", "importo", "exporti", "edu pack", "scholar", "corp", "brand", "nameless", "maxi")
NULL_SHARE = 0.045


def _word(n: int) -> str:
    """dsdgen's syllable word of a number: one syllable a decimal digit,
    least significant first."""
    return "".join(SYLLABLE[int(d)] for d in reversed(str(n)))


def date_dim(rows: int) -> dict:
    days = np.datetime64("1900-01-02") + np.arange(rows)
    months = days.astype("datetime64[M]")
    return {
        "d_date_sk": (JULIAN_1900_01_02 + np.arange(rows)).astype(np.int32),
        "d_year": (days.astype("datetime64[Y]").astype(np.int64) + 1970).astype(np.int32),
        "d_moy": (months.astype(np.int64) % 12 + 1).astype(np.int32),
    }


def item(rows: int, base: np.random.Generator) -> dict:
    category = base.integers(1, 11, rows)
    klass = base.integers(1, 17, rows)
    number = base.integers(1, 18, rows)
    brand = np.array([f"{_word(k)}{_word(c)} #{n}" for k, c, n in zip(klass, category, number)], dtype=object)
    return {
        "i_item_sk": np.arange(1, rows + 1, dtype=np.int32),
        "i_brand_id": (category * 1_000_000 + klass * 1_000 + number).astype(np.int32),
        "i_brand": brand,
        "i_manufact_id": base.integers(1, 1001, rows).astype(np.int32),
        "i_manager_id": base.integers(1, 101, rows).astype(np.int32),
    }


def host_tables(config: dict, seed: int, rows: int) -> dict:
    base = np.random.default_rng(BASE_STREAM)
    n_dates = int(config["tables"]["date_dim"]["rows"])
    n_items = int(config["tables"]["item"]["rows"])
    dd = date_dim(n_dates)
    it = item(n_items, base)

    sold = (dd["d_date_sk"] >= JULIAN_1900_01_02 + 35794) & (dd["d_date_sk"] <= JULIAN_1900_01_02 + 35794 + 1826)
    weight = np.where(sold, np.select([dd["d_moy"] >= 11, dd["d_moy"] >= 8], [3.0, 2.0], 1.0), 0.0)
    date_sk = base.choice(dd["d_date_sk"], size=rows, p=weight / weight.sum())
    item_sk = base.integers(1, n_items + 1, rows, dtype=np.int32)
    price = base.integers(100, 2_000_001, rows) / 100.0  # decimal(7,2), to the cent
    date_valid = base.random(rows) >= NULL_SHARE
    price_valid = base.random(rows) >= NULL_SHARE

    rng = np.random.default_rng(seed)
    order = rng.permutation(rows)
    order_price = rng.permutation(rows)
    return {
        "date_dim": dd,
        "item": it,
        "store_sales": {
            "ss_sold_date_sk": (date_sk[order], date_valid[order]),
            "ss_item_sk": item_sk[order],
            "ss_ext_sales_price": (price[order_price], price_valid[order_price]),
        },
    }
