"""TPC-DS stepping-stone queries (BASELINE.json configs[2]/[3]): q3
(2-way hash join + sort) and q95 (multi-join with semi-join order
filtering — the exchange-heavy shape). Dimension values that are strings
in the spec are dictionary codes here (int lanes); the relational
algebra — joins, semi-joins, grouped aggregates, order-by — is the part
under test.

Deterministic generators produce a coherent star schema at a row-count
scale: foreign keys reference the generated dimension key ranges.
"""

from __future__ import annotations

from typing import Dict

import jax.numpy as jnp
import numpy as np

from ..columnar import Column, Table
from ..columnar import dtype as dt
from ..ops import bitutils, copying
from ..ops.expressions import col, lit
from ..ops.sort import sort_by_key

__all__ = [
    "gen_store", "gen_store_wide", "gen_web",
    "q3", "q7", "q7_distributed", "q19", "q19_distributed",
    "q42", "q52", "q52_distributed", "q55", "q55_distributed",
    "q94", "q94_distributed", "q95", "q98",
]



def _exact_total(col) -> float:
    """Exact grand total of a FLOAT64-bit column: one-segment windowed
    accumulation (jnp.sum on a float_view would re-round through f32 on
    TPU) + lossless host bit-view readback."""
    from ..ops.f64acc import segment_sum_f64bits

    bits = col.data
    if bits.shape[0] == 0:
        return 0.0
    seg = jnp.zeros((bits.shape[0],), jnp.int32)
    return float(np.asarray(segment_sum_f64bits(bits, seg, 1)).view(np.float64)[0])

def _int_col(arr: np.ndarray, d=dt.INT32) -> Column:
    return Column(d, data=jnp.asarray(arr.astype(np.dtype(jnp.dtype(d.jnp_dtype).name))))


def _f64_col(arr: np.ndarray) -> Column:
    return Column(dt.FLOAT64, data=bitutils.float_store(jnp.asarray(arr), dt.FLOAT64))


def gen_store(num_sales: int, seed: int = 42) -> Dict[str, Table]:
    """store_sales + date_dim + item star for q3."""
    rng = np.random.default_rng(seed)
    n_dates, n_items = 365 * 5, 1000

    date_dim = Table(
        [
            _int_col(np.arange(n_dates)),  # d_date_sk
            _int_col(1998 + np.arange(n_dates) // 365),  # d_year
            _int_col(1 + (np.arange(n_dates) % 365) // 31),  # d_moy (approx calendar)
        ],
        ["d_date_sk", "d_year", "d_moy"],
    )
    item = Table(
        [
            _int_col(np.arange(n_items)),  # i_item_sk
            _int_col(rng.integers(1, 1000, n_items)),  # i_manufact_id
            _int_col(rng.integers(1, 500, n_items)),  # i_brand_id (dict code)
            _int_col(rng.integers(1, 100, n_items)),  # i_manager_id
        ],
        ["i_item_sk", "i_manufact_id", "i_brand_id", "i_manager_id"],
    )
    store_sales = Table(
        [
            _int_col(rng.integers(0, n_dates, num_sales)),  # ss_sold_date_sk
            _int_col(rng.integers(0, n_items, num_sales)),  # ss_item_sk
            _f64_col(rng.uniform(1, 1000, num_sales).round(2)),  # ss_ext_sales_price
        ],
        ["ss_sold_date_sk", "ss_item_sk", "ss_ext_sales_price"],
    )
    # drawn AFTER the fact columns so adding it (round 5, q42) left every
    # pre-existing column's random sequence untouched
    item = Table(
        list(item.columns) + [_int_col(rng.integers(1, 12, n_items))],  # i_category_id
        list(item.names) + ["i_category_id"],
    )
    return {"store_sales": store_sales, "date_dim": date_dim, "item": item}


def gen_store_wide(num_sales: int, seed: int = 42) -> Dict[str, Table]:
    """Full store-sales star for the q7/q19 class: fact + date_dim +
    item + customer_demographics + promotion + customer +
    customer_address + store. String dimension values (gender, zip
    prefixes, channel flags) are dictionary codes in int lanes, as
    everywhere in this tier."""
    rng = np.random.default_rng(seed)
    n_dates, n_items = 365 * 5, 1000
    n_cdemo, n_promo, n_cust, n_addr, n_store = 200, 50, 2000, 500, 20

    date_dim = Table(
        [
            _int_col(np.arange(n_dates)),  # d_date_sk
            _int_col(1998 + np.arange(n_dates) // 365),  # d_year
            _int_col(1 + (np.arange(n_dates) % 365) // 31),  # d_moy
        ],
        ["d_date_sk", "d_year", "d_moy"],
    )
    item = Table(
        [
            _int_col(np.arange(n_items)),  # i_item_sk
            _int_col(rng.permutation(n_items)),  # i_item_id (distinct code)
            _int_col(rng.integers(1, 500, n_items)),  # i_brand_id
            _int_col(rng.integers(1, 1000, n_items)),  # i_manufact_id
            _int_col(rng.integers(1, 100, n_items)),  # i_manager_id
        ],
        ["i_item_sk", "i_item_id", "i_brand_id", "i_manufact_id", "i_manager_id"],
    )
    customer_demographics = Table(
        [
            _int_col(np.arange(n_cdemo)),  # cd_demo_sk
            _int_col(rng.integers(0, 2, n_cdemo)),  # cd_gender (code: 1 = 'M')
            _int_col(rng.integers(0, 5, n_cdemo)),  # cd_marital_status (2 = 'S')
            _int_col(rng.integers(0, 7, n_cdemo)),  # cd_education_status (3 = College)
        ],
        ["cd_demo_sk", "cd_gender", "cd_marital_status", "cd_education_status"],
    )
    promotion = Table(
        [
            _int_col(np.arange(n_promo)),  # p_promo_sk
            _int_col(rng.integers(0, 2, n_promo)),  # p_channel_email (0 = 'N')
            _int_col(rng.integers(0, 2, n_promo)),  # p_channel_event (0 = 'N')
        ],
        ["p_promo_sk", "p_channel_email", "p_channel_event"],
    )
    customer = Table(
        [
            _int_col(np.arange(n_cust)),  # c_customer_sk
            _int_col(rng.integers(0, n_addr, n_cust)),  # c_current_addr_sk
        ],
        ["c_customer_sk", "c_current_addr_sk"],
    )
    customer_address = Table(
        [
            _int_col(np.arange(n_addr)),  # ca_address_sk
            _int_col(rng.integers(0, 300, n_addr)),  # ca_zip5 (5-digit prefix code)
        ],
        ["ca_address_sk", "ca_zip5"],
    )
    store = Table(
        [
            _int_col(np.arange(n_store)),  # s_store_sk
            _int_col(rng.integers(0, 300, n_store)),  # s_zip5
        ],
        ["s_store_sk", "s_zip5"],
    )
    store_sales = Table(
        [
            _int_col(rng.integers(0, n_dates, num_sales)),  # ss_sold_date_sk
            _int_col(rng.integers(0, n_items, num_sales)),  # ss_item_sk
            _int_col(rng.integers(0, n_cdemo, num_sales)),  # ss_cdemo_sk
            _int_col(rng.integers(0, n_promo, num_sales)),  # ss_promo_sk
            _int_col(rng.integers(0, n_cust, num_sales)),  # ss_customer_sk
            _int_col(rng.integers(0, n_store, num_sales)),  # ss_store_sk
            _int_col(rng.integers(1, 100, num_sales)),  # ss_quantity
            _f64_col(rng.uniform(1, 200, num_sales).round(2)),  # ss_list_price
            _f64_col(rng.uniform(0, 50, num_sales).round(2)),  # ss_coupon_amt
            _f64_col(rng.uniform(1, 150, num_sales).round(2)),  # ss_sales_price
            _f64_col(rng.uniform(1, 1000, num_sales).round(2)),  # ss_ext_sales_price
        ],
        [
            "ss_sold_date_sk", "ss_item_sk", "ss_cdemo_sk", "ss_promo_sk",
            "ss_customer_sk", "ss_store_sk", "ss_quantity", "ss_list_price",
            "ss_coupon_amt", "ss_sales_price", "ss_ext_sales_price",
        ],
    )
    # srjt-plan (ISSUE 14) star extensions — every new random column /
    # table is drawn AFTER all pre-existing draws (the q42 pattern
    # above), so the original columns' random sequences are untouched
    # and the earlier oracle tests stay bit-identical.
    n_hdemo, n_times = 100, 1440
    store = Table(
        list(store.columns) + [_int_col(rng.integers(0, 10, n_store))],  # s_state (code)
        list(store.names) + ["s_state"],
    )
    store_sales = Table(
        list(store_sales.columns) + [
            _int_col(rng.integers(0, max(num_sales // 8, 1), num_sales)),  # ss_ticket_number
            _int_col(rng.integers(0, n_hdemo, num_sales)),  # ss_hdemo_sk
            _int_col(rng.integers(0, n_times, num_sales)),  # ss_sold_time_sk
        ],
        list(store_sales.names) + ["ss_ticket_number", "ss_hdemo_sk", "ss_sold_time_sk"],
    )
    customer = Table(
        list(customer.columns) + [_int_col(rng.permutation(n_cust))],  # c_customer_id
        list(customer.names) + ["c_customer_id"],
    )
    household_demographics = Table(
        [
            _int_col(np.arange(n_hdemo)),  # hd_demo_sk
            _int_col(rng.integers(0, 10, n_hdemo)),  # hd_dep_count
            _int_col(rng.integers(0, 5, n_hdemo)),  # hd_vehicle_count
            _int_col(rng.integers(0, 6, n_hdemo)),  # hd_buy_potential (code)
        ],
        ["hd_demo_sk", "hd_dep_count", "hd_vehicle_count", "hd_buy_potential"],
    )
    time_dim = Table(  # one row per minute (deterministic, no rng cost)
        [
            _int_col(np.arange(n_times)),  # t_time_sk
            _int_col(np.arange(n_times) // 60),  # t_hour
            _int_col(np.arange(n_times) % 60),  # t_minute
        ],
        ["t_time_sk", "t_hour", "t_minute"],
    )
    date_dim = Table(  # derived day-of-week lane (deterministic)
        list(date_dim.columns) + [_int_col(np.arange(n_dates) % 7)],
        list(date_dim.names) + ["d_dow"],
    )
    return {
        "store_sales": store_sales,
        "date_dim": date_dim,
        "item": item,
        "customer_demographics": customer_demographics,
        "promotion": promotion,
        "customer": customer,
        "customer_address": customer_address,
        "store": store,
        "household_demographics": household_demographics,
        "time_dim": time_dim,
    }


def q3(tables: Dict[str, Table], manufact_id: int = 128, month: int = 11) -> Table:
    """SELECT d_year, i_brand_id, sum(ss_ext_sales_price) sum_agg
    FROM date_dim, store_sales, item
    WHERE d_date_sk = ss_sold_date_sk AND ss_item_sk = i_item_sk
      AND i_manufact_id = :m AND d_moy = :mo
    GROUP BY d_year, i_brand_id
    ORDER BY d_year, sum_agg DESC, i_brand_id
    """
    item = tables["item"]
    dates = tables["date_dim"]
    ss = tables["store_sales"]

    # the WHOLE stage — star joins (with build-side dim filters), group
    # keys, aggregate — lowers through ONE compiled program; the bounded
    # domains come from the DIMENSION tables (tiny, so the host sync is
    # cheap) — not hard-coded, so any caller-supplied star schema works
    year_lo = int(jnp.min(dates.column("d_year").data))
    year_hi = int(jnp.max(dates.column("d_year").data))
    n_brands = int(jnp.max(item.column("i_brand_id").data)) + 1
    n_dates = int(jnp.max(dates.column("d_date_sk").data)) + 1
    n_items = int(jnp.max(item.column("i_item_sk").data)) + 1
    agg = _q3_pipeline(
        year_lo, year_hi - year_lo + 1, n_brands, n_dates, n_items,
        int(manufact_id), int(month),
    )(ss, {"date_dim": dates, "item": item})
    agg = Table(
        [
            Column(dt.INT32, data=agg.column("year_idx").data + jnp.int32(year_lo)),
            agg.column("i_brand_id"),
            agg.column("ss_ext_sales_price_sum"),
        ],
        ["d_year", "i_brand_id", "ss_ext_sales_price_sum"],
    )
    # ORDER BY d_year asc, sum desc, brand asc
    order_keys = Table(
        [agg.column("d_year"), agg.column("ss_ext_sales_price_sum"), agg.column("i_brand_id")],
        ["d_year", "s", "b"],
    )
    return sort_by_key(agg, order_keys, ascending=[True, False, True])


import functools


@functools.lru_cache(maxsize=16)
def _q3_pipeline(year_lo: int, n_years: int, n_brands: int, n_dates: int, n_items: int,
                 manufact_id: int, month: int):
    from ..pipeline import Agg, GroupKey, JoinSpec, PlanSpec, compile_plan

    return compile_plan(
        PlanSpec(
            joins=(
                JoinSpec(
                    build="date_dim", probe_key="ss_sold_date_sk", build_key="d_date_sk",
                    num_keys=n_dates, payload=("d_year",),
                    build_filter=col("d_moy") == lit(np.int32(month)),
                ),
                JoinSpec(
                    build="item", probe_key="ss_item_sk", build_key="i_item_sk",
                    num_keys=n_items, payload=("i_brand_id",),
                    build_filter=col("i_manufact_id") == lit(np.int32(manufact_id)),
                ),
            ),
            project=(("year_idx", col("d_year") - lit(np.int32(year_lo))),),
            group_by=(GroupKey("year_idx", n_years), GroupKey("i_brand_id", n_brands)),
            aggregates=(Agg("ss_ext_sales_price", "sum", "ss_ext_sales_price_sum"),),
        )
    )




def q7(
    tables: Dict[str, Table],
    gender: int = 1,
    marital: int = 2,
    education: int = 3,
    year: int = 2000,
) -> Table:
    """TPC-DS q7 — the 4-way star join with FLOAT64 AVG aggregates. SQL:

        SELECT i_item_id, avg(ss_quantity) agg1, avg(ss_list_price) agg2,
               avg(ss_coupon_amt) agg3, avg(ss_sales_price) agg4
        FROM store_sales, customer_demographics, date_dim, item, promotion
        WHERE ss_sold_date_sk = d_date_sk AND ss_item_sk = i_item_sk
          AND ss_cdemo_sk = cd_demo_sk AND ss_promo_sk = p_promo_sk
          AND cd_gender = :g AND cd_marital_status = :m
          AND cd_education_status = :e
          AND (p_channel_email = 'N' OR p_channel_event = 'N')
          AND d_year = :y
        GROUP BY i_item_id ORDER BY i_item_id

    All four dimension joins, the demographic/promotion/date filters,
    and the four EXACT means (integer mean via the limb divider, f64
    means via the windowed accumulator) lower through ONE compiled
    program."""
    item = tables["item"]
    n_item_ids = int(jnp.max(item.column("i_item_id").data)) + 1
    n_dates = int(jnp.max(tables["date_dim"].column("d_date_sk").data)) + 1
    n_items = int(jnp.max(item.column("i_item_sk").data)) + 1
    n_cdemo = int(jnp.max(tables["customer_demographics"].column("cd_demo_sk").data)) + 1
    n_promo = int(jnp.max(tables["promotion"].column("p_promo_sk").data)) + 1
    agg = _q7_pipeline(
        n_item_ids, n_dates, n_items, n_cdemo, n_promo,
        int(gender), int(marital), int(education), int(year),
    )(
        tables["store_sales"],
        {
            "date_dim": tables["date_dim"],
            "item": item,
            "customer_demographics": tables["customer_demographics"],
            "promotion": tables["promotion"],
        },
    )
    return sort_by_key(agg, agg.select(["i_item_id"]), ascending=[True])


@functools.lru_cache(maxsize=16)
def _q7_pipeline(n_item_ids: int, n_dates: int, n_items: int, n_cdemo: int,
                 n_promo: int, gender: int, marital: int, education: int, year: int):
    from ..pipeline import Agg, GroupKey, JoinSpec, PlanSpec, compile_plan

    return compile_plan(
        PlanSpec(
            joins=(
                JoinSpec(
                    build="date_dim", probe_key="ss_sold_date_sk", build_key="d_date_sk",
                    num_keys=n_dates,
                    build_filter=col("d_year") == lit(np.int32(year)),
                ),
                JoinSpec(
                    build="customer_demographics", probe_key="ss_cdemo_sk",
                    build_key="cd_demo_sk", num_keys=n_cdemo,
                    build_filter=(col("cd_gender") == lit(np.int32(gender)))
                    & (col("cd_marital_status") == lit(np.int32(marital)))
                    & (col("cd_education_status") == lit(np.int32(education))),
                ),
                JoinSpec(
                    build="promotion", probe_key="ss_promo_sk", build_key="p_promo_sk",
                    num_keys=n_promo,
                    build_filter=(col("p_channel_email") == lit(np.int32(0)))
                    | (col("p_channel_event") == lit(np.int32(0))),
                ),
                JoinSpec(
                    build="item", probe_key="ss_item_sk", build_key="i_item_sk",
                    num_keys=n_items, payload=("i_item_id",),
                ),
            ),
            group_by=(GroupKey("i_item_id", n_item_ids),),
            aggregates=(
                Agg("ss_quantity", "mean", "agg1"),
                Agg("ss_list_price", "mean", "agg2"),
                Agg("ss_coupon_amt", "mean", "agg3"),
                Agg("ss_sales_price", "mean", "agg4"),
            ),
        )
    )


def q7_distributed(
    tables: Dict[str, Table], mesh,
    gender: int = 1, marital: int = 2, education: int = 3, year: int = 2000,
) -> Table:
    """q7 on the distributed Table operators: pre-filtered dims join the
    sharded fact, then a distributed group-by with EXACT means (partial
    limb sums + counts merge across shards, one division at the end) —
    results must be BIT-identical to single-chip ``q7``."""
    from ..parallel.table_ops import distributed_groupby_table, distributed_join_table

    ss = tables["store_sales"]
    dsel = (col("d_year") == lit(np.int32(year))).evaluate(tables["date_dim"])
    d1 = copying.apply_boolean_mask(tables["date_dim"], dsel).select(["d_date_sk"])
    d1 = Table(d1.columns, ["ss_sold_date_sk"])
    cd = tables["customer_demographics"]
    csel = (
        (col("cd_gender") == lit(np.int32(gender)))
        & (col("cd_marital_status") == lit(np.int32(marital)))
        & (col("cd_education_status") == lit(np.int32(education)))
    ).evaluate(cd)
    c1 = copying.apply_boolean_mask(cd, csel).select(["cd_demo_sk"])
    c1 = Table(c1.columns, ["ss_cdemo_sk"])
    pr = tables["promotion"]
    psel = (
        (col("p_channel_email") == lit(np.int32(0)))
        | (col("p_channel_event") == lit(np.int32(0)))
    ).evaluate(pr)
    p1 = copying.apply_boolean_mask(pr, psel).select(["p_promo_sk"])
    p1 = Table(p1.columns, ["ss_promo_sk"])
    i1 = tables["item"].select(["i_item_sk", "i_item_id"])
    i1 = Table(i1.columns, ["ss_item_sk", "i_item_id"])

    j, o1 = distributed_join_table(ss, d1, on=["ss_sold_date_sk"], mesh=mesh, how="inner")
    j, o2 = distributed_join_table(j, c1, on=["ss_cdemo_sk"], mesh=mesh, how="inner")
    j, o3 = distributed_join_table(j, p1, on=["ss_promo_sk"], mesh=mesh, how="inner")
    j, o4 = distributed_join_table(j, i1, on=["ss_item_sk"], mesh=mesh, how="inner")
    if o1 or o2 or o3 or o4:
        raise RuntimeError("join capacity overflow — raise capacity")
    agg, o5 = distributed_groupby_table(
        j, ["i_item_id"],
        [
            ("ss_quantity", "mean", "agg1"),
            ("ss_list_price", "mean", "agg2"),
            ("ss_coupon_amt", "mean", "agg3"),
            ("ss_sales_price", "mean", "agg4"),
        ],
        mesh,
    )
    if o5:
        raise RuntimeError("groupby capacity overflow — raise group_capacity")
    return sort_by_key(agg, agg.select(["i_item_id"]), ascending=[True])


def q19(
    tables: Dict[str, Table], manager_id: int = 8, month: int = 11, year: int = 1998
) -> Table:
    """TPC-DS q19 — 5-way star join with a CROSS-DIMENSION inequality
    (customer zip != store zip) evaluated on joined payload columns. SQL:

        SELECT i_brand_id, i_manufact_id, sum(ss_ext_sales_price) ext_price
        FROM date_dim, store_sales, item, customer, customer_address, store
        WHERE d_date_sk = ss_sold_date_sk AND ss_item_sk = i_item_sk
          AND i_manager_id = :mgr AND d_moy = :moy AND d_year = :yr
          AND ss_customer_sk = c_customer_sk
          AND c_current_addr_sk = ca_address_sk
          AND substr(ca_zip,1,5) <> substr(s_zip,1,5)
          AND ss_store_sk = s_store_sk
        GROUP BY i_brand_id, i_manufact_id
        ORDER BY ext_price DESC, i_brand_id, i_manufact_id

    The customer join's payload (c_current_addr_sk) becomes the NEXT
    join's probe key — chained payload-probe joins in one program — and
    the zip comparison runs as the plan filter over two payloads."""
    item = tables["item"]
    n_brands = int(jnp.max(item.column("i_brand_id").data)) + 1
    n_manufact = int(jnp.max(item.column("i_manufact_id").data)) + 1
    n_dates = int(jnp.max(tables["date_dim"].column("d_date_sk").data)) + 1
    n_items = int(jnp.max(item.column("i_item_sk").data)) + 1
    n_cust = int(jnp.max(tables["customer"].column("c_customer_sk").data)) + 1
    n_addr = int(jnp.max(tables["customer_address"].column("ca_address_sk").data)) + 1
    n_store = int(jnp.max(tables["store"].column("s_store_sk").data)) + 1
    agg = _q19_pipeline(
        n_brands, n_manufact, n_dates, n_items, n_cust, n_addr, n_store,
        int(manager_id), int(month), int(year),
    )(
        tables["store_sales"],
        {
            "date_dim": tables["date_dim"],
            "item": item,
            "customer": tables["customer"],
            "customer_address": tables["customer_address"],
            "store": tables["store"],
        },
    )
    order_keys = Table(
        [agg.column("ext_price"), agg.column("i_brand_id"), agg.column("i_manufact_id")],
        ["p", "b", "m"],
    )
    return sort_by_key(agg, order_keys, ascending=[False, True, True])


@functools.lru_cache(maxsize=16)
def _q19_pipeline(n_brands: int, n_manufact: int, n_dates: int, n_items: int,
                  n_cust: int, n_addr: int, n_store: int, manager_id: int,
                  month: int, year: int):
    from ..pipeline import Agg, GroupKey, JoinSpec, PlanSpec, compile_plan

    return compile_plan(
        PlanSpec(
            joins=(
                JoinSpec(
                    build="date_dim", probe_key="ss_sold_date_sk", build_key="d_date_sk",
                    num_keys=n_dates,
                    build_filter=(col("d_moy") == lit(np.int32(month)))
                    & (col("d_year") == lit(np.int32(year))),
                ),
                JoinSpec(
                    build="item", probe_key="ss_item_sk", build_key="i_item_sk",
                    num_keys=n_items, payload=("i_brand_id", "i_manufact_id"),
                    build_filter=col("i_manager_id") == lit(np.int32(manager_id)),
                ),
                JoinSpec(
                    build="customer", probe_key="ss_customer_sk",
                    build_key="c_customer_sk", num_keys=n_cust,
                    payload=("c_current_addr_sk",),
                ),
                JoinSpec(
                    # probe key is the PREVIOUS join's payload
                    build="customer_address", probe_key="c_current_addr_sk",
                    build_key="ca_address_sk", num_keys=n_addr, payload=("ca_zip5",),
                ),
                JoinSpec(
                    build="store", probe_key="ss_store_sk", build_key="s_store_sk",
                    num_keys=n_store, payload=("s_zip5",),
                ),
            ),
            filter=col("ca_zip5") != col("s_zip5"),
            group_by=(
                GroupKey("i_brand_id", n_brands),
                GroupKey("i_manufact_id", n_manufact),
            ),
            aggregates=(Agg("ss_ext_sales_price", "sum", "ext_price"),),
        )
    )


def q19_distributed(
    tables: Dict[str, Table], mesh,
    manager_id: int = 8, month: int = 11, year: int = 1998,
) -> Table:
    """q19 on the distributed Table operators; the zip inequality runs
    shard-local after the address/store payloads arrive. Results must be
    BIT-identical to single-chip ``q19``."""
    from ..parallel.table_ops import distributed_groupby_table, distributed_join_table

    ss = tables["store_sales"]
    dsel = (
        (col("d_moy") == lit(np.int32(month))) & (col("d_year") == lit(np.int32(year)))
    ).evaluate(tables["date_dim"])
    d1 = copying.apply_boolean_mask(tables["date_dim"], dsel).select(["d_date_sk"])
    d1 = Table(d1.columns, ["ss_sold_date_sk"])
    isel = (col("i_manager_id") == lit(np.int32(manager_id))).evaluate(tables["item"])
    i1 = copying.apply_boolean_mask(tables["item"], isel).select(
        ["i_item_sk", "i_brand_id", "i_manufact_id"]
    )
    i1 = Table(i1.columns, ["ss_item_sk", "i_brand_id", "i_manufact_id"])
    c1 = tables["customer"].select(["c_customer_sk", "c_current_addr_sk"])
    c1 = Table(c1.columns, ["ss_customer_sk", "c_current_addr_sk"])
    a1 = tables["customer_address"].select(["ca_address_sk", "ca_zip5"])
    a1 = Table(a1.columns, ["c_current_addr_sk", "ca_zip5"])
    s1 = tables["store"].select(["s_store_sk", "s_zip5"])
    s1 = Table(s1.columns, ["ss_store_sk", "s_zip5"])

    j, o1 = distributed_join_table(ss, d1, on=["ss_sold_date_sk"], mesh=mesh, how="inner")
    j, o2 = distributed_join_table(j, i1, on=["ss_item_sk"], mesh=mesh, how="inner")
    j, o3 = distributed_join_table(j, c1, on=["ss_customer_sk"], mesh=mesh, how="inner")
    j, o4 = distributed_join_table(j, a1, on=["c_current_addr_sk"], mesh=mesh, how="inner")
    j, o5 = distributed_join_table(j, s1, on=["ss_store_sk"], mesh=mesh, how="inner")
    if o1 or o2 or o3 or o4 or o5:
        raise RuntimeError("join capacity overflow — raise capacity")
    keep = (col("ca_zip5") != col("s_zip5")).evaluate(j)
    j = copying.apply_boolean_mask(j, keep)
    agg, o6 = distributed_groupby_table(
        j, ["i_brand_id", "i_manufact_id"],
        [("ss_ext_sales_price", "sum", "ext_price")], mesh,
    )
    if o6:
        raise RuntimeError("groupby capacity overflow — raise group_capacity")
    order_keys = Table(
        [agg.column("ext_price"), agg.column("i_brand_id"), agg.column("i_manufact_id")],
        ["p", "b", "m"],
    )
    return sort_by_key(agg, order_keys, ascending=[False, True, True])



def _attach_year_and_sort(agg: Table, year: int, key_col: str, order_cols, ascending) -> Table:
    """Shared epilogue of the q42/q52 reporting family: re-attach the
    constant d_year the year-filter consumed, then ORDER BY. One
    definition so the single-chip and distributed variants cannot
    drift apart (their bit-identity contract)."""
    agg = Table(
        [
            Column(dt.INT32, data=jnp.full((agg.num_rows,), year, jnp.int32)),
            agg.column(key_col),
            agg.column("ext_price"),
        ],
        ["d_year", key_col, "ext_price"],
    )
    order_keys = Table(
        [agg.column(c) for c in order_cols], [f"k{i}" for i in range(len(order_cols))]
    )
    return sort_by_key(agg, order_keys, ascending=list(ascending))


def q42(tables: Dict[str, Table], manager_id: int = 1, month: int = 11, year: int = 2000) -> Table:
    """TPC-DS q42 (category revenue for a manager-month): the q3 shape
    grouped by (d_year, i_category_id). SQL:

        SELECT d_year, i_category_id, sum(ss_ext_sales_price)
        FROM date_dim, store_sales, item
        WHERE d_date_sk = ss_sold_date_sk AND ss_item_sk = i_item_sk
          AND i_manager_id = :mgr AND d_moy = :moy AND d_year = :yr
        GROUP BY d_year, i_category_id
        ORDER BY sum DESC, d_year, i_category_id
    """
    item = tables["item"]
    dates = tables["date_dim"]
    n_cats = int(jnp.max(item.column("i_category_id").data)) + 1
    agg = _q42_pipeline(n_cats, int(manager_id), int(month), int(year))(
        tables["store_sales"], {"date_dim": dates, "item": item}
    )
    return _attach_year_and_sort(
        agg, year, "i_category_id",
        ["ext_price", "d_year", "i_category_id"], [False, True, True],
    )


@functools.lru_cache(maxsize=16)
def _q42_pipeline(n_cats: int, manager_id: int, month: int, year: int):
    from ..pipeline import Agg, GroupKey, JoinSpec, PlanSpec, compile_plan

    return compile_plan(
        PlanSpec(
            joins=(
                JoinSpec(
                    build="date_dim", probe_key="ss_sold_date_sk",
                    build_key="d_date_sk", num_keys=None,  # sort-merge
                    build_filter=(col("d_moy") == lit(month)) & (col("d_year") == lit(year)),
                ),
                JoinSpec(
                    build="item", probe_key="ss_item_sk",
                    build_key="i_item_sk", num_keys=None,  # sort-merge
                    payload=("i_category_id",),
                    build_filter=col("i_manager_id") == lit(manager_id),
                ),
            ),
            group_by=(GroupKey("i_category_id", n_cats),),
            aggregates=(Agg("ss_ext_sales_price", "sum", "ext_price"),),
        )
    )


def q52(tables: Dict[str, Table], manager_id: int = 1, month: int = 11, year: int = 2000) -> Table:
    """TPC-DS q52 (brand revenue for a manager-month; q55's plan carrying
    d_year through). SQL:

        SELECT d_year, i_brand_id, sum(ss_ext_sales_price) ext_price
        FROM date_dim, store_sales, item
        WHERE d_date_sk = ss_sold_date_sk AND ss_item_sk = i_item_sk
          AND i_manager_id = :mgr AND d_moy = :moy AND d_year = :yr
        GROUP BY d_year, i_brand_id ORDER BY d_year, ext_price DESC, i_brand_id
    """
    item = tables["item"]
    n_brands = int(jnp.max(item.column("i_brand_id").data)) + 1
    agg = _q55_pipeline(n_brands, int(manager_id), int(month), int(year))(
        tables["store_sales"], {"date_dim": tables["date_dim"], "item": item}
    )
    return _attach_year_and_sort(
        agg, year, "i_brand_id", ["d_year", "ext_price", "i_brand_id"], [True, False, True]
    )


def q52_distributed(
    tables: Dict[str, Table], mesh, manager_id: int = 1, month: int = 11, year: int = 2000
) -> Table:
    """q52 on the distributed Table operators (q55's exchange plan with
    the year column re-attached). BIT-identical to single-chip q52."""
    agg = q55_distributed(tables, mesh, manager_id=manager_id, month=month, year=year)
    return _attach_year_and_sort(
        agg, year, "i_brand_id", ["d_year", "ext_price", "i_brand_id"], [True, False, True]
    )


def q55(tables: Dict[str, Table], manager_id: int = 28, month: int = 11, year: int = 1999) -> Table:
    """TPC-DS q55 (brand revenue for one manager-month). SQL:

        SELECT i_brand_id, sum(ss_ext_sales_price) ext_price
        FROM date_dim, store_sales, item
        WHERE d_date_sk = ss_sold_date_sk AND ss_item_sk = i_item_sk
          AND i_manager_id = :mgr AND d_moy = :moy AND d_year = :yr
        GROUP BY i_brand_id ORDER BY ext_price DESC, i_brand_id

    Exercises the SORT-MERGE JoinSpec lowering (num_keys=None): both
    star joins binary-search sorted build keys inside the one compiled
    program — no bounded-domain declaration anywhere, matching cudf's
    general hash join (SURVEY §2.8)."""
    item = tables["item"]
    dates = tables["date_dim"]
    ss = tables["store_sales"]
    n_brands = int(jnp.max(item.column("i_brand_id").data)) + 1
    agg = _q55_pipeline(n_brands, int(manager_id), int(month), int(year))(
        ss, {"date_dim": dates, "item": item}
    )
    order_keys = Table(
        [agg.column("ext_price"), agg.column("i_brand_id")], ["p", "b"]
    )
    return sort_by_key(agg, order_keys, ascending=[False, True])


@functools.lru_cache(maxsize=16)
def _q55_pipeline(n_brands: int, manager_id: int, month: int, year: int):
    from ..pipeline import Agg, GroupKey, JoinSpec, PlanSpec, compile_plan

    return compile_plan(
        PlanSpec(
            joins=(
                JoinSpec(
                    build="date_dim", probe_key="ss_sold_date_sk",
                    build_key="d_date_sk", num_keys=None,  # sort-merge
                    build_filter=(col("d_moy") == lit(month)) & (col("d_year") == lit(year)),
                ),
                JoinSpec(
                    build="item", probe_key="ss_item_sk",
                    build_key="i_item_sk", num_keys=None,  # sort-merge
                    payload=("i_brand_id",),
                    build_filter=col("i_manager_id") == lit(manager_id),
                ),
            ),
            group_by=(GroupKey("i_brand_id", n_brands),),
            aggregates=(Agg("ss_ext_sales_price", "sum", "ext_price"),),
        )
    )


def q55_distributed(tables: Dict[str, Table], mesh, manager_id: int = 28, month: int = 11, year: int = 1999) -> Table:
    """q55 on the Table-level distributed operators: filtered dim tables
    inner-join the fact across the mesh, then a distributed group-by.
    Must produce results identical to single-chip ``q55``."""
    from ..parallel.table_ops import distributed_groupby_table, distributed_join_table

    item = tables["item"]
    dates = tables["date_dim"]
    ss = tables["store_sales"]

    dsel = ((col("d_moy") == lit(month)) & (col("d_year") == lit(year))).evaluate(dates)
    d1 = copying.apply_boolean_mask(dates, dsel).select(["d_date_sk"])
    d1 = Table(d1.columns, ["ss_sold_date_sk"])
    isel = (col("i_manager_id") == lit(manager_id)).evaluate(item)
    i1 = copying.apply_boolean_mask(item, isel).select(["i_item_sk", "i_brand_id"])
    i1 = Table(i1.columns, ["ss_item_sk", "i_brand_id"])

    j1, o1 = distributed_join_table(ss, d1, on=["ss_sold_date_sk"], mesh=mesh, how="inner")
    j2, o2 = distributed_join_table(j1, i1, on=["ss_item_sk"], mesh=mesh, how="inner")
    if o1 or o2:
        raise RuntimeError("join capacity overflow — raise capacity")
    agg, o3 = distributed_groupby_table(
        j2, ["i_brand_id"], [("ss_ext_sales_price", "sum", "ext_price")], mesh
    )
    if o3:
        raise RuntimeError("groupby capacity overflow — raise group_capacity")
    order_keys = Table([agg.column("ext_price"), agg.column("i_brand_id")], ["p", "b"])
    return sort_by_key(agg, order_keys, ascending=[False, True])

def gen_web(num_sales: int, seed: int = 7) -> Dict[str, Table]:
    """web_sales + web_returns + date_dim for q95. Orders have 1-4 line
    items; some span multiple warehouses; some are returned."""
    rng = np.random.default_rng(seed)
    n_orders = max(num_sales // 2, 1)
    n_dates = 365 * 5

    order_of_row = rng.integers(0, n_orders, num_sales)
    web_sales = Table(
        [
            _int_col(order_of_row),  # ws_order_number
            _int_col(rng.integers(0, 15, num_sales)),  # ws_warehouse_sk
            _int_col(rng.integers(0, n_dates, num_sales)),  # ws_ship_date_sk
            _f64_col(rng.uniform(1, 100, num_sales).round(2)),  # ws_ext_ship_cost
            _f64_col(rng.uniform(-50, 200, num_sales).round(2)),  # ws_net_profit
        ],
        ["ws_order_number", "ws_warehouse_sk", "ws_ship_date_sk", "ws_ext_ship_cost", "ws_net_profit"],
    )
    returned = rng.choice(n_orders, size=max(n_orders // 10, 1), replace=False)
    web_returns = Table([_int_col(returned)], ["wr_order_number"])
    date_dim = Table([_int_col(np.arange(n_dates))], ["d_date_sk"])
    # srjt-plan (ISSUE 14) extensions for the q92 family — drawn AFTER
    # every pre-existing column, keeping the q94/q95 sequences intact
    n_items = 200
    web_sales = Table(
        list(web_sales.columns) + [
            _int_col(rng.integers(0, n_dates, num_sales)),  # ws_sold_date_sk
            _int_col(rng.integers(0, n_items, num_sales)),  # ws_item_sk
            _f64_col(rng.uniform(0, 100, num_sales).round(2)),  # ws_ext_discount_amt
        ],
        list(web_sales.names) + ["ws_sold_date_sk", "ws_item_sk", "ws_ext_discount_amt"],
    )
    item = Table(
        [
            _int_col(np.arange(n_items)),  # i_item_sk
            _int_col(rng.integers(1, 100, n_items)),  # i_manufact_id
        ],
        ["i_item_sk", "i_manufact_id"],
    )
    return {"web_sales": web_sales, "web_returns": web_returns,
            "date_dim": date_dim, "item": item}


def q98(tables: Dict[str, Table], month: int = 11, year: int = 2000) -> Table:
    """TPC-DS q98 shape — the WINDOW-RATIO reporting family (q12/q20/
    q98): item revenue with each item's share of its CLASS partition.
    SQL shape:

        SELECT i_category, i_class(-> brand here), sum(ss_ext_sales_price) itemrevenue,
               sum(ss_ext_sales_price) * 100 /
                 sum(sum(ss_ext_sales_price)) OVER (PARTITION BY i_category) revenueratio
        FROM store_sales, item, date_dim
        WHERE ss_item_sk = i_item_sk AND ss_sold_date_sk = d_date_sk
          AND d_moy = :moy AND d_year = :yr
        GROUP BY i_category, i_class ORDER BY i_category, revenueratio

    Exercises the round-5 window tier (ops/window.window_aggregate)
    composed AFTER a compiled star-join aggregation: the partitioned
    sum runs the exact f64 accumulator, so the ratio's numerator and
    denominator are both correctly rounded."""
    from ..ops.window import window_aggregate

    item = tables["item"]
    n_cats = int(jnp.max(item.column("i_category_id").data)) + 1
    n_brands = int(jnp.max(item.column("i_brand_id").data)) + 1
    agg = _q98_pipeline(n_cats, n_brands, int(month), int(year))(
        tables["store_sales"], {"date_dim": tables["date_dim"], "item": item}
    )
    w = window_aggregate(
        agg, ["i_category_id"], [], [("itemrevenue", "sum", "cat_total")]
    )
    ratio = (
        (col("itemrevenue") * lit(100.0)) / col("cat_total")
    ).evaluate(w)
    out = Table(
        [
            w.column("i_category_id"),
            w.column("i_brand_id"),
            w.column("itemrevenue"),
            ratio,
        ],
        ["i_category_id", "i_brand_id", "itemrevenue", "revenueratio"],
    )
    order_keys = Table(
        [out.column("i_category_id"), out.column("revenueratio"), out.column("i_brand_id")],
        ["c", "r", "b"],
    )
    return sort_by_key(out, order_keys, ascending=[True, True, True])


@functools.lru_cache(maxsize=16)
def _q98_pipeline(n_cats: int, n_brands: int, month: int, year: int):
    from ..pipeline import Agg, GroupKey, JoinSpec, PlanSpec, compile_plan

    return compile_plan(
        PlanSpec(
            joins=(
                JoinSpec(
                    build="date_dim", probe_key="ss_sold_date_sk",
                    build_key="d_date_sk", num_keys=None,
                    build_filter=(col("d_moy") == lit(month)) & (col("d_year") == lit(year)),
                ),
                JoinSpec(
                    build="item", probe_key="ss_item_sk",
                    build_key="i_item_sk", num_keys=None,
                    payload=("i_category_id", "i_brand_id"),
                ),
            ),
            group_by=(
                GroupKey("i_category_id", n_cats),
                GroupKey("i_brand_id", n_brands),
            ),
            aggregates=(Agg("ss_ext_sales_price", "sum", "itemrevenue"),),
        )
    )


def _q95_family(tables: Dict[str, Table], returns_how: str, ship_lo: int, ship_hi: int, mesh=None) -> dict:
    """TPC-DS q95 (EXISTS returns) and q94 (NOT EXISTS returns) as ONE
    plan, so the four entry points cannot drift: per-order multi-warehouse
    detection, ship-date filter, semi-join on the multi-warehouse set, then
    a semi (q95) or anti (q94) join on returned orders, per-order sums,
    exact totals. ``mesh=None`` compiles it for one chip; over a mesh both
    fact tables are row-sharded, ``web_sales`` is shuffled once on the
    order number for the warehouse group-by, the filtered line items and
    ``web_returns`` once each to meet it (``plan.insert_exchanges``), and
    the two membership joins and the per-order aggregate run where the
    rows lie (results must be identical — the distributed tests pin it)."""
    from .. import plan as P

    sharded = ("web_sales", "web_returns")
    ws_wh = P.Aggregate(P.Scan("web_sales", columns=("ws_order_number", "ws_warehouse_sk")),
                        keys=("ws_order_number",),
                        aggs=(P.AggSpec("ws_warehouse_sk", "min", "wh_lo"),
                              P.AggSpec("ws_warehouse_sk", "max", "wh_hi")))
    ws_wh = P.Project(P.Filter(ws_wh, P.pcol("wh_lo") != P.pcol("wh_hi")),
                      (("ws_order_number", P.pcol("ws_order_number")),))
    ws1 = P.Filter(P.Scan("web_sales", columns=("ws_order_number", "ws_ship_date_sk",
                                                "ws_ext_ship_cost", "ws_net_profit")),
                   (P.pcol("ws_ship_date_sk") >= P.plit(np.int32(ship_lo)))
                   & (P.pcol("ws_ship_date_sk") <= P.plit(np.int32(ship_hi))))
    ws1 = P.Join(ws1, ws_wh, on=(("ws_order_number", "ws_order_number"),), how="semi")
    ws1 = P.Join(ws1, P.Scan("web_returns", columns=("wr_order_number",)),
                 on=(("ws_order_number", "wr_order_number"),),
                 how="anti" if returns_how == "left_anti" else "semi")
    plan = P.Aggregate(ws1, keys=("ws_order_number",),
                       aggs=(P.AggSpec("ws_ext_ship_cost", "sum", "ws_ext_ship_cost_sum"),
                             P.AggSpec("ws_net_profit", "sum", "ws_net_profit_sum")))
    binding = None
    if mesh is not None:
        binding = P.MeshBinding(mesh, sharded, axis=mesh.axis_names[-1])
        plan = P.insert_exchanges(plan, binding.world, sharded=sharded)
    per = P.compile_ir(plan, {t: tables[t] for t in sharded}, name="q95_family", mesh=binding)()
    return {
        "order_count": int(per.num_rows),
        "total_shipping_cost": _exact_total(per.column("ws_ext_ship_cost_sum")),
        "total_net_profit": _exact_total(per.column("ws_net_profit_sum")),
    }


def q94(tables: Dict[str, Table], ship_lo: int = 400, ship_hi: int = 460) -> dict:
    """TPC-DS q94 — q95's NOT EXISTS variant: returned orders EXCLUDED
    via a true left ANTI join (Spark's NOT EXISTS lowering)."""
    return _q95_family(tables, "left_anti", int(ship_lo), int(ship_hi))


def q94_distributed(tables: Dict[str, Table], mesh, ship_lo: int = 400, ship_hi: int = 460) -> dict:
    """q94 compiled for ``mesh``, as ``q95_distributed``; identical to
    single-chip ``q94`` (pinned by test)."""
    return _q95_family(tables, "left_anti", int(ship_lo), int(ship_hi), mesh=mesh)


def q95(tables: Dict[str, Table], ship_lo: int = 400, ship_hi: int = 460) -> dict:
    """Returned-order shipping report. SQL shape:

        WITH ws_wh AS (SELECT ws_order_number FROM web_sales
                       GROUP BY ws_order_number
                       HAVING count(distinct ws_warehouse_sk) > 1)
        SELECT count(distinct ws_order_number), sum(ws_ext_ship_cost),
               sum(ws_net_profit)
        FROM web_sales ws1
        WHERE ws_ship_date_sk BETWEEN :lo AND :hi
          AND ws_order_number IN (SELECT * FROM ws_wh)
          AND ws_order_number IN (SELECT wr_order_number FROM web_returns)

    The IN-subqueries run as true left-semi joins (the plan Spark
    produces for IN; ops.join.left_semi_join). Shares its plan body
    with q94 (_q95_family)."""
    return _q95_family(tables, "left_semi", int(ship_lo), int(ship_hi))


def q95_distributed(tables: Dict[str, Table], mesh, ship_lo: int = 400, ship_hi: int = 460) -> dict:
    """q95 compiled for ``mesh`` (``plan.compile_ir(..., mesh=)``): the same
    plan, its fact tables row-sharded, three Exchange stages as all-to-alls
    and the group-bys and membership joins where the rows then lie. Must
    produce results identical to single-chip ``q95``."""
    return _q95_family(tables, "left_semi", int(ship_lo), int(ship_hi), mesh=mesh)

