"""Table-level distributed operator tests on the virtual 8-device CPU
mesh — pandas as the relational oracle; q95 distributed must equal q95
single-chip bit-for-bit on counts and to float tolerance on sums."""

import numpy as np
import pandas as pd
import pytest

import spark_rapids_jni_tpu  # noqa: F401
import jax.numpy as jnp
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.columnar import dtype as dt
from spark_rapids_jni_tpu.ops import bitutils
from spark_rapids_jni_tpu.parallel.mesh import make_mesh
from spark_rapids_jni_tpu.parallel.table_ops import (
    default_capacity,
    dict_decode,
    dict_encode,
    distributed_groupby_table,
    distributed_join_table,
    exchange_table,
)

import jax


@pytest.fixture
def mesh8():
    return make_mesh({"data": 8}, devices=jax.devices()[:8])


def _int_col(vals, d=dt.INT32, validity=None):
    v = None if validity is None else jnp.asarray(np.asarray(validity, bool))
    return Column(d, data=jnp.asarray(np.asarray(vals)), validity=v)


def _f64_col(vals):
    return Column(dt.FLOAT64, data=bitutils.float_store(jnp.asarray(np.asarray(vals, np.float64)), dt.FLOAT64))


def test_default_capacity_scales():
    # O(N/P^2) with headroom, not O(N/P)
    assert default_capacity(1 << 20, 64) == 4 * (1 << 20) // 64
    assert default_capacity(32, 8) == 32          # tiny shards: floor wins
    assert default_capacity(1024, 8) == 512


def test_dict_encode_roundtrip():
    vals = ["apple", "pear", None, "apple", "", "Ünïcode", "pear"]
    col = Column.from_pylist(vals, dt.STRING)
    codes, d = dict_encode(col)
    out = dict_decode(codes.data, d, validity=codes.validity)
    assert out.to_pylist() == vals
    # equal strings share a code
    c = np.asarray(codes.data)
    assert c[0] == c[3] and c[1] == c[6] and c[0] != c[1]


def test_exchange_table_preserves_rows(mesh8, rng):
    n = 1000
    keys = rng.integers(0, 37, n)
    vals = rng.integers(-100, 100, n)
    strs = [f"name_{int(k) % 11}" if k % 5 else None for k in keys]
    t = Table(
        [_int_col(keys.astype(np.int64), dt.INT64), _int_col(vals), Column.from_pylist(strs, dt.STRING)],
        ["k", "v", "s"],
    )
    out, ovf = exchange_table(t, ["k"], mesh8)
    assert not ovf
    got = sorted(zip(out.column("k").to_pylist(), out.column("v").to_pylist(),
                     [x if x is not None else "<null>" for x in out.column("s").to_pylist()]))
    want = sorted(zip(keys.tolist(), vals.tolist(),
                      [x if x is not None else "<null>" for x in strs]))
    assert got == want


def test_distributed_groupby_table_int_keys(mesh8, rng):
    n = 2000
    k1 = rng.integers(0, 13, n).astype(np.int64)
    k2 = rng.integers(0, 3, n)
    v = rng.integers(-50, 50, n).astype(np.int64)
    w = rng.standard_normal(n)
    t = Table(
        [_int_col(k1, dt.INT64), _int_col(k2), _int_col(v, dt.INT64), _f64_col(w)],
        ["k1", "k2", "v", "w"],
    )
    out, ovf = distributed_groupby_table(
        t, ["k1", "k2"],
        [("v", "sum", "v_sum"), ("v", "count", "v_cnt"), ("v", "min", "v_min"),
         ("v", "max", "v_max"), ("w", "sum", "w_sum"), ("v", "mean", "v_mean")],
        mesh8,
    )
    assert not ovf
    df = pd.DataFrame({"k1": k1, "k2": k2, "v": v, "w": w})
    want = df.groupby(["k1", "k2"]).agg(
        v_sum=("v", "sum"), v_cnt=("v", "count"), v_min=("v", "min"),
        v_max=("v", "max"), w_sum=("w", "sum"), v_mean=("v", "mean"),
    ).reset_index()

    got = pd.DataFrame({
        "k1": out.column("k1").to_pylist(),
        "k2": out.column("k2").to_pylist(),
        "v_sum": out.column("v_sum").to_pylist(),
        "v_cnt": out.column("v_cnt").to_pylist(),
        "v_min": out.column("v_min").to_pylist(),
        "v_max": out.column("v_max").to_pylist(),
        "w_sum": [float(x) for x in np.asarray(bitutils.float_view(out.column("w_sum").data, dt.FLOAT64))],
        "v_mean": [float(x) for x in np.asarray(bitutils.float_view(out.column("v_mean").data, dt.FLOAT64))],
    }).sort_values(["k1", "k2"]).reset_index(drop=True)
    want = want.sort_values(["k1", "k2"]).reset_index(drop=True)
    assert got["k1"].tolist() == want["k1"].tolist()
    assert got["v_sum"].tolist() == want["v_sum"].tolist()
    assert got["v_cnt"].tolist() == want["v_cnt"].tolist()
    assert got["v_min"].tolist() == want["v_min"].tolist()
    assert got["v_max"].tolist() == want["v_max"].tolist()
    np.testing.assert_allclose(got["w_sum"], want["w_sum"], rtol=1e-9)
    np.testing.assert_allclose(got["v_mean"], want["v_mean"], rtol=1e-9)


def test_distributed_groupby_string_keys_and_null_values(mesh8, rng):
    n = 600
    kc = rng.integers(0, 7, n)
    keys = [f"grp_{int(k)}" for k in kc]
    vals = rng.integers(0, 100, n).astype(np.int64)
    vvalid = rng.integers(0, 4, n) > 0  # 25% null values
    t = Table(
        [Column.from_pylist(keys, dt.STRING), _int_col(vals, dt.INT64, validity=vvalid)],
        ["k", "v"],
    )
    out, ovf = distributed_groupby_table(
        t, ["k"], [("v", "sum", "v_sum"), ("v", "count", "v_cnt")], mesh8
    )
    assert not ovf
    df = pd.DataFrame({"k": keys, "v": np.where(vvalid, vals, np.nan)})
    want = df.groupby("k").agg(v_sum=("v", "sum"), v_cnt=("v", "count")).reset_index()
    got = pd.DataFrame({
        "k": out.column("k").to_pylist(),
        "v_sum": out.column("v_sum").to_pylist(),
        "v_cnt": out.column("v_cnt").to_pylist(),
    }).sort_values("k").reset_index(drop=True)
    want = want.sort_values("k").reset_index(drop=True)
    assert got["k"].tolist() == want["k"].tolist()
    assert got["v_sum"].tolist() == [int(x) for x in want["v_sum"]]
    assert got["v_cnt"].tolist() == [int(x) for x in want["v_cnt"]]


def test_distributed_join_inner_multikey(mesh8, rng):
    nl, nr = 700, 300
    lk1 = rng.integers(0, 20, nl); lk2 = rng.integers(0, 4, nl)
    lv = rng.integers(0, 1000, nl)
    rk1 = rng.integers(0, 20, nr); rk2 = rng.integers(0, 4, nr)
    rv = rng.integers(0, 1000, nr)
    left = Table([_int_col(lk1), _int_col(lk2), _int_col(lv)], ["a", "b", "lv"])
    right = Table([_int_col(rk1), _int_col(rk2), _int_col(rv)], ["a", "b", "rv"])
    out, ovf = distributed_join_table(left, right, on=["a", "b"], mesh=mesh8, how="inner")
    assert not ovf
    dfl = pd.DataFrame({"a": lk1, "b": lk2, "lv": lv})
    dfr = pd.DataFrame({"a": rk1, "b": rk2, "rv": rv})
    want = dfl.merge(dfr, on=["a", "b"])
    got = sorted(zip(out.column("a").to_pylist(), out.column("b").to_pylist(),
                     out.column("lv").to_pylist(), out.column("rv").to_pylist()))
    want_t = sorted(zip(want["a"], want["b"], want["lv"], want["rv"]))
    assert got == want_t


@pytest.mark.parametrize("how", ["left_semi", "left_anti"])
def test_distributed_join_semi_anti(mesh8, rng, how):
    nl, nr = 500, 120
    lk = rng.integers(0, 40, nl).astype(np.int64)
    lv = rng.integers(0, 1000, nl)
    rk = rng.integers(0, 25, nr).astype(np.int64)
    left = Table([_int_col(lk, dt.INT64), _int_col(lv)], ["k", "v"])
    right = Table([_int_col(rk, dt.INT64)], ["k"])
    out, ovf = distributed_join_table(left, right, on=["k"], mesh=mesh8, how=how)
    assert not ovf
    in_right = np.isin(lk, rk)
    keep = in_right if how == "left_semi" else ~in_right
    want = sorted(zip(lk[keep].tolist(), lv[keep].tolist()))
    got = sorted(zip(out.column("k").to_pylist(), out.column("v").to_pylist()))
    assert got == want


def test_distributed_join_string_key(mesh8, rng):
    lk = [f"u{int(x)}" for x in rng.integers(0, 15, 200)]
    rk = [f"u{int(x)}" for x in rng.integers(0, 9, 60)]
    left = Table([Column.from_pylist(lk, dt.STRING), _int_col(np.arange(200))], ["k", "v"])
    right = Table([Column.from_pylist(rk, dt.STRING)], ["k"])
    out, ovf = distributed_join_table(left, right, on=["k"], mesh=mesh8, how="left_semi")
    assert not ovf
    rset = set(rk)
    want = sorted((k, i) for i, k in enumerate(lk) if k in rset)
    got = sorted(zip(out.column("k").to_pylist(), out.column("v").to_pylist()))
    assert got == want


def test_q95_distributed_matches_single_chip(mesh8):
    from spark_rapids_jni_tpu.models.tpcds import gen_web, q95, q95_distributed

    tables = gen_web(4000)
    want = q95(tables)
    got = q95_distributed(tables, mesh8)
    assert got["order_count"] == want["order_count"]
    np.testing.assert_allclose(got["total_shipping_cost"], want["total_shipping_cost"], rtol=1e-9)
    np.testing.assert_allclose(got["total_net_profit"], want["total_net_profit"], rtol=1e-9)


def test_groupby_all_null_group_is_null(mesh8):
    # group 1's values are ALL null: Spark returns NULL for sum/min/max/
    # mean and 0 for count
    keys = np.array([0, 0, 1, 1, 2], np.int64)
    vals = np.array([5, 7, 99, 98, 3], np.int64)
    vvalid = np.array([True, True, False, False, True])
    t = Table(
        [_int_col(keys, dt.INT64), _int_col(vals, dt.INT64, validity=vvalid)],
        ["k", "v"],
    )
    out, ovf = distributed_groupby_table(
        t, ["k"],
        [("v", "sum", "s"), ("v", "min", "mn"), ("v", "max", "mx"),
         ("v", "mean", "avg"), ("v", "count", "c")],
        mesh8,
    )
    assert not ovf
    rows = {k: i for i, k in enumerate(out.column("k").to_pylist())}
    assert set(rows) == {0, 1, 2}
    for name in ("s", "mn", "mx", "avg"):
        col = out.column(name).to_pylist()
        assert col[rows[1]] is None, name
        assert col[rows[0]] is not None, name
    assert out.column("c").to_pylist()[rows[1]] == 0
    assert out.column("s").to_pylist()[rows[0]] == 12
    assert out.column("mn").to_pylist()[rows[0]] == 5
    assert out.column("mx").to_pylist()[rows[0]] == 7


def test_memory_budget_split_retry(mesh8, monkeypatch):
    """A skewed key whose overflow escalation would exceed the device
    budget must SPLIT the batch and re-run, not grow buffers until OOM
    (the reference's RMM retry / 2 GiB batching discipline)."""
    from spark_rapids_jni_tpu.utils import memory as mem

    # nine rows in ten share a key, so the buckets grow to a whole shard's
    # 512 slots: 22 bytes a slot (two lanes, a route index, a flag), ~180KB a
    # device, exceeds the budget; each half's 256 slots (~90KB) fit
    monkeypatch.setenv("SRJT_DEVICE_MEMORY_BUDGET", "160000")
    rng = np.random.default_rng(3)
    n = 4096
    keys = np.where(rng.integers(0, 10, n) < 9, 0, rng.integers(0, 50, n))
    vals = rng.integers(0, 100, n)
    t = Table(
        [_int_col(keys, dt.INT64), _int_col(vals, dt.INT64)], ["k", "v"]
    )
    before = mem.split_retry_count()
    out, ovf = distributed_groupby_table(
        t, ["k"], [("v", "sum", "v_sum"), ("v", "mean", "v_mean")], mesh8
    )
    assert mem.split_retry_count() > before, "expected a memory-driven split"
    assert not ovf
    want, wc = {}, {}
    for k, v in zip(keys.tolist(), vals.tolist()):
        want[k] = want.get(k, 0) + v
        wc[k] = wc.get(k, 0) + 1
    got = dict(zip(out.column("k").to_pylist(), out.column("v_sum").to_pylist()))
    gotm = dict(zip(out.column("k").to_pylist(), out.column("v_mean").to_pylist()))
    assert got == want
    for k in want:
        assert abs(gotm[k] - want[k] / wc[k]) < 1e-9


def test_exchange_over_budget_raises_retryable(mesh8, monkeypatch):
    from spark_rapids_jni_tpu.utils.errors import RetryableError
    from spark_rapids_jni_tpu.utils.memory import MemoryBudgetExceeded

    monkeypatch.setenv("SRJT_DEVICE_MEMORY_BUDGET", "1000")
    t = Table([_int_col(np.arange(64), dt.INT64)], ["k"])
    with pytest.raises(MemoryBudgetExceeded) as ei:
        exchange_table(t, ["k"], mesh8)
    assert isinstance(ei.value, RetryableError)  # Spark task-retry class


def test_an_empty_table_goes_through_the_mesh_and_comes_back_empty(mesh8):
    """No row at all: every shard holds one absent slot, so no program is
    traced over no slots, and the answer keeps its columns."""
    t = Table([_int_col(np.zeros(0, np.int64), dt.INT64), _int_col(np.zeros(0, np.int64), dt.INT64)], ["k", "v"])
    out, ovf = exchange_table(t, ["k"], mesh8)
    assert not ovf and out.num_rows == 0 and list(out.names) == ["k", "v"]
    out, ovf = distributed_groupby_table(t, ["k"], [("v", "sum", "s"), ("v", "count", "n")], mesh8)
    assert not ovf and out.num_rows == 0 and list(out.names) == ["k", "s", "n"]
