"""A STRING key is ordered and compared over ALL its bytes (ISSUE 32).

Every caller of ``ops/sort.py::string_key_lanes`` — ``sorted_order``,
the group boundaries of ``groupby_aggregate``, ``nunique``, the join ids
of ``ops/join.py::_factorize`` and the window partitions — against plain
Python (``sorted``, dicts of lists) on seeded strings that share
prefixes of 15, 16, 17 and 40 bytes, with equal and unequal lengths,
empties, NULLs and a string that is a prefix of another. Until PR 32
only the first 16 bytes and the length took part: two dsdgen brands
(``exportischolar #1`` / ``#2``) were one group.
"""

import numpy as np
import pytest

from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.columnar import dtype as dt
from spark_rapids_jni_tpu.ops.aggregate import groupby_aggregate
from spark_rapids_jni_tpu.ops.join import inner_join, left_semi_join
from spark_rapids_jni_tpu.ops.sort import sort_by_key, string_key_lanes
from spark_rapids_jni_tpu.ops.window import window_aggregate
from spark_rapids_jni_tpu.utils import metrics

SEEDS = (32, 3200104759, 77)
BRANDS = ["exportischolar #1", "exportischolar #2", "exportischolar #1",
          "importoamalgamalg #11", "importoamalgamalg #12", "exportischolar #2"]


def _pool(rng):
    """Distinct strings that differ only past a shared prefix."""
    pool = {"", "a", "a\x00", "b"}
    for width in (15, 16, 17, 40):
        stem = "".join(rng.choice(list("abcdefgh"), width))
        pool.add(stem)  # a prefix of every string below
        for tail in ("1", "2", "10", "\x00", "zz", "\xe9"):  # equal and unequal lengths, a two-byte character
            pool.add(stem + tail)
        pool.add(stem[:-1] + "~")  # same length as the stem, last byte differs
    return sorted(pool)


def _keys(seed, n=160, nulls=True):
    rng = np.random.default_rng(seed)
    pool = _pool(rng)
    vals = [pool[i] for i in rng.integers(0, len(pool), n)]
    if nulls:
        for i in rng.choice(n, n // 10, replace=False):
            vals[i] = None
    return rng, vals


def _strings(vals):
    return Column.from_pylist(vals, dt.STRING)


def _ints(vals):
    return Column.from_numpy(np.asarray(vals, np.int64), dt.INT64)


def _utf8(s):
    return s.encode("utf-8")  # Spark's UTF8String binary order


def test_the_six_brands_are_four_groups():
    out = groupby_aggregate(Table([_strings(BRANDS)], ["brand"]),
                            Table([_ints([1, 2, 1, 3, 4, 4])], ["v"]), [("v", "sum")])
    assert out.columns[0].to_pylist() == ["exportischolar #1", "exportischolar #2",
                                          "importoamalgamalg #11", "importoamalgamalg #12"]
    assert np.asarray(out.columns[1].data).tolist() == [2, 6, 3, 4]


@pytest.mark.parametrize("seed", SEEDS)
def test_groupby_on_a_string_key(seed):
    rng, keys = _keys(seed)
    vals = rng.integers(0, 1000, len(keys))
    out = groupby_aggregate(Table([_strings(keys)], ["k"]), Table([_ints(vals)], ["v"]),
                            [("v", "sum"), ("v", "count_all")])
    want = {}
    for k, v in zip(keys, vals):
        want.setdefault(k, []).append(int(v))
    order = sorted(want, key=lambda k: (k is not None, _utf8(k or "")))  # the NULL group first
    assert out.columns[0].to_pylist() == order
    assert np.asarray(out.columns[1].data).tolist() == [sum(want[k]) for k in order]
    assert np.asarray(out.columns[2].data).tolist() == [len(want[k]) for k in order]


@pytest.mark.parametrize("seed", SEEDS)
def test_groupby_on_a_string_key_beside_an_int_key(seed):
    rng, keys = _keys(seed, nulls=False)
    other = rng.integers(0, 3, len(keys))
    out = groupby_aggregate(Table([_ints(other), _strings(keys)], ["o", "k"]),
                            Table([_ints(np.ones(len(keys)))], ["v"]), [("v", "sum")])
    want = {}
    for o, k in zip(other, keys):
        want[(int(o), _utf8(k))] = want.get((int(o), _utf8(k)), 0) + 1
    got = list(zip(np.asarray(out.columns[0].data).tolist(), map(_utf8, out.columns[1].to_pylist())))
    assert got == sorted(want)
    assert np.asarray(out.columns[2].data).tolist() == [want[g] for g in got]


@pytest.mark.parametrize("nulls_first", (True, False), ids=("nulls_first", "nulls_last"))
@pytest.mark.parametrize("ascending", (True, False), ids=("asc", "desc"))
@pytest.mark.parametrize("seed", SEEDS)
def test_sort_by_a_string_key(seed, ascending, nulls_first):
    _, keys = _keys(seed)
    rows = list(range(len(keys)))
    out = sort_by_key(Table([_ints(rows)], ["row"]), Table([_strings(keys)], ["k"]),
                      ascending=[ascending], nulls_first=[nulls_first])
    valid = sorted((r for r in rows if keys[r] is not None), key=lambda r: _utf8(keys[r]),
                   reverse=not ascending)  # sorted() is stable in both directions, as the op is
    null = [r for r in rows if keys[r] is None]
    got = np.asarray(out.columns[0].data).tolist()
    assert [keys[r] for r in got] == [keys[r] for r in (null + valid if nulls_first else valid + null)]
    if ascending:  # stable: equal keys keep their row order
        assert got == (null + valid if nulls_first else valid + null)


@pytest.mark.parametrize("seed", SEEDS)
def test_inner_join_on_a_string_key(seed):
    rng, lk = _keys(seed, 120)
    _, rk = _keys(seed + 1, 60)
    pool = sorted({k for k in lk if k is not None})
    rk = [k if k is None else pool[i % len(pool)] for i, k in enumerate(rk)]  # keys that do meet
    left = Table([_strings(lk), _ints(range(len(lk)))], ["k", "l"])
    right = Table([_strings(rk), _ints(range(len(rk)))], ["k", "r"])
    out = inner_join(left, right, ["k"])
    got = sorted(zip(out.column("k").to_pylist(), np.asarray(out.column("l").data).tolist(),
                     np.asarray(out.column("r").data).tolist()))
    want = sorted((a, i, j) for i, a in enumerate(lk) for j, b in enumerate(rk) if a is not None and a == b)
    assert want and got == want


@pytest.mark.parametrize("seed", SEEDS)
def test_left_semi_join_on_a_string_key(seed):
    _, lk = _keys(seed, 120)
    _, rk = _keys(seed + 2, 12)
    out = left_semi_join(Table([_strings(lk), _ints(range(len(lk)))], ["k", "l"]),
                         Table([_strings(rk)], ["k"]), ["k"])
    have = {k for k in rk if k is not None}
    assert np.asarray(out.column("l").data).tolist() == [i for i, k in enumerate(lk) if k in have]


@pytest.mark.parametrize("seed", SEEDS)
def test_nunique_of_a_string_column(seed):
    rng, vals = _keys(seed)
    groups = rng.integers(0, 4, len(vals))
    out = groupby_aggregate(Table([_ints(groups)], ["g"]), Table([_strings(vals)], ["s"]), [("s", "nunique")])
    want = [len({v for g, v in zip(groups, vals) if g == k and v is not None}) for k in sorted(set(groups.tolist()))]
    assert np.asarray(out.columns[1].data).tolist() == want


@pytest.mark.parametrize("seed", SEEDS)
def test_window_partitioned_by_a_string_key(seed):
    rng, keys = _keys(seed, nulls=False)
    vals = rng.permutation(len(keys))
    out = window_aggregate(Table([_strings(keys), _ints(vals)], ["k", "v"]), ["k"], [("v", True)],
                           [("v", "row_number", "rn"), ("v", "count", "n")])
    want_rn, want_n = [], []
    for k, v in zip(keys, vals):
        mine = sorted(int(w) for kk, w in zip(keys, vals) if kk == k)
        want_rn.append(mine.index(int(v)) + 1)
        want_n.append(len(mine))
    assert np.asarray(out.column("rn").data).tolist() == want_rn
    assert np.asarray(out.column("n").data).tolist() == want_n


@pytest.mark.parametrize("ascending", (True, False), ids=("asc", "desc"))
@pytest.mark.parametrize("seed", SEEDS)
def test_sort_and_group_by_200_byte_keys(seed, ascending):
    """27 lanes: more than one sort program takes (``_LEXSORT_LANES``), so
    the order is built chunk by chunk from the minor lanes up."""
    rng = np.random.default_rng(seed)
    stem = "".join(rng.choice(list("ab"), 190))
    pool = sorted({stem + "".join(rng.choice(list("abc"), 10)) for _ in range(12)}
                  | {stem[:k] + "c" for k in (3, 95, 100, 189)} | {stem, stem[:96]})
    keys = [pool[i] for i in rng.integers(0, len(pool), 90)]
    other = rng.integers(0, 2, len(keys))
    rows = list(range(len(keys)))
    out = sort_by_key(Table([_ints(rows)], ["row"]), Table([_ints(other), _strings(keys)], ["o", "k"]),
                      ascending=[True, ascending])
    want = sorted(sorted(rows, key=lambda r: _utf8(keys[r]), reverse=not ascending), key=lambda r: other[r])
    assert [(other[r], keys[r]) for r in np.asarray(out.columns[0].data).tolist()] == [(other[r], keys[r]) for r in want]
    grouped = groupby_aggregate(Table([_strings(keys)], ["k"]), Table([_ints(np.ones(len(keys)))], ["v"]), [("v", "sum")])
    assert grouped.columns[0].to_pylist() == sorted(set(keys), key=_utf8)
    assert np.asarray(grouped.columns[1].data).tolist() == [keys.count(k) for k in sorted(set(keys), key=_utf8)]


@pytest.mark.parametrize("longest,lanes", [(0, 0), (8, 1), (16, 2), (17, 3), (22, 3), (200, 25)])
def test_the_lanes_follow_the_longest_key(longest, lanes):
    col = _strings(["x" * longest, "", "x" * (longest // 2)])
    reg = metrics.registry()
    before = reg.value("keys.string.columns"), reg.value("keys.string.lanes")
    out = string_key_lanes(col)
    assert [str(k.dtype) for k in out] == ["uint64"] * lanes + ["uint32"]  # the bytes, then the length
    assert np.asarray(out[-1]).tolist() == [longest, 0, longest // 2]
    assert (reg.value("keys.string.columns") - before[0], reg.value("keys.string.lanes") - before[1]) == (1, lanes + 1)


@pytest.mark.parametrize("seed", SEEDS)
def test_a_lane_is_its_eight_bytes_big_endian(seed):
    _, vals = _keys(seed, 40, nulls=False)
    out = [np.asarray(k) for k in string_key_lanes(_strings(vals))]
    for r, v in enumerate(vals):
        raw = _utf8(v)
        padded = raw + b"\x00" * (8 * (len(out) - 1) - len(raw))
        assert [int(k[r]) for k in out[:-1]] == [int.from_bytes(padded[j:j + 8], "big")
                                                 for j in range(0, len(padded), 8)]
        assert int(out[-1][r]) == len(raw)
