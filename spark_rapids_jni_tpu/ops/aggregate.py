"""Hash-aggregate tier: groupby + reductions (cudf groupby, SURVEY §2.8).

TPU-first: sort-based grouping instead of a device hash table — XLA has
a first-class sort but no general hash table; sort + segment-reduce is
the canonical accelerator formulation. Pipeline:

1. stable sort rows by key columns (ops/sort total-order keys); with a
   row mask (``present``: the rows a filter kept, handed on in place of a
   compacted table) one more lane in front of the keys puts the absent
   rows last,
2. group boundaries from neighbor inequality (nulls compare equal,
   SQL GROUP BY semantics), counted over the present rows only: the
   absent rows trail, so "is this sorted row present" is one compare of
   an iota with the mask's count (``live``), never a gather of the mask,
   and they take the segment id one past the last group,
3. ``jax.ops.segment_*`` reductions with num_segments synced to host
   once (the output-allocation sync every engine pays); a FLOAT64 sum
   or mean is ONE device program after that sync (``_f64_sum_mean``:
   gather, exact accumulation, the mean's long division and the
   rounding, compiled once per shape and group count and kept). Every
   aggregate reads ``live`` as part of its rows' validity. ``count_all``
   is no reduction at all: a group's rows are the next group's start less
   its own (the last group ends where the present rows do),
4. group keys gathered from each segment's first row.

The masked form answers bit for bit what the form over the compacted table
answers (the sort is stable and the exact sums do not depend on order); it
trades the filter's N-sized ``nonzero`` scatter and a gather a column for a
group-by over N slots in place of the kept rows.

The dense form (keys that span a handful of values: dictionary-coded flags,
booleans, small enums, a global aggregate's column of zeros) takes steps 1,
2 and 4 out. Where every key is a fixed-width integer or a BOOL8, one probe
program reads each key's minimum, maximum and whether it holds a NULL over
the present rows (one round trip: ``key_domain``); where the product of the
keys' ranges is at most ``_DENSE_MAX_SLOTS`` (64) the rows' group ids come
from the codes:

1d. ``slot = sum(code_i * stride_i)``, ``code_i`` 0 for a NULL (nulls first)
    else ``key_i - min_i`` (+1 behind a NULL), leftmost key most significant,
    so ascending slots ARE the sort path's group order; one program counts
    the present rows of every slot (the operator's one ``group_count`` read),
2d. a second program numbers the occupied slots 0, 1, ... in place: the rows'
    group ids where the rows lie, the absent rows one past the last group,
3d. step 3 as it is with no ``order``: no aggregate gathers its column,
    ``live`` is the mask itself, ``count_all`` is the slots' counts,
4d. the group keys are rebuilt from slot arithmetic (``min_i + code``), on
    the host: nothing is gathered from the table.

Any other key set, or a domain past the bound, enters the sort path as it
was; a STRING, DECIMAL128 or float key is refused by its dtype before any
launch. Both forms give the same lanes in the same row order.

Supported aggs: sum, count (valid), count_all, min, max, mean,
nunique, and the variance family — var/std (sample, Spark
var_samp/stddev_samp) and var_pop/stddev_pop (population).
FLOAT64 SUM/MEAN are EXACT on every backend — including TPU, which has
no f64 datapath — via the windowed integer accumulator in ops/f64acc
(correctly rounded f64 of the exact real sum; bit-identical CPU vs TPU).
min/max on floats use the exact total-order transform, so they are exact
everywhere too. FLOAT32 sums accumulate in f32 (documented; Spark
promotes float sums to double before they reach this tier).
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import Column, Table
from ..columnar import dtype as dt
from ..columnar.dtype import TypeId
from ..utils import metrics, tracing
from ..utils.dispatch import op_boundary
from . import bitutils, f64acc
from .copying import gather
from .sort import sorted_order, string_key_lanes

__all__ = ["groupby_aggregate", "groupby_sum_bounded"]


def groupby_sum_bounded(
    keys: jnp.ndarray, vals: jnp.ndarray, num_keys: int, f64_bits: bool = False
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """GROUP BY SUM for a BOUNDED integer key domain [0, num_keys):
    one scatter-add pass, no sort — the hash-aggregate hot path for
    dictionary-coded group columns (cudf hash agg does the same when the
    build side fits). The caller states the domain; ``groupby_aggregate``
    is the general route and reads it: it numbers the groups from the keys'
    codes where they span a handful of values, and sorts otherwise. Returns
    (sums[num_keys], counts[num_keys]); keys outside the domain are dropped
    into a trash segment.

    O(N) and HBM-bandwidth-bound on TPU, where the general path pays an
    O(N log^2 N) sort.

    ``vals`` contract: float32 sums in f32 (MXU kernel on TPU);
    integers sum in two's-complement int64 (uint64 keeps its low 64
    sum bits — wrap past 2^63 is the caller's to reinterpret, as in
    cudf's u64 accumulator). Pass ``f64_bits=True`` when ``vals`` is
    FLOAT64 IEEE-bit storage (the columnar FLOAT64 format,
    ops/bitutils): returns EXACT f64 sums as uint64 bits via the
    ops/f64acc windowed accumulator. An explicit flag, not dtype
    punning — a real UINT64 integer column must keep integer semantics.
    """
    if f64_bits:  # FLOAT64 bits: exact integer-limb path
        if vals.dtype != jnp.uint64:
            raise ValueError("f64_bits vals must be uint64 IEEE-bit storage")
        seg = jnp.where((keys >= 0) & (keys < num_keys), keys, num_keys).astype(jnp.int32)
        sums = f64acc.segment_sum_f64bits(vals, seg, num_keys + 1)[:num_keys]
        counts = jax.ops.segment_sum(
            jnp.ones_like(seg, jnp.int64), seg, num_segments=num_keys + 1
        )[:num_keys]
        return sums, counts
    if (
        vals.dtype == jnp.float32
        and num_keys <= 65536
        and keys.shape[0] < (1 << 24)  # counts ride an f32 accumulator:
        # exact only while every per-key count stays below 2^24
        and jax.default_backend() == "tpu"
    ):
        # float path on hardware: the outer-product MXU kernel in place
        # of the XLA scatter (speed-up over the scatter: not measured on
        # this round's chip; see pallas_kernels). Integer sums stay on
        # the exact int64 scatter path.
        from .pallas_kernels import pallas_groupby_sum_outer

        return pallas_groupby_sum_outer(keys, vals, num_keys)

    seg = jnp.where((keys >= 0) & (keys < num_keys), keys, num_keys).astype(jnp.int32)
    if jnp.issubdtype(vals.dtype, jnp.integer):
        vals = vals.astype(jnp.int64)
    sums = jax.ops.segment_sum(vals, seg, num_segments=num_keys + 1)[:num_keys]
    counts = jax.ops.segment_sum(jnp.ones_like(seg, jnp.int64), seg, num_segments=num_keys + 1)[
        :num_keys
    ]
    return sums, counts


def _keys_equal_neighbor(col: Column, order: jnp.ndarray) -> jnp.ndarray:
    """[N-1] bool: sorted row i equals row i-1 for this key (nulls equal)."""
    v = col.valid_mask()[order]
    same_valid = v[1:] == v[:-1]
    if col.dtype.id == TypeId.STRING:
        same = jnp.ones((max(order.shape[0] - 1, 0),), bool)
        for lane in string_key_lanes(col):  # every byte and the length
            k = lane[order]
            same = same & (k[1:] == k[:-1])
    elif col.dtype.id == TypeId.DECIMAL128:
        d = col.data[order]
        same = jnp.all(d[1:] == d[:-1], axis=1)
    else:
        d = col.data[order]
        same = d[1:] == d[:-1]
    both_null = (~v[1:]) & (~v[:-1])
    return same_valid & (same | both_null)


def _live_rows(present: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(live [N] bool, count): which SORTED rows are present, and how many.
    ``sorted_order(..., present=)`` puts the absent rows last, so the sorted
    mask is an iota under the mask's count: no gather through ``order``."""
    count = jnp.sum(present, dtype=jnp.int32)
    return jnp.arange(present.shape[0], dtype=jnp.int32) < count, count


def _segment_ids(keys: Table, order: jnp.ndarray, live=None) -> Tuple[jnp.ndarray, int]:
    """Sorted rows' group ids and the group count (the one host sync).
    With ``live`` the groups are those of the live rows; the rows behind
    them take the id ``num``, one past the last group."""
    n = keys.num_rows
    if n == 0:
        return jnp.zeros((0,), jnp.int32), 0
    eq = jnp.ones((n - 1,), bool)
    for col in keys.columns:
        eq = eq & _keys_equal_neighbor(col, order)
    starts = jnp.concatenate([jnp.ones((1,), bool), ~eq])
    if live is None:
        seg = jnp.cumsum(starts).astype(jnp.int32) - 1
        num = int(tracing.device_wait(seg[-1], "group_count")) + 1  # host sync: group count
        return seg, num
    opened = jnp.cumsum(starts & live).astype(jnp.int32)
    seg = jnp.where(live, opened - 1, opened[-1])
    return seg, int(tracing.device_wait(opened[-1], "group_count"))  # host sync: group count


# The widest key domain (the product over the keys of max - min + 1, one more
# for a key that holds a NULL) the dense form takes: the widest the sweep of
# benchmarks/calls/pr37_dense.py measured on the chip (PERF.md 6, PR 37). At
# 6,001,215 rows the dense form was ahead of the sorted one by the sort's and
# the gathers' ~340 ms at every domain from 1 to 64 (240 against 580 ms up to
# 16 groups; 2,265 against 2,732 at 64, where both forms' exact sums have left
# f64acc's masked reductions, ``num_segments <= 16``, for scatters): no
# break-even lies below it. The slot programs compare every row with every
# slot, and the domain is a shape of theirs (rounded up as a group count is).
_DENSE_MAX_SLOTS = 64


def _dense_key_dtypes(keys: Table) -> bool:
    """Keys whose order is their integer order: every one a fixed-width
    integer or a BOOL8. Read from the dtypes alone: a STRING, DECIMAL128 or
    float key launches nothing here."""
    return all(c.dtype.is_integral or c.dtype.id == TypeId.BOOL8 for c in keys.columns)


@tracing.launches
@jax.jit
def _key_domain(datas, validities, present):
    """The probe: each key's (minimum, maximum) over the rows that are
    present and hold a value (the dtype's own maximum and minimum where none
    does), whether a present row is NULL there, and the count of the present
    rows. One pass over the keys, a handful of scalars out."""
    lows, highs, nulls = [], [], []
    for data, validity in zip(datas, validities):
        ok = present if validity is None else validity if present is None else validity & present
        info = np.iinfo(data.dtype)
        top, bottom = np.asarray(info.max, data.dtype), np.asarray(info.min, data.dtype)
        lows.append(jnp.min(data if ok is None else jnp.where(ok, data, top)))
        highs.append(jnp.max(data if ok is None else jnp.where(ok, data, bottom)))
        null = None if validity is None else ~validity if present is None else ~validity & present
        nulls.append(jnp.zeros((), bool) if null is None else jnp.any(null))
    count = datas[0].shape[0] if present is None else jnp.sum(present, dtype=jnp.int32)
    return tuple(lows), tuple(highs), tuple(nulls), count


def _slots(datas, validities, mins, shifts, strides) -> jnp.ndarray:
    """[N] int32 (traced): ``sum(code_i * stride_i)``, a key's code 0 for a
    NULL, else ``key - min`` (+ its shift: 1 behind a NULL). What an absent
    row gets is garbage; its readers mask it."""
    slot = jnp.zeros(datas[0].shape, jnp.int32)
    for i, (data, validity) in enumerate(zip(datas, validities)):
        code = (data - mins[i]).astype(jnp.int32) + shifts[i]
        if validity is not None:
            code = jnp.where(validity, code, 0)
        slot = slot + code * strides[i]
    return slot


def _slot_hits(slot, present, domain: int) -> jnp.ndarray:
    """[domain, N] bool (traced, never materialised: its readers reduce it)."""
    hit = slot[None, :] == jnp.arange(domain, dtype=jnp.int32)[:, None]
    return hit if present is None else hit & present[None, :]


@tracing.launches
@functools.partial(jax.jit, static_argnames=("domain",))
def _slot_counts(datas, validities, present, mins, shifts, strides, *, domain: int):
    """[domain] int32: the present rows of every slot (``count_all``, and
    which slots are groups)."""
    hit = _slot_hits(_slots(datas, validities, mins, shifts, strides), present, domain)
    return jnp.sum(hit, axis=1, dtype=jnp.int32)


@tracing.launches
@jax.jit
def _slot_group_ids(datas, validities, present, mins, shifts, strides, group_of_slot, groups):
    """[N] int32: every row's group id where the row lies — its slot's place
    among the occupied slots (``group_of_slot``, [domain]) — and ``groups``,
    one past the last, for an absent row: what ``_segment_ids`` gives the
    sorted rows, with no order."""
    hit = _slot_hits(_slots(datas, validities, mins, shifts, strides), present, group_of_slot.shape[0])
    seg = jnp.sum(jnp.where(hit, group_of_slot[:, None], 0), axis=0, dtype=jnp.int32)
    return seg if present is None else jnp.where(present, seg, groups)


class _Groups(NamedTuple):
    """What either form of the group-by hands its aggregates."""

    order: Optional[jnp.ndarray]  # [N] the rows sorted by key; None in the dense form: the rows lie where they are
    seg: jnp.ndarray  # [N] int32 group ids, of the sorted rows or (dense) of the rows as they lie
    num: int  # groups
    live: Optional[jnp.ndarray]  # [N] bool, which of the rows ``seg`` numbers are present; None: all
    keys: Table  # [num] the groups' keys, ascending
    sizes: Callable[[], Column]  # [num] INT64 COUNT(*), made where an aggregate asks for it

    @property
    def dense(self) -> bool:
        return self.order is None


def _dense_groups(keys: Table, present) -> Optional[_Groups]:
    """The groups of integer keys from their codes, or None where the sort
    path has to run: the domain is past ``_DENSE_MAX_SLOTS`` or no row is
    present. Two round trips (the keys' domain, the slots' counts), three
    small programs, all inside ``groupby.segments``. The form is known only
    when the probe is back, so a refusal has its own ``groupby.segments``
    span (``dense`` False and the ``domain``) in front of the sort's."""
    with tracing.span("groupby.segments") as sp:
        datas = tuple(c.data for c in keys.columns)
        validities = tuple(c.validity for c in keys.columns)
        probe = tracing.device_wait(_key_domain(datas, validities, present), "key_domain")
        lows, highs, nulls, count = jax.device_get(probe)
        # Python integers: an INT64 key's max - min may not fit 64 bits
        spans = [max(int(hi) - int(lo) + 1, 0) for lo, hi in zip(lows, highs)]
        ranges = [span + bool(null) for span, null in zip(spans, nulls)]
        domain = math.prod(ranges) if int(count) else 0
        if not 0 < domain <= _DENSE_MAX_SLOTS:
            sp.annotate(dense=False, domain=domain)
            return None
        # a key with no value present has nothing to subtract: only its NULL code is met
        mins = tuple(np.asarray(lo if span else 0, lo.dtype) for lo, span in zip(lows, spans))
        shifts = np.asarray(nulls, np.int32)
        strides = np.asarray([math.prod(ranges[i + 1:]) for i in range(len(ranges))], np.int32)
        layout = (datas, validities, present, mins, shifts, strides)
        # compiled for the domain rounded up as a group count is: the slots added are empty
        counts_dev = _slot_counts(*layout, domain=_static_groups(domain))
        counts = np.asarray(tracing.device_wait(counts_dev, "group_count"))  # host sync: group count
        occupied = np.flatnonzero(counts)
        num = len(occupied)
        sp.annotate(dense=True, domain=domain, groups=num)
        group_of_slot = np.full(counts.shape, num, np.int32)
        group_of_slot[occupied] = np.arange(num, dtype=np.int32)
        seg = _slot_group_ids(*layout, group_of_slot, np.int32(num))

    def put(a):  # beside the programs' own outputs, wherever those live
        return jax.device_put(a, counts_dev.sharding)

    with tracing.span("groupby.keys"):
        out = []
        for col, lo, shift, stride, width in zip(keys.columns, mins, shifts, strides, ranges):
            code = occupied // int(stride) % width
            data = (code - int(shift)).astype(lo.dtype) + lo  # wraps back into the key's dtype
            null = (code == 0) & bool(shift)
            out.append(Column(col.dtype, data=put(np.where(null, 0, data).astype(lo.dtype)),
                              validity=None if col.validity is None else put(~null)))
        sizes = Column(dt.INT64, data=put(counts[occupied].astype(np.int64)))
    return _Groups(None, seg, num, present, Table(out, list(keys.names)), lambda: sizes)


def _static_groups(num: int) -> int:
    """The group count ``_f64_sum_mean`` is compiled for: ``num`` rounded
    up to three significant bits (17 -> 20, 1000 -> 1024; up to 8 as it
    is), so a stream of batches whose count drifts meets at most eight
    programs an octave. The segments added are empty, sum to +0.0 and
    are sliced off."""
    q = 1 << max(num.bit_length() - 3, 0)
    return -(-num // q) * q


@tracing.launches
@functools.partial(jax.jit, static_argnames=("num", "how"))
def _f64_sum_mean(data, validity, order, seg, live, *, num: int, how: str):
    """Exact FLOAT64 ``sum`` or ``mean`` of every group as ONE program:
    the column's u64 lanes and validity gathered through ``order``, then
    ops/f64acc's windowed integer accumulation, carry normalisation, the
    long division of a mean and the rounding. Returns (IEEE bits [num],
    any row valid [num]) — the lanes the un-jitted chain returns, on
    every backend. ``f64acc``'s public functions stay plain (the fused
    pipeline and the mesh programs trace them into their own programs);
    the program boundary is here, at the eager op. ``live`` (None, or the
    sorted rows that are present) is part of a row's validity: ``num`` may
    be padded past the id the absent rows carry, so they are masked, not
    left to fall out of range. ``order`` None is the identity (the dense
    form: ``seg`` numbers the rows where they lie): nothing is gathered.
    "Any row valid" is the accumulation's own per-group exponent maximum
    read before its clamp (every valid row's is at least 1), so no pass
    over the rows is spent on it: for ``num`` up to 16 the program holds
    no scatter."""
    bits = _in_order(data, order)
    valid = _sorted_valid(validity, order, live, data.shape[0])
    if how == "sum":
        return f64acc._segment_sum(bits, seg, num, valid)
    out_bits, _, any_valid = f64acc._segment_mean(bits, seg, num, valid)
    return out_bits, any_valid


def _in_order(x: jnp.ndarray, order) -> jnp.ndarray:
    """``x``'s rows through ``order``; None is the identity and gathers nothing."""
    return x if order is None else x[order]


def _sorted_valid(validity, order, live, n: int) -> jnp.ndarray:
    """[N] bool: the sorted row (of ``n``) holds a value and is present."""
    if validity is None:
        return jnp.ones((n,), bool) if live is None else live
    valid = _in_order(validity, order)
    return valid if live is None else valid & live


def _is_f64_sum_mean(col: Column, how: str) -> bool:
    return how in ("sum", "mean") and col.dtype.id == TypeId.FLOAT64


def _agg_column(col: Column, order, seg, num, how: str, live=None) -> Column:
    d = col.dtype
    if _is_f64_sum_mean(col, how):
        # exact on all backends: windowed integer accumulation over
        # the stored IEEE bits (ops/f64acc) — correctly rounded f64,
        # bit-identical CPU vs TPU; matches the reference's real-f64
        # device reduction semantics (cudf segment reduce, SURVEY §2.8)
        metrics.registry().counter("groupby.agg.jitted").inc()
        padded = _static_groups(num)
        out_bits, any_valid = _f64_sum_mean(
            col.data, col.validity, order, seg, live, num=padded, how=how
        )
        if padded != num:
            out_bits, any_valid = out_bits[:num], any_valid[:num]
        return Column(dt.FLOAT64, data=out_bits, validity=any_valid)
    metrics.registry().counter("groupby.agg.eager").inc()
    sorted_valid = _sorted_valid(col.validity, order, live, len(col))

    if how == "count":
        data = jax.ops.segment_sum(sorted_valid.astype(jnp.int64), seg, num)
        return Column(dt.INT64, data=data)

    if how in _VAR_STD_HOWS:
        # numeric inputs only (Spark var_samp/stddev_samp — and the
        # var_pop/stddev_pop population variants — analysis rule):
        # BOOL8/TIMESTAMP/DURATION would silently compute variance over
        # raw codes / epoch ticks (ADVICE r5 low #5)
        if not (d.is_integral or d.is_floating):
            raise ValueError(
                f"{how} requires a numeric (integral or floating) column, got {d!r}"
            )
        return _var_std_column(col, order, seg, num, how, sorted_valid)

    any_valid = jax.ops.segment_max(sorted_valid.astype(jnp.int32), seg, num) > 0

    if how in ("min", "max") and d.is_fixed_width and d.id != TypeId.DECIMAL128:
        # exact via total-order keys even for floats on TPU
        key = _in_order(bitutils.total_order_key(col.data, d), order)
        udt = key.dtype
        fill = jnp.asarray(~jnp.zeros((), udt)) if how == "min" else jnp.zeros((), udt)
        key = jnp.where(sorted_valid, key, fill)
        red = jax.ops.segment_min if how == "min" else jax.ops.segment_max
        best = red(key, seg, num)
        data = _from_total_order(best, d)
        return Column(d, data=data, validity=any_valid)

    if how in ("sum", "mean"):
        if d.is_floating:  # FLOAT32
            vals = _in_order(col.data, order)
            vals = jnp.where(sorted_valid, vals, 0)
            s = jax.ops.segment_sum(vals, seg, num)
            if how == "mean":
                cnt = jax.ops.segment_sum(sorted_valid.astype(vals.dtype), seg, num)
                s = s / jnp.maximum(cnt, 1)
                return Column(
                    dt.FLOAT64,
                    data=bitutils.float_store(s, dt.FLOAT64),
                    validity=any_valid,
                )
            return Column(dt.FLOAT32, data=s.astype(jnp.float32), validity=any_valid)
        if d.id == TypeId.DECIMAL128:
            # limb-wise int64 partial sums + carry renormalize: summing
            # two's-complement limbs mod 2^128 is exact signed addition
            # (wraps on >128-bit overflow, like int128 accumulation would)
            limbs = _in_order(col.data, order)
            limbs = jnp.where(sorted_valid[:, None], limbs, 0)
            parts = [
                jax.ops.segment_sum(limbs[:, k].astype(jnp.int64), seg, num) for k in range(4)
            ]
            out = jnp.zeros((num, 4), jnp.uint32)
            carry = jnp.zeros((num,), jnp.int64)
            for k in range(4):
                t = parts[k] + carry
                out = out.at[:, k].set((t & 0xFFFFFFFF).astype(jnp.uint32))
                carry = t >> 32
            return Column(d, data=out, validity=any_valid)
        if how == "mean":
            vals = _in_order(col.data, order).astype(jnp.float64)
            vals = jnp.where(sorted_valid, vals, 0)
            s = jax.ops.segment_sum(vals, seg, num)
            cnt = jax.ops.segment_sum(sorted_valid.astype(jnp.float64), seg, num)
            m = s / jnp.maximum(cnt, 1)
            return Column(dt.FLOAT64, data=bitutils.float_store(m, dt.FLOAT64), validity=any_valid)
        # integral sum -> int64 (Spark sum semantics)
        vals = _in_order(col.data, order).astype(jnp.int64)
        vals = jnp.where(sorted_valid, vals, 0)
        s = jax.ops.segment_sum(vals, seg, num)
        return Column(dt.INT64, data=s, validity=any_valid)

    raise ValueError(f"unsupported aggregation {how!r} on {d!r}")


_VAR_STD_HOWS = ("var", "std", "var_pop", "stddev_pop")


def _var_std_column(col: Column, order, seg, num, how: str, sorted_valid) -> Column:
    """Sample variance / stddev (Spark var_samp / stddev_samp: DOUBLE
    out, NULL below two valid rows; q17/q39's missing primitive), plus
    the POPULATION variants ``var_pop`` / ``stddev_pop`` (Spark
    var_pop / stddev_pop: the same M2 divided by n instead of n-1,
    NULL only when NO valid rows — one valid row yields 0.0). Both
    families share the stable two-pass M2; only the divisor and the
    NULL threshold differ (VERDICT item 6, first slice).

    STABLE two-pass formulation — deviations from the group mean, not
    the raw-moment sumsq - sum^2/n (which cancels catastrophically for
    large-mean data: values ~1e9 with stddev ~1 would return noise).
    Pass 1 computes correctly rounded group means (segment_mean
    machinery); pass 2 sums (x - mean)^2. On the f64-less tier the
    deviation and square evaluate in the dd (double-f32, ~2^-48/op)
    domain, materialize to f64 bits through the elementwise two-addend
    adder, and segment-sum EXACTLY through the windowed accumulator —
    precision is set by the per-element deviation arithmetic, relative
    to the DEVIATIONS rather than the raw moments. The [G]-scale
    divide by (n-1) runs in real f64 on the host (this op is an eager
    boundary; the groupby already pays a host sync for the group
    count).

    Precision limit on the f64-less (dd) tier: non-FLOAT64 inputs pass
    through the dd split (~48-bit effective mantissa), so integer
    values with magnitude above 2^48 lose low bits BEFORE the
    deviation is formed — var/std of int64 data beyond +-2^48 is
    approximate there, while the real-f64 backend branch keeps the
    full 53-bit f64 mantissa (ADVICE r5 low #5)."""
    d = col.dtype
    if bitutils.backend_has_f64():
        if d.id == TypeId.FLOAT64:
            x = bitutils.float_view(col.data, d)
        else:
            x = col.data.astype(jnp.float64)
        xs = jnp.where(sorted_valid, _in_order(x, order), 0.0)
        cnt_dev = jax.ops.segment_sum(sorted_valid.astype(jnp.int64), seg, num)
        mean = jax.ops.segment_sum(xs, seg, num) / jnp.maximum(cnt_dev, 1)
        dx = jnp.where(sorted_valid, xs - mean[seg], 0.0)
        m2_np = np.asarray(jax.ops.segment_sum(dx * dx, seg, num), np.float64)
        cnt = np.asarray(cnt_dev).astype(np.float64)
    else:
        if d.id == TypeId.FLOAT64:
            pair = f64acc.dd_from_f64bits(col.data)
            xbits = _in_order(col.data, order)  # exact stored bits — no dd round trip
        else:
            pair = f64acc.dd_from_any(col.data)
            xbits = _in_order(f64acc.dd_to_f64bits(pair), order)
        mean_bits, cnt_dev = f64acc.segment_mean_f64bits(
            xbits, seg, num, valid=sorted_valid
        )
        mean_pair = f64acc.dd_from_f64bits(mean_bits)
        sp = f64acc.DD(_in_order(pair.hi, order), _in_order(pair.lo, order))
        dx = sp - f64acc.DD(mean_pair.hi[seg], mean_pair.lo[seg])
        d2 = dx * dx
        d2bits = f64acc.dd_to_f64bits(d2)
        m2bits = f64acc.segment_sum_f64bits(d2bits, seg, num, valid=sorted_valid)
        m2_np = np.asarray(m2bits).view(np.float64)
        cnt = np.asarray(cnt_dev).astype(np.float64)
    pop = how in ("var_pop", "stddev_pop")
    ok = cnt >= (1 if pop else 2)
    var = m2_np / np.maximum(cnt - (0 if pop else 1), 1.0)
    var = np.maximum(var, 0.0)
    out = np.sqrt(var) if how in ("std", "stddev_pop") else var
    return Column(
        dt.FLOAT64,
        data=jnp.asarray(np.where(ok, out, 0.0).view(np.uint64)),
        validity=jnp.asarray(ok),
    )


def _from_total_order(key: jnp.ndarray, d) -> jnp.ndarray:
    """Inverse of bitutils.total_order_key."""
    from jax import lax

    if d.id == TypeId.FLOAT64:
        neg = (key >> jnp.uint64(63)) == 0
        bits = jnp.where(neg, key ^ jnp.uint64(0xFFFFFFFFFFFFFFFF), key & ~jnp.uint64(1 << 63))
        return bits
    if d.id == TypeId.FLOAT32:
        neg = (key >> jnp.uint32(31)) == 0
        bits = jnp.where(neg, key ^ jnp.uint32(0xFFFFFFFF), key & ~jnp.uint32(1 << 31))
        return lax.bitcast_convert_type(bits, jnp.float32)
    if d.is_signed or d.np_dtype.kind == "i":
        sign_bit = jnp.asarray(1 << (8 * d.size_bytes - 1), dtype=key.dtype)
        return lax.bitcast_convert_type(key ^ sign_bit, d.jnp_dtype)
    return key.astype(d.jnp_dtype)


@op_boundary("groupby_aggregate")
def groupby_aggregate(
    keys: Table, values: Table, aggs: Sequence[Tuple[str, str]], present=None
) -> Table:
    """GROUP BY keys, computing aggs = [(value_col_name, how), ...].

    Returns a Table of unique keys followed by one column per agg named
    ``{col}_{how}``. Row order is key-sorted (callers needing original
    first-appearance order can re-sort; SQL imposes none).

    ``present`` (a ``bool[N]`` device array, or None: every row) names the
    rows that take part: the answer is, bit for bit, that over
    ``apply_boolean_mask(..., present)`` of both tables, with no
    compaction — the absent rows are sorted last and masked out of every
    aggregate (every ``how`` takes it, ``nunique`` too).

    Integer keys that span at most ``_DENSE_MAX_SLOTS`` values take the
    dense form (module docstring): no sort, no gather, the same answer.
    """
    # phase spans (srjt-trace): each times what the HOST did in the
    # phase — dispatching the phase's programs, and in
    # ``groupby.segments`` the one sync — not what the device did
    n = keys.num_rows
    groups = _dense_groups(keys, present) if n and _dense_key_dtypes(keys) else None
    metrics.registry().counter("groupby.sorted" if groups is None else "groupby.dense").inc()
    if groups is None:
        with tracing.span("groupby.sort", rows=n, keys=len(keys.columns), masked=present is not None):
            order = sorted_order(keys, present=present)
        with tracing.span("groupby.segments") as sp:
            # the host stalls in this phase's DISPATCH while the sort is still in the
            # device's queue (q1: 197 of the phase's 249 ms, and no value is read before
            # the group count): waiting for the order first gives that stall its name
            tracing.device_wait(order, "sort_order")
            # ``end``: where the last group's rows end in the sorted rows
            live, end = (None, n) if present is None else _live_rows(present)
            seg, num = _segment_ids(keys, order, live)
            sp.annotate(groups=num)
        if present is not None and num == 0 and n:
            # no row is present: the empty table's answer, dtypes and all
            order = seg = seg[:0]
            keys, values = gather(keys, order), gather(values, order)
            present = live = None
            n = end = 0

        with tracing.span("groupby.keys"):
            first_of_group = jnp.searchsorted(seg, jnp.arange(num, dtype=jnp.int32), side="left")
            out_keys = gather(keys, order[first_of_group] if n else jnp.zeros((0,), jnp.int32))
        groups = _Groups(order, seg, num, live, out_keys, lambda: _group_sizes(first_of_group, end))

    out_cols: List[Column] = list(groups.keys.columns)
    out_names: List[str] = list(groups.keys.names)
    for col_name, how in aggs:
        col = values.column(col_name)
        with tracing.span(
            f"groupby.agg.{how}", col=col_name, dtype=col.dtype.id.name,
            jit=_is_f64_sum_mean(col, how),
        ):
            if how == "nunique":
                out_cols.append(_nunique_column(keys, col, present, groups))
            elif how == "count_all":
                metrics.registry().counter("groupby.agg.eager").inc()
                out_cols.append(groups.sizes())
            else:
                out_cols.append(_agg_column(col, groups.order, groups.seg, groups.num, how, groups.live))
        out_names.append(f"{col_name}_{how}")
    return Table(out_cols, out_names)


def _group_sizes(first_of_group: jnp.ndarray, end) -> Column:
    """COUNT(*) of every group from where the groups start in the sorted
    rows: a group's rows are the next group's start less its own, and the
    last group ends at ``end``, the count of the rows that are present.
    No scatter: a ``segment_sum`` of ones over 6 M rows was 432 ms a q1
    request on the v5e (PERF.md, PR 33) for numbers the boundaries hold."""
    bounds = jnp.concatenate([first_of_group.astype(jnp.int64),
                              jnp.reshape(jnp.asarray(end, jnp.int64), (1,))])
    return Column(dt.INT64, data=bounds[1:] - bounds[:-1])


def _nunique_column(keys: Table, col: Column, present, groups: _Groups) -> Column:
    """COUNT(DISTINCT col) per group, nulls excluded (SQL semantics).

    Re-sorts by (keys..., col) so equal values are adjacent within each
    group; a value is a NEW distinct when it is valid and differs from
    its predecessor (or the predecessor is another group / null — nulls
    sort first within the group under nulls_first). Under a row mask
    (``present``, and ``live`` as ``_live_rows`` gives it) the second sort
    takes the mask's lane too. The dense form knows every row's group
    (``seg``, where the rows lie; the absent rows one past the last): the
    sort is by (group, col), and the absent rows trail by their id."""
    n, num, live = keys.num_rows, groups.num, groups.live
    if groups.dense:
        order2 = sorted_order(Table([Column(dt.INT32, data=groups.seg), col], ["__g", "__v"]))
        seg2 = groups.seg[order2]
        live = None if present is None else seg2 < num
    else:
        both = Table(list(keys.columns) + [col], list(keys.names) + ["__v"])
        order2 = sorted_order(both, present=present)
        seg2, num2 = _segment_ids(keys, order2, live)
        if num2 != num:
            raise AssertionError("group count mismatch between sort orders")
    if n == 0:
        return Column(dt.INT64, data=jnp.zeros((0,), jnp.int64))

    valid = _sorted_valid(col.validity, order2, live, n)
    same_val = _keys_equal_neighbor(col, order2)  # [n-1], value equal to prev
    same_group = seg2[1:] == seg2[:-1]
    prev_valid = valid[:-1]
    is_new_tail = valid[1:] & ~(same_group & same_val & prev_valid)
    is_new = jnp.concatenate([valid[:1], is_new_tail])
    data = jax.ops.segment_sum(is_new.astype(jnp.int64), seg2, num)
    return Column(dt.INT64, data=data)
