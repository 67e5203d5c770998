"""Multi-key stable sort (cudf::sorted_order / sort_by_key tier).

TPU-first: every fixed-width key is mapped through
``bitutils.total_order_key`` to an unsigned integer whose order matches
the value order EXACTLY (floats via the IEEE total-order transform — so
FLOAT64 sorts are exact on TPU even though f64 arithmetic is
approximated). Null ordering is folded in by splitting the null flag
into a leading key. The composite sort is ``jnp.lexsort``, which XLA
lowers to its sort HLO on TPU.

String keys are ordered and compared over ALL their bytes
(``string_key_lanes``): ceil(longest / 8) big-endian u64 lanes of the
zero-padded bytes and, last, the length — byte-wise lexicographic with a
shorter string before a longer one it prefixes (Spark's UTF8String
binary order). The lane count follows the longest string present in the
column (``Column.max_char_len``, memoized), so a 22-byte brand costs
three lanes and a 200-byte description twenty-five; equality of every
lane is equality of the strings. The same lanes decide group boundaries
(ops/aggregate ``_keys_equal_neighbor``), join ids, window partitions
and ``nunique``.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar import Column, Table
from ..columnar.dtype import TypeId
from ..utils import metrics, tracing
from ..utils.dispatch import op_boundary
from . import bitutils
from .copying import gather, gather_column

__all__ = ["sorted_order", "sort_by_key", "string_key_lanes"]


@tracing.launches
@functools.partial(jax.jit, static_argnames=("lanes",))
def _string_lanes(offsets, chars, *, lanes: int) -> Tuple[jnp.ndarray, ...]:
    """``lanes`` big-endian u64 lanes of each row's bytes (shorter rows
    pad \\0), then the row's length as u32: one program per shape and
    lane count. A lane is two u32 halves joined by one shift — no 64-bit
    add or carry chain for the TPU compiler to fold."""
    starts = offsets[:-1]
    lens = offsets[1:] - starts
    last = chars.shape[0] - 1

    def byte(at):  # [N] u32: each row's byte ``at``, NUL past its end; row-major 1-D, no [N, 8] tile padding
        return jnp.where(at < lens, chars[jnp.clip(starts + at, 0, last)], 0).astype(jnp.uint32)

    def half(at):
        return (byte(at) << 24) | (byte(at + 1) << 16) | (byte(at + 2) << 8) | byte(at + 3)

    out = []
    for j in range(lanes):
        hi, lo = half(8 * j), half(8 * j + 4)
        out.append((hi.astype(jnp.uint64) << jnp.uint64(32)) | lo.astype(jnp.uint64))
    out.append(lens.astype(jnp.uint32))
    return tuple(out)


def string_key_lanes(col: Column) -> List[jnp.ndarray]:
    """Major-first unsigned key lanes of a STRING column over all its
    bytes: their lexicographic order is the strings' byte-wise order and
    their equality the strings' equality. The one source of a STRING
    key's lanes (sort, group boundaries, join ids, window partitions,
    nunique); null rows read as whatever bytes they hold — callers rank
    or mask nulls themselves."""
    lanes = -(-col.max_char_len // 8)
    reg = metrics.registry()
    reg.counter("keys.string.columns").inc()
    reg.counter("keys.string.lanes").inc(lanes + 1)
    return list(_string_lanes(col.offsets, col.chars, lanes=lanes))


def _column_keys(col: Column, ascending: bool, nulls_first: bool,
                 string_lanes: Optional[Sequence[jnp.ndarray]] = None) -> List[jnp.ndarray]:
    """Minor-to-major NOT applied here; returns [null_key, k2?, k1] style
    major-first list of u-int key lanes for one column. A STRING key's
    value lanes are ``string_lanes`` where given (made before a program
    that traces this: their count is a host value)."""
    if col.dtype.id == TypeId.STRING:
        lanes = string_key_lanes(col) if string_lanes is None else list(string_lanes)
    elif col.dtype.id == TypeId.DECIMAL128:
        # flip sign bit of the top limb; compare limbs high->low
        top = col.data[:, 3] ^ jnp.uint32(1 << 31)
        lanes = [
            (top.astype(jnp.uint64) << jnp.uint64(32)) | col.data[:, 2].astype(jnp.uint64),
            (col.data[:, 1].astype(jnp.uint64) << jnp.uint64(32))
            | col.data[:, 0].astype(jnp.uint64),
        ]
    else:
        lanes = [bitutils.total_order_key(col.data, col.dtype)]
    if not ascending:
        lanes = [~k if k.dtype in (jnp.uint64, jnp.uint32) else jnp.invert(k) for k in lanes]
    null_rank = (
        col.valid_mask().astype(jnp.uint8)
        if nulls_first
        else (~col.valid_mask()).astype(jnp.uint8)
    )
    return [null_rank] + lanes


def sorted_order(
    table: Table,
    ascending: Optional[Sequence[bool]] = None,
    nulls_first: Optional[Sequence[bool]] = None,
    present: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Stable gather indices ordering the table by its columns (leftmost
    key is most significant), parity with cudf::sorted_order semantics.
    ``present`` (``bool[N]``: the rows a filter kept) puts one more lane in
    front of every key, so the absent rows lie last and the rows that are
    present come first, in the order they would have had alone."""
    ncols = table.num_columns
    asc = list(ascending) if ascending is not None else [True] * ncols
    nf = list(nulls_first) if nulls_first is not None else [True] * ncols
    lanes: List[jnp.ndarray] = [] if present is None else [(~present).astype(jnp.uint8)]
    for col, a, f in zip(table.columns, asc, nf):
        lanes.extend(_column_keys(col, a, f))
    if tracing.is_enabled():  # lands on the caller's span: groupby.sort, op.sort_by_key, join.factorize
        tracing.annotate(
            key_lanes=len(lanes),
            string_keys=sum(c.dtype.id == TypeId.STRING for c in table.columns),
        )
    return _lexsort(lanes).astype(jnp.int32)


# The most lanes one sort program takes. The TPU compiler's time for a
# variadic sort grows faster than its operand count (14 operands compile
# in minutes off the chip, 29 not in 25): a wider key — a 200-byte STRING
# is 27 lanes — is sorted chunk by chunk from its minor lanes up, each a
# stable sort of the order so far, which is the same order. Every
# fixed-width key set in use and a 64-byte STRING stay one program.
_LEXSORT_LANES = 12


# ``jnp.lexsort`` is a program of its own name when called eagerly
_launch_lexsort = tracing.launches(jnp.lexsort)


def _lexsort(lanes: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """Stable order of major-first ``lanes``."""
    order = None
    for hi in range(len(lanes), 0, -_LEXSORT_LANES):
        chunk = lanes[max(hi - _LEXSORT_LANES, 0):hi]
        if order is not None:
            chunk = [k[order] for k in chunk]
        # lexsort: LAST key is primary -> reverse to make the major lane dominate
        step = _launch_lexsort(tuple(reversed(chunk)))
        order = step if order is None else order[step]
    return order


def _lane_count(col: Column, string_lanes) -> int:
    """How many lanes ``_column_keys`` makes for the key ``col``, without
    making them: its null rank and its value's."""
    if string_lanes is not None:
        return 1 + len(string_lanes)
    return 1 + (2 if col.dtype.id == TypeId.DECIMAL128 else 1)


def _ragged(col: Column) -> bool:
    """A column whose gather needs the host's total of its characters or children."""
    return col.dtype.id in (TypeId.STRING, TypeId.LIST)


@tracing.launches
@functools.partial(jax.jit, static_argnames=("ascending", "nulls_first"))
def _sort_gather(keys, string_lanes, values, *, ascending, nulls_first):
    """A Sort as ONE program: every key's lanes (``_column_keys``; a STRING
    key's value lanes come in as ``string_lanes``), their stable
    ``_lexsort``, and every fixed-width value column's data and validity
    gathered through the order. The order is a permutation, so no bounds
    mask and no clip. Returns the sorted columns and the order."""
    lanes: List[jnp.ndarray] = []
    for col, sl, a, f in zip(keys, string_lanes, ascending, nulls_first):
        lanes.extend(_column_keys(col, a, f, sl))
    order = _lexsort(lanes).astype(jnp.int32)
    out = [Column(c.dtype, data=c.data[order], validity=None if c.validity is None else c.validity[order])
           for c in values]
    return out, order


@op_boundary("sort_by_key")
def sort_by_key(values: Table, keys: Table, ascending=None, nulls_first=None) -> Table:
    """``values`` in the stable order of ``keys`` (``gather(values,
    sorted_order(keys, ...))``, to the bit): the key lanes, the sort and
    every fixed-width column's gather are one program (``_sort_gather``);
    a STRING key's lanes are made before it, a STRING or LIST value column
    is gathered after it, through the order."""
    # what the sort waits for is what its caller left in the device's queue (q1: the seven
    # aggregate programs behind ``values``): named here, before the operator reads its input
    tracing.device_wait((keys, values), "sort_input")
    if values.num_rows == 0:  # nothing to order: the empty gather every caller of ``gather`` gets
        return gather(values, sorted_order(keys, ascending, nulls_first))
    ncols = keys.num_columns
    asc = tuple(ascending) if ascending is not None else (True,) * ncols
    nf = tuple(nulls_first) if nulls_first is not None else (True,) * ncols
    string_lanes = tuple(tuple(string_key_lanes(c)) if c.dtype.id == TypeId.STRING else None
                         for c in keys.columns)
    # a STRING key enters as its validity and offsets (its null rank and length): no characters
    key_cols = tuple(Column(c.dtype, validity=c.validity, offsets=c.offsets) if sl is not None else c
                     for c, sl in zip(keys.columns, string_lanes))
    if tracing.is_enabled():
        tracing.annotate(
            key_lanes=sum(_lane_count(c, sl) for c, sl, _, _ in zip(key_cols, string_lanes, asc, nf)),
            string_keys=sum(sl is not None for sl in string_lanes),
        )
    fixed, order = _sort_gather(key_cols, string_lanes,
                                tuple(c for c in values.columns if not _ragged(c)),
                                ascending=asc, nulls_first=nf)
    reg = metrics.registry()
    reg.counter("sort.one_program").inc()
    fixed = iter(fixed)
    cols = []
    for c in values.columns:
        if _ragged(c):
            reg.counter("sort.columns_outside").inc()
            cols.append(gather_column(c, order))
        else:
            cols.append(next(fixed))
    return Table(cols, values.names)
