"""Row movement primitives: gather, boolean-mask filter, slice, concat.

The cuDF-tier copying surface (SURVEY §2.8 — `cudf::gather`,
`apply_boolean_mask`, `concatenate`) rebuilt TPU-first: a gather over a
Table is one fused XLA gather per buffer; string columns re-derive
offsets from gathered lengths and gather chars with the searchsorted
row-binning pattern shared with row_conversion.

Static-shape discipline: ops whose output size is data-dependent
(filter) sync the size to host once (the reference's kernels do the same
via a device count + allocation).
"""

from __future__ import annotations

from typing import List, Sequence

import jax.numpy as jnp
import numpy as np
from jax import lax

from ..columnar import Column, Table
from ..columnar import dtype as dt
from ..columnar.dtype import TypeId
from ..utils import tracing

__all__ = ["gather", "gather_column", "apply_boolean_mask", "concatenate", "slice_table"]


def _all_null_column(d, n_out: int) -> Column:
    from ..columnar.dtype import TypeId as _T

    valid = jnp.zeros((n_out,), bool)
    if d.id == _T.STRING:
        return Column(
            d,
            validity=valid,
            offsets=jnp.zeros((n_out + 1,), jnp.int32),
            chars=jnp.zeros((0,), jnp.uint8),
        )
    if d.id == _T.LIST:
        return Column(
            d,
            validity=valid,
            offsets=jnp.zeros((n_out + 1,), jnp.int32),
            child=Column(dt.INT8, data=jnp.zeros((0,), jnp.int8)),
        )
    if d.id == _T.DECIMAL128:
        return Column(d, data=jnp.zeros((n_out, 4), jnp.uint32), validity=valid)
    return Column(d, data=jnp.zeros((n_out,), d.jnp_dtype), validity=valid)


def gather_column(col: Column, idx: jnp.ndarray, check_bounds: bool = False) -> Column:
    """New column with rows col[idx[i]]. Out-of-range -> null when
    check_bounds, matching cudf's bounds-policy NULLIFY."""
    n_out = idx.shape[0]
    n_in = len(col)
    idx = idx.astype(jnp.int32)
    if n_in == 0:
        # gathering from an empty source (e.g. the null-extended side of
        # an outer join against an empty table): every row is OOB-null
        if not check_bounds and n_out > 0:
            raise IndexError("gather from empty column without check_bounds")
        return _all_null_column(col.dtype, n_out)
    oob = (idx < 0) | (idx >= n_in)
    safe = jnp.clip(idx, 0, max(n_in - 1, 0))

    valid = None
    if col.validity is not None:
        valid = col.validity[safe]
    if check_bounds:
        v = jnp.ones((n_out,), bool) if valid is None else valid
        valid = v & ~oob

    if col.dtype.id == TypeId.STRING:
        offs = col.offsets
        lens = (offs[1:] - offs[:-1])[safe]
        new_offs = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(lens, dtype=jnp.int32)]
        )
        total = int(tracing.device_wait(new_offs[-1], "string_chars"))  # host sync: chars allocation
        if total == 0:
            chars = jnp.zeros((0,), jnp.uint8)
        else:
            j = jnp.arange(total, dtype=jnp.int32)
            row_of = jnp.searchsorted(new_offs, j, side="right").astype(jnp.int32) - 1
            src = offs[safe[row_of]] + (j - new_offs[row_of])
            chars = col.chars[src]
        return Column(col.dtype, validity=valid, offsets=new_offs, chars=chars)
    if col.dtype.id == TypeId.LIST:
        offs = col.offsets
        lens = (offs[1:] - offs[:-1])[safe]
        new_offs = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(lens, dtype=jnp.int32)]
        )
        total = int(tracing.device_wait(new_offs[-1], "list_children"))
        j = jnp.arange(total, dtype=jnp.int32)
        row_of = jnp.searchsorted(new_offs, j, side="right").astype(jnp.int32) - 1
        src = offs[safe[row_of]] + (j - new_offs[row_of])
        child = gather_column(col.child, src)
        return Column(col.dtype, validity=valid, offsets=new_offs, child=child)
    return Column(col.dtype, data=col.data[safe], validity=valid)


def gather(table: Table, idx: jnp.ndarray, check_bounds: bool = False) -> Table:
    return Table([gather_column(c, idx, check_bounds) for c in table.columns], table.names)


def apply_boolean_mask(table: Table, mask) -> Table:
    """Keep rows where mask is True (and non-null): cudf apply_boolean_mask."""
    if isinstance(mask, Column):
        m = mask.data.astype(bool)
        if mask.validity is not None:
            m = m & mask.validity
    else:
        m = jnp.asarray(mask, bool)
    # ``jnp.nonzero`` reads its own size: the wait for the mask comes first, under its own name
    idx = jnp.nonzero(tracing.device_wait(m, "mask_nonzero"))[0].astype(jnp.int32)  # host sync on size
    return gather(table, idx)


def slice_table(table: Table, start: int, end: int) -> Table:
    n = table.num_rows
    idx = jnp.arange(max(0, min(start, n)), max(0, min(end, n)), dtype=jnp.int32)
    return gather(table, idx)


def concatenate(tables: Sequence[Table]) -> Table:
    """Row-wise concat of same-schema tables (cudf::concatenate)."""
    tables = [t for t in tables if t.num_rows > 0] or list(tables[:1])
    first = tables[0]
    out: List[Column] = []
    for ci in range(first.num_columns):
        cols = [t.columns[ci] for t in tables]
        d = cols[0].dtype
        has_valid = any(c.validity is not None for c in cols)
        valid = (
            jnp.concatenate([c.valid_mask() for c in cols]) if has_valid else None
        )
        if d.id == TypeId.STRING:
            lens = jnp.concatenate([c.offsets[1:] - c.offsets[:-1] for c in cols])
            offs = jnp.concatenate(
                [jnp.zeros((1,), jnp.int32), jnp.cumsum(lens, dtype=jnp.int32)]
            )
            chars = jnp.concatenate([c.chars for c in cols])
            out.append(Column(d, validity=valid, offsets=offs, chars=chars))
        else:
            out.append(Column(d, data=jnp.concatenate([c.data for c in cols]), validity=valid))
    return Table(out, first.names)
