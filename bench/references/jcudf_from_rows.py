"""Plain inverse of the JCUDF row layout (RowConversion.java:44-117) in
numpy: each column's value read at its aligned offset of the row and
viewed as its type, its validity bit ``c % 8`` of validity byte ``c // 8``.
The offsets are ``jcudf_rows.layout``'s, so both directions share one
statement of the layout."""

from __future__ import annotations

import importlib.util
import os

import numpy as np


def _jcudf_rows():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "jcudf_rows.py")
    spec = importlib.util.spec_from_file_location("bench_references_jcudf_rows", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


layout = _jcudf_rows().layout


def columns(rows, dtypes, honour_nulls: bool = True):
    """[(values, valid)] a column in schema order: ``rows`` is
    [n, row_size] uint8, ``dtypes`` the columns' numpy dtypes; ``values``
    is [n] of its dtype, ``valid`` [n] bool. ``honour_nulls=False`` is the
    control: every row valid, whatever its bit says."""
    n = rows.shape[0]
    starts, validity_off, row_size = layout([(None, np.empty(0, d), None) for d in dtypes])
    if rows.shape[1] != row_size:
        raise ValueError(f"rows of {rows.shape[1]} bytes, the schema's are {row_size}")
    out = []
    for c, (d, start) in enumerate(zip(dtypes, starts)):
        d = np.dtype(d)
        values = np.ascontiguousarray(rows[:, start:start + d.itemsize]).view(d).reshape(n)
        if honour_nulls:
            valid = (rows[:, validity_off + c // 8] >> np.uint8(c % 8)) & np.uint8(1) == 1
        else:
            valid = np.ones(n, bool)
        out.append((values, valid))
    return out
