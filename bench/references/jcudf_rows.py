"""Plain JCUDF row layout (RowConversion.java:44-117) in numpy: every
column aligned to its own size, validity bytes after the last column
(bit ``c % 8`` of byte ``c // 8`` set when valid), rows padded to 8."""

from __future__ import annotations

import numpy as np


def layout(cols):
    """(start offset of each column, offset of the validity bytes, row size)."""
    off, starts = 0, []
    for _, data, _ in cols:
        size = data.dtype.itemsize
        off = -(-off // size) * size
        starts.append(off)
        off += size
    row_size = -(-(off + (len(cols) + 7) // 8) // 8) * 8
    return starts, off, row_size


def rows(cols, honour_nulls: bool = True):
    """[rows, row_size] uint8. ``honour_nulls=False`` is the control: it
    breaks the guarantee that a null's validity bit is clear."""
    n = len(cols[0][1])
    starts, validity_off, row_size = layout(cols)
    out = np.zeros((n, row_size), np.uint8)
    for c, ((_, data, validity), start) in enumerate(zip(cols, starts)):
        size = data.dtype.itemsize
        out[:, start:start + size] = data.view(np.uint8).reshape(n, size)
        valid = np.ones(n, np.uint8) if validity is None or not honour_nulls else validity.astype(np.uint8)
        out[:, validity_off + c // 8] |= valid << np.uint8(c % 8)
    return out, row_size
