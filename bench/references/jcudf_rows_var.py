"""Plain JCUDF row layout with variable-width columns
(RowConversion.java:44-117) in numpy: every fixed-width value aligned to
its own size; a STRING's slot two ``u32`` (offset of its characters from
the row's start, length) aligned to 4; validity bytes after the last slot
(bit ``c % 8`` of byte ``c // 8`` set when valid); the characters of the
row's strings in column order straight after the validity bytes; the row
padded with zero bytes to a multiple of 8; ``int32`` row offsets; a batch
ends before it would pass 2^31 - 1 bytes.

A column is ``(type name, data, validity or None)``; a STRING's ``data``
is ``(int32 offsets [rows + 1], uint8 characters)``.
"""

from __future__ import annotations

import numpy as np

MAX_BATCH_BYTES = (1 << 31) - 1
CHUNK_ROWS = 1 << 16  # rows built at a time: bounds the padded scratch, changes no byte


def is_string(col) -> bool:
    return isinstance(col[1], tuple)


def rows_of(cols) -> int:
    if not cols:
        return 0
    return len(cols[0][1][0]) - 1 if is_string(cols[0]) else len(cols[0][1])


def layout(cols):
    """(start of each column's slot, offset of the validity bytes, end of
    the fixed section: where the first string's characters land)."""
    off, starts = 0, []
    for col in cols:
        size, align = (8, 4) if is_string(col) else (col[1].dtype.itemsize,) * 2
        off = -(-off // align) * align
        starts.append(off)
        off += size
    return starts, off, off + (len(cols) + 7) // 8


def row_sizes(cols) -> np.ndarray:
    """[rows] int64: the fixed section and the row's characters, padded to 8."""
    _, _, fixed_end = layout(cols)
    size = np.full(rows_of(cols), fixed_end, np.int64)
    for col in cols:
        if is_string(col):
            size += np.diff(col[1][0].astype(np.int64))
    return -(-size // 8) * 8


def batches_of(sizes: np.ndarray, max_batch_bytes: int = MAX_BATCH_BYTES):
    """[(first row, row past the last)]: rows are taken while the batch
    stays within ``max_batch_bytes``; a table of no rows is one empty batch."""
    cum = np.concatenate([[0], np.cumsum(sizes, dtype=np.int64)])
    out, start = [], 0
    while start < len(sizes):
        end = int(np.searchsorted(cum, cum[start] + max_batch_bytes, side="right")) - 1
        if end == start:
            raise ValueError(f"row {start} alone passes the batch limit")
        out.append((start, end))
        start = end
    return out or [(0, 0)]


def _padded_rows(cols, lo: int, hi: int, width: int, honour_nulls: bool) -> np.ndarray:
    """[hi - lo, width] uint8: rows lo..hi, each from byte 0 of its row, zero
    beyond its end."""
    starts, validity_off, fixed_end = layout(cols)
    n = hi - lo
    out = np.zeros((n, width), np.uint8)
    chars_at = np.full(n, fixed_end, np.int64)  # where the next string of each row goes
    for c, (col, start) in enumerate(zip(cols, starts)):
        _, data, validity = col
        if is_string(col):
            offs, chars = data
            o = offs[lo:hi + 1].astype(np.int64)
            lens = np.diff(o)
            slot = np.stack([chars_at, lens], axis=1).astype("<u4")
            out[:, start:start + 8] = slot.view(np.uint8).reshape(n, 8)
            row = np.repeat(np.arange(n), lens)
            within = np.arange(o[-1] - o[0]) - np.repeat(o[:-1] - o[0], lens)
            out[row, chars_at[row] + within] = chars[o[0]:o[-1]]
            chars_at = chars_at + lens
        else:
            size = data.dtype.itemsize
            out[:, start:start + size] = data[lo:hi].view(np.uint8).reshape(n, size)
        valid = np.ones(n, np.uint8) if validity is None or not honour_nulls else validity[lo:hi].astype(np.uint8)
        out[:, validity_off + c // 8] |= valid << np.uint8(c % 8)
    return out


def rows(cols, honour_nulls: bool = True, max_batch_bytes: int = MAX_BATCH_BYTES):
    """[(int32 offsets [rows of the batch + 1], uint8 bytes)], a batch each.
    ``honour_nulls=False`` is the control: it breaks the guarantee that a
    null's validity bit is clear."""
    sizes = row_sizes(cols)
    out = []
    for first, last in batches_of(sizes, max_batch_bytes):
        offsets = np.concatenate([[0], np.cumsum(sizes[first:last], dtype=np.int64)])
        blob = np.zeros(int(offsets[-1]), np.uint8)
        for lo in range(first, last, CHUNK_ROWS):
            hi = min(lo + CHUNK_ROWS, last)
            width = int(sizes[lo:hi].max())
            padded = _padded_rows(cols, lo, hi, width, honour_nulls)
            keep = np.arange(width)[None, :] < sizes[lo:hi, None]
            blob[offsets[lo - first]:offsets[hi - first]] = padded[keep]
        out.append((offsets.astype(np.int32), blob))
    return out
