"""The reference's row-conversion benchmark table with strings: host numpy
arrays only.

NVIDIA/spark-rapids-jni v22.12 ``src/main/cpp/benchmarks/row_conversion.cpp``
:69-138: ``variable_or_fixed_width`` with strings cycles the nine
fixed-width types of ``fixed_width`` and STRING over 155 columns. Set down
from memory of the file (this sandbox has no network; the configuration
lists it under ``assumed``): the nine in ``fixed_width``'s order, STRING
last; string lengths as the source's generator draws them by default,
normal over 0-32 bytes (mean 16, a sixth of the range as its deviation),
printable ASCII. Assumed on top: columns with ``i % 4 == 1`` carry ~10%
nulls (8 of the 15 STRING columns among them) and a NULL string has no
characters. The table is one fixed draw whose rows the seed permutes, so
every seed moves the same bytes in another order: the same byte total,
the same longest row, the same longest string a column.

A STRING column's data is ``(int32 offsets [rows + 1], uint8 characters)``,
never a Python object a row.
"""

from __future__ import annotations

import numpy as np

BASE_STREAM = 221234
NUMPY_CODE = {"INT8": "i1", "INT16": "i2", "INT32": "i4", "INT64": "i8",
              "UINT8": "u1", "UINT16": "u2", "UINT64": "u8", "BOOL8": "u1"}
CYCLE = ("INT8", "INT32", "INT16", "INT64", "INT32", "BOOL8", "UINT16", "UINT8", "UINT64", "STRING")
MAX_LEN = 32


def _string_column(base, p, validity):
    """A fixed draw of lengths and of a [rows, MAX_LEN] block of printable
    ASCII, permuted by rows, then laid end to end."""
    rows = len(p)
    lens = np.clip(np.rint(base.normal(MAX_LEN / 2, MAX_LEN / 6, rows)), 0, MAX_LEN).astype(np.int32)
    block = base.integers(0x20, 0x7F, (rows, MAX_LEN), dtype=np.uint8)
    if validity is not None:
        lens[~validity] = 0
    lens, block = lens[p], block[p]
    offsets = np.zeros(rows + 1, np.int32)
    np.cumsum(lens, out=offsets[1:])
    return offsets, block[np.arange(MAX_LEN)[None, :] < lens[:, None]]


def host_tables(config: dict, seed: int, rows: int) -> dict:
    """{"table": [(type name, data, validity or None), ...]} in column order."""
    ncols = int(config["tables"]["table"]["columns"])
    cycle = tuple(config["tables"]["table"]["types"])
    if cycle != CYCLE:
        raise SystemExit(f"bench: the configuration's types {cycle} are not the source's cycle {CYCLE}")
    base = np.random.default_rng(BASE_STREAM)
    p = np.random.default_rng(seed).permutation(rows)  # one row order for the whole table
    cols = []
    for i in range(ncols):
        tname = CYCLE[i % len(CYCLE)]
        validity = (base.random(rows) >= 0.1) if i % 4 == 1 else None
        if tname == "STRING":
            data = _string_column(base, p, validity)
        elif tname == "BOOL8":
            data = base.integers(0, 2, rows, dtype=np.uint8)[p]
        else:
            d = np.dtype(NUMPY_CODE[tname])
            info = np.iinfo(d)
            data = base.integers(info.min, info.max, rows, dtype=d, endpoint=True)[p]
        cols.append((tname, data, None if validity is None else validity[p]))
    return {"table": cols}
