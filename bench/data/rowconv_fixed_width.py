"""The reference's row-conversion benchmark table: host numpy arrays only.

NVIDIA/spark-rapids-jni v22.12 ``src/main/cpp/benchmarks/row_conversion.cpp``
:27-67: ``fixed_width`` cycles nine fixed-width types over 212 columns with
full-range random values. The list and its order are the source's
``cycle_dtypes({...}, 212)``: INT32 comes twice and there is no UINT32
(set down from two readers' memory of the file: this sandbox has no
network, PERF.md section 7). Under JCUDF alignment that order makes a
1,160-byte row. Assumed on top: every fourth column ~10% null
(the reference's generator default carries nulls too), and the table is one
fixed draw whose rows the seed permutes, so every seed moves the same
bytes in another order.
"""

from __future__ import annotations

import numpy as np

BASE_STREAM = 221200
# (TypeId name in the program's columnar.dtype, numpy dtype), in the order of row_conversion.cpp:31-40
NUMPY_CODE = {"INT8": "i1", "INT16": "i2", "INT32": "i4", "INT64": "i8",
              "UINT8": "u1", "UINT16": "u2", "UINT64": "u8", "BOOL8": "u1"}
CYCLE = ("INT8", "INT32", "INT16", "INT64", "INT32", "BOOL8", "UINT16", "UINT8", "UINT64")


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
        d = np.dtype(NUMPY_CODE[tname])
        if tname == "BOOL8":
            data = base.integers(0, 2, rows, dtype=np.uint8)
        else:
            info = np.iinfo(d)
            data = base.integers(info.min, info.max, rows, dtype=d, endpoint=True)
        validity = (base.random(rows) >= 0.1) if i % 4 == 0 else None
        cols.append((tname, data[p], None if validity is None else validity[p]))
    return {"table": cols}
