"""The least time the chip could take for a request, from the schema.

Bytes of every input column a request reads, once, plus the bytes of its
result, over the HBM peak of the device. The byte count comes from the
builder's host tables (the configuration's schema at the run's row
counts) and the query's READS list, never from the program, so it reads the same work whatever implements
it. Peaks: bench/peaks.json, keyed by ``device_kind``; an unknown kind is
an error, not a default.
"""

from __future__ import annotations

from .loader import read_json


def peak(device_kind: str) -> dict:
    peaks = read_json("peaks.json")
    if device_kind not in peaks:
        raise SystemExit(f"bench: no peaks for device kind {device_kind!r} in bench/peaks.json")
    return peaks[device_kind]


def column_bytes(host: dict, reads: dict) -> int:
    """Bytes of the named columns of the builder's host tables, as a
    columnar engine holds them: the values at their width; for a column
    with nulls a validity bit a row; for strings the characters and a
    4-byte offset a row."""
    total = 0
    for table, cols in reads.items():
        for c in cols:
            a, valid = host[table][c] if isinstance(host[table][c], tuple) else (host[table][c], None)
            if a.dtype == object:
                total += sum(len(s.encode()) for s in a) + 4 * (len(a) + 1)
            else:
                total += a.nbytes
            if valid is not None:
                total += (len(a) + 7) // 8
    return total


def least_ms(request_bytes: int, device_kind: str) -> float:
    return 1e3 * request_bytes / peak(device_kind)["hbm_bytes_per_s"]
