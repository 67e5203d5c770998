"""The bytes a request's exchanges have to move off a chip, from the schema.

For each exchange the query's reference names (rows entering, table,
columns carried): rows x the bytes of those columns as a columnar engine
holds them (the values at their width, a validity bit a row where the
column carries nulls) x (chips - 1) / chips, the share of hash-partitioned
rows whose home is another chip, over the chips. Counted from the
builder's host tables and the REFERENCE's row counts, never from the
program: padding, bucket matrices and a second shuffle of rows that were
in place are not work. Peaks: bench/ici_peaks.json, keyed by
``device_kind``; an unknown kind is an error, not a default.
"""

from __future__ import annotations

from .loader import read_json


def row_bytes(host: dict, table: str, columns) -> float:
    total = 0.0
    for c in columns:
        a = host[table][c]
        total += (a[0].dtype.itemsize + 1 / 8) if isinstance(a, tuple) else a.dtype.itemsize
    return total


def off_chip_per_chip(host: dict, exchanges, chips: int) -> float:
    """Bytes that leave each chip, a request."""
    moved = sum(rows * row_bytes(host, table, columns) for rows, table, columns in exchanges)
    return moved * (chips - 1) / chips / chips


def ici_peak(device_kind: str) -> dict:
    peaks = read_json("ici_peaks.json")
    if device_kind not in peaks:
        raise SystemExit(f"bench: no ICI peak for device kind {device_kind!r} in bench/ici_peaks.json")
    return peaks[device_kind]
