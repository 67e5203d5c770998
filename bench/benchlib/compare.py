"""The comparison that decides ``correct``: numbers, each beside its limit.

A result table of the program (host copies of its columns) against the
plain reference's frame: names, row count, keys and counts exactly, every
other column by its widest relative gap. The limits are data
(``bench/configs/<config>.json`` -> ``limits``); PERF.md says what
readings each was set from.
"""

from __future__ import annotations

import math
from typing import Dict, List

import numpy as np


def table_numbers(got: Dict[str, np.ndarray], nulls: int, want, exact) -> Dict[str, float]:
    """{"shape_diff", "exact_diff", "rel_gap"} of one answer. ``got`` maps
    column name to host values in the program's column order; ``want`` is
    the reference's pandas frame; ``exact`` names keys and counts; ``nulls``
    counts the NULLs among them. A NULL in any other column is a NaN."""
    names = list(got)
    rows = len(next(iter(got.values()))) if got else 0
    if names != list(want.columns) or rows != len(want) or rows == 0:
        # an empty answer proves nothing and counts as a wrong shape
        return {"shape_diff": 1.0, "exact_diff": math.inf, "rel_gap": math.inf}
    exact_diff, gap = float(nulls), 0.0
    for n in names:
        w = want[n].to_numpy()
        g = got[n]
        if n in exact:
            if w.dtype == object:  # strings
                exact_diff += float(sum(a != b for a, b in zip(g, w)))
            else:
                exact_diff += float(np.count_nonzero(g.astype(np.int64) != w.astype(np.int64)))
            continue
        g, w = g.astype(np.float64), w.astype(np.float64)
        null = np.isnan(w)  # a NULL of the reference (a sum over no value) has to be a NULL of the answer
        exact_diff += float(np.count_nonzero(np.isnan(g) != null))
        g, w = g[~null & ~np.isnan(g)], w[~null & ~np.isnan(g)]
        if not np.all(np.isfinite(g)):
            gap = math.inf
            continue
        if len(g):
            scale = np.maximum(np.abs(w), np.finfo(np.float64).tiny)
            gap = max(gap, float(np.max(np.abs(g - w) / scale)))
    return {"shape_diff": 0.0, "exact_diff": exact_diff, "rel_gap": gap}


def worst(numbers: List[Dict[str, float]]) -> Dict[str, float]:
    """The widest of each number over many answers."""
    out: Dict[str, float] = {}
    for d in numbers:
        for k, v in d.items():
            out[k] = max(out.get(k, 0.0), v)
    return out


def judge(readings: Dict[str, float], limits: Dict[str, float]) -> List[dict]:
    """Each reading beside its limit; a reading with no limit is a fault
    of the benchmark's files, not a pass."""
    checks = []
    for name, value in readings.items():
        key = name.rsplit(".", 1)[-1]
        if key not in limits:
            raise SystemExit(f"bench: no limit for {name!r} in the configuration's limits")
        limit = float(limits[key])
        ok = bool(value <= limit) and not math.isnan(value)
        checks.append({"name": name, "value": value, "limit": limit, "ok": ok})
    return checks
