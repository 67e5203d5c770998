"""From a profiler trace and a span log to numbers.

The profiler's ``.xplane.pb`` is first cut down to a *reduced trace*, a
plain dict that bench/fixtures/ can hold as JSON:

    {"anchor": {"trace_ns": ..., "wall_s": ...},
     "devices": {"/device:TPU:0": {"ops": [[name, start_ns, dur_ns], ...],
                                   "programs": [[name, start_ns, dur_ns], ...]}}}

``ops`` are the device's operations (the "XLA Ops" line), ``programs``
its program launches (the "XLA Modules" line). Times are nanoseconds on
the profiler's clock; ``anchor`` is one host event whose wall-clock time
the harness read itself, so spans (wall clock) and device events land on
one clock. Every reduction below works on that dict and on lists of
spans ``{"name", "ts" (wall s), "dur_us"}``: no JAX needed.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

import numpy as np

ANCHOR = "bench.anchor"
_OPS_LINES = ("XLA Ops",)
_PROGRAM_LINES = ("XLA Modules",)
_NOT_OPS = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops", "Framework Name Scope",
            "Source code", "Launch Stats")


def short(name: str) -> str:
    """An HLO instruction's text cut to what tells it apart: its name and
    the start of its shapes, without layouts, in at most 64 characters."""
    name = re.sub(r"\{[^{}]*\}", "", name.lstrip("%"))
    return re.sub(r"\s+", " ", name)[:64]


def reduce_xplane(trace_dir: str, anchor_wall_s: float) -> dict:
    """Read the newest ``.xplane.pb`` under ``trace_dir`` with nothing but
    JAX and cut it down to the reduced form."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
                   key=os.path.getmtime)
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    devices, anchor_ns, seen = {}, None, []
    for plane in data.planes:
        lines = list(plane.lines)
        seen.append((plane.name, [ln.name for ln in lines]))
        if plane.name.startswith("/device:") and "TPU" in plane.name and "SparseCore" not in plane.name:
            ops = [ln for ln in lines if ln.name in _OPS_LINES]
            if not ops:  # a trace without the line: every line that holds operations
                ops = [ln for ln in lines if ln.name not in _NOT_OPS]
            devices[plane.name] = {
                "ops": [[short(e.name), int(e.start_ns), int(e.duration_ns)] for ln in ops for e in ln.events],
                "programs": [[short(e.name), int(e.start_ns), int(e.duration_ns)]
                             for ln in lines if ln.name in _PROGRAM_LINES for e in ln.events],
            }
        elif anchor_ns is None and plane.name.startswith("/host:"):
            for ln in lines:
                for e in ln.events:
                    if e.name == ANCHOR:
                        anchor_ns = int(e.start_ns)
                        break
                if anchor_ns is not None:
                    break
    if anchor_ns is None:
        raise RuntimeError(f"the trace holds no {ANCHOR!r} event; planes: {seen}")
    return {"anchor": {"trace_ns": anchor_ns, "wall_s": anchor_wall_s}, "devices": devices,
            "planes_seen": seen}


def to_trace_ns(trace: dict, wall_s: float) -> float:
    a = trace["anchor"]
    return a["trace_ns"] + (wall_s - a["wall_s"]) * 1e9


def _merged(intervals: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Union of [start, end) rows as sorted disjoint starts and ends."""
    if len(intervals) == 0:
        return np.zeros(0), np.zeros(0)
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    idx = np.flatnonzero(new)
    last = np.append(idx[1:] - 1, len(iv) - 1)
    return starts, ends[last]


def _clipped_ops(dev: dict, w0: float, w1: float) -> np.ndarray:
    if not dev["ops"]:
        return np.zeros((0, 2))
    a = np.array([[s, s + d] for _, s, d in dev["ops"]], float)
    a = np.clip(a, w0, w1)
    return a[a[:, 1] > a[:, 0]]


def busy_seconds(trace: dict, w0_ns: float, w1_ns: float) -> float:
    """Seconds in which an operation ran on the device inside the window,
    averaged over the devices in the trace."""
    per = []
    for dev in trace["devices"].values():
        s, e = _merged(_clipped_ops(dev, w0_ns, w1_ns))
        per.append(float((e - s).sum()) / 1e9)
    return sum(per) / len(per) if per else 0.0


def programs_in(trace: dict, w0_ns: float, w1_ns: float) -> int:
    """Device program launches that start inside the window, all devices."""
    return sum(1 for dev in trace["devices"].values() for _, s, _ in dev["programs"] if w0_ns <= s < w1_ns)


def top_device_ops(trace: dict, w0_ns: float, w1_ns: float, n: int = 10) -> List[list]:
    total: Dict[str, float] = {}
    for dev in trace["devices"].values():
        for name, s, d in dev["ops"]:
            lo, hi = max(s, w0_ns), min(s + d, w1_ns)
            if hi > lo:
                total[name] = total.get(name, 0.0) + (hi - lo) / 1e9
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def innermost_timeline(spans: List[dict], trace: dict) -> Tuple[np.ndarray, List[str]]:
    """Cut the host's time at every span boundary and name each piece by
    the span that was opened last among those open (the innermost one).
    Returns boundaries (trace ns, ascending) and the name of each piece
    between consecutive boundaries ('' where no span is open)."""
    ivs = []
    for s in spans:
        a = to_trace_ns(trace, s["ts"])
        ivs.append((a, a + s["dur_us"] * 1e3, s["name"]))
    points = sorted({p for a, b, _ in ivs for p in (a, b)})
    if len(points) < 2:
        return np.array(points, float), []
    ivs.sort()
    names, open_, k = [], [], 0
    for left in points[:-1]:
        while k < len(ivs) and ivs[k][0] <= left:
            open_.append(ivs[k])
            k += 1
        open_ = [iv for iv in open_ if iv[1] > left]
        names.append(max(open_)[2] if open_ else "")
    return np.array(points, float), names


def idle_by_span(trace: dict, spans: List[dict], w0_ns: float, w1_ns: float, n: int = 10) -> List[list]:
    """The device's idle seconds inside the window, attributed to the
    innermost span open on the host at the time. Idle time under no
    span goes to '(no span)'. First device of the trace."""
    if not trace["devices"]:
        return []
    dev = next(iter(trace["devices"].values()))
    bs, be = _merged(_clipped_ops(dev, w0_ns, w1_ns))
    cum = np.concatenate([[0.0], np.cumsum(be - bs)])

    def busy_before(t):
        """Busy ns before each time t."""
        i = np.searchsorted(bs, t, side="right")  # intervals that started by t
        full = cum[i]
        over = np.where(i > 0, np.maximum(be[np.maximum(i - 1, 0)] - t, 0.0), 0.0) if len(be) else 0.0
        return full - over

    points, names = innermost_timeline(spans, trace)
    cuts = [w0_ns] + [p for p in points if w0_ns < p < w1_ns] + [w1_ns]
    cuts = np.array(cuts, float)
    mids = (cuts[:-1] + cuts[1:]) / 2
    if len(points) >= 2:
        idx = np.searchsorted(points, mids, side="right") - 1
        piece = [names[i] if 0 <= i < len(names) else "" for i in idx]
    else:
        piece = [""] * len(mids)
    idle = (cuts[1:] - cuts[:-1]) - (busy_before(cuts[1:]) - busy_before(cuts[:-1]))
    total: Dict[str, float] = {}
    for name, ns in zip(piece, idle):
        key = name or "(no span)"
        total[key] = total.get(key, 0.0) + float(ns) / 1e9
    return [[k[:64], v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n] if v > 0]


def span_mean_ms(spans: List[dict], name: str, requests: int) -> "float | None":
    """Summed duration of the spans of that name over the requests."""
    hit = [s["dur_us"] for s in spans if s["name"] == name]
    if not hit or not requests:
        return None
    return sum(hit) / 1e3 / requests


def read_span_log(base: str) -> List[dict]:
    """Every span of every process that logged under ``base``
    (``<base>.<pid>.jsonl``)."""
    import json

    out = []
    for path in sorted(glob.glob(base + ".*.jsonl")):
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                rec = json.loads(line)
                if rec.get("kind") == "span":  # a flushed root ("trace") carries its tree again
                    out.append(rec)
    return out
