"""srjt-trace span emitter + slow-query flight recorder (ISSUE 12).

The sink half of the tracing subsystem (utils/tracing.py owns the
context/span front door): this module writes the per-process JSON-lines
span log and keeps the bounded ring of recently completed query traces.

- **Span log**: ``SRJT_TRACE_LOG=<base>`` makes every process append
  its finished spans to ``<base>.<pid>.jsonl`` — one JSON object per
  line, one file per process (client, each sidecar worker, each
  exchange peer), which is exactly the join input
  ``python -m spark_rapids_jni_tpu.analysis.tracemerge`` turns into
  per-trace trees and Chrome/Perfetto JSON. The log is written PER
  REQUEST, not per span: a finished span's record is appended to an
  in-memory list, and the list is serialised and written in one
  ``write()`` at a flush point — a root trace finishing
  (``QueryTrace.finish``), a ``remote_scope`` exiting (the worker's /
  exchange peer's end of a request), ``close_log()`` /
  ``set_log_path()``, the sidecar ``STATS`` verb, interpreter exit,
  and the list passing ``_BUFFER_MAX`` records (so a process that
  never finishes a root cannot grow without bound). A span that
  finishes after its request's flush point (a hedge loser, a
  straggling thread) is flushed on its own.
- **Flight recorder**: every finished ROOT trace lands in a ring of
  the last ``SRJT_TRACE_RING`` traces; queries that were shed, failed,
  cancelled, expired, or slower than ``SRJT_SLOW_QUERY_SEC`` are
  FLUSHED automatically — the full span tree plus a metrics-delta
  snapshot goes to the span log as a ``{"kind": "trace", ...}`` line,
  so the evidence for "why was THIS query slow" survives the process.
  ``runtime.explain_last()`` renders the worst recent query from the
  ring as an annotated span tree.

Stage summary counters (``trace.spans`` / ``trace.traces`` /
``trace.flushed`` / ``trace.unsampled``) are registry-direct so bench
drivers can emit a per-stage trace summary next to their
``{"metrics": ...}`` lines and ``metrics.reset()`` scopes them per
stage. A finished span costs one counter increment and one list
append.

Disabled posture: nothing here runs unless utils/tracing's gate armed a
span in the first place — the module's own fast-outs are one attribute
read (no path configured == no I/O).
"""

from __future__ import annotations

import atexit
import json
import os
import threading
from collections import deque
from typing import List, Optional

from . import knobs

__all__ = [
    "emit_span",
    "flush",
    "note_trace",
    "note_unsampled",
    "record_trace",
    "recorder",
    "FlightRecorder",
    "set_log_path",
    "log_path",
    "resolved_log_path",
    "close_log",
    "explain_last",
    "render_trace",
    "stage_summary",
    "stats_section",
    "reset_for_tests",
]

_log_lock = threading.Lock()
_log_base: Optional[str] = knobs.get_str("SRJT_TRACE_LOG") or None
_log_file = None
_log_file_path: Optional[str] = None
# finished records waiting for the next flush point (guarded by
# _log_lock); past _BUFFER_MAX the appender flushes, so the list is
# bounded without a knob
_buffer: List[dict] = []
_BUFFER_MAX = 4096


def log_path() -> Optional[str]:
    """The configured span-log BASE path (the per-process file adds a
    ``.<pid>`` suffix; see ``resolved_log_path``)."""
    return _log_base


def resolved_log_path() -> Optional[str]:
    """The per-process span-log file this process appends to, or None:
    ``<base>.<pid>.jsonl`` — per-process files keep worker and client
    logs separate for the tracemerge join, with no cross-process write
    interleaving to reason about."""
    if _log_base is None:
        return None
    root, ext = os.path.splitext(_log_base)
    return f"{root}.{os.getpid()}{ext or '.jsonl'}"


def set_log_path(base: Optional[str]) -> None:
    """Install (or clear) the span-log base path. What is buffered goes
    to the OLD path first; the per-process file of the new one opens
    lazily on the first flush."""
    global _log_base
    with _log_lock:
        _flush_locked()
        _close_locked()
        _log_base = base


def close_log() -> None:
    """Flush what is buffered and close the file (a reader may open it
    now; the next flush re-opens it for append)."""
    set_log_path(_log_base)


def _close_locked() -> None:
    global _log_file, _log_file_path
    if _log_file is not None:
        try:
            _log_file.close()
        except OSError:
            pass
        _log_file = None
        _log_file_path = None


def _flush_locked() -> None:
    """Serialise the buffered records and append them in ONE write; a
    bad path degrades the log, never the op being traced."""
    global _log_file, _log_file_path
    if not _buffer:
        return
    recs = _buffer[:]
    del _buffer[:]  # srjt-race: guarded-by(_log_lock)
    path = resolved_log_path()
    if path is None:
        return
    if _log_file is None or _log_file_path != path:
        _close_locked()
        d = os.path.dirname(path)
        try:
            if d:
                os.makedirs(d, exist_ok=True)
            _log_file = open(path, "a")
            _log_file_path = path
        except OSError:
            return
    try:
        _log_file.write(
            "".join(json.dumps(r, default=str) + "\n" for r in recs)
        )
        _log_file.flush()
    except (OSError, ValueError):
        pass


def flush() -> None:
    """Write every buffered record now (the flush points are listed in
    the module docstring)."""
    with _log_lock:
        _flush_locked()


atexit.register(flush)


def _buffer_record(rec: dict) -> None:
    if _log_base is None:
        return
    with _log_lock:
        _buffer.append(rec)
        if len(_buffer) >= _BUFFER_MAX:
            _flush_locked()


def emit_span(rec: dict) -> None:
    """One finished span: counted (``trace.spans``; metrics.reset()
    scopes it per bench stage) and buffered for the per-process log."""
    _registry().counter("trace.spans").inc()
    _buffer_record(rec)


def _registry():
    from . import metrics

    return metrics.registry()


def note_trace() -> None:
    _registry().counter("trace.traces").inc()


def note_unsampled() -> None:
    _registry().counter("trace.unsampled").inc()


# ---------------------------------------------------------------------------
# the flight recorder
# ---------------------------------------------------------------------------


class FlightRecorder:
    """Bounded ring of the last N completed query traces. ``record``
    decides the auto-flush: non-ok status (shed / failed / cancelled /
    expired / error) always flushes; an ok trace flushes when it ran
    longer than ``SRJT_SLOW_QUERY_SEC`` (unset: never). Flushing
    appends the FULL trace record — span tree + metrics delta — to the
    span log at once (behind the spans buffered before it), so a
    storm's evidence is on disk even if the process dies before anyone
    calls explain_last()."""

    def __init__(self, capacity: Optional[int] = None):
        if capacity is None:
            capacity = knobs.get_int("SRJT_TRACE_RING")
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=int(capacity))
        self._recorded = 0
        self._flushed = 0

    def record(self, rec: dict) -> None:
        slow_s = knobs.get_float("SRJT_SLOW_QUERY_SEC")
        to_log = rec.get("status") != "ok" or (
            slow_s is not None and rec.get("duration_s", 0.0) > slow_s
        )
        if to_log:
            rec = dict(rec)
            rec["flushed"] = True
        with self._lock:
            self._ring.append(rec)
            self._recorded += 1
            if to_log:
                self._flushed += 1
        if to_log:
            _registry().counter("trace.flushed").inc()
            _buffer_record(rec)
            flush()

    def last(self, n: int = 1) -> List[dict]:
        with self._lock:
            items = list(self._ring)
        return items[-n:]

    def worst(self) -> Optional[dict]:
        """The worst recent query: failures outrank successes, then
        duration decides — the trace explain_last() renders."""
        with self._lock:
            items = list(self._ring)
        if not items:
            return None
        return max(
            items,
            key=lambda r: (
                0 if r.get("status") == "ok" else 1,
                r.get("duration_s", 0.0),
            ),
        )

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "ring": len(self._ring),
                "capacity": self._ring.maxlen,
                "recorded": self._recorded,
                "flushed": self._flushed,
            }

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()


_recorder: Optional[FlightRecorder] = None
_recorder_lock = threading.Lock()


def recorder() -> FlightRecorder:
    global _recorder
    if _recorder is None:
        with _recorder_lock:
            if _recorder is None:
                _recorder = FlightRecorder()
    return _recorder


def record_trace(rec: dict) -> None:
    recorder().record(rec)


def reset_for_tests() -> None:
    """Fresh recorder + closed log handle (tests only)."""
    global _recorder
    with _recorder_lock:
        _recorder = None
    close_log()


# ---------------------------------------------------------------------------
# rendering (runtime.explain_last)
# ---------------------------------------------------------------------------


def _fmt_span(s: dict, parent: Optional[dict] = None) -> str:
    dur = s.get("dur_us", 0.0)
    dur_txt = f"{dur / 1e3:.2f}ms" if dur < 1e6 else f"{dur / 1e6:.3f}s"
    ann = dict(s.get("annotations") or {})
    name = s.get("name")
    if name == "device.wait":
        # the host's wait for the device, by its site, and the share of
        # the span that asked for it: "it sat 843 ms in exchange.table,
        # waiting for what?" answered without a profiler
        name = f"device.wait({ann.pop('what', '?')})"
        if parent is not None and parent.get("dur_us"):
            dur_txt += f" = {100.0 * dur / parent['dur_us']:.1f}% of {parent.get('name')}"
    elif name == "device.launch":
        name = f"device.launch({ann.pop('program', '?')})"
    ann_txt = "".join(f" {k}={v}" for k, v in sorted(ann.items()))
    status = s.get("status", "ok")
    status_txt = "" if status == "ok" else f" [{status}]"
    return f"{name} {dur_txt}{status_txt} (pid {s.get('pid')}){ann_txt}"


def render_trace(rec: dict) -> str:
    """An annotated span tree for one recorded trace: the
    ``explain_last`` rendering. Spans are nested by parent id and
    ordered by start time; spans whose parent is missing from the
    record (in-memory cap overflow, cross-process children) are listed
    under an ``(unparented)`` marker rather than dropped."""
    spans = list(rec.get("spans") or [])
    by_id = {s["span"]: s for s in spans}
    children: dict = {}
    roots: List[dict] = []
    orphans: List[dict] = []
    for s in spans:
        p = s.get("parent")
        if p is None:
            roots.append(s)
        elif p in by_id:
            children.setdefault(p, []).append(s)
        else:
            orphans.append(s)
    lines = [
        f"trace {rec.get('trace')} {rec.get('name')} "
        f"status={rec.get('status')} {rec.get('duration_s', 0.0):.3f}s"
        + ("  [flushed]" if rec.get("flushed") else "")
    ]
    delta = rec.get("metrics_delta") or {}
    if delta:
        top = sorted(delta.items(), key=lambda kv: -abs(kv[1]))[:8]
        lines.append(
            "  metrics-delta: "
            + ", ".join(f"{k}+{v}" for k, v in top)
        )
    if rec.get("dropped_spans"):
        lines.append(f"  ({rec['dropped_spans']} spans dropped at the "
                     "in-memory cap; the span log has them all)")

    def walk(s: dict, indent: int, parent: Optional[dict] = None) -> None:
        lines.append("  " * indent + "- " + _fmt_span(s, parent))
        for c in sorted(children.get(s["span"], ()),
                        key=lambda x: x.get("ts", 0.0)):
            walk(c, indent + 1, s)

    for r in sorted(roots, key=lambda x: x.get("ts", 0.0)):
        walk(r, 1)
    if orphans:
        lines.append("  (unparented)")
        for s in sorted(orphans, key=lambda x: x.get("ts", 0.0)):
            walk(s, 2)
    return "\n".join(lines)


def explain_last() -> Optional[str]:
    """Render the WORST recent query (failures first, then duration)
    from the flight-recorder ring, or None when nothing was traced.
    This is the local-process view; the cross-process tree lives in the
    span logs (``analysis.tracemerge`` joins them)."""
    rec = recorder().worst()
    return None if rec is None else render_trace(rec)


# ---------------------------------------------------------------------------
# stage summary / stats sections
# ---------------------------------------------------------------------------


def stage_summary() -> dict:
    """The per-stage trace summary bench drivers emit next to their
    ``{"metrics": ...}`` lines: span, trace and flushed-trace counts of
    the stage's registry window. Which span grew is read from the span
    log (``analysis.tracemerge``), not from here."""
    reg = _registry()
    return {
        "spans": reg.value("trace.spans"),
        "traces": reg.value("trace.traces"),
        "flushed": reg.value("trace.flushed"),
    }


def stats_section() -> dict:
    """The ``trace`` section of runtime.stats_report(): registry
    counters plus the flight recorder's ring state (None-safe before
    anything was traced — a stats poll never mints the recorder)."""
    out = dict(stage_summary())
    out["unsampled"] = _registry().value("trace.unsampled")
    out["log"] = resolved_log_path()
    with _recorder_lock:
        rec = _recorder
    out["recorder"] = None if rec is None else rec.snapshot()
    return out
