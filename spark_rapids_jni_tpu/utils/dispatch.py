"""Op-boundary dispatch wrapper: the JNI-entry-point analog.

Every reference JNI export runs the same preamble — device binding,
exception translation, NVTX range (RowConversionJni.cpp:42-57 pattern,
SURVEY §2.2). ``op_boundary`` is that preamble for the TPU build: fault
injection hook, tracing scope, backend-error classification (fatal vs
retryable), deadline scope/cancel point (utils/deadline.py), and —
when the retry orchestrator is armed (utils/retry.py,
``SRJT_RETRY_ENABLED=1``) — bounded retry with exponential backoff for
RetryableError, all in one decorator applied to public ops.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Dict, Optional

from . import deadline, faultinj, metrics, tracing
from .errors import DeviceError, classify
from .. import memgov

__all__ = ["op_boundary", "note_tier"]


# Kernel-tier observability (ISSUE 13): tiered ops report which
# formulation actually served a dispatch — ``pallas`` (kernel tier) or
# ``xla`` (the XLA formulation, selected by knob, backend or shape).
# Counted REGISTRY-DIRECT (the memory.split_retries
# discipline: durable bookkeeping, independent of the
# SRJT_METRICS_ENABLED hot-path gate) so BENCH drivers and the premerge
# kernel-tier gate can prove the pallas path engaged; with tracing
# armed the tier also lands as an annotation on the active op span, so
# flight-recorder output shows which kernel a slow query ran. Handles
# are cached (the record_op idiom): one dict read per note after the
# first dispatch of a tier.
_tier_handles: Dict[str, object] = {}
_tier_handles_lock = threading.Lock()


def note_tier(tier: str, op: Optional[str] = None) -> None:
    """Record the serving tier of the current dispatch (see above)."""
    c = _tier_handles.get(tier)
    if c is None:
        with _tier_handles_lock:
            c = _tier_handles.get(tier)
            if c is None:
                c = metrics.registry().counter(f"dispatch.tier.{tier}")
                _tier_handles[tier] = c
    c.inc()
    if metrics.is_enabled() and op is not None:
        metrics.event("dispatch.tier", op=op, tier=tier)
    if tracing.is_enabled():
        tracing.annotate(tier=tier)


def _run_boundary(attempt, name: str):
    """The dispatch core shared by the scoped and unscoped deadline
    branches of ``op_boundary``: retry arming + metrics timing. Only the
    OUTERMOST boundary owns the retry loop — a nested op's
    RetryableError propagates to the outer attempt, so a persistent
    failure costs max_attempts total re-runs, not
    max_attempts^nesting-depth. The retry-dispatch decision is written
    out twice so the disarmed-metrics path touches no clock."""
    from . import retry

    if not metrics.is_enabled():
        if retry.is_enabled() and not retry.in_attempt():
            return retry.call_with_retry(attempt, op_name=name)
        return attempt()
    t0 = time.perf_counter()
    try:
        if retry.is_enabled() and not retry.in_attempt():
            return retry.call_with_retry(attempt, op_name=name)
        return attempt()
    finally:
        metrics.record_op(name, time.perf_counter() - t0)


def op_boundary(name: str):
    """Wrap a public op with the dispatch preamble.

    - ``faultinj.maybe_inject(name)`` fires configured faults first
      (the CUPTI-callback interception point); injection sits INSIDE
      the retry attempt so chaos-injected RetryableErrors exercise the
      recovery path, not just the classification,
    - ``tracing.func_range(name)`` scopes the body for XProf,
    - backend exceptions are classified into Fatal/Retryable
      (CATCH_STD analog); host-side ValueError/TypeError/KeyError/
      IndexError pass through unchanged,
    - DEADLINE (utils/deadline.py): every wrapped op accepts a reserved
      ``deadline_s=`` keyword that opens a per-call budget scope; with
      none, the OUTERMOST boundary under an ambient ``SRJT_DEADLINE_SEC``
      opens the per-query scope — so one knob bounds the whole dispatch
      including retries and backoff sleeps. Nested boundaries do not
      open new scopes; they are cancel points consuming the enclosing
      budget (``DeadlineExceeded`` raises before the body runs once the
      budget is gone or the cancel token tripped). With no deadline
      anywhere the extra cost is one reserved-kwarg pop plus a
      context-var read,
    - with the retry orchestrator armed, RetryableError re-runs the op
      under the module RetryPolicy; FatalDeviceError NEVER retries.
      Disarmed (the default), RetryableError propagates to the caller
      unchanged — the seed's Spark-task-retry contract,
    - with the metrics subsystem armed (utils/metrics.py,
      ``SRJT_METRICS_ENABLED=1``), every dispatch records a call count
      and wall-clock histogram (``op.<name>.calls`` /
      ``op.<name>.wall_us``) spanning the full boundary including any
      retries/backoff; disarmed, the only cost is one boolean read —
      no clock, no registry touch,
    - MEMORY GOVERNOR (memgov/, ISSUE 4): with the governor armed
      (``SRJT_SPILL_ENABLED``, or implicitly by a declared
      ``SRJT_DEVICE_MEMORY_BUDGET``), the OUTERMOST boundary on a
      thread acquires the byte-weighted admission semaphore with the
      op's footprint estimate before dispatch — the reserved
      ``memory_bytes=`` keyword overrides the default input-bytes ×
      ``SRJT_MEMGOV_HEADROOM`` estimate — and releases it after.
      Admission sits INSIDE the retry attempt: a retryable admission
      denial (``MemoryBudgetExceeded``) rides the orchestrator's
      backoff/split machinery like any other RESOURCE_EXHAUSTED class.
      Disarmed (the default), the cost is one reserved-kwarg pop plus
      one boolean read — the metrics-stub pattern.
    """

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            budget_s = kwargs.pop("deadline_s", None)
            mem_bytes = kwargs.pop("memory_bytes", None)

            def attempt():
                faultinj.maybe_inject(name)
                adm = (
                    memgov.admit(name, args, kwargs, mem_bytes)
                    if memgov.is_enabled()
                    else None
                )
                try:
                    with tracing.func_range(name):
                        try:
                            return fn(*args, **kwargs)
                        except DeviceError:
                            raise
                        except (ValueError, TypeError, KeyError, IndexError):
                            raise
                        except Exception as e:  # backend / runtime failures
                            if type(e).__module__.startswith("spark_rapids_jni_tpu"):
                                # the op's own documented API errors (CastError,
                                # ParquetReadError, ...) are results, not failures
                                raise
                            raise classify(e) from e
                finally:
                    if adm is not None:
                        adm.release()

            # deadline scoping mirrors the retry nesting guard inside
            # _run_boundary: one scope per query, owned by the boundary
            # that opened it. The common fully-disarmed path pays two
            # kwargs.pops, two boolean reads (memgov + tracing gates),
            # a context-var read, and two extra frames (_run_boundary
            # and `scoped`) on top of what the seed paid — no clock, no
            # context manager.
            def scoped():
                dl = deadline.current()
                bs = budget_s
                if bs is None and dl is None:
                    bs = deadline.default_budget()
                if bs is not None:
                    with deadline.scope(bs) as d:
                        d.check(name)
                        return _run_boundary(attempt, name)
                if dl is not None:
                    dl.check(name)  # nested boundary: cancel point only
                return _run_boundary(attempt, name)

            # srjt-trace (ISSUE 12): the op span covers the WHOLE
            # boundary — deadline scope, every retry attempt, every
            # backoff — so retry annotations and split child spans
            # (utils/retry.py) land inside it. A nested boundary's span
            # is a child; the OUTERMOST boundary with no active trace
            # auto-roots a one-op trace (tracing.op_span policy).
            if tracing.is_enabled():
                with tracing.op_span(name):
                    return scoped()
            return scoped()

        return wrapper

    return deco
