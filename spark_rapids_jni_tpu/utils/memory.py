"""Device-memory budget + retryable-OOM semantics (SURVEY §2.8 RMM row).

The reference threads RMM memory resources through every op signature
(row_conversion.hpp:27-49) and relies on the plugin's retry-on-OOM
discipline; its 2 GiB batching (row_conversion.cu:100-105) is the
splitting mechanism. Here device memory is XLA-owned, so the analog is
*predictive*: ops that grow buffers data-dependently (the exchange
capacity escalation in parallel/table_ops.py) estimate their device
footprint BEFORE dispatch and, over budget, either raise
``MemoryBudgetExceeded`` (a ``RetryableError``: Spark task retry
semantics apply) or split the batch and re-run — never drive XLA into
an allocator OOM that may poison the client.

ENFORCEMENT lives in ``spark_rapids_jni_tpu/memgov`` (ISSUE 4): the
byte-weighted admission controller gates every outermost op_boundary
dispatch on this module's budget, and the spillable buffer catalog
demotes cold buffers device->host->disk under pressure. This module
keeps the shared pieces both tiers consume: the budget resolution
(memoized backend probe, live env override, live ``bytes_in_use``
subtraction) and the footprint estimators.
"""

from __future__ import annotations

from . import knobs
from .errors import RetryableError

__all__ = [
    "MemoryBudgetExceeded",
    "device_memory_budget",
    "exchange_bytes_estimate",
    "split_retry_count",
]


class MemoryBudgetExceeded(RetryableError):
    """A requested device buffer footprint exceeds the memory budget.
    Retryable: the caller may split the batch (ops with split-retry do
    so automatically) or the task may re-run elsewhere."""


# observability: how many batch splits the memory tier has forced.
# The count lives in the metrics registry (utils/metrics.py,
# ``memory.split_retries``) — registry-direct, so it keeps counting
# whether or not SRJT_METRICS_ENABLED arms the hot-path tier (a split
# is a rare recovery event, not a hot path).
_SPLIT_COUNTER = "memory.split_retries"


def split_retry_count() -> int:
    """DEPRECATED: thin alias over the metrics registry counter
    ``memory.split_retries``; read it via
    ``utils.metrics.registry().counter("memory.split_retries").value``
    (or a ``runtime.stats_report()`` snapshot) in new code."""
    from . import metrics

    return metrics.registry().counter(_SPLIT_COUNTER).value


def _note_split() -> None:
    from . import metrics

    metrics.registry().counter(_SPLIT_COUNTER).inc()
    metrics.event("memory.split_retry")


# memoized backend probe: resolving the budget used to re-import jax
# and re-read memory_stats() on EVERY call, which the memgov admission
# controller now makes per-dispatch. The resolved limit is cached; the
# env override stays live (the test hook), and live bytes_in_use is
# subtracted when the backend reports it.
_RESOLVED: "int | None" = None
_STATS_DEV = None  # device whose memory_stats() reports live bytes_in_use
_MIN_BUDGET = 64 << 20  # floor after bytes_in_use subtraction


# HBM per chip by ``device_kind``, for a TPU whose runtime reports no
# ``bytes_limit``. Source: Google Cloud documentation, "TPU v5e" (16 GB
# per chip). A kind that is not listed is an error, not a default.
_TPU_HBM_BYTES = {"TPU v5 lite": 16 << 30}
_CPU_BUDGET = 4 << 30  # host RAM share; the CPU backend reports no limit


def _resolve_backend_budget() -> int:
    """One-time probe: half of the limit the device reports (the other
    half is headroom for XLA temps), else half of its kind's HBM from
    the table above; remembers the device handle when it can report
    live ``bytes_in_use``."""
    global _STATS_DEV
    import jax

    dev = jax.local_devices()[0]
    stats = dev.memory_stats()
    if stats and stats.get("bytes_limit"):
        if stats.get("bytes_in_use") is not None:
            _STATS_DEV = dev
        return int(stats["bytes_limit"] * 0.5)
    if dev.platform == "cpu":
        return _CPU_BUDGET
    hbm = _TPU_HBM_BYTES.get(dev.device_kind) if dev.platform == "tpu" else None
    if hbm is None:
        raise RuntimeError(
            f"device {dev.device_kind!r} ({dev.platform}) reports no "
            "bytes_limit and is not in utils/memory._TPU_HBM_BYTES: set "
            "SRJT_DEVICE_MEMORY_BUDGET or add its HBM size with a source"
        )
    return hbm // 2


def device_memory_budget() -> int:
    """Usable device bytes for a single op's working buffers.

    Resolution order: ``SRJT_DEVICE_MEMORY_BUDGET`` (bytes; read LIVE —
    the test hook and the operator override), else the memoized backend
    probe — half the limit the device reports, else half its kind's HBM from
    a small table (an unknown kind raises), else the host RAM share on
    CPU — minus the backend's live ``bytes_in_use`` when it reports
    one (floored at 64 MiB so transient allocator spikes degrade to
    splitting, never to a zero budget). The budget is per-op headroom,
    not the raw chip size: XLA temps routinely need a small multiple of
    the declared buffers."""
    # `is not None`, not truthiness: an explicit 0 is a real operator
    # contract (arm the governor, force everything over-budget), never
    # "unset" (the declared default is None)
    env = knobs.get_int("SRJT_DEVICE_MEMORY_BUDGET")
    if env is not None:
        return env
    global _RESOLVED
    if _RESOLVED is None:
        _RESOLVED = _resolve_backend_budget()
    budget = _RESOLVED
    if _STATS_DEV is not None:
        try:
            in_use = int(_STATS_DEV.memory_stats().get("bytes_in_use") or 0)
        except Exception:  # srjt-lint: allow-broad-except(live bytes_in_use probe is advisory; a failed stats call must not sink the budget query)
            in_use = 0
        if in_use:
            budget = max(budget - in_use, _MIN_BUDGET)
    return budget


def exchange_bytes_estimate(row_bytes: int, n_parts: int, capacity: int) -> int:
    """PER-DEVICE bytes an all_to_all exchange program needs at a given
    per-destination ``capacity``: each shard holds its own [n_parts,
    capacity] bucket matrix per lane, doubled for the send/receive pair
    the collective keeps live. Compared against the per-device
    budget — a fleet-total estimate would over-reject by n_parts."""
    return 2 * n_parts * capacity * max(row_bytes, 1)
