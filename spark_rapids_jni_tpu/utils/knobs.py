"""Central SRJT_* knob registry + typed environment accessors (ISSUE 7).

Before this module every subsystem read ``os.environ`` directly with
its own ad-hoc parser (``env_float`` in retry, ``_env_int`` in the
pool, ``_env_seconds`` in the sidecar, bare ``int(raw)`` in memgov),
and the README/PACKAGING knob tables drifted from the code — 40 knobs
in code, 34 documented. This registry is the single source of truth:

- every knob is DECLARED here once — name, type, default, validation,
  one-line doc — and read through the typed ``get_*`` accessors,
- ``srjt-lint`` (analysis/lint.py) fails the build on any SRJT_* string
  literal that is not declared here, on any direct ``os.environ`` read
  of an SRJT key outside this file, and on any drift between this
  registry and the README/PACKAGING knob tables,
- ``python -m spark_rapids_jni_tpu.analysis.lint --knob-table`` renders
  the registry as the markdown table the docs embed.

Parsing posture (inherited from the original ``env_float``): malformed
values WARN and fall back to the declared default — a bad knob degrades
the feature, never crashes an import or a query. ``positive=True``
knobs additionally reject values <= 0 (a zero socket deadline would
make sockets non-blocking, not timeout-free — the C++ client applies
the same v > 0 rule).

This module is deliberately dependency-free (stdlib only, no locks, no
package imports): it must be importable by the package ``__init__``
BEFORE the lockdep shim (analysis/lockdep.py) decides whether to
instrument ``threading``, and by every utils module without cycles.

Accessors read the environment LIVE on every call (the test hook and
operator-override contract); modules that latch a value at import time
(metrics/retry arming) do so explicitly at their own import site.
"""

from __future__ import annotations

import os
from typing import Dict, Iterable, Optional

__all__ = [
    "Knob",
    "declare",
    "knob",
    "all_knobs",
    "names",
    "is_declared",
    "is_set",
    "get_raw",
    "get_str",
    "get_bool",
    "get_int",
    "get_float",
    "env_float",
    "markdown_table",
    "SENTINELS",
]

_TRUE = ("1", "true", "yes")
_FALSE = ("0", "false", "no")

# NOT env knobs: stdout/wire handshake sentinel lines that share the
# SRJT_ prefix (spawn harnesses poll for them). Declared so srjt-lint
# can tell a sentinel literal from an undeclared knob.
SENTINELS = frozenset({"SRJT_SIDECAR_READY", "SRJT_EXCHANGE_READY"})


class Knob:
    """One declared knob: the registry row and its validation spec."""

    __slots__ = ("name", "type", "default", "doc", "positive", "minimum",
                 "choices", "scope")

    def __init__(self, name, type, default, doc, positive=False,
                 minimum=None, choices=None, scope="python"):
        self.name = name
        self.type = type  # "bool" | "int" | "float" | "str"
        self.default = default
        self.doc = doc
        self.positive = positive  # floats/ints: value must be > 0
        self.minimum = minimum  # ints: clamp floor (pool sizes etc.)
        self.choices = choices  # strs: allowed values (warn + default)
        # "python" | "native" | "harness": where the knob is consumed —
        # native knobs are read by the C++ client, harness knobs by
        # bench/test drivers; all are documented from this one registry
        self.scope = scope


_REGISTRY: Dict[str, Knob] = {}


def declare(name: str, type: str, default, doc: str, **kw) -> Knob:
    if name in _REGISTRY:
        raise ValueError(f"knob {name} declared twice")
    if not name.startswith("SRJT_"):
        raise ValueError(f"knob {name} must carry the SRJT_ prefix")
    k = Knob(name, type, default, doc, **kw)
    _REGISTRY[name] = k
    return k


def knob(name: str) -> Knob:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"undeclared knob {name!r}: declare it in utils/knobs.py "
            "(srjt-lint enforces this)"
        ) from None


def all_knobs() -> Iterable[Knob]:
    return [_REGISTRY[n] for n in sorted(_REGISTRY)]


def names() -> frozenset:
    return frozenset(_REGISTRY)


def is_declared(name: str) -> bool:
    return name in _REGISTRY


def _warn(msg: str) -> None:
    import warnings

    warnings.warn(f"knobs: {msg}", stacklevel=3)


def get_raw(name: str, env=None) -> Optional[str]:
    """The raw environment string for a declared knob, or None when
    unset. The untyped escape hatch — prefer the typed accessors."""
    knob(name)  # undeclared reads fail loudly, even through the API
    return (os.environ if env is None else env).get(name)


def is_set(name: str, env=None) -> bool:
    """True when the knob is present AND non-empty in the environment."""
    return bool(get_raw(name, env))


def get_str(name: str, env=None, default=...) -> Optional[str]:
    k = knob(name)
    if default is ...:
        default = k.default
    raw = get_raw(name, env)
    if raw is None or raw == "":
        return default
    if k.choices and raw.lower() not in k.choices:
        _warn(f"unknown {name}={raw!r}; using {default!r}")
        return default
    return raw.lower() if k.choices else raw


def get_bool(name: str, env=None, default=...) -> bool:
    """Tri-state text -> bool: explicit true/false spellings win, any
    other spelling WARNS and keeps the default (same degradation
    contract as the numeric accessors), unset/empty keeps it silently —
    so a default-on knob (SRJT_INTEGRITY_CHECKS) only disarms on an
    explicit "0", and a default-off one (SRJT_METRICS_ENABLED) only
    arms on an explicit "1"."""
    k = knob(name)
    if default is ...:
        default = k.default
    raw = get_raw(name, env)
    if raw is None or raw == "":
        return bool(default)
    low = raw.lower()
    if low in _TRUE:
        return True
    if low in _FALSE:
        return False
    _warn(f"ignoring malformed {name}={raw!r}; using {bool(default)!r}")
    return bool(default)


def get_int(name: str, env=None, default=...) -> Optional[int]:
    k = knob(name)
    if default is ...:
        default = k.default
    raw = get_raw(name, env)
    if raw is None or raw == "":
        return default
    try:
        v = int(raw)
    except ValueError:
        _warn(f"ignoring malformed {name}={raw!r}; using {default!r}")
        return default
    if k.positive and v <= 0:
        _warn(f"{name}={raw!r} must be > 0; keeping default {default!r}")
        return default
    if k.minimum is not None:
        v = max(v, k.minimum)
    return v


def get_float(name: str, env=None, default=...) -> Optional[float]:
    k = knob(name)
    if default is ...:
        default = k.default
    return env_float(
        os.environ if env is None else env, name, default,
        positive=k.positive,
    )


def env_float(env, key: str, default, positive: bool = False):
    """Parse a float env knob, warning and falling back to ``default``
    on malformed input — and, with ``positive=True``, on values <= 0.
    The historical shared parser (born in utils/retry.py); the typed
    ``get_float`` accessor above is the declared-knob front door, this
    remains for callers carrying an injected env mapping."""
    raw = env.get(key)
    if raw is None or raw == "":
        return default
    try:
        v = float(raw)
    except ValueError:
        _warn(f"ignoring malformed {key}={raw!r}")
        return default
    if positive and v <= 0:
        _warn(f"{key}={raw!r} must be > 0; keeping default {default}")
        return default
    return v


def markdown_table(scope: Optional[str] = None) -> str:
    """Render the registry as the markdown knob table the docs embed
    (``python -m spark_rapids_jni_tpu.analysis.lint --knob-table``)."""
    rows = ["| knob | type | default | description |",
            "|---|---|---|---|"]
    for k in all_knobs():
        if scope is not None and k.scope != scope:
            continue
        d = "—" if k.default is None else repr(k.default).strip("'\"")
        rows.append(f"| `{k.name}` | {k.type} | `{d}` | {k.doc} |")
    return "\n".join(rows)


# ---------------------------------------------------------------------------
# THE registry: every SRJT_* knob in the tree, grouped by subsystem.
# srjt-lint fails on any SRJT literal in code that is missing here and
# on any entry here missing from the README/PACKAGING knob tables.
# ---------------------------------------------------------------------------

# retry orchestrator (utils/retry.py, PR 1)
declare("SRJT_RETRY_ENABLED", "bool", False,
        "arm op-boundary retry (bounded backoff + retry-with-split)")
declare("SRJT_RETRY_MAX_ATTEMPTS", "int", 4,
        "total attempts incl. the first", positive=True)
declare("SRJT_RETRY_BASE_DELAY_MS", "float", 25.0, "first backoff delay")
declare("SRJT_RETRY_MAX_DELAY_MS", "float", 1000.0, "backoff ceiling")
declare("SRJT_RETRY_JITTER", "float", 0.25,
        "multiplicative jitter fraction in [0,1)")
declare("SRJT_RETRY_SPLIT_DEPTH", "int", 3,
        "max halvings in retry_with_split")
declare("SRJT_RETRY_SEED", "int", None,
        "jitter RNG seed (deterministic chaos runs)")

# deadlines + circuit breaker (utils/deadline.py, PR 3)
declare("SRJT_DEADLINE_SEC", "float", None,
        "ambient per-query wall-clock budget in seconds (unset: "
        "unbounded, the seed contract)", positive=True)
declare("SRJT_BREAKER_THRESHOLD", "int", 5,
        "consecutive sidecar supervision failures before the breaker "
        "opens", positive=True)
declare("SRJT_BREAKER_COOLDOWN_SEC", "float", 30.0,
        "breaker open -> half-open probe delay", positive=True)

# metrics + tracing (utils/metrics.py / utils/tracing.py, PR 2)
declare("SRJT_METRICS_ENABLED", "bool", False,
        "arm hot-path instrumentation (per-op wall time, shuffle "
        "bytes, retry/backoff counters per error class)")
declare("SRJT_METRICS_LOG", "str", None,
        "append one JSON object per runtime event to this path "
        "(line-atomic, shareable across worker + client)")
declare("SRJT_TRACE_ENABLED", "bool", False,
        "arm distributed per-query tracing (srjt-trace spans with "
        "cross-process propagation) plus the jax named-scope/"
        "TraceAnnotation ranges on every op boundary (the NVTX-range "
        "analog; visible in XProf)")

# distributed tracing + flight recorder (utils/tracing.py /
# utils/trace_sink.py, ISSUE 12)
declare("SRJT_TRACE_LOG", "str", None,
        "span-log base path: each process appends its finished spans "
        "(and flushed trace trees) to <base>.<pid>.jsonl — the "
        "analysis.tracemerge join input")
declare("SRJT_TRACE_SAMPLE", "float", 1.0,
        "fraction of root traces sampled (0 disables roots entirely; "
        "unsampled queries cost one RNG draw)")
declare("SRJT_SLOW_QUERY_SEC", "float", None,
        "flight recorder: a completed query slower than this flushes "
        "its full span tree + metrics delta to SRJT_TRACE_LOG "
        "(shed/failed queries always flush)", positive=True)
declare("SRJT_TRACE_RING", "int", 64,
        "flight recorder ring capacity: completed query traces kept "
        "in memory for runtime.explain_last()", minimum=1)
declare("SRJT_TRACE_MAX_SPANS", "int", 4096,
        "per-trace in-memory span cap (overflow counted; the span LOG "
        "is never capped)", minimum=16)

# integrity + fault injection (utils/integrity.py / utils/faultinj.py)
declare("SRJT_INTEGRITY_CHECKS", "bool", True,
        "0 disables every CRC check (frames ship legacy framing, "
        "spills skip verify, exchanges skip the checksum)")
declare("SRJT_FAULTINJ_CONFIG", "str", None,
        "JSON chaos profile path (hot-reloaded on mtime change); a "
        "malformed config degrades the injector, never the process")
declare("SRJT_CHAOS_EXIT_ON_OP", "int", None,
        "sidecar worker chaos: die (exit 42) after consuming a request "
        "for this op code, before any response")
declare("SRJT_FAULTINJ_WORKER", "str", None,
        "this process's worker tag (w0, w1, ...) for per-worker fault "
        "rule keys like sidecar.worker.<OP>@w1; the pool sets it on "
        "every spawned worker")
declare("SRJT_FAULTINJ_RANK", "str", None,
        "this process's exchange-rank tag (r0, r1, ...) for per-rank "
        "fault rule keys like exchange.connect@r2; the exchange-worker "
        "harness sets it on every spawned rank")

# sidecar supervision (sidecar.py, PRs 1/3/5)
declare("SRJT_SIDECAR_TIMEOUT_SEC", "float", 600.0,
        "per-request sidecar socket deadline (both clients; truncated "
        "to the remaining budget under a deadline scope)",
        positive=True)
declare("SRJT_SIDECAR_DEADLINE_S", "float", None,
        "float override of SRJT_SIDECAR_TIMEOUT_SEC for the Python "
        "client (wins when both are set)", positive=True)
declare("SRJT_SIDECAR_HEARTBEAT_S", "float", 30.0,
        "idle-connection PING probe interval", positive=True)
declare("SRJT_SIDECAR_STATS_TIMEOUT_SEC", "float", 5.0,
        "STATS-verb probe deadline (throwaway connection, never the "
        "heavy-op budget)", positive=True)
declare("SRJT_SIDECAR_HEARTBEAT_TIMEOUT_SEC", "float", 5.0,
        "native C++ client: heartbeat() PING deadline (NOT the "
        "heavy-op SRJT_SIDECAR_TIMEOUT_SEC)", scope="native",
        positive=True)
declare("SRJT_PYTHON", "str", None,
        "native C++ client: python executable used to fork the sidecar "
        "worker", scope="native")

# worker pool + slab arena (sidecar_pool.py, PRs 5/6)
declare("SRJT_SIDECAR_POOL_SIZE", "int", 1,
        "workers in the supervised pool (1 = single-worker footprint)",
        minimum=1)
declare("SRJT_POOL_RESPAWN_MAX", "int", 3,
        "spawn attempts per worker death before the slot stays dead",
        minimum=1)
declare("SRJT_POOL_RESPAWN_DELAY_S", "float", 0.5,
        "pause between failed respawn attempts")
declare("SRJT_ARENA_SLAB_BYTES", "int", 64 << 20,
        "slab arena size, rounded up to a power of two (memfd-backed, "
        "virtual until touched)", minimum=4096)

# tail tolerance: gray-failure quarantine + hedged dispatch
# (sidecar_pool.py, ISSUE 9)
declare("SRJT_QUARANTINE_ENABLED", "bool", True,
        "arm the gray-failure detector: persistently-slow pool workers "
        "are quarantined out of routing and background-probed")
declare("SRJT_QUARANTINE_SLOW_FACTOR", "float", 3.0,
        "a sample slower than this multiple of the pool-wide op-class "
        "p50 is a strike", positive=True)
declare("SRJT_QUARANTINE_STRIKES", "int", 5,
        "net strikes (slow samples minus clean ones) before a worker "
        "is quarantined", minimum=1)
declare("SRJT_QUARANTINE_MIN_SAMPLES", "int", 20,
        "op-class samples required before the detector issues "
        "verdicts (cold starts are never strikes)", minimum=1)
declare("SRJT_QUARANTINE_PROBES", "int", 3,
        "consecutive clean probes before a quarantined worker is "
        "reinstated", minimum=1)
declare("SRJT_QUARANTINE_PROBE_INTERVAL_S", "float", 0.25,
        "pause between background probes of a quarantined worker",
        positive=True)
declare("SRJT_QUARANTINE_PROBE_SLOW_S", "float", 0.25,
        "a probe round-trip slower than this is dirty (resets the "
        "clean-probe run)", positive=True)
declare("SRJT_HEDGE_ENABLED", "bool", True,
        "arm hedged dispatch: a pool request outliving the op-class "
        "p95 launches one duplicate on a different healthy worker, "
        "first valid response wins")
declare("SRJT_HEDGE_BUDGET_PCT", "float", 10.0,
        "global hedge budget: duplicates stay under this percent of "
        "total pool calls", positive=True)
declare("SRJT_HEDGE_MIN_SAMPLES", "int", 20,
        "op-class samples required before hedging arms (cold ops "
        "never hedge)", minimum=1)
declare("SRJT_HEDGE_MIN_DELAY_S", "float", 0.05,
        "floor on the hedge trigger delay: ops faster than this "
        "never hedge", positive=True)
declare("SRJT_HEDGE_SHED_WINDOW_S", "float", 5.0,
        "hedging auto-disarms for this long after a serve-layer shed "
        "(an overloaded pool must not carry duplicate load)",
        positive=True)

# adaptive timeouts (sidecar.py / parallel/shuffle.py, ISSUE 9)
declare("SRJT_ADAPTIVE_TIMEOUT_ENABLED", "bool", True,
        "derive per-op socket deadlines from observed latency "
        "quantiles (q99 x multiplier) instead of the static knob "
        "once enough samples exist")
declare("SRJT_ADAPTIVE_TIMEOUT_MULT", "float", 4.0,
        "adaptive deadline = observed op q99 x this multiplier",
        positive=True)
declare("SRJT_ADAPTIVE_TIMEOUT_FLOOR_S", "float", 10.0,
        "adaptive deadlines never shrink below this floor",
        positive=True)
declare("SRJT_ADAPTIVE_TIMEOUT_MIN_SAMPLES", "int", 40,
        "per-op samples required before the adaptive deadline "
        "replaces the static knob (cold-start ops keep the knob)",
        minimum=1)

# cross-process exchange (parallel/shuffle.py, PR 6)
declare("SRJT_EXCHANGE_MODE", "str", "mesh",
        "mesh (in-process collective) or tcp (cross-process frames); "
        "the --exchange-worker harness defaults to tcp and refuses "
        "mesh", choices=("mesh", "tcp"))
declare("SRJT_EXCHANGE_TIMEOUT_SEC", "float", 30.0,
        "per-fetch deadline on the TCP exchange (always clamped by an "
        "active query deadline)", positive=True)
declare("SRJT_EXCHANGE_RETAIN_EPOCHS", "int", 4,
        "published exchange rounds kept servable; older epochs are "
        "evicted on publish", minimum=1)

# cluster membership + liveness (parallel/cluster.py, ISSUE 16)
declare("SRJT_CLUSTER_HEARTBEAT_SEC", "float", 0.5,
        "heartbeat cadence: each rank PINGs every peer this often; "
        "misses drive the alive -> suspect -> dead transitions",
        positive=True)
declare("SRJT_CLUSTER_HEARTBEAT_TIMEOUT_SEC", "float", 2.0,
        "per-PING deadline budget (utils/deadline scope); a PING "
        "slower than this counts as a miss", positive=True)
declare("SRJT_CLUSTER_SUSPECT_MISSES", "int", 2,
        "consecutive heartbeat misses before an ALIVE peer is marked "
        "SUSPECT (still routable, health-degraded)", minimum=1)
declare("SRJT_CLUSTER_DEAD_MISSES", "int", 4,
        "consecutive heartbeat misses before a SUSPECT peer is marked "
        "DEAD: the generation bumps and recovery engages", minimum=1)
declare("SRJT_CLUSTER_QUORUM_FRACTION", "float", 0.5,
        "alive fraction (self included) at or below which the cluster "
        "is degraded: serving sheds Overloaded(cluster_degraded)",
        positive=True)
declare("SRJT_CLUSTER_TOPOLOGY", "str", "auto",
        "exchange plan over the ClusterView: all_to_all (direct pulls "
        "from every peer), tree (hypercube rounds, power-of-two "
        "worlds), or auto (tree iff world is a power of two >= 4)",
        choices=("auto", "all_to_all", "tree"))

# memory governor (memgov/, PR 4)
declare("SRJT_DEVICE_MEMORY_BUDGET", "int", None,
        "device byte budget (read LIVE; unset: memoized backend probe "
        "minus live bytes_in_use)")
declare("SRJT_HOST_MEMORY_BUDGET", "int", 0,
        "host-tier bytes before host->disk demotion (0 = unlimited)")
declare("SRJT_SPILL_ENABLED", "bool", None,
        "1/0 arms/disarms the governor explicitly; unset: armed iff a "
        "device budget is declared")
declare("SRJT_SPILL_DIR", "str", None,
        "disk-tier directory (unset: per-process dir under the system "
        "tempdir)")
declare("SRJT_ADMISSION_MAX_CONCURRENT", "int", 0,
        "cap on concurrently admitted ops (0 = bytes only)")
declare("SRJT_ADMISSION_MAX_WAIT_SEC", "float", 30.0,
        "admission queue wait before the retryable "
        "MemoryBudgetExceeded", positive=True)
declare("SRJT_MEMGOV_HEADROOM", "float", 2.0,
        "input-bytes -> footprint multiplier for the default estimate",
        positive=True)
declare("SRJT_MEMGOV_DROP_SMCACHE", "bool", False,
        "1 lets pressure drop compiled shard_map executables as a "
        "last resort")

# out-of-core partitioned execution (plan/ooc.py, ISSUE 18)
declare("SRJT_OOC_ENABLED", "bool", False,
        "arm out-of-core degradation: a plan whose estimated peak "
        "exceeds the armed SRJT_DEVICE_MEMORY_BUDGET is rewritten "
        "(partition_for_ooc, verifier-discharged) into K hash "
        "partitions streamed through the compiled pipeline and merged")
declare("SRJT_OOC_PARTITIONS", "int", 0,
        "partition count K for out-of-core plans; 0 = auto (smallest "
        "K <= 64 whose per-partition estimate fits half the device "
        "budget)")
declare("SRJT_OOC_PREFETCH", "bool", True,
        "overlap the next partition's spill-in (catalog "
        "re-materialization + a sidecar-pool ping) with the current "
        "partition's compute")
declare("SRJT_OOC_METRICS", "str", None,
        "JSONL path appended one line per out-of-core run (partitions, "
        "resumes, lineage recomputes, spill count, wall) — the "
        "premerge ooc tier's artifact gate")

# concurrent serving runtime (serve/, ISSUE 8)
declare("SRJT_SERVE_MAX_CONCURRENT", "int", 4,
        "scheduler dispatch slots: queries executing concurrently "
        "across the op_boundary -> memgov -> sidecar-pool path",
        minimum=1)
declare("SRJT_SERVE_QUEUE_DEPTH", "int", 64,
        "per-tenant bounded FIFO queue depth; a full queue sheds "
        "lowest-priority-first with retryable Overloaded", minimum=1)
declare("SRJT_SERVE_MAX_QUEUED", "int", 0,
        "global queued-query cap across all tenants (0 = per-tenant "
        "bounds only); past it the overload controller sheds at "
        "admission")
declare("SRJT_SERVE_MAX_QUEUE_AGE_SEC", "float", 30.0,
        "overload controller: oldest-queued-query age past which "
        "admission sheds lowest-priority-first", positive=True)
declare("SRJT_SERVE_RETRY_AFTER_SEC", "float", 0.25,
        "default retry_after_s backoff hint carried by a shed's "
        "Overloaded error", positive=True)

# serving-tier caches (cache/, ISSUE 17)
declare("SRJT_PLAN_CACHE", "bool", False,
        "arm the compiled-plan cache: serve.submit(plan) keys on the "
        "parameterized structural fingerprint, a hit skips "
        "rewrite->verify->compile and rebinds the fresh literals into "
        "the cached optimized plan (re-verified once per structure at "
        "insert, not per submission)")
declare("SRJT_SUBRESULT_CACHE", "bool", False,
        "arm the subresult cache: scan/aggregate stage outputs are "
        "registered as memgov catalog entries (kind=cache) keyed by "
        "(parameterized subtree fingerprint, literal bindings, table "
        "generations), so eviction/spill tiering/byte accounting ride "
        "the governor")
declare("SRJT_CACHE_SHARING", "bool", True,
        "in-flight single-flight sharing of identical submissions "
        "(multi-query optimization): concurrent queries with one plan "
        "key attach to ONE computation and fan the result out — only "
        "consulted when SRJT_PLAN_CACHE is armed")
declare("SRJT_CACHE_PLAN_ENTRIES", "int", 64,
        "parameterized-structure entries the compiled-plan cache "
        "retains (LRU past it)", minimum=1)
declare("SRJT_CACHE_PLAN_VARIANTS", "int", 8,
        "fully-bound CompiledPlan variants retained per structure "
        "entry (exact-literal resubmission reuses the artifact "
        "outright; LRU past it)", minimum=1)
declare("SRJT_CACHE_SUBRESULT_BYTES", "int", 256 * 1024 * 1024,
        "byte cap on subresult-cache catalog entries; past it the "
        "cache LRU-unregisters its own entries (on top of — never "
        "instead of — memgov's spill/eviction pressure)", minimum=1)
declare("SRJT_SERVE_FORECAST_BUDGET_SEC", "float", 0.0,
        "admission-cost forecasting: predicted seconds of queued plan "
        "runtime (observed-cost EWMA carried by cached plans) the "
        "scheduler accepts before shedding with "
        "Overloaded(cause=\"forecast\"); 0 disables the forecaster")

# crash-recoverable serving: durable query journal + spill/checkpoint
# re-attach (serve/journal.py, memgov/persist.py, ISSUE 20)
declare("SRJT_JOURNAL_DIR", "str", None,
        "arm the durable query journal: serve.submit appends an "
        "fsync'd CRC-framed record per admitted query (and its state "
        "transitions) to segmented logs under this directory; a "
        "restarted coordinator replays it to answer DONE work by "
        "digest and resubmit incomplete work (unset: today's "
        "volatile posture — zero new files, no fsync on submit)")
declare("SRJT_JOURNAL_SEGMENT_BYTES", "int", 4 * 1024 * 1024,
        "journal segment roll threshold: an append that would push "
        "the active segment past this many bytes opens a new one",
        minimum=4096)
declare("SRJT_JOURNAL_FSYNC", "bool", True,
        "0 skips the per-append fsync (crash window widens to the OS "
        "page cache; replay still truncates any torn tail)")
declare("SRJT_SPILL_MANIFESTS", "bool", False,
        "arm durable spill metadata: every disk-tier spill/checkpoint "
        "frame gains a CRC-framed sidecar manifest, a fresh process "
        "re-attaches surviving entries into its catalog "
        "(memgov.reattached) and a startup sweep reclaims frames "
        "owned by a provably-dead PID (memgov.orphans_reclaimed)")
declare("SRJT_OOC_DURABLE_CHECKPOINTS", "bool", False,
        "force every completed out-of-core partition checkpoint to "
        "the disk tier at registration (with SRJT_SPILL_MANIFESTS "
        "this is what a restarted coordinator resumes past; off, "
        "checkpoints demote to host and die with the process)")

# Pallas kernel tier (ops/pallas_kernels.py, ISSUE 13)
declare("SRJT_PALLAS_JOIN", "bool", True,
        "arm the paged-hash-table Pallas join tier for single int-key "
        "inner/left joins (0 forces the XLA sort-probe formulation; "
        "unsupported shapes/dtypes fall back automatically either way)")
declare("SRJT_PALLAS_DECODE", "bool", False,
        "arm the fused ragged-decode Pallas kernel for string-column "
        "row decode (off: the XLA scatter/funnel formulation serves). "
        "Off by default since the v5e's compiler refuses the kernel at "
        "lowering (ROADMAP.md A2); interpret mode still runs it")
declare("SRJT_PALLAS_INTERPRET", "bool", False,
        "run kernel-tier Pallas paths through the Pallas interpreter "
        "off-TPU (hermetic CI parity of the exact kernel bodies; "
        "production CPU keeps the XLA formulations)")

# runtime / harness
declare("SRJT_NATIVE_LIB", "str", None,
        "explicit libsrjt.so path (before the packaged / dev-build "
        "candidates)")
declare("SRJT_TEST_TPU", "bool", False,
        "run the hermetic test suite against real TPU devices instead "
        "of the virtual 8-device CPU mesh", scope="harness")
declare("SRJT_RESULTS", "str", None,
        "bench drivers append BENCH/JSONL result rows to this path",
        scope="harness")

# plan compiler (plan/, ISSUE 14)
declare("SRJT_PLAN_REPORT", "str", None,
        "append one JSON line per compiled-plan execution (node counts, "
        "rewrites fired, per-stage estimate-vs-actual bytes) to this "
        "path — the ci/premerge.sh compiler tier's artifact source",
        scope="harness")

# plan verification + differential fuzzing (plan/verifier.py,
# analysis/plancheck.py, analysis/planfuzz.py, ISSUE 15)
declare("SRJT_PLANCHECK_ROWS", "int", 256,
        "rows bound per generator when the plancheck CLI compiles the "
        "checked-in plans (compile-only — no execution)",
        scope="harness", positive=True)
declare("SRJT_PLANCHECK_FUZZ_SEEDS", "str", "1234",
        "comma-separated base seeds for the planfuzz differential "
        "smoke; every generated plan is a pure function of "
        "(seed, index)", scope="harness")
declare("SRJT_PLANCHECK_FUZZ_PLANS", "int", 50,
        "plans generated per base seed by the planfuzz CLI",
        scope="harness", minimum=1)

# statistics + cost-based optimizer (plan/stats/, plan/optimizer.py,
# ISSUE 19)
declare("SRJT_STATS_ENABLED", "bool", True,
        "collect per-column sketches (row count, min/max, HLL distinct "
        "count, equi-depth histogram, null fraction) lazily at Scan and "
        "cache them against table generation stamps; 0 falls the "
        "compiler back to its hand-tuned selectivity/width heuristics")
declare("SRJT_STATS_HISTOGRAM_BINS", "int", 16,
        "equi-depth histogram bins per sketched column (more bins = "
        "tighter range-predicate selectivity, more stats memory)",
        minimum=2)
declare("SRJT_STATS_HLL_BITS", "int", 9,
        "HyperLogLog register-index bits per sketched column (2^bits "
        "registers; 9 = 512 registers ~= 3.6% standard error; read "
        "sites clamp to at most 14)", minimum=4)
declare("SRJT_STATS_MAX_ROWS", "int", 262144,
        "head-sample cap per column when collecting sketches; counts "
        "above the cap are scaled back up by the sampling ratio",
        positive=True)
declare("SRJT_CBO_ENABLED", "bool", True,
        "run the cost-based optimizer pass after the default rewrite: "
        "join-order enumeration, build-side commutes, and physical join "
        "strategy resolution, each fired as a verified rewrite with its "
        "own PLAN006 obligation (requires SRJT_STATS_ENABLED)")
declare("SRJT_CBO_DP_TABLES", "int", 6,
        "join-chain length up to which the exact subset-DP order search "
        "runs; longer chains use the greedy fanout-sorted fallback",
        minimum=2)
declare("SRJT_CBO_CALIBRATION", "str", "artifacts/plan_compile.jsonl",
        "plan-report JSONL the byte-estimate calibration is learned "
        "from (per-stage-kind median actual/est, clamped to [0.5, 2x]); "
        "missing file = neutral factors")

# correctness tooling (analysis/, ISSUE 7)
declare("SRJT_LOCKDEP", "bool", False,
        "arm the runtime lock-order instrumentation "
        "(analysis/lockdep.py): records per-thread acquisition stacks, "
        "reports lock-order cycles and blocking-while-locked events at "
        "process exit")
declare("SRJT_LOCKDEP_DIR", "str", "artifacts/lockdep",
        "directory lockdep writes its per-process JSON reports into "
        "(merged/gated by python -m "
        "spark_rapids_jni_tpu.analysis.lockdep)")
declare("SRJT_RACE", "bool", False,
        "arm the dynamic race detector (srjt-race layer 2, rides the "
        "lockdep shim): per-thread vector clocks over lock/Event/"
        "Thread/Semaphore/Barrier edges; unordered accesses to tracked "
        "state land as race_pairs in the lockdep report and fail the "
        "merge gate")
