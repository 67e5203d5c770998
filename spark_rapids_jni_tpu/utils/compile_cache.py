"""Persistent XLA compile cache at a place that does not move.

The cache directory is part of every entry's key, so a path built from
``tempfile``, a pid or the clock would never hit. Where
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and this module
sets no directory; otherwise the cache lives in ``<checkout>/.jax_cache``,
derived from the package's own location. ``JAX_ENABLE_COMPILATION_CACHE=false``
(JAX's own switch, which ``tests/conftest.py`` exports) turns the cache
off for a process and every child that inherits its environment.

``configure()`` also registers the ``jax.monitoring`` listeners that
count compiles (always on; a listener runs only when JAX compiles or
reads its cache, so the cost outside a compile is zero):

    xla.backend_compiles    counter  programs handed to the backend
                                     (a persistent-cache hit included)
    xla.backend_compile_s   counter  seconds spent there
    xla.cache_hits          counter  persistent-cache hits
    xla.cache_misses        counter  programs compiled and written to it
    xla.cache_retrieval_s   counter  seconds spent reading hits

They appear in ``metrics.snapshot()``, hence in
``runtime.stats_report()`` and the sidecar worker's ``STATS`` reply:
"which step recompiled" is the counter's difference across the step.
With tracing on, each backend compile is also an ``xla.compile`` span
(annotations ``fun``, and ``cache`` = ``hit`` / ``miss`` where the
cache events tell) under whichever span is open on the compiling
thread.
"""

from __future__ import annotations

import os
import threading

# <checkout>/.jax_cache: the directory that holds the package
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure() -> "str | None":
    """Place JAX's persistent cache. Returns the directory this call
    set, or None where the environment had already placed it."""
    import jax

    # keep every program: the kernels compile in 0.1-6 s each and the
    # default 1 s threshold would drop most of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _register_listeners()
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):  # JAX reads it itself
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_registered = False
# the cache verdict of the compile in flight on this thread: the hit /
# miss events fire inside the backend-compile duration event
_tls = threading.local()


def _on_event(event: str, **kw) -> None:
    verdict = _CACHE_EVENTS.get(event)
    if verdict is None:
        return
    from . import metrics

    _tls.cache = verdict
    metrics.registry().counter(
        "xla.cache_hits" if verdict == "hit" else "xla.cache_misses"
    ).inc()


def _on_duration(event: str, duration_secs: float, **kw) -> None:
    if event == _BACKEND_COMPILE:
        from . import metrics, tracing

        reg = metrics.registry()
        reg.counter("xla.backend_compiles").inc()
        reg.counter("xla.backend_compile_s").inc(duration_secs)
        ann = {"fun": str(kw["fun_name"])} if "fun_name" in kw else {}
        verdict = getattr(_tls, "cache", None)
        if verdict is not None:
            ann["cache"] = verdict
            _tls.cache = None
        tracing.closed_span("xla.compile", duration_secs, **ann)
    elif event == _CACHE_RETRIEVAL:
        from . import metrics

        metrics.registry().counter("xla.cache_retrieval_s").inc(duration_secs)


def _register_listeners() -> None:
    """Once a process: the compile counters and the ``xla.compile``
    span (module docstring)."""
    global _registered
    if _registered:
        return
    _registered = True
    from jax import monitoring

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
