"""What JAX says about the device, and the refusal to run without one."""

from __future__ import annotations

import sys


def info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, as the backend reports it."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()]
    return int(max(peaks))


def require(device: dict, chips: int, rehearse: bool) -> None:
    """No accelerator, or fewer chips than the cell asks for: no result.
    Only the rehearsal may go on, and it can never print a device metric."""
    if rehearse:
        return
    if device["platform"] != "tpu":
        print(f"bench: no accelerator (platform {device['platform']!r}); --rehearse walks the "
              "control flow on the CPU", file=sys.stderr)
        raise SystemExit(3)
    if device["count"] < chips:
        print(f"bench: the cell asks for {chips} chip(s), JAX reports {device['count']}", file=sys.stderr)
        raise SystemExit(3)


def profiler_options():
    """Device events and TraceMe ranges; no Python call tracing (it slows
    the host and swells the trace)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def start_profile(trace_dir: str) -> float:
    """Start the profiler and drop the anchor event; returns the anchor's
    wall-clock time."""
    import time

    import jax

    from .tracered import ANCHOR

    jax.profiler.start_trace(trace_dir, profiler_options=profiler_options())
    with jax.profiler.TraceAnnotation(ANCHOR):
        wall = time.time()
        time.sleep(0.001)
    return wall
