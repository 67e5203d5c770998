"""Memoized jit(shard_map) executables for the distributed op tier.

Why this exists: an EAGER shard_map executes its body primitive-by-
primitive (one tiny XLA compile per op — ~100 s wall for the exact-f64
window graph on a 1-core box), so every site wraps its shard_map in
``jax.jit``. But jit's executable cache is keyed on the *callable
object*: a body closure rebuilt per call would retrace and recompile
the whole program every time. This module is the missing memo — the
jitted callable is cached on an explicit key of everything the body
closes over (mesh, axis, capacities, lane counts, agg descriptors);
jit then layers its own per-shape cache under each entry.

The key MUST capture every closed-over static. A missed key component
means two configs share one compiled program — jit re-traces on shape
changes, but a Python-level static (a capacity, an agg list) baked into
the first trace would silently serve the second config. Sites therefore
build keys from ALL their locals that feed the body.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Callable

import jax

from ..utils import tracing

# every distributed site imports the symbol from here so the memo and
# the primitive stay in one place
shard_map = jax.shard_map

_CACHE: OrderedDict = OrderedDict()
_MAX_ENTRIES = 128


def cached_sm(key, build: Callable):
    """Return the memoized jitted shard_map for ``key``, building it
    with ``build()`` (-> jax.jit(jax.shard_map(...))) on first use."""
    f = _CACHE.get(key)
    if f is None:
        while len(_CACHE) >= _MAX_ENTRIES:
            _CACHE.popitem(last=False)
        # every mesh program is launched from here: ONE place for its ``device.launch`` event
        f = _CACHE[key] = tracing.launches(build())
    else:
        _CACHE.move_to_end(key)
    return f


def entry_count() -> int:
    return len(_CACHE)


def clear() -> int:
    """Drop every memoized executable; returns how many were dropped.
    Compiled programs hold device constants, so this frees real device
    memory at the cost of recompiling on next use — the memory
    governor's pressure loop (memgov/pressure.py) calls it as an
    opt-in last resort (SRJT_MEMGOV_DROP_SMCACHE=1)."""
    n = len(_CACHE)
    _CACHE.clear()
    return n
