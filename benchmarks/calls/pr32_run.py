#!/usr/bin/env python3
"""PR 32: bench/run.py with the set-up's compiles counted (the plan_serve
driver keeps no such fact): every backend compile of 2 s or more by name
on stderr, and at exit one ``xla`` line with the registry's counters.
Run from the root of a checkout with bench/run.py's own arguments.
"""
import atexit
import os
import runpy
import sys


def _compiled(event, duration_secs, **kw):
    if event == "/jax/core/compile/backend_compile_duration" and duration_secs >= 2.0:
        print(f"[pr32] backend compile {duration_secs:.1f}s {kw.get('fun_name')}", file=sys.stderr, flush=True)


def _counters():
    from spark_rapids_jni_tpu.utils import metrics

    reg = metrics.registry()
    keys = ("backend_compiles", "backend_compile_s", "cache_hits", "cache_misses", "cache_retrieval_s")
    print("[pr32] xla " + " ".join(f"{k} {reg.value('xla.' + k):.1f}" for k in keys), file=sys.stderr, flush=True)


def main() -> None:
    root = os.getcwd()
    sys.path.insert(0, os.path.join(root, "bench"))
    sys.path.insert(1, root)
    sys.argv[0] = os.path.join(root, "bench", "run.py")
    from jax import monitoring  # touches no backend

    monitoring.register_event_duration_secs_listener(_compiled)
    atexit.register(_counters)
    runpy.run_path(sys.argv[0], run_name="__main__")


if __name__ == "__main__":
    main()
