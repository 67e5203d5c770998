"""The sidecar worker, started by the benchmark so that the one process
that owns the chip can say what the client cannot see: the device JAX
reports, its peak memory, and a profiler trace of the window.

Runs ``spark_rapids_jni_tpu.sidecar``'s own ``__main__`` unchanged; beside
it one thread answers single words on a control socket:

    device        -> {"platform", "kind", "count"}
    memory        -> {"memory_peak_bytes"}
    trace_start D -> starts the profiler into directory D, {"anchor_wall_s"}
    trace_stop    -> stops it

Usage: worker_main.py <control socket> -m spark_rapids_jni_tpu.sidecar --socket <path>
"""

from __future__ import annotations

import json
import runpy
import socket
import sys
import threading


def _control(path: str) -> None:
    from benchlib import device  # bench/ is this script's directory: already on sys.path

    srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    srv.bind(path)
    srv.listen(4)
    while True:
        conn, _ = srv.accept()
        with conn:
            words = conn.makefile("r").readline().split()
            try:
                if words[0] == "device":
                    reply = device.info()
                elif words[0] == "memory":
                    reply = {"memory_peak_bytes": device.memory_peak_bytes()}
                elif words[0] == "trace_start":
                    reply = {"anchor_wall_s": device.start_profile(words[1])}
                elif words[0] == "trace_stop":
                    import jax

                    jax.profiler.stop_trace()
                    reply = {}
                else:
                    reply = {"error": f"unknown word {words[0]!r}"}
            except Exception as e:  # the client reports it and fails the run
                reply = {"error": f"{type(e).__name__}: {e}"}
            conn.sendall((json.dumps(reply) + "\n").encode())


def main() -> None:
    ctl = sys.argv[1]
    sys.argv = ["spark_rapids_jni_tpu.sidecar"] + sys.argv[4:]  # drop: ctl, -m, module
    threading.Thread(target=_control, args=(ctl,), daemon=True).start()
    runpy.run_module("spark_rapids_jni_tpu.sidecar", run_name="__main__", alter_sys=True)


if __name__ == "__main__":
    main()
