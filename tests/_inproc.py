"""The in-process sidecar worker the pool, tail, tracing and data-plane
suites share: the real protocol loop without a subprocess."""

import os
import signal
import socket
import struct
import tempfile
import threading

import numpy as np

from spark_rapids_jni_tpu import sidecar

_JOIN_S = 5.0  # bound on waiting for a killed worker's threads


class InProcWorker:
    """Duck-types the Popen surface SidecarPool supervises, but serves
    ``sidecar._handle_conn`` from threads in THIS process. ``kill()``
    models kill -9: the listener and every live connection drop
    mid-frame, exactly what a client of a SIGKILLed worker observes.

    A handler closes its last spans and bumps its last
    ``sidecar.worker.*`` counters AFTER the client has its answer, so
    ``kill()`` also waits for every thread of the worker: nothing of a
    dead worker is left writing into the process-global span sink or
    metrics registry when the next test starts."""

    def __init__(self):
        self.sock_path = tempfile.mktemp(prefix="srjt-inproc-") + ".sock"
        self.pid = os.getpid()
        self.returncode = None
        self._conns = []
        self._handlers = []
        self._srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self._srv.bind(self.sock_path)
        self._srv.listen(8)
        self._t = threading.Thread(target=self._accept_loop, daemon=True)
        self._t.start()

    def _accept_loop(self):
        while True:
            try:
                conn, _ = self._srv.accept()
            except OSError:
                return  # killed
            self._conns.append(conn)

            def _serve(c=conn):
                try:
                    sidecar._handle_conn(c, "cpu", lambda: None)
                except OSError:
                    pass  # kill() closed the socket under the handler

            t = threading.Thread(target=_serve, daemon=True)
            self._handlers.append(t)
            t.start()

    # Popen surface the pool touches
    def poll(self):
        return self.returncode

    def wait(self, timeout=None):
        return self.returncode if self.returncode is not None else 0

    def terminate(self):
        self.kill()

    def kill(self):
        if self.returncode is None:
            self.returncode = -signal.SIGKILL
        # the listener first, and its thread joined, so that _conns is
        # whole before the connections drop
        self._drop(self._srv)
        self._t.join(_JOIN_S)
        for c in self._conns:
            self._drop(c)
        try:
            os.unlink(self.sock_path)
        except OSError:
            pass
        for t in self._handlers:
            t.join(_JOIN_S)

    @staticmethod
    def _drop(sock):
        # close() alone leaves a thread blocked in accept() or recv() on
        # the socket asleep for good: shut it down first
        try:
            sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            sock.close()
        except OSError:
            pass


def inproc_spawn(startup_timeout_s=None, env=None):
    w = InProcWorker()
    return w, w.sock_path


def groupby_payload(n=600, k=16, seed=3):
    """A GROUPBY request body: k groups over n (int64 key, f32 value) rows."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, k, n).astype(np.int64)
    vals = rng.standard_normal(n).astype(np.float32)
    return struct.pack("<IQ", k, n) + keys.tobytes() + vals.tobytes()
