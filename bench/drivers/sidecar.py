"""Entry driver ``sidecar``: a request is one operator call as the C ABI
client makes it: the table, in the wire layout of
``native/src/sidecar.cc``, copied into a leased region of the slab arena
(the program's ``lease`` and ``ArenaRegion.write``), sent to the one
worker that owns the chip, and the reply taken back to the client's
hands, to its last byte. The wire bytes are built once in set-up: the
benchmark's own Python serialisation is no part of a request, so all the
client time inside one is the program's. The client process never touches
JAX; the worker is started through bench/worker_main.py, which runs the
program's worker unchanged and answers for the device.
"""

from __future__ import annotations

import functools
import json
import os
import socket
import stat
import struct
import shutil
import sys
import tempfile
import time

import numpy as np

from benchlib import device, loader, tracered

KEEP = 6  # answers held for the comparison: ~1.2 GB each


def _encode_table(cols, type_ids) -> bytes:
    """The walker layout of sidecar._read_table."""
    out = [struct.pack("<I", len(cols))]
    for (_, data, validity), type_id in zip(cols, type_ids):
        out.append(struct.pack("<iiQ", type_id, 0, len(data)))
        if validity is None:
            out.append(b"\x00")
        else:
            out.append(b"\x01")
            out.append(validity.astype("u1").tobytes())
        out.append(struct.pack("<Q", data.nbytes))
        out.append(data.tobytes())
    return b"".join(out)


class Session:
    def __init__(self, cell: dict, seed: int, rehearse: bool, trace: bool, workdir: str):
        self.cell, self.seed, self.rehearse, self.trace, self.workdir = cell, seed, rehearse, trace, workdir
        self.config, self.traffic = cell["config"], cell["traffic"]
        self.kept, self.seen = [], 0
        self.pick = np.random.default_rng(seed)  # which answers are compared: drawn from the seed
        self.facts, self.client_s = {}, []
        self.pool = None

    def _ctl(self, *words) -> dict:
        with socket.socket(socket.AF_UNIX, socket.SOCK_STREAM) as s:
            s.settimeout(300)
            s.connect(self.ctl_path)
            s.sendall((" ".join(words) + "\n").encode())
            reply = json.loads(s.makefile("r").readline())
        if "error" in reply:
            raise RuntimeError(f"worker control {words[0]}: {reply['error']}")
        return reply

    def setup(self) -> None:
        from spark_rapids_jni_tpu import sidecar
        from spark_rapids_jni_tpu.columnar.dtype import TypeId
        from spark_rapids_jni_tpu.sidecar_pool import SidecarPool

        self.sidecar = sidecar
        self.op = getattr(sidecar, self.traffic["op"])
        # python_exe of the program's own spawn_worker: a launcher that puts
        # worker_main.py and the control socket in front of its arguments
        # (under TMPDIR: a socket path holds 107 characters, a checkout's may be longer)
        self.ctl_dir = tempfile.mkdtemp(prefix="srjt-bench-")
        self.ctl_path = os.path.join(self.ctl_dir, "ctl.sock")
        launcher = os.path.join(self.workdir, "worker_python")
        with open(launcher, "w") as f:
            f.write(f'#!/bin/sh\nexec "{sys.executable}" "{os.path.join(loader.BENCH_DIR, "worker_main.py")}" '
                    f'"{self.ctl_path}" "$@"\n')
        os.chmod(launcher, os.stat(launcher).st_mode | stat.S_IXUSR)
        env = {}
        if self.trace:
            self.span_base = os.path.join(self.workdir, "spans")
            env = {"SRJT_TRACE_ENABLED": "1", "SRJT_TRACE_LOG": self.span_base}
            from spark_rapids_jni_tpu.utils import trace_sink, tracing

            tracing.set_enabled(True)
            trace_sink.set_log_path(self.span_base)
            self._tracing = tracing
        spawn = functools.partial(sidecar.spawn_worker, python_exe=launcher)
        t0 = time.perf_counter()
        self.pool = SidecarPool(size=int(self.traffic["pool_size"]), startup_timeout_s=300.0,
                                spawn_fn=spawn, env=env or None)
        self.facts["worker_start_s"] = time.perf_counter() - t0
        backend = self.pool.call(sidecar.OP_PING).decode()
        self.device = self._ctl("device")
        if backend != self.device["platform"]:
            raise RuntimeError(f"worker PING says {backend!r}, its JAX says {self.device}")
        device.require(self.device, self.cell["chips"], self.rehearse)

        spec = self.config["tables"]["table"]
        self.nrows = spec["rehearse_rows" if self.rehearse else "rows"]
        t0 = time.perf_counter()
        self.cols = loader.module("data", self.config["data"]).host_tables(self.config, self.seed, self.nrows)["table"]
        self.type_ids = [int(TypeId[t].value) for t, _, _ in self.cols]
        self.payload = _encode_table(self.cols, self.type_ids)  # once: not the program's work
        self.facts["data_s"] = time.perf_counter() - t0
        jcudf = loader.module("references", self.config["reference"])
        _, _, self.row_size = jcudf.layout(self.cols)
        self.table_bytes = sum(d.nbytes + (0 if v is None else len(v)) for _, d, v in self.cols)
        self.reply_bytes = self.nrows * self.row_size + 4 * (self.nrows + 1) + 20
        # the first lease sizes the slab: request and reply both fit
        self.pool.ensure_slab(min_bytes=max(self.table_bytes + 32 * len(self.cols), self.reply_bytes) + 4096)
        for i in range(max(2, int(self.traffic.get("warmup_requests", 2)))):
            t0 = time.perf_counter()
            self.issue(-1)
            self.facts[f"warmup_{i}_s"] = time.perf_counter() - t0
        self.client_s = []

    def issue(self, i: int):
        span = self._tracing.start_trace("bench.request") if self.trace else None
        try:
            t0 = time.perf_counter()
            # one region holds the request and then the reply, as the native client's one arena does:
            # a reply that does not fit its request's region is streamed over the socket instead
            region = self.pool.lease(max(len(self.payload), self.reply_bytes))
            try:
                region.write(self.payload)
                t1 = time.perf_counter()
                if span is not None:
                    with span.activate():
                        reply = self.pool.call(self.op, region=region)
                else:
                    reply = self.pool.call(self.op, region=region)
            finally:
                region.release()
            t2 = time.perf_counter()
            (nbatches,) = struct.unpack_from("<I", reply, 0)
            (nrows,) = struct.unpack_from("<Q", reply, 4)
            t3 = time.perf_counter()
        finally:
            if span is not None:
                span.finish()
        self.client_s.append((t1 - t0) + (t3 - t2))
        return self.nrows, (nbatches, nrows, reply)

    def keep(self, i: int, handle) -> None:
        """A sample of at most KEEP answers, drawn from the seed (reservoir):
        holding one costs no copy, dropping one frees ~1.2 GB."""
        self.seen += 1
        if len(self.kept) < KEEP:
            self.kept.append((i, handle))
        else:
            j = int(self.pick.integers(0, self.seen))
            if j < KEEP:
                self.kept[j] = (i, handle)

    def start_trace(self) -> None:
        self.trace_dir = os.path.join(self.workdir, "profile")
        self.anchor_wall = self._ctl("trace_start", self.trace_dir)["anchor_wall_s"]

    def stop_trace(self):
        from spark_rapids_jni_tpu.utils import trace_sink

        self._ctl("trace_stop")
        trace_sink.close_log()
        self.stats = self.pool.worker_stats(fold=False)  # the worker flushes its spans as it answers
        trace = tracered.reduce_xplane(self.trace_dir, self.anchor_wall)
        return trace, tracered.read_span_log(self.span_base)

    def memory_peak_bytes(self) -> int:
        return int(self._ctl("memory")["memory_peak_bytes"])

    def request_bytes(self) -> int:
        return self.table_bytes + self.nrows * self.row_size + 4 * (self.nrows + 1)

    def release(self) -> None:
        snap = self.pool.snapshot()
        self.pool_numbers = {"host_fallbacks": float(snap["host_fallbacks"]),
                             "worker_deaths": float(snap["worker_deaths"] + snap["failovers"])}
        self.pool.shutdown()
        self.pool = None

    def check(self, substitute=None) -> dict:
        """Bytes of each kept answer against the JCUDF layout reference.
        ``substitute`` puts the control (nulls not honoured) in the
        program's place."""
        jcudf = loader.module("references", self.config["reference"])
        want, row_size = jcudf.rows(self.cols)
        offsets = np.arange(self.nrows + 1, dtype=np.int64) * row_size
        if substitute is not None:
            got, _ = jcudf.rows(self.cols, honour_nulls=False)
            return {"to_rows.bytes_diff": float(np.count_nonzero(got != want)), "to_rows.frame_diff": 0.0,
                    "pool.host_fallbacks": 0.0, "pool.worker_deaths": 0.0}
        frame_diff, bytes_diff = 0.0, 0.0
        for _, (nbatches, nrows, reply) in self.kept:
            if nbatches != 1 or nrows != self.nrows or len(reply) != self.reply_bytes:
                frame_diff += 1
                bytes_diff = float("inf")
                continue
            offs = np.frombuffer(reply, np.int32, self.nrows + 1, 12)
            (blob_len,) = struct.unpack_from("<Q", reply, 12 + 4 * (self.nrows + 1))
            if blob_len != self.nrows * row_size or not np.array_equal(offs, offsets):
                frame_diff += 1
                bytes_diff = float("inf")
                continue
            blob = np.frombuffer(reply, np.uint8, blob_len, 20 + 4 * (self.nrows + 1)).reshape(self.nrows, row_size)
            bytes_diff += float(np.count_nonzero(blob != want))
        if not self.kept:
            frame_diff = 1.0
        return {"to_rows.bytes_diff": bytes_diff, "to_rows.frame_diff": frame_diff,
                "pool.host_fallbacks": self.pool_numbers["host_fallbacks"],
                "pool.worker_deaths": self.pool_numbers["worker_deaths"]}

    def close(self) -> None:
        if self.pool is not None:
            self.pool.shutdown()
            self.pool = None
        shutil.rmtree(getattr(self, "ctl_dir", ""), ignore_errors=True)
