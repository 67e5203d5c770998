"""Entry driver ``sidecar_var``: the ``sidecar`` driver's request (one
operator call as the C ABI client makes it, over one leased region of the
slab arena, to the last reply byte in the client's hands) for a table
that holds STRING columns. A STRING column goes over the wire as the
walker layout of ``native/src/sidecar.cc`` has it (``int32`` offsets, a
length, the bytes), the reply's row offsets are ragged and it is parsed
batch by batch, and the comparison adds the worker's own count of calls
on the scatter path. Everything else is the ``sidecar`` driver's, unchanged.
"""

from __future__ import annotations

import functools
import os
import stat
import struct
import sys
import tempfile
import time

import numpy as np

from benchlib import device, loader

_base = loader.module("drivers", "sidecar")


def _encode_table(cols, type_ids) -> bytes:
    """The walker layout of sidecar._decode_table, STRING columns included."""
    out = [struct.pack("<I", len(cols))]
    for (_, data, validity), type_id in zip(cols, type_ids):
        offsets, body = data if isinstance(data, tuple) else (None, data)
        rows = len(body) if offsets is None else len(offsets) - 1
        out.append(struct.pack("<iiQ", type_id, 0, rows))
        if validity is None:
            out.append(b"\x00")
        else:
            out.append(b"\x01")
            out.append(validity.astype("u1").tobytes())
        if offsets is not None:
            out.append(offsets.astype("<i4").tobytes())
        out.append(struct.pack("<Q", body.nbytes))
        out.append(body.tobytes())
    return b"".join(out)


def parse_reply(reply) -> list:
    """[(rows, int32 offsets, uint8 bytes)] a batch, as views of ``reply``;
    raises ValueError where the frame does not hold what it says."""
    try:
        (nbatches,) = struct.unpack_from("<I", reply, 0)
        pos, out = 4, []
        for _ in range(nbatches):
            (rows,) = struct.unpack_from("<Q", reply, pos)
            offsets = np.frombuffer(reply, np.int32, rows + 1, pos + 8)
            pos += 8 + 4 * (rows + 1)
            (blob_len,) = struct.unpack_from("<Q", reply, pos)
            out.append((rows, offsets, np.frombuffer(reply, np.uint8, blob_len, pos + 8)))
            pos += 8 + blob_len
    except struct.error as e:
        raise ValueError(str(e)) from e
    if pos != len(reply):
        raise ValueError(f"{len(reply) - pos} bytes beyond the last batch")
    return out


class Session(_base.Session):
    def setup(self) -> None:
        from spark_rapids_jni_tpu import sidecar
        from spark_rapids_jni_tpu.columnar.dtype import TypeId
        from spark_rapids_jni_tpu.sidecar_pool import SidecarPool

        self.sidecar = sidecar
        self.op = getattr(sidecar, self.traffic["op"])
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
        self.jcudf = loader.module("references", self.config["reference"])
        if self.jcudf.layout(self.cols)[2] != int(spec["fixed_end"]):
            raise SystemExit("bench: the table's fixed section is not the configuration's fixed_end")
        sizes = self.jcudf.row_sizes(self.cols)
        batches = self.jcudf.batches_of(sizes)
        # values, validity bytes, offsets and characters in; rows and their offsets out
        self.table_bytes = sum(sum(a.nbytes for a in (d if isinstance(d, tuple) else (d,)))
                               + (0 if v is None else len(v)) for _, d, v in self.cols)
        self.rows_bytes = int(sizes.sum()) + sum(4 * (hi - lo + 1) for lo, hi in batches)
        self.reply_bytes = 4 + 16 * len(batches) + self.rows_bytes
        self.facts["rows_gb"] = int(sizes.sum()) / 1e9
        self.pool.ensure_slab(min_bytes=max(len(self.payload), self.reply_bytes) + 4096)
        for i in range(max(2, int(self.traffic.get("warmup_requests", 2)))):
            t0 = time.perf_counter()
            self.issue(-1)
            self.facts[f"warmup_{i}_s"] = time.perf_counter() - t0
        self.client_s = []

    def request_bytes(self) -> int:
        return self.table_bytes + self.rows_bytes

    def release(self) -> None:
        # the worker's own counters first: how many calls took the scatter
        # path (0.0 from a program that does not count them), and what it compiled
        counters = {}
        for stats in self.pool.worker_stats(fold=False).values():
            for k, v in ((stats.get("snapshot") or {}).get("counters") or {}).items():
                counters[k] = counters.get(k, 0.0) + float(v)
        self.worker_counters = {k: v for k, v in counters.items() if k.startswith(("rowconv.", "xla."))}
        self.scatter_encodes = counters.get("rowconv.to_rows.scatter", 0.0)
        super().release()

    def check(self, substitute=None) -> dict:
        """Each kept reply, batch by batch, against the JCUDF layout
        reference: the frame (batches, rows, offsets, lengths), then the
        bytes. ``substitute`` puts the control (nulls not honoured) in the
        program's place."""
        want = self.jcudf.rows(self.cols)
        numbers = {"to_rows.bytes_diff": 0.0, "to_rows.frame_diff": 0.0,
                   "pool.host_fallbacks": 0.0, "pool.worker_deaths": 0.0, "worker.scatter_encodes": 0.0}
        if substitute is not None:
            got = self.jcudf.rows(self.cols, honour_nulls=False)
            numbers["to_rows.bytes_diff"] = float(sum(np.count_nonzero(g != w) for (_, g), (_, w) in zip(got, want)))
            return numbers
        for _, (_, _, reply) in self.kept:
            try:
                got = parse_reply(reply)
            except ValueError:
                got = []
            framed = len(got) == len(want) and all(
                rows == len(wo) - 1 and np.array_equal(offs, wo) and len(blob) == len(wb)
                for (rows, offs, blob), (wo, wb) in zip(got, want))
            if not framed:
                numbers["to_rows.frame_diff"] += 1
                numbers["to_rows.bytes_diff"] = float("inf")
                continue
            numbers["to_rows.bytes_diff"] += float(sum(np.count_nonzero(blob != wb)
                                                       for (_, _, blob), (_, wb) in zip(got, want)))
        if not self.kept:
            numbers["to_rows.frame_diff"] = 1.0
        numbers["pool.host_fallbacks"] = self.pool_numbers["host_fallbacks"]
        numbers["pool.worker_deaths"] = self.pool_numbers["worker_deaths"]
        numbers["worker.scatter_encodes"] = self.scatter_encodes
        print(f"fact worker counters {self.worker_counters}", file=sys.stderr)
        return numbers
