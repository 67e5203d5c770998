"""Entry driver ``sidecar_from``: the ``sidecar`` driver's request (one
operator call as the C ABI client makes it, over one leased region of the
slab arena, to the last reply byte in the client's hands) in the other
direction: one ``CONVERT_FROM_ROWS`` of the seeded table's JCUDF rows, in
the layout of ``sidecar.py``'s op table and ``native/src/sidecar.cc``
(``ncols``, type ids, scales, ``nrows``, ``int32`` offsets, the blob's
length, the rows), built once in set-up. The reply is the table in the
walker layout; its header is read in ``issue()``, its columns in
``check()``, against ``bench/references/jcudf_from_rows.py``. The
comparison adds the worker's own count of decodes that took the gathered
form. Everything else is the ``sidecar`` driver's, unchanged.
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


def _encode_request(type_ids, rows) -> bytes:
    """The CONVERT_FROM_ROWS payload of ``rows`` ([n, row_size] uint8)."""
    n, row_size = rows.shape
    offsets = np.arange(n + 1, dtype=np.int64) * row_size
    if offsets[-1] > np.iinfo(np.int32).max:
        raise SystemExit("bench: the rows pass one 2 GiB batch: int32 offsets cannot hold them")
    return b"".join([
        struct.pack("<I", len(type_ids)),
        np.asarray(type_ids, "<i4").tobytes(),
        np.zeros(len(type_ids), "<i4").tobytes(),  # scales: no DECIMAL in the table
        struct.pack("<Q", n),
        offsets.astype("<i4").tobytes(),
        struct.pack("<Q", rows.nbytes),
        rows,
    ])


def parse_reply(reply) -> list:
    """[(type id, scale, rows, validity uint8 or None, data uint8)] a
    column, as views of ``reply``; raises ValueError where the frame does
    not hold what it says."""
    try:
        (ncols,) = struct.unpack_from("<I", reply, 0)
        pos, out = 4, []
        for _ in range(ncols):
            type_id, scale, n = struct.unpack_from("<iiQ", reply, pos)
            pos += 16
            has_validity = reply[pos]
            pos += 1
            validity = None
            if has_validity:
                validity = np.frombuffer(reply, np.uint8, n, pos)
                pos += n
            (dlen,) = struct.unpack_from("<Q", reply, pos)
            out.append((type_id, scale, n, validity, np.frombuffer(reply, np.uint8, dlen, pos + 8)))
            pos += 8 + dlen
    except (struct.error, IndexError) as e:
        raise ValueError(str(e)) from e
    if pos != len(reply):
        raise ValueError(f"{len(reply) - pos} bytes beyond the last column")
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
        cols = loader.module("data", self.config["data"]).host_tables(self.config, self.seed, self.nrows)["table"]
        self.type_ids = [int(TypeId[t].value) for t, _, _ in cols]
        self.dtypes = [data.dtype for _, data, _ in cols]
        rows, row_size = loader.module("references", "jcudf_rows").rows(cols)
        if row_size != int(spec["row_bytes_jcudf"]):
            raise SystemExit(f"bench: the table's JCUDF row is {row_size} bytes, the configuration's "
                             f"{spec['row_bytes_jcudf']}")
        self.payload = _encode_request(self.type_ids, rows)  # once: not the program's work
        del rows, cols
        # the rows the request sends, as a view of the payload: what the reference decodes
        self.rows = np.frombuffer(self.payload, np.uint8, self.nrows * row_size,
                                  len(self.payload) - self.nrows * row_size).reshape(self.nrows, row_size)
        self.facts["data_s"] = time.perf_counter() - t0
        self.jcudf = loader.module("references", self.config["reference"])
        ncols = len(self.type_ids)
        # the reply's layout today: every column with its validity bytes
        self.reply_bytes = 4 + ncols * (25 + self.nrows) + self.nrows * sum(d.itemsize for d in self.dtypes)
        self.pool.ensure_slab(min_bytes=max(len(self.payload), self.reply_bytes) + 4096)
        for i in range(max(2, int(self.traffic.get("warmup_requests", 2)))):
            t0 = time.perf_counter()
            self.issue(-1)
            self.facts[f"warmup_{i}_s"] = time.perf_counter() - t0
        self.client_s = []

    def issue(self, i: int):
        span = self._tracing.start_trace("bench.request") if self.trace else None
        try:
            t0 = time.perf_counter()
            # one region holds the request and then the reply, as the native client's one arena does
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
            (ncols,) = struct.unpack_from("<I", reply, 0)
            (nrows,) = struct.unpack_from("<Q", reply, 12) if ncols else (0,)
            t3 = time.perf_counter()
        finally:
            if span is not None:
                span.finish()
        self.client_s.append((t1 - t0) + (t3 - t2))
        return self.nrows, (ncols, nrows, reply)

    def request_bytes(self) -> int:
        # a constant of the configuration (rows and offsets in, the columns out), scaled to a rehearsal's rows
        return int(self.config["request_bytes"]) * self.nrows // int(self.config["tables"]["table"]["rows"])

    def release(self) -> None:
        # the worker's own counters first: how many decodes took the gathered
        # form (0.0 from a program that does not count them), and what it compiled
        counters = {}
        for stats in self.pool.worker_stats(fold=False).values():
            for k, v in ((stats.get("snapshot") or {}).get("counters") or {}).items():
                counters[k] = counters.get(k, 0.0) + float(v)
        self.worker_counters = {k: v for k, v in counters.items() if k.startswith(("rowconv.", "xla."))}
        self.gathered_decodes = counters.get("rowconv.from_rows.gathered", 0.0)
        super().release()

    def check(self, substitute=None) -> dict:
        """Each kept reply, column by column, against the plain decode of
        the rows the request sent: the frame (columns, type ids, scales,
        rows, lengths), then every validity flag and the value bytes of
        every valid row. ``substitute`` puts the control (nulls not
        honoured) in the program's place."""
        want = self.jcudf.columns(self.rows, self.dtypes)
        numbers = {"from_rows.bytes_diff": 0.0, "from_rows.validity_diff": 0.0, "from_rows.frame_diff": 0.0,
                   "pool.host_fallbacks": 0.0, "pool.worker_deaths": 0.0, "worker.gathered_decodes": 0.0}
        if substitute is not None:
            got = self.jcudf.columns(self.rows, self.dtypes, honour_nulls=False)
            numbers["from_rows.validity_diff"] = float(sum(np.count_nonzero(g != w)
                                                           for (_, g), (_, w) in zip(got, want)))
            return numbers
        for _, (_, _, reply) in self.kept:
            try:
                got = parse_reply(reply)
            except ValueError:
                got = []
            framed = len(got) == len(want) and all(
                t == tid and s == 0 and n == self.nrows and len(data) == n * d.itemsize
                and (v is None or len(v) == n)
                for (t, s, n, v, data), tid, d in zip(got, self.type_ids, self.dtypes))
            if not framed:
                numbers["from_rows.frame_diff"] += 1
                numbers["from_rows.bytes_diff"] = float("inf")
                continue
            for (_, _, n, v, data), (values, valid), d in zip(got, want, self.dtypes):
                got_valid = np.ones(n, bool) if v is None else v != 0
                numbers["from_rows.validity_diff"] += float(np.count_nonzero(got_valid != valid))
                diff = data.reshape(n, d.itemsize) != values.view(np.uint8).reshape(n, d.itemsize)
                numbers["from_rows.bytes_diff"] += float(np.count_nonzero(diff[valid]))
        if not self.kept:
            numbers["from_rows.frame_diff"] = 1.0
        numbers["pool.host_fallbacks"] = self.pool_numbers["host_fallbacks"]
        numbers["pool.worker_deaths"] = self.pool_numbers["worker_deaths"]
        numbers["worker.gathered_decodes"] = self.gathered_decodes
        print(f"fact worker counters {self.worker_counters}", file=sys.stderr)
        return numbers
