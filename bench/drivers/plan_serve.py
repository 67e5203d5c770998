"""Entry driver ``plan_serve``: a request is its queries, one after the
other, each ``serve.Scheduler.submit(cp).result()`` on a plan compiled
once in set-up by ``plan.compile_ir``, to ``block_until_ready`` on every
column of the result: what a Spark task does when it calls the operator
and waits.
"""

from __future__ import annotations

import os
import time

import numpy as np

from benchlib import compare, device, loader, roofline, tracered


def _values(a):
    return a[0] if isinstance(a, tuple) else a


def _series(pd, a):
    """A host column as the reference sees it: a NULL is NaN."""
    if isinstance(a, tuple):
        return pd.Series(a[0].astype(np.float64)).where(a[1])
    return pd.Series(a)


class Session:
    def __init__(self, cell: dict, seed: int, rehearse: bool, trace: bool, workdir: str):
        self.cell, self.seed, self.rehearse, self.trace, self.workdir = cell, seed, rehearse, trace, workdir
        self.config, self.traffic = cell["config"], cell["traffic"]
        self.queries = [(q, loader.module("queries", q)) for q in self.traffic["request"]]
        self.kept = []  # (request index, [result Table of each query])
        self.facts = {}

    # -- set-up: data from the seed, compile, warm-up -------------------------

    def setup(self) -> None:
        t0 = time.perf_counter()
        import jax

        import spark_rapids_jni_tpu  # noqa: F401  (x64 and the compile cache before any array)
        from spark_rapids_jni_tpu import plan as P
        from spark_rapids_jni_tpu import serve
        from spark_rapids_jni_tpu.columnar import Column, Table
        from spark_rapids_jni_tpu.columnar import dtype as dt
        from spark_rapids_jni_tpu.utils import trace_sink, tracing

        self.jax = jax
        self.device = device.info()
        device.require(self.device, self.cell["chips"], self.rehearse)
        self.facts["import_and_backend_s"] = time.perf_counter() - t0
        if self.trace:
            self.span_base = os.path.join(self.workdir, "spans")
            tracing.set_enabled(True)
            trace_sink.set_log_path(self.span_base)
        self._trace_sink = trace_sink

        rows_key = "rehearse_rows" if self.rehearse else "rows"
        self.rows = {t: spec[rows_key] for t, spec in self.config["tables"].items()}
        builder = loader.module("data", self.config["data"])
        t0 = time.perf_counter()
        self.host = builder.host_tables(self.config, self.seed, self.rows[self.config["scaled_table"]])
        types = {"float64": dt.FLOAT64, "int64": dt.INT64, "int32": dt.INT32, "int8": dt.INT8,
                 "timestamp_days": dt.TIMESTAMP_DAYS}

        def column(a, kind):
            a, valid = a if isinstance(a, tuple) else (a, None)  # (values, valid) where it carries nulls
            if kind == "string":
                return Column.from_pylist(list(a), dt.STRING)
            return Column.from_numpy(np.ascontiguousarray(a), types[kind], validity=valid)

        self.tables = {}
        for name, cols in self.host.items():
            spec = self.config["tables"][name]["columns"]
            self.rows[name] = len(_values(next(iter(cols.values()))))
            self.tables[name] = Table([column(a, spec[c]) for c, a in cols.items()], list(cols))
        jax.block_until_ready([x for t in self.tables.values() for c in t.columns
                               for x in (c.data, c.validity, c.offsets, c.chars) if x is not None])
        self.facts["data_s"] = time.perf_counter() - t0

        self.sched = serve.Scheduler(max_concurrent=1, name="bench")
        self.compiled, plan_s = [], 0.0
        for name, q in self.queries:
            t0 = time.perf_counter()
            self.compiled.append(P.compile_ir(q.plan(P), {t: self.tables[t] for t in q.TABLES}, name=name))
            plan_s += time.perf_counter() - t0
        self.facts["plan_compile_ir_s"] = plan_s
        self.request_rows = sum(self.rows[t] for _, q in self.queries for t in q.TABLES
                                if t == self.config["scaled_table"])
        for i in range(max(2, int(self.traffic.get("warmup_requests", 2)))):
            t0 = time.perf_counter()
            self.issue(-1)
            self.facts[f"warmup_{i}_s"] = time.perf_counter() - t0

    # -- the window -------------------------------------------------------------

    def issue(self, i: int):
        outs = []
        for cp in self.compiled:
            out = self.sched.submit(cp).result()
            self.jax.block_until_ready([x for c in out.columns for x in (c.data, c.validity) if x is not None])
            outs.append(out)
        return self.request_rows, outs

    def keep(self, i: int, handle) -> None:
        self.kept.append((i, handle))  # result tables are a few rows: every answer is compared

    def start_trace(self) -> None:
        self.trace_dir = os.path.join(self.workdir, "profile")
        self.anchor_wall = device.start_profile(self.trace_dir)

    def stop_trace(self):
        self.jax.profiler.stop_trace()
        self._trace_sink.close_log()
        trace = tracered.reduce_xplane(self.trace_dir, self.anchor_wall)
        return trace, tracered.read_span_log(self.span_base)

    def memory_peak_bytes(self) -> int:
        return device.memory_peak_bytes()

    # -- after the window ---------------------------------------------------------

    def request_bytes(self) -> int:
        """Input columns each query reads, once, plus its result."""
        total = 0
        for (_, q), out in zip(self.queries, self.kept[0][1]):
            total += roofline.column_bytes(self.host, q.READS)
            total += sum(np.asarray(x).nbytes for c in out.columns for x in (c.data, c.offsets, c.chars)
                         if x is not None)
        return total

    def release(self) -> None:
        self.sched.shutdown()
        self.compiled, self.tables = [], {}

    def check(self, substitute=None) -> dict:
        """Every answer of the window against the plain reference, which
        is computed once here (same data, same query: same answer).
        ``substitute`` puts a reference of another precision in the
        program's place (the control)."""
        import pandas as pd

        from spark_rapids_jni_tpu.columnar.dtype import TypeId

        frames = {n: pd.DataFrame({c: _series(pd, a) for c, a in cols.items()}) for n, cols in self.host.items()}
        readings = {}
        for k, (name, q) in enumerate(self.queries):
            want = q.reference(frames, np.float64)
            numbers = []
            if substitute is not None:
                ctl = q.reference(frames, substitute)
                numbers.append(compare.table_numbers({n: ctl[n].to_numpy() for n in ctl.columns}, 0, want, q.EXACT))
            for _, outs in (self.kept if substitute is None else []):
                out = outs[k]
                got, nulls = {}, 0
                for n, c in zip(out.names, out.columns):
                    if c.dtype.id == TypeId.STRING:
                        offs, chars = np.asarray(c.offsets), np.asarray(c.chars).tobytes()
                        got[n] = np.array([chars[a:b].decode() for a, b in zip(offs[:-1], offs[1:])], dtype=object)
                    else:
                        a = np.asarray(c.data)
                        got[n] = a.view(np.float64).copy() if c.dtype.id == TypeId.FLOAT64 else a
                    if c.validity is not None:
                        invalid = ~np.asarray(c.validity)
                        if n in q.EXACT:
                            nulls += int(np.count_nonzero(invalid))
                        else:
                            got[n][invalid] = np.nan
                numbers.append(compare.table_numbers(got, nulls, want, q.EXACT))
            for key, v in compare.worst(numbers).items():
                readings[f"{name}.{key}"] = v
        return readings

    def close(self) -> None:
        pass
