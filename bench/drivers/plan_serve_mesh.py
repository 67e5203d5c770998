"""Entry driver ``plan_serve_mesh``: ``plan_serve`` over the chips of one
host. The cell's ``chips`` devices make a mesh (the traffic file names its
axis), the configuration's ``sharded`` tables are laid row-sharded over it
in file order and the others copied whole to every chip, the driver places
the plan's exchanges for that world (``P.insert_exchanges``) and hands the
mesh to ``plan.compile_ir``. A request is still
``serve.Scheduler.submit(cp).result()`` to ``block_until_ready`` on every
column of the result; the window, the trace and the comparison are
``plan_serve``'s, with three readings more: an answer that counts no order,
an exchange overflow that was not retried, inputs on fewer devices than
the cell has chips; ``exchange.overflows`` itself is printed as a fact.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

from benchlib import device, exchange_bytes, loader

plan_serve = loader.module("drivers", "plan_serve")

_TYPES = ("float64", "int64", "int32", "int8", "timestamp_days")


class Session(plan_serve.Session):
    def setup(self) -> None:
        t0 = time.perf_counter()
        chips = int(self.cell["chips"])
        if self.rehearse and "jax" not in sys.modules and "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
            # the rehearsal's mesh is of virtual CPU devices
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + f" --xla_force_host_platform_device_count={chips}").strip()
        import jax

        import spark_rapids_jni_tpu  # noqa: F401  (x64 and the compile cache before any array)
        from spark_rapids_jni_tpu import plan as P
        from spark_rapids_jni_tpu import serve
        from spark_rapids_jni_tpu.columnar import Column, Table
        from spark_rapids_jni_tpu.columnar import dtype as dt
        from spark_rapids_jni_tpu.parallel.mesh import make_mesh
        from spark_rapids_jni_tpu.utils import metrics, trace_sink, tracing

        if not hasattr(P, "MeshBinding"):
            print("bench: the program in this checkout compiles no plan for a mesh (no plan.MeshBinding)",
                  file=sys.stderr)
            raise SystemExit(2)
        self.jax = jax
        self.device = device.info()
        device.require(self.device, chips, self.rehearse)
        if len(jax.devices()) < chips:
            print(f"bench: the rehearsal needs {chips} devices, JAX has {len(jax.devices())} "
                  f"(XLA_FLAGS=--xla_force_host_platform_device_count={chips} before JAX starts)", file=sys.stderr)
            raise SystemExit(3)
        self.facts["import_and_backend_s"] = time.perf_counter() - t0
        if self.trace:
            self.span_base = os.path.join(self.workdir, "spans")
            tracing.set_enabled(True)
            trace_sink.set_log_path(self.span_base)
        self._trace_sink, self._registry = trace_sink, metrics.registry()
        from jax import monitoring

        def compiled(event, duration_secs, **kw):  # set-up's long compiles, by name, on stderr
            if event == "/jax/core/compile/backend_compile_duration" and duration_secs >= 2.0:
                print(f"[bench] backend compile {duration_secs:.1f}s {kw.get('fun_name')}", file=sys.stderr, flush=True)

        monitoring.register_event_duration_secs_listener(compiled)
        xla0 = {k: self._registry.value(f"xla.{k}") for k in ("backend_compiles", "backend_compile_s", "cache_hits")}

        rows_key = "rehearse_rows" if self.rehearse else "rows"
        self.rows = {t: spec[rows_key] for t, spec in self.config["tables"].items()}
        builder = loader.module("data", self.config["data"])
        t0 = time.perf_counter()
        self.host = builder.host_tables(self.config, self.seed, self.rows[self.config["scaled_table"]])
        types = {k: getattr(dt, k.upper()) for k in _TYPES}

        def column(a, kind):
            a, valid = a if isinstance(a, tuple) else (a, None)  # (values, valid) where it carries nulls
            if kind == "string":
                return Column.from_pylist(list(a), dt.STRING)
            return Column.from_numpy(np.ascontiguousarray(a), types[kind], validity=valid)

        axis, size = next(iter(self.traffic["mesh"].items()))
        if int(size) != chips:
            raise SystemExit(f"bench: the traffic's mesh {self.traffic['mesh']} is not of the cell's {chips} chips")
        self.mesh = P.MeshBinding(make_mesh({axis: chips}, devices=jax.devices()[:chips]),
                                  sharded=self.config["sharded"], axis=axis)
        tables = {}
        for name, cols in self.host.items():
            spec = self.config["tables"][name]["columns"]
            self.rows[name] = len(plan_serve._values(next(iter(cols.values()))))
            tables[name] = Table([column(a, spec[c]) for c, a in cols.items()], list(cols))
        self.tables = self.mesh.place(tables)  # sharded or copied, once, in set-up
        del tables
        jax.block_until_ready([x for t in self.tables.values() for c in getattr(t, "table", t).columns
                               for x in (c.data, c.validity, c.offsets, c.chars) if x is not None])
        self.facts["data_s"] = time.perf_counter() - t0

        self.sched = serve.Scheduler(max_concurrent=1, name="bench")
        self.compiled, plan_s = [], 0.0
        for name, q in self.queries:
            t0 = time.perf_counter()
            plan = P.insert_exchanges(q.plan(P), self.mesh.world, sharded=self.config["sharded"])
            self.compiled.append(P.compile_ir(plan, {t: self.tables[t] for t in q.TABLES}, name=name, mesh=self.mesh))
            plan_s += time.perf_counter() - t0
        self.facts["plan_compile_ir_s"] = plan_s
        self.request_rows = sum(self.rows[t] for _, q in self.queries for t in q.TABLES
                                if t == self.config["scaled_table"])
        self.input_devices = len({d for n in self.config["sharded"] for c in self.tables[n].table.columns
                                  for d in c.data.sharding.device_set})
        for i in range(max(2, int(self.traffic.get("warmup_requests", 2)))):
            t0 = time.perf_counter()
            self.issue(-1)
            self.facts[f"warmup_{i}_s"] = time.perf_counter() - t0
        self.counters0 = self._exchange_counters()
        self.facts.update({f"xla_{k}": float(self._registry.value(f"xla.{k}") - v) for k, v in xla0.items()})

    def _exchange_counters(self) -> dict:
        return {k: self._registry.value(f"exchange.{k}") for k in
                ("programs", "rows_in", "bytes_offered", "overflows", "capacity_retries")}

    def request_bytes(self) -> int:
        # after the window: what the window's exchanges offered, and (for exchange_ici_share) what q95 has to move
        self.facts.update({f"exchange_{k}": float(v - self.counters0[k]) for k, v in self._exchange_counters().items()})
        self.facts["exchange_bytes_off_chip"] = float(sum(
            exchange_bytes.off_chip_per_chip(self.host, q.exchanges(self._frames()), int(self.cell["chips"]))
            for _, q in self.queries))
        return super().request_bytes()

    def _frames(self):
        import pandas as pd

        if getattr(self, "_frames_memo", None) is None:
            self._frames_memo = {n: pd.DataFrame({c: plan_serve._series(pd, a) for c, a in cols.items()})
                                 for n, cols in self.host.items()}
        return self._frames_memo

    def check(self, substitute=None) -> dict:
        readings = super().check(substitute)
        if substitute is None:
            now = self._exchange_counters()
            for name, q in self.queries:
                want = q.reference(self._frames(), np.float64)
                readings[f"{name}.empty_answer"] = float(int(want[q.EXACT[0]].iloc[0]) == 0)
            readings["exchange.unretried_overflows"] = float(now["overflows"] - now["capacity_retries"])
            print(f"fact exchange.overflows {now['overflows']} exchange.capacity_retries {now['capacity_retries']}",
                  file=sys.stderr)  # printed with the result: a fact of the run, no limit can judge it
            readings["mesh.devices_short"] = float(int(self.cell["chips"]) - self.input_devices)
        return readings
