#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the system still starts on the chip.

Drives the two paths a user calls, once each, at sizes a user would call
real, and checks every answer against a plain reference written here
(pandas / numpy on host copies of the same data):

  Phase A  the plan path in one process: TPC-H q1/q6 over a 6,001,215-row
           ``lineitem`` and TPC-DS q3/q55 over a 2,880,404-row
           ``store_sales`` star, as plan IR through ``plan.compile_ir``
           and ``serve.Scheduler.submit(...).result()``, each cold and
           warm; then one op-tier ``join_gather_maps`` (the JNI caller's
           surface) that must be served by the paged-hash Pallas kernel.
  Phase B  the sidecar path the JNI client uses: one worker, PING must
           answer ``tpu``, the reference's own benchmark table (1 Mi rows
           x 212 fixed-width columns) through CONVERT_TO_ROWS and back
           through CONVERT_FROM_ROWS over the slab arena, speaking the
           wire layout of ``native/src/sidecar.cc``, then one
           GROUPBY_SUM_F32 (1 Mi rows, 4096 keys).
  Phase C  only with ``--chips 4``: the shuffle across a four-device
           mesh: ``q95_distributed`` against pandas, and
           ``distributed_groupby_table`` against its single-device twin
           (``groupby_aggregate``) in the same process.

One owner of the chip at a time: this parent never initialises a JAX
backend; it runs each phase as one child process after another, and the
Phase B child itself stays off the chip so that its worker can have it.
The script never sets JAX_PLATFORMS, never carries on after a failed
check, and reports success only if every phase ran on platform "tpu".
Without an accelerator it exits non-zero and prints no result line;
``--tiny`` shrinks the data for the CPU rehearsal and changes nothing else.

stdout: one JSON object per phase, then as the LAST line
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
Progress goes to stderr.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import signal
import struct
import subprocess
import sys
import threading
import time

# The driver allows the one-chip run 1200 s, compilation included. The
# four-chip phase, which only a builder runs, compiles five exchange
# programs of 80-140 s each and the group-by's op-tier twin when the
# compile cache is cold.
TIME_LIMIT_S = {1: 1140, 4: 2400}

# rows of each input, keyed by --tiny. Real: TPC-H SF1 lineitem, TPC-DS
# SF1 store_sales (fact and join probe) and item (join build), the
# reference's 1 Mi-row transcode and group-by axes.
SIZES = {
    False: dict(lineitem=6_001_215, store=2_880_404, build=18_000,
                probe=2_880_404, rows=1 << 20, gb_rows=1 << 20,
                web=1 << 20),
    True: dict(lineitem=20_000, store=20_000, build=2_000, probe=20_000,
               rows=4096, gb_rows=1 << 14, web=8192),
}
TRANSCODE_COLS = 212  # reference benchmarks/row_conversion.cpp:27-67
GROUPBY_KEYS = 4096


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def note(msg: str) -> None:
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def require_accelerator(platform: str, tiny: bool) -> None:
    """Real sizes are for the chip; only the tiny rehearsal may run
    where JAX found no accelerator (and it still cannot succeed)."""
    if platform != "tpu" and not tiny:
        note(f"no accelerator: platform is {platform!r} (--tiny runs the CPU rehearsal)")
        raise SystemExit(3)


def digest(*arrays) -> str:
    import numpy as np

    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a))
    return h.hexdigest()[:16]


def peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# Phase A: the plan path
# ---------------------------------------------------------------------------


def _host(col):
    """Host copy of a column's values (FLOAT64 is stored as IEEE bits)."""
    import numpy as np

    from spark_rapids_jni_tpu.columnar.dtype import TypeId

    a = np.asarray(col.data)
    return a.view(np.float64) if col.dtype.id == TypeId.FLOAT64 else a


def _frame(table, names):
    import pandas as pd

    return pd.DataFrame({n: _host(table.column(n)) for n in names})


def _q1(P, tpch, lineitem):
    """TPC-H q1 with its full aggregate list, and its pandas twin."""
    import numpy as np

    cutoff = tpch.D_1998_12_01 - 90
    one = P.plit(1.0)
    disc_price = P.pcol("l_extendedprice") * (one - P.pcol("l_discount"))
    x = P.Filter(P.Scan("lineitem"), P.pcol("l_shipdate") <= P.plit(np.int32(cutoff)))
    x = P.Project(x, (
        ("l_returnflag", P.pcol("l_returnflag")),
        ("l_linestatus", P.pcol("l_linestatus")),
        ("qty", P.pcol("l_quantity")),
        ("price", P.pcol("l_extendedprice")),
        ("disc", P.pcol("l_discount")),
        ("disc_price", disc_price),
        ("charge", disc_price * (one + P.pcol("l_tax"))),
    ))
    agg = P.Aggregate(x, keys=("l_returnflag", "l_linestatus"), aggs=(
        P.AggSpec("qty", "sum", "sum_qty"),
        P.AggSpec("price", "sum", "sum_base_price"),
        P.AggSpec("disc_price", "sum", "sum_disc_price"),
        P.AggSpec("charge", "sum", "sum_charge"),
        P.AggSpec("qty", "mean", "avg_qty"),
        P.AggSpec("price", "mean", "avg_price"),
        P.AggSpec("disc", "mean", "avg_disc"),
        P.AggSpec(None, "count_all", "count_order"),
    ))
    ir = P.Sort(agg, (("l_returnflag", True), ("l_linestatus", True)))

    def reference():
        df = _frame(lineitem, lineitem.names)
        df = df[df.l_shipdate <= cutoff].copy()
        df["disc_price"] = df.l_extendedprice * (1 - df.l_discount)
        df["charge"] = df.disc_price * (1 + df.l_tax)
        return df.groupby(["l_returnflag", "l_linestatus"]).agg(
            sum_qty=("l_quantity", "sum"),
            sum_base_price=("l_extendedprice", "sum"),
            sum_disc_price=("disc_price", "sum"),
            sum_charge=("charge", "sum"),
            avg_qty=("l_quantity", "mean"),
            avg_price=("l_extendedprice", "mean"),
            avg_disc=("l_discount", "mean"),
            count_order=("l_quantity", "size"),
        ).reset_index().sort_values(["l_returnflag", "l_linestatus"])

    exact = ("l_returnflag", "l_linestatus", "count_order")
    return ir, reference, exact


def _q6(P, lineitem):
    import numpy as np

    pred = (
        (P.pcol("l_shipdate") >= P.plit(np.int32(731)))  # 1994-01-01
        & (P.pcol("l_shipdate") < P.plit(np.int32(1096)))  # 1995-01-01
        & (P.pcol("l_discount") >= P.plit(0.05))
        & (P.pcol("l_discount") <= P.plit(0.07))
        & (P.pcol("l_quantity") < P.plit(24.0))
    )
    x = P.Project(P.Filter(P.Scan("lineitem"), pred),
                  (("rev", P.pcol("l_extendedprice") * P.pcol("l_discount")),))
    ir = P.Aggregate(x, keys=(), aggs=(P.AggSpec("rev", "sum", "revenue"),))

    def reference():
        import pandas as pd

        df = _frame(lineitem, ["l_shipdate", "l_discount", "l_quantity", "l_extendedprice"])
        m = ((df.l_shipdate >= 731) & (df.l_shipdate < 1096) & (df.l_discount >= 0.05)
             & (df.l_discount <= 0.07) & (df.l_quantity < 24))
        return pd.DataFrame({"revenue": [float((df.l_extendedprice[m] * df.l_discount[m]).sum())]})

    return ir, reference, ()


def _star(store):
    ss = _frame(store["store_sales"], ["ss_sold_date_sk", "ss_item_sk", "ss_ext_sales_price"])
    dd = _frame(store["date_dim"], ["d_date_sk", "d_year", "d_moy"])
    it = _frame(store["item"], ["i_item_sk", "i_manufact_id", "i_brand_id", "i_manager_id"])
    return ss, dd, it


def _q3(plans, store):
    def reference():
        ss, dd, it = _star(store)
        j = ss.merge(dd[dd.d_moy == 11], left_on="ss_sold_date_sk", right_on="d_date_sk")
        j = j.merge(it[it.i_manufact_id == 128], left_on="ss_item_sk", right_on="i_item_sk")
        g = j.groupby(["d_year", "i_brand_id"])["ss_ext_sales_price"].sum().reset_index()
        g = g.rename(columns={"ss_ext_sales_price": "ss_ext_sales_price_sum"})
        return g.sort_values(["d_year", "ss_ext_sales_price_sum", "i_brand_id"],
                             ascending=[True, False, True])

    return plans.q3_plan(manufact_id=128, month=11), reference, ("d_year", "i_brand_id")


def _q55(plans, store):
    def reference():
        ss, dd, it = _star(store)
        j = ss.merge(dd[(dd.d_moy == 11) & (dd.d_year == 1999)],
                     left_on="ss_sold_date_sk", right_on="d_date_sk")
        j = j.merge(it[it.i_manager_id == 28], left_on="ss_item_sk", right_on="i_item_sk")
        g = j.groupby("i_brand_id")["ss_ext_sales_price"].sum().reset_index()
        g = g.rename(columns={"ss_ext_sales_price": "ext_price"})
        return g.sort_values(["ext_price", "i_brand_id"], ascending=[False, True])

    return plans.q55_plan(manager_id=28, month=11, year=1999), reference, ("i_brand_id",)


def _run_query(name, ir, tables, reference, exact, sched, P):
    """compile_ir -> submit -> result(), cold then warm; compare with
    the reference (exact on keys and counts, rtol 1e-9 on f64 sums)."""
    import numpy as np

    import jax

    t0 = time.perf_counter()
    cp = P.compile_ir(ir, tables, name=name)
    plan_s = time.perf_counter() - t0
    runs = []
    for _ in range(2):
        t0 = time.perf_counter()
        out = sched.submit(cp).result()
        jax.block_until_ready([c.data for c in out.columns])
        runs.append(time.perf_counter() - t0)
    want = reference()
    require(list(out.names) == list(want.columns),
            f"{name}: columns {out.names} != {list(want.columns)}")
    require(out.num_rows == len(want), f"{name}: {out.num_rows} rows, reference has {len(want)}")
    require(out.num_rows > 0, f"{name}: empty answer proves nothing")
    got = {n: _host(out.column(n)) for n in out.names}
    for n in out.names:
        require(out.column(n).validity is None or bool(np.asarray(out.column(n).validity).all()),
                f"{name}.{n}: unexpected nulls")
        if n in exact:
            require(np.array_equal(got[n], want[n].values), f"{name}.{n}: keys/counts differ")
        else:
            require(np.all(np.isfinite(got[n])), f"{name}.{n}: not finite")
            np.testing.assert_allclose(got[n], want[n].values, rtol=1e-9, err_msg=f"{name}.{n}")
    note(f"{name}: plan {plan_s:.2f}s cold {runs[0]:.2f}s warm {runs[1]:.3f}s rows {out.num_rows}")
    return {
        "plan_s": plan_s, "cold_s": runs[0], "warm_s": runs[1],
        "stages": [s.kind for s in cp.stages], "rows_out": out.num_rows,
        "digest": digest(*got.values()),
    }


def _paged_join(build_n, probe_n, seed):
    """The op-tier join the JNI caller reaches: inner, one INT64 key,
    dimension-sized build side, fact-sized probe side."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.columnar import dtype as dt
    from spark_rapids_jni_tpu.ops.join import join_gather_maps

    rng = np.random.default_rng(seed)
    build = rng.permutation(build_n).astype(np.int64) + 1  # i_item_sk is 1-based
    probe = build[rng.integers(0, build_n, probe_n)]
    left = Table([Column(dt.INT64, data=jnp.asarray(probe))], ["k"])
    right = Table([Column(dt.INT64, data=jnp.asarray(build))], ["k"])
    runs = []
    for _ in range(2):
        t0 = time.perf_counter()
        lmap, rmap = jax.block_until_ready(join_gather_maps(left, right, "inner"))
        runs.append(time.perf_counter() - t0)
    lmap, rmap = np.asarray(lmap), np.asarray(rmap)
    require(len(lmap) == probe_n and len(rmap) == probe_n,
            f"join: {len(lmap)} pairs, every probe row has exactly one match ({probe_n})")
    require(np.array_equal(np.sort(lmap), np.arange(probe_n)), "join: a probe row is missing or doubled")
    require(np.array_equal(build[rmap], probe[lmap]), "join: build[r] != probe[l]")
    note(f"join_gather_maps: cold {runs[0]:.2f}s warm {runs[1]:.3f}s")
    return {"build_rows": build_n, "probe_rows": probe_n, "cold_s": runs[0],
            "warm_s": runs[1], "digest": digest(lmap, rmap)}


def phase_a(args) -> dict:
    import jax

    import spark_rapids_jni_tpu  # noqa: F401  (x64 + compile cache before any array)
    from spark_rapids_jni_tpu import plan as P
    from spark_rapids_jni_tpu import serve
    from spark_rapids_jni_tpu.models import tpcds, tpcds_plans, tpch
    from spark_rapids_jni_tpu.utils import metrics

    device = device_info()
    require_accelerator(device["platform"], args.tiny)
    note(f"phase A on {device}")
    sz = SIZES[args.tiny]
    t0 = time.perf_counter()
    lineitem = tpch.gen_lineitem(sz["lineitem"], seed=args.seed)
    store = tpcds.gen_store(sz["store"], seed=args.seed)
    jax.block_until_ready([c.data for c in lineitem.columns])
    gen_s = time.perf_counter() - t0
    tables = {"lineitem": {"lineitem": lineitem}, "store": store}
    shapes = {name: [t.num_rows, t.num_columns]
              for name, t in {"lineitem": lineitem, **store}.items()}
    note(f"tables {shapes} generated in {gen_s:.1f}s")

    queries = {}
    with serve.Scheduler(max_concurrent=1, name="chip-smoke") as sched:
        for name, (ir, reference, exact), bound in (
            ("q1", _q1(P, tpch, lineitem), "lineitem"),
            ("q6", _q6(P, lineitem), "lineitem"),
            ("q3", _q3(tpcds_plans, store), "store"),
            ("q55", _q55(tpcds_plans, store), "store"),
        ):
            queries[name] = _run_query(name, ir, tables[bound], reference, exact, sched, P)
    join = _paged_join(sz["build"], sz["probe"], args.seed)

    reg = metrics.registry()
    tiers = {t: int(reg.value(f"dispatch.tier.{t}")) for t in ("pallas", "xla", "degrade")}
    require(tiers["degrade"] == 0, f"a kernel gave way to XLA: {tiers}")
    require(tiers["pallas"] >= 1, f"the paged-hash join kernel served nothing: {tiers}")
    return {"phase": "A", "device": device, "gen_s": gen_s, "tables": shapes,
            "queries": queries, "join": join, "tiers": tiers,
            "compile_cache": jax.config.jax_compilation_cache_dir,
            "peak_bytes_in_use": peak_bytes()}


# ---------------------------------------------------------------------------
# Phase B: the sidecar path, in the wire layout of native/src/sidecar.cc
# ---------------------------------------------------------------------------

# the nine integral types the reference cycles (row_conversion.cpp:31-40):
# (TypeId name, numpy dtype)
_NINE_INT_TYPES = (
    ("INT8", "i1"), ("INT16", "i2"), ("INT32", "i4"), ("INT64", "i8"),
    ("UINT8", "u1"), ("UINT16", "u2"), ("UINT32", "u4"), ("UINT64", "u8"),
    ("BOOL8", "u1"),
)


def _make_columns(rows, seed):
    """[(type_id, numpy data, validity or None)]: 212 columns cycled over
    the nine types, full-range values, every fourth column ~10% null."""
    import numpy as np

    from spark_rapids_jni_tpu.columnar.dtype import TypeId

    rng = np.random.default_rng(seed)
    cols = []
    for i in range(TRANSCODE_COLS):
        tname, code = _NINE_INT_TYPES[i % len(_NINE_INT_TYPES)]
        d = np.dtype(code)
        if tname == "BOOL8":
            data = rng.integers(0, 2, rows, dtype=np.uint8)
        else:
            info = np.iinfo(d)
            data = rng.integers(info.min, info.max, rows, dtype=d, endpoint=True)
        validity = (rng.random(rows) >= 0.1) if i % 4 == 0 else None
        cols.append((int(TypeId[tname].value), data, validity))
    return cols


def _encode_table(cols) -> bytes:
    """The walker layout of sidecar._read_table."""
    out = [struct.pack("<I", len(cols))]
    for type_id, data, validity in cols:
        out.append(struct.pack("<iiQ", type_id, 0, len(data)))
        if validity is None:
            out.append(b"\x00")
        else:
            out.append(b"\x01")
            out.append(validity.astype("u1").tobytes())
        out.append(struct.pack("<Q", data.nbytes))
        out.append(data.tobytes())
    return b"".join(out)


def _decode_table(buf, expect):
    """Inverse of _encode_table for fixed-width columns; ``expect`` gives
    the numpy dtype of each column."""
    import numpy as np

    (ncols,) = struct.unpack_from("<I", buf, 0)
    require(ncols == len(expect), f"from_rows returned {ncols} columns, sent {len(expect)}")
    pos, cols = 4, []
    for _, want, _ in expect:
        type_id, _scale, n = struct.unpack_from("<iiQ", buf, pos)
        pos += 16
        validity = None
        if buf[pos]:
            validity = np.frombuffer(buf, np.uint8, n, pos + 1).astype(bool)
            pos += n
        pos += 1
        (dlen,) = struct.unpack_from("<Q", buf, pos)
        pos += 8
        cols.append((type_id, np.frombuffer(buf, want.dtype, dlen // want.dtype.itemsize, pos), validity))
        pos += dlen
    require(pos == len(buf), f"from_rows: {len(buf) - pos} trailing bytes")
    return cols


def _jcudf_rows(cols, rows):
    """Plain JCUDF reference (RowConversion.java:44-117): every column
    aligned to its own size, validity bytes after the last column (bit
    ``c % 8`` of byte ``c // 8`` set when valid), rows padded to 8."""
    import numpy as np

    off, starts = 0, []
    for _, data, _ in cols:
        size = data.dtype.itemsize
        off = -(-off // size) * size
        starts.append(off)
        off += size
    validity_off = off
    row_size = -(-(off + (len(cols) + 7) // 8) // 8) * 8
    out = np.zeros((rows, row_size), np.uint8)
    for c, ((_, data, validity), start) in enumerate(zip(cols, starts)):
        size = data.dtype.itemsize
        out[:, start:start + size] = data.view(np.uint8).reshape(rows, size)
        valid = np.ones(rows, np.uint8) if validity is None else validity.astype(np.uint8)
        out[:, validity_off + c // 8] |= valid << np.uint8(c % 8)
    return out, row_size


def phase_b(args) -> dict:
    import numpy as np

    import jax

    from spark_rapids_jni_tpu import sidecar
    from spark_rapids_jni_tpu.sidecar_pool import SidecarPool

    sz = SIZES[args.tiny]
    rows = sz["rows"]
    t0 = time.perf_counter()
    cols = _make_columns(rows, args.seed)
    payload = _encode_table(cols)
    want_rows, row_size = _jcudf_rows(cols, rows)
    gen_s = time.perf_counter() - t0
    note(f"phase B table {rows} x {TRANSCODE_COLS}, {len(payload)} bytes, row size {row_size}")

    timings = {}

    def timed(name, fn):
        t0 = time.perf_counter()
        out = fn()
        timings[name] = time.perf_counter() - t0
        note(f"{name}: {timings[name]:.2f}s")
        return out

    # one worker: it owns the chip for as long as it lives
    t0 = time.perf_counter()
    with SidecarPool(size=1, startup_timeout_s=300.0) as pool:
        timings["worker_start_s"] = time.perf_counter() - t0
        backend = pool.call(sidecar.OP_PING).decode()
        note(f"worker PING -> {backend}")
        require_accelerator(backend, args.tiny)
        # the first lease sizes the slab: request and response both fit
        pool.ensure_slab(min_bytes=max(len(payload), rows * row_size + 4 * rows + 64))

        # columns -> JCUDF rows
        for leg in ("to_rows_cold_s", "to_rows_warm_s"):
            resp = timed(leg, lambda: pool.call_arena(sidecar.OP_CONVERT_TO_ROWS, payload))
        (nbatches,) = struct.unpack_from("<I", resp, 0)
        require(nbatches == 1, f"to_rows: {nbatches} batches for a table under 2 GiB")
        (nrows,) = struct.unpack_from("<Q", resp, 4)
        require(nrows == rows, f"to_rows: {nrows} rows, sent {rows}")
        offsets = np.frombuffer(resp, np.int32, rows + 1, 12)
        (blob_len,) = struct.unpack_from("<Q", resp, 12 + 4 * (rows + 1))
        blob = np.frombuffer(resp, np.uint8, blob_len, 20 + 4 * (rows + 1))
        require(np.array_equal(offsets, np.arange(rows + 1, dtype=np.int64) * row_size),
                "to_rows: offsets are not multiples of the JCUDF row size")
        require(blob_len == rows * row_size and np.array_equal(blob.reshape(rows, row_size), want_rows),
                "to_rows: row bytes differ from the JCUDF reference")

        # JCUDF rows -> columns, from the bytes the worker itself produced
        head = struct.pack("<I", len(cols))
        head += np.array([c[0] for c in cols], np.int32).tobytes()
        head += np.zeros(len(cols), np.int32).tobytes()
        request = b"".join([head, struct.pack("<Q", rows), offsets.tobytes(),
                            struct.pack("<Q", blob_len), blob.tobytes()])
        for leg in ("from_rows_cold_s", "from_rows_warm_s"):
            back = timed(leg, lambda: pool.call_arena(sidecar.OP_CONVERT_FROM_ROWS, request))
        for c, ((tid, data, validity), (gtid, gdata, gvalid)) in enumerate(zip(cols, _decode_table(back, cols))):
            require(gtid == tid, f"from_rows col {c}: type {gtid} != {tid}")
            valid = np.ones(rows, bool) if validity is None else validity
            require(np.array_equal(np.ones(rows, bool) if gvalid is None else gvalid, valid),
                    f"from_rows col {c}: validity differs")
            require(np.array_equal(gdata[valid], data[valid]), f"from_rows col {c}: values differ")

        # the stepping-stone aggregate (BASELINE.json configs[0])
        rng = np.random.default_rng(args.seed)
        n = sz["gb_rows"]
        keys = rng.integers(0, GROUPBY_KEYS, n).astype(np.int64)
        vals = rng.standard_normal(n).astype(np.float32)
        gb_req = struct.pack("<IQ", GROUPBY_KEYS, n) + keys.tobytes() + vals.tobytes()
        for leg in ("groupby_cold_s", "groupby_warm_s"):
            gb = timed(leg, lambda: pool.call(sidecar.OP_GROUPBY_SUM_F32, gb_req))
        sums = np.frombuffer(gb, np.float32, GROUPBY_KEYS, 0)
        counts = np.frombuffer(gb, np.int64, GROUPBY_KEYS, 4 * GROUPBY_KEYS)
        require(np.array_equal(counts, np.bincount(keys, minlength=GROUPBY_KEYS)), "groupby: counts differ")
        np.testing.assert_allclose(sums, np.bincount(keys, weights=vals, minlength=GROUPBY_KEYS),
                                   rtol=1e-5, atol=1e-3, err_msg="groupby sums")

        snap = pool.snapshot()
        stats = pool.worker_stats(fold=False)["w0"]
        breaker = sidecar.breaker().state()
        require(snap["host_fallbacks"] == 0, f"{snap['host_fallbacks']} op(s) were answered by the host engine")
        require(snap["worker_deaths"] == 0 and snap["failovers"] == 0, f"the worker did not survive: {snap}")
        require(breaker == "closed", f"breaker is {breaker}")
        require(stats["backend"] == backend, f"worker STATS says {stats['backend']}, PING said {backend}")
    # the parent of the worker never took the chip itself
    require(not jax._src.xla_bridge._backends, "the sidecar client initialised a JAX backend")
    counters = (stats.get("snapshot") or {}).get("counters") or {}
    return {"phase": "B", "worker_backend": backend, "gen_s": gen_s,
            "rows": rows, "cols": TRANSCODE_COLS, "row_size": row_size,
            "table_bytes": len(payload), "rows_digest": digest(blob),
            "timings": timings, "arena_bytes": snap["arena_bytes"],
            "host_fallbacks": snap["host_fallbacks"], "breaker": breaker,
            "worker_requests": {k.rsplit(".", 1)[1]: v for k, v in counters.items()
                                if k.startswith("sidecar.worker.requests.")}}


# ---------------------------------------------------------------------------
# Phase C: the shuffle on four chips
# ---------------------------------------------------------------------------


def _q95_reference(web, ship_lo=400, ship_hi=460) -> dict:
    """TPC-DS q95 in pandas: orders shipped in the window, from more than
    one warehouse, and returned."""
    ws = _frame(web["web_sales"], ["ws_order_number", "ws_warehouse_sk", "ws_ship_date_sk",
                                   "ws_ext_ship_cost", "ws_net_profit"])
    returned = set(_host(web["web_returns"].column("wr_order_number")).tolist())
    warehouses = ws.groupby("ws_order_number").ws_warehouse_sk.nunique()
    multi = set(warehouses[warehouses > 1].index.tolist())
    sel = ws[ws.ws_ship_date_sk.between(ship_lo, ship_hi) & ws.ws_order_number.isin(multi)
             & ws.ws_order_number.isin(returned)]
    return {"order_count": int(sel.ws_order_number.nunique()),
            "total_shipping_cost": float(sel.ws_ext_ship_cost.sum()),
            "total_net_profit": float(sel.ws_net_profit.sum())}


def phase_c(args) -> dict:
    import numpy as np

    import jax

    import spark_rapids_jni_tpu  # noqa: F401
    from spark_rapids_jni_tpu.models import tpcds
    from spark_rapids_jni_tpu.ops.aggregate import groupby_aggregate
    from spark_rapids_jni_tpu.parallel.mesh import make_mesh, shard_table_rows
    from spark_rapids_jni_tpu.parallel.table_ops import distributed_groupby_table

    device = device_info()
    require_accelerator(device["platform"], args.tiny)
    require(device["count"] >= 4, f"phase C needs four devices, JAX reports {device['count']}")
    note(f"phase C on {device}")
    mesh = make_mesh({"data": 4}, devices=jax.devices()[:4])
    sz = SIZES[args.tiny]
    timings = {}

    def device_ids(table):
        return sorted({d.id for c in table.columns for d in c.data.sharding.device_set})

    # TPC-DS q95 as a compiled plan over the mesh (plan.compile_ir(..., mesh=)):
    # three exchanges, two shard-local group-bys and two membership joins; against pandas. (Not against tpcds.q95 in this process:
    # cold, that op-tier twin alone ran past 580 s on the chip without
    # finishing; tests/test_table_ops.py holds the two equal on the CPU.)
    web = tpcds.gen_web(sz["web"], seed=args.seed)
    on_mesh = dict(web, web_sales=shard_table_rows(web["web_sales"], mesh))
    placed = device_ids(on_mesh["web_sales"])
    require(len(placed) == 4, f"sharded inputs live on devices {placed}, not on four")
    t0 = time.perf_counter()
    got_q = tpcds.q95_distributed(on_mesh, mesh)  # raises on a capacity overflow
    timings["q95_cold_s"] = time.perf_counter() - t0
    ref_q = _q95_reference(web)
    require(ref_q["order_count"] > 0, "q95: empty answer proves nothing")
    require(got_q["order_count"] == ref_q["order_count"], f"q95 on the mesh {got_q} != pandas {ref_q}")
    np.testing.assert_allclose([got_q["total_shipping_cost"], got_q["total_net_profit"]],
                               [ref_q["total_shipping_cost"], ref_q["total_net_profit"]],
                               rtol=1e-9, err_msg="q95 on the mesh vs pandas")
    note(f"q95_distributed equals pandas: {got_q} in {timings['q95_cold_s']:.1f}s")

    # Table-level GROUP BY across the mesh (the same sharded layer, end to end:
    # place, exchange, shard-local group-by, gather) vs. the single-device operator
    fact = tpcds.gen_store(sz["store"], seed=args.seed)["store_sales"]
    sharded = shard_table_rows(fact, mesh)
    aggs = [("ss_ext_sales_price", "sum", "ss_ext_sales_price_sum"),
            ("ss_sold_date_sk", "max", "ss_sold_date_sk_max")]
    for leg in ("groupby_cold_s", "groupby_warm_s"):
        t0 = time.perf_counter()
        got, overflow = distributed_groupby_table(sharded, ["ss_item_sk"], aggs, mesh)
        jax.block_until_ready([c.data for c in got.columns])
        timings[leg] = time.perf_counter() - t0
    require(not overflow, "distributed groupby: exchange capacity overflow")
    want = groupby_aggregate(fact.select(["ss_item_sk"]),
                             fact.select(["ss_ext_sales_price", "ss_sold_date_sk"]),
                             [("ss_ext_sales_price", "sum"), ("ss_sold_date_sk", "max")])
    order = np.argsort(np.asarray(got.column("ss_item_sk").data), kind="stable")
    require(got.num_rows == want.num_rows, f"groupby: {got.num_rows} groups, single device has {want.num_rows}")
    for name in got.names:  # exact f64 sums: bit-identical to the single device
        require(np.array_equal(np.asarray(got.column(name).data)[order], np.asarray(want.column(name).data)),
                f"distributed groupby: {name} differs from the single-device answer")
    note(f"distributed_groupby_table equals its single-device twin: {timings}")
    return {"phase": "C", "device": device, "mesh": dict(mesh.shape),
            "input_devices": sorted(set(placed + device_ids(sharded))),
            "answer_devices": device_ids(got), "q95_rows": sz["web"], "q95": got_q,
            "groupby_rows": fact.num_rows, "groups": got.num_rows,
            "timings": timings, "peak_bytes_in_use": peak_bytes()}


# ---------------------------------------------------------------------------
# the parent: one child after another, never a backend of its own
# ---------------------------------------------------------------------------

PHASES = {"A": phase_a, "B": phase_b, "C": phase_c}


def _run_child(phase: str, args, deadline: float) -> dict:
    """Run one phase in its own process group; forward its stdout; return
    the phase object it printed last. Raises if the child failed."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase, "--seed", str(args.seed)]
    if args.tiny:
        cmd.append("--tiny")
    child = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True, start_new_session=True)
    timer = threading.Timer(max(deadline - time.monotonic(), 1.0),
                            lambda: os.killpg(child.pid, signal.SIGKILL))
    timer.start()
    try:
        last = None
        for line in child.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
            if line.startswith("{"):
                last = line
        rc = child.wait()
    finally:
        timer.cancel()
        try:  # whatever the phase started goes with it (the sidecar worker too)
            os.killpg(child.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        child.wait()
    require(rc == 0, f"phase {phase} exited with code {rc}")
    require(last is not None, f"phase {phase} printed no result")
    result = json.loads(last)
    require(result.get("phase") == phase, f"phase {phase} printed {last!r}")
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--tiny", action="store_true", help="shrink the data (CPU rehearsal)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip phase")
    ap.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.phase:  # a child: one phase, one object
        emit(PHASES[args.phase](args))
        return 0

    deadline = time.monotonic() + TIME_LIMIT_S[args.chips]
    try:  # the first failed phase ends the run; its own traceback is above
        results = [_run_child(p, args, deadline) for p in (("C",) if args.chips == 4 else ("A", "B"))]
    except SmokeFailure as e:
        note(f"FAILED: {e}")
        return 1
    device = next(r["device"] for r in results if "device" in r)
    platforms = {r["device"]["platform"] if "device" in r else r["worker_backend"] for r in results}
    if platforms != {"tpu"}:
        note(f"every check held, but not on the chip: {sorted(platforms)}")
        return 3
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
