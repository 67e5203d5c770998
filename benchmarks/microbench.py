"""Microbenchmark harness — the nvbench tier (SURVEY §2.6).

Reproduces the reference's benchmark axes on whatever device jax sees:

- ``row_conversion_fixed``: 212 columns cycled over 9 int types ×
  {1M, 4M} rows, both directions (reference
  benchmarks/row_conversion.cpp:27-67, 140-143),
- ``row_conversion_mixed``: 155 columns ± STRING, the reference's column
  count and its >1M-row guard for strings but NOT its table: INT32,
  FLOAT64, INT64, INT16 cycled, a STRING of 1-32 bytes at every tenth
  column, in process. The reference's own type list (its nine fixed-width
  types and STRING) through the sidecar is the benchmark cell
  ``rowconv-155x1m-strings.to-rows`` (bench/data/rowconv_var_width.py),
- ``cast_string``: string->int and string->decimal thread-per-row
  kernels (reference cast kernels, cast_string.cu:654-655),
- ``groupby``: the hash-agg tier on the 1M-row stepping stone.

Protocol (matches the nvbench discipline): deterministic seeded input
(models/datagen), compile/warmup excluded, median of N timed reps,
reports rows/s and achieved GB/s (bytes read, the reference's
global-memory counter, row_conversion.cpp:65-66).

Usage::

    python benchmarks/microbench.py                  # all, small sizes
    python benchmarks/microbench.py --bench row_conversion_fixed \
        --rows 4194304 --reps 10
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Callable, List

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.utils import knobs
from spark_rapids_jni_tpu.columnar import dtype as dt
from spark_rapids_jni_tpu.models.datagen import Profile, create_random_table, cycle_dtypes

# the reference cycles 9 integral types (row_conversion.cpp:31-40)
_NINE_INT_TYPES = [
    dt.INT8, dt.INT16, dt.INT32, dt.INT64,
    dt.UINT8, dt.UINT16, dt.UINT32, dt.UINT64,
    dt.BOOL8,
]


def _sync(out) -> None:
    # block on ONE leaf: device execution is ordered, and syncing every
    # output array costs a host↔device round trip each, which would
    # swamp the kernel time for many-column results
    leaves = jax.tree_util.tree_leaves(out)
    if leaves:
        jax.block_until_ready(leaves[-1])


def _time(fn: Callable[[], object], reps: int) -> float:
    _sync(fn())  # warmup + compile
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        _sync(fn())
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def _table_bytes(t: Table) -> int:
    total = 0
    for c in t.columns:
        for buf in (c.data, c.validity, c.offsets, c.chars):
            if buf is not None:
                total += buf.size * buf.dtype.itemsize
    return total


_HBM_ROOFLINE_GBS = 819.0  # v5e HBM bandwidth; nothing real exceeds it


def _report(
    name: str, rows: int, cols: int, secs: float, nbytes: int,
    protocol: str = "rawsync", **extra,
) -> None:
    """protocol: 'chained' = latency-cancelled two-length chain (trusted);
    'rawsync' = block_until_ready wall time — optimistic under remote
    backends that acknowledge before completion. Any rawsync number above
    the HBM roofline is tagged suspect_rawsync (SURVEY §6 discipline).
    ``extra`` fields land verbatim on the row (the kernel-tier axes
    attach tier/bit_identical/vs_baseline evidence)."""
    rec = {
        "bench": name,
        "rows": rows,
        "cols": cols,
        "secs": round(secs, 6),
        "mrows_per_s": round(rows / secs / 1e6, 2),
        "gb_per_s": round(nbytes / secs / 1e9, 3),
        "protocol": protocol,
        "fingerprint": _platform_fingerprint(),
        **extra,
    }
    if protocol != "chained" and rec["gb_per_s"] > _HBM_ROOFLINE_GBS:
        rec["suspect_rawsync"] = True
    print(json.dumps(rec), flush=True)
    out_path = knobs.get_str("SRJT_RESULTS")
    if out_path:
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")


_FP = None


def _platform_fingerprint() -> dict:
    """Attached to EVERY artifact row (VERDICT r4 weak #7): identical
    code measured 118.4 -> 72.9 GB/s across rounds with no fingerprint
    to attribute the drift to; this pins {versions, backend, host,
    date} so cross-round comparisons are anchored."""
    global _FP
    if _FP is None:
        import datetime
        import socket

        import jaxlib

        try:
            from importlib.metadata import version

            libtpu = version("libtpu")
        except Exception:
            libtpu = None
        _FP = {
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "libtpu": libtpu,
            "backend": jax.default_backend(),
            "device": str(jax.devices()[0]),
            "host": socket.gethostname(),
            "date": datetime.date.today().isoformat(),
        }
    return _FP


def _chained_secs(run, reps: int, k_short: int = 1, k_long: int = 9) -> float:
    """Two-length chained-timing scaffold (bench.py discipline): run(k)
    must execute a k-iteration data-dependent device chain and block on
    a real host pull; the length difference cancels fixed latency."""
    run(k_short), run(k_long)  # compile both lengths
    ts, tl = [], []
    for _ in range(reps):
        t0 = time.perf_counter(); run(k_short); ts.append(time.perf_counter() - t0)
        t0 = time.perf_counter(); run(k_long); tl.append(time.perf_counter() - t0)
    return max((float(np.median(tl)) - float(np.median(ts))) / (k_long - k_short), 1e-9)


def _chained_transcode_secs(table, reps: int) -> float:
    """Latency-cancelling protocol for the encode axis (bench.py
    discipline): a data-dependent on-device chain at two lengths; the
    difference isolates per-iteration device time even when a remote
    backend acknowledges block_until_ready before completion. Only
    valid for single-batch (<2GiB) tables."""
    import jax.numpy as jnp
    from jax import lax

    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.ops import row_conversion as rc
    from functools import partial

    layout = rc.compute_row_layout(table.dtypes())
    n = table.num_rows
    cols = tuple(table.columns)

    @partial(jax.jit, static_argnums=(2,))
    def chain(c0_data, rest, iters: int):
        # `rest` rides as a pytree ARG (closing over 211 device arrays
        # would bake ~1GB of constants into the HLO)
        def body(_, carry):
            cols2 = (Column(cols[0].dtype, data=carry, validity=cols[0].validity),) + tuple(rest)
            blob = rc._to_rows_fixed(layout, cols2, n)
            perturb = (blob[0] == 0).astype(carry.dtype)  # data dependency
            return carry ^ perturb

        return lax.fori_loop(0, iters, body, c0_data)

    def run(k):
        out = chain(cols[0].data, cols[1:], k)
        return float(jnp.sum(out.astype(jnp.int32)))  # host pull: real completion

    return _chained_secs(run, reps)


def _chained_decode_secs(row_col, dtypes, reps: int) -> float:
    """Chained-protocol decode (grouped form): each iteration's blob
    depends on the previous decode's first output byte."""
    import jax.numpy as jnp
    from jax import lax
    from functools import partial

    from spark_rapids_jni_tpu.columnar import Column
    from spark_rapids_jni_tpu.columnar import dtype as dtm
    from spark_rapids_jni_tpu.ops import row_conversion as rc

    dtypes = tuple(dtypes)
    offsets = row_col.offsets
    stride = getattr(row_col, "_uniform_stride", None)

    @partial(jax.jit, static_argnums=(1,))
    def chain(blob0, iters: int):
        def body(_, blob):
            lc = Column(dtm.LIST, offsets=offsets, child=Column(dtm.INT8, data=blob))
            if stride is not None:
                lc._uniform_stride = stride  # skip the traced host probe
            g = rc.convert_from_rows_grouped(lc, dtypes)
            gv = g.groups[0] if isinstance(g.groups, (list, tuple)) else next(iter(g.groups.values()))
            first = gv.reshape(-1)[0]  # data dependency
            return blob.at[0].set(blob[0] ^ first.astype(blob.dtype))

        return lax.fori_loop(0, iters, body, blob0)

    def run(k):
        out = chain(row_col.child.data, k)
        return float(out.reshape(-1)[0])  # host pull: real completion

    return _chained_secs(run, reps)


def bench_row_conversion_fixed(rows: int, reps: int, cols: int = 212) -> None:
    from spark_rapids_jni_tpu.ops import row_conversion as rc

    table = create_random_table(cycle_dtypes(_NINE_INT_TYPES, cols), rows, seed=42)
    nbytes = _table_bytes(table)

    secs = _time(lambda: rc.convert_to_rows(table), reps)
    _report("row_conversion_fixed_to_rows", rows, cols, secs, nbytes)

    row_cols = rc.convert_to_rows(table)  # >2GiB tables span several batches
    dtypes = table.dtypes()
    secs = _time(lambda: [rc.convert_from_rows(b, dtypes) for b in row_cols], reps)
    _report("row_conversion_fixed_from_rows", rows, cols, secs, nbytes)

    # grouped decode: the fused-pipeline form — one program, O(width
    # groups) output buffers instead of O(columns). The per-column
    # variant above additionally pays one buffer registration per
    # column+validity, which is runtime overhead, not decode work;
    # this axis isolates the decode.
    secs = _time(
        lambda: [rc.convert_from_rows_grouped(b, dtypes).groups for b in row_cols], reps
    )
    _report("row_conversion_fixed_from_rows_grouped", rows, cols, secs, nbytes)

    # chained (trusted) variants LAST: their loop state churns the
    # allocator enough to distort any axis measured after them
    if len(row_cols) == 1:  # single batch (the chains assume one program)
        secs = _chained_decode_secs(row_cols[0], dtypes, max(reps // 2, 2))
        _report("row_conversion_fixed_from_rows_chained", rows, cols, secs, nbytes, "chained")
        secs = _chained_transcode_secs(table, max(reps // 2, 2))
        _report("row_conversion_fixed_to_rows_chained", rows, cols, secs, nbytes, "chained")


def bench_row_conversion_mixed(rows: int, reps: int, cols: int = 155, strings: bool = True) -> None:
    """155 columns over a type list of this harness's own (four fixed-width
    types, a STRING at every tenth column, lengths 1-32): not the
    reference's table. Every rate the comments of ops/ragged_bytes.py and
    ops/row_conversion.py quoted before PR 34 was read here."""
    from spark_rapids_jni_tpu.ops import row_conversion as rc

    base = [dt.INT32, dt.FLOAT64, dt.INT64, dt.INT16]
    dtypes = cycle_dtypes(base, cols)
    profiles = {}
    if strings:
        if rows > (1 << 20):
            print(json.dumps({"bench": "row_conversion_mixed_strings", "skipped": "rows>1M"}))
            return
        for i in range(0, cols, 10):  # sprinkle string columns
            dtypes[i] = dt.STRING
            profiles[i] = Profile(min_length=1, max_length=32)
    table = create_random_table(dtypes, rows, seed=42, profiles=profiles)
    nbytes = _table_bytes(table)
    secs = _time(lambda: rc.convert_to_rows(table), reps)
    name = "row_conversion_mixed" + ("_strings" if strings else "")
    _report(name + "_to_rows", rows, cols, secs, nbytes)

    # decode direction (the reference benches both axes). Known-slow: the ragged char
    # extraction is element-granular u8 gathering — recorded honestly;
    # the Pallas DMA compaction is the planned fix.
    row_cols = rc.convert_to_rows(table)
    if len(row_cols) == 1:
        secs = _time(
            lambda: rc.convert_from_rows(row_cols[0], table.dtypes()), max(reps // 2, 1)
        )
        _report(name + "_from_rows", rows, cols, secs, nbytes)


def bench_cast_string(rows: int, reps: int) -> None:
    import jax.numpy as jnp
    from jax import lax
    from functools import partial

    from spark_rapids_jni_tpu.ops.cast_string import (
        _INT_LIMITS, _padded_chars, _parse_integer, string_to_integer,
    )
    from spark_rapids_jni_tpu.columnar.dtype import TypeId

    rng = np.random.default_rng(42)
    vals = [str(int(v)) for v in rng.integers(-(10**8), 10**8, rows)]
    col = Column.from_pylist(vals, dt.STRING)
    nbytes = int(col.chars.size)
    secs = _time(lambda: string_to_integer(col, False, dt.INT64), reps)
    _report("cast_string_to_int64", rows, 1, secs, nbytes)

    # chained (trusted): each iteration's first char depends on the
    # previous parse's accumulator, so the kernel invocations serialize
    chars, lens, max_len = _padded_chars(col)
    in_valid = col.valid_mask()
    max_mag, neg_mag = _INT_LIMITS[TypeId.INT64]

    @partial(jax.jit, static_argnums=(1,))
    def chain(chars0, iters: int):
        def body(_, c):
            acc, _neg, _valid = _parse_integer(
                c, lens, in_valid, True, max_mag, neg_mag, False, max_len
            )
            perturb = (acc[0] & jnp.uint64(1)).astype(jnp.uint8)
            return c.at[0, 0].set(c[0, 0] ^ perturb)

        return lax.fori_loop(0, iters, body, chars0)

    def run(k):
        return float(chain(chars, k)[0, 0])

    secs = _chained_secs(run, max(reps // 2, 2), k_long=33)
    _report("cast_string_to_int64_chained", rows, 1, secs, nbytes, "chained")


def bench_groupby(rows: int, reps: int) -> None:
    from spark_rapids_jni_tpu.ops.aggregate import groupby_sum_bounded
    from spark_rapids_jni_tpu.parallel.distributed import shard_groupby_sum

    import jax.numpy as jnp
    from jax import lax
    from functools import partial

    rng = np.random.default_rng(42)
    keys = jnp.asarray(rng.integers(0, 4096, rows), jnp.int64)
    vals = jnp.asarray(rng.standard_normal(rows), jnp.float32)
    present = jnp.ones((rows,), bool)
    fn = jax.jit(shard_groupby_sum, static_argnums=(3,))
    secs = _time(lambda: fn(keys, vals, present, 8192), reps)
    _report("groupby_sum", rows, 2, secs, rows * 12)

    # chained (trusted): bench.py's headline protocol on the same input
    @partial(jax.jit, static_argnums=(2, 3))
    def chain(keys0, vals0, num_keys: int, iters: int):
        def body(_, carry):
            k, acc = carry
            sums, _counts = groupby_sum_bounded(k, vals0, num_keys)
            perturb = (sums[0] == 0.0).astype(k.dtype)
            return k ^ perturb, acc + sums[0]

        _, acc = lax.fori_loop(0, iters, body, (keys0, jnp.float32(0)))
        return acc

    def run(k):
        return float(chain(keys, vals, 4096, k))

    secs = _chained_secs(run, max(reps // 2, 2), k_long=257)
    _report("groupby_sum_chained", rows, 2, secs, rows * 12, "chained")


def _chained_pipeline_secs(pipe, table, perturb_col: str, reps: int, k_long: int) -> float:
    """Chained-protocol timing for a CompiledPipeline: each iteration
    perturbs one input column by a value derived from the previous
    iteration's aggregates, so XLA must run the programs serially."""
    import jax.numpy as jnp
    from jax import lax
    from functools import partial

    from spark_rapids_jni_tpu.columnar import Column, Table

    names = list(table.names)
    cols = tuple(table.columns)
    ci = names.index(perturb_col)
    base = cols[ci]

    @partial(jax.jit, static_argnums=(2,))
    def chain(data0, rest, iters: int):
        def body(_, data):
            cols2 = list(rest)
            cols2.insert(ci, Column(base.dtype, data=data, validity=base.validity))
            out = pipe._fn(Table(cols2, names), {})
            leaf = jax.tree_util.tree_leaves(out)[0].reshape(-1)[0]
            bump = (leaf == 0).astype(data.dtype)  # 0 in practice; dependency only
            return data + bump

        return lax.fori_loop(0, iters, body, data0)

    rest = cols[:ci] + cols[ci + 1:]

    def run(k):
        return float(chain(base.data, rest, k).reshape(-1)[0])

    return _chained_secs(run, reps, k_long=k_long)


def bench_tpch(rows: int, reps: int) -> None:
    """Fused q1/q6 through the generic compiled-pipeline builder
    (BASELINE configs[1]). Times the jitted device program only (the
    host-side group compaction is excluded, like the reference's
    nvbench timing excludes result download)."""
    from spark_rapids_jni_tpu.models import compiled, tpch

    li = tpch.gen_lineitem(rows, seed=42)
    nbytes = _table_bytes(li)
    q6_cols = ["l_shipdate", "l_discount", "l_quantity", "l_extendedprice"]
    q6_bytes = _table_bytes(li.select(q6_cols))
    q6 = compiled.q6_pipeline()
    secs = _time(lambda: q6._fn(li, {}), reps)
    _report("tpch_q6_fused", rows, 4, secs, q6_bytes)

    q1 = compiled.q1_pipeline()
    secs = _time(lambda: q1._fn(li, {}), reps)
    _report("tpch_q1_fused", rows, li.num_columns, secs, nbytes)

    # chained (trusted) variants; q6's per-iteration time is tiny, so
    # its chain must be long enough that the long-short difference
    # dwarfs the host sync's run-to-run jitter. The int8-MXU limb
    # kernel + elementwise add2 keep exact-f64 pipelines short enough
    # per iteration that the long chains are safe.
    secs = _chained_pipeline_secs(q6, li, "l_extendedprice", max(reps // 2, 2), 129)
    _report("tpch_q6_fused_chained", rows, 4, secs, q6_bytes, "chained")
    secs = _chained_pipeline_secs(q1, li, "l_extendedprice", max(reps // 2, 2), 129)
    _report("tpch_q1_fused_chained", rows, li.num_columns, secs, nbytes, "chained")


def _time_spread(fn: Callable[[], object], reps: int):
    """(median, worst, per-rep list) — the kernel-tier axes gate on the
    WORST rep (the bench.py vs_baseline_worst discipline: a lucky run
    must not masquerade as the result)."""
    _sync(fn())  # warmup + compile
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        _sync(fn())
        times.append(time.perf_counter() - t0)
    return float(np.median(times)), float(max(times)), times


def _tier_count(tier: str) -> int:
    from spark_rapids_jni_tpu.utils import metrics

    return metrics.registry().counter(f"dispatch.tier.{tier}").value


def _forced_xla(knob_name: str):
    import contextlib

    @contextlib.contextmanager
    def scope():
        # srjt-lint: allow-environ(harness save/restore of a declared knob around the forced-XLA twin measurement; not a config read)
        prev = os.environ.get(knob_name)
        os.environ[knob_name] = "0"
        try:
            yield
        finally:
            if prev is None:
                del os.environ[knob_name]
            else:
                os.environ[knob_name] = prev

    return scope()


def bench_join(rows: int, reps: int) -> None:
    """Paged-kernel join axis (ISSUE 13): ``rows`` probe rows against a
    16 Ki-row build side (the TPC-DS fact-x-dimension shape the paged
    tier targets), inner gather maps. Measures the ARMED tier, then the
    forced-XLA sort-probe formulation in the same process; the tier row
    carries which kernel actually ran (dispatch.tier counters), the
    bit-identity verdict, and vs_baseline(_worst) = XLA median over the
    tier's median (worst) rep — the premerge kernel-tier gate's
    evidence."""
    import jax.numpy as jnp

    from spark_rapids_jni_tpu.ops import join as join_ops

    build = 1 << 14
    rng = np.random.default_rng(42)
    rk = rng.integers(0, build, build).astype(np.int64)
    lk = rng.integers(0, 2 * build, rows).astype(np.int64)  # ~half match
    lt = Table([Column(dt.INT64, data=jnp.asarray(lk))], ["k"])
    rt = Table([Column(dt.INT64, data=jnp.asarray(rk))], ["k"])
    nbytes = rows * 8 + build * 8

    p0 = _tier_count("pallas")
    tier_med, tier_worst, _ = _time_spread(
        lambda: join_ops.join_gather_maps(lt, rt, "inner"), reps
    )
    engaged = "pallas" if _tier_count("pallas") > p0 else "xla"
    got = join_ops.join_gather_maps(lt, rt, "inner")
    with _forced_xla("SRJT_PALLAS_JOIN"):
        xla_med, _, _ = _time_spread(
            lambda: join_ops.join_gather_maps(lt, rt, "inner"), reps
        )
        want = join_ops.join_gather_maps(lt, rt, "inner")
    bit_identical = bool(
        np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
        and np.array_equal(np.asarray(got[1]), np.asarray(want[1]))
    )
    _report(
        "join_inner_paged", rows, 1, tier_med, nbytes,
        tier=engaged, bit_identical=bit_identical,
        xla_secs=round(xla_med, 6),
        vs_baseline=round(xla_med / tier_med, 3),
        vs_baseline_worst=round(xla_med / tier_worst, 3),
    )


def bench_ragged_decode(rows: int, reps: int) -> None:
    """Fused ragged-decode axis (ISSUE 13): ``rows`` strings of 0-32
    bytes compacted out of a row-blob-shaped pool (inter-row gaps, the
    convert_from_rows source layout). Same tier-vs-forced-XLA protocol
    and row evidence as bench_join."""
    import jax.numpy as jnp

    from spark_rapids_jni_tpu.ops.ragged_bytes import (
        ragged_compact, ragged_compact_tiered,
    )

    rng = np.random.default_rng(42)
    lens = rng.integers(0, 33, rows).astype(np.int64)
    gaps = np.full(rows, 120, np.int64)  # the fixed-section stride analog
    base = np.cumsum(np.concatenate([[0], (lens + gaps)[:-1]]))
    pool = jnp.asarray(
        rng.integers(0, 255, int(base[-1] + lens[-1]) + 128).astype(np.uint8)
    )
    basej = jnp.asarray(base)
    offs = jnp.asarray(np.concatenate([[0], np.cumsum(lens)]))
    total = int(offs[-1])

    p0 = _tier_count("pallas")
    tier_med, tier_worst, _ = _time_spread(
        lambda: ragged_compact_tiered(pool, basej, offs, total), reps
    )
    engaged = "pallas" if _tier_count("pallas") > p0 else "xla"
    got = np.asarray(ragged_compact_tiered(pool, basej, offs, total))
    # the XLA twin is timed DIRECTLY (ragged_compact never consults the
    # knob), so no forcing scope is needed on this axis
    xla_med, _, _ = _time_spread(
        lambda: ragged_compact(pool, basej, offs, total), reps
    )
    want = np.asarray(ragged_compact(pool, basej, offs, total))
    _report(
        "ragged_decode_fused", rows, 1, tier_med, total,
        tier=engaged, bit_identical=bool(np.array_equal(got, want)),
        xla_secs=round(xla_med, 6),
        vs_baseline=round(xla_med / tier_med, 3),
        vs_baseline_worst=round(xla_med / tier_worst, 3),
    )


_BENCHES = {
    "row_conversion_fixed": bench_row_conversion_fixed,
    "row_conversion_mixed": bench_row_conversion_mixed,
    "cast_string": bench_cast_string,
    "groupby": bench_groupby,
    "tpch": bench_tpch,
    "join": bench_join,
    "ragged_decode": bench_ragged_decode,
}


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--bench", choices=sorted(_BENCHES) + ["all"], default="all")
    p.add_argument("--rows", type=int, default=1 << 17)
    p.add_argument("--reps", type=int, default=5)
    args = p.parse_args()
    # row_conversion_fixed runs LAST: its chained variants leave loop
    # state that distorts axes measured after them in the same process
    all_order = sorted(_BENCHES, key=lambda nm: (nm == "row_conversion_fixed", nm))
    names: List[str] = all_order if args.bench == "all" else [args.bench]
    for name in names:
        _BENCHES[name](args.rows, args.reps)


if __name__ == "__main__":
    main()
