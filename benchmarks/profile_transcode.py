"""Stage-level chained profiling of the fixed-width transcode paths.

Decomposes the 212-col x 1M axis (the reference bench axis,
row_conversion.cpp:27-67) into its constituent device stages so the
dominant cost is measurable in isolation — every number uses the
two-length chained protocol (bench.py discipline), so host-sync latency
cancels and XLA cannot overlap iterations.

Usage::

    python benchmarks/profile_transcode.py [--rows N] [--reps R]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import spark_rapids_jni_tpu  # noqa: F401  (x64 on before arrays exist)
import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_jni_tpu.columnar import Column
from spark_rapids_jni_tpu.columnar import dtype as dt
from spark_rapids_jni_tpu.models.datagen import create_random_table, cycle_dtypes
from spark_rapids_jni_tpu.ops import row_conversion as rc
from spark_rapids_jni_tpu.ops.ragged_bytes import u32_rows_to_u8_flat

_NINE = [dt.INT8, dt.INT16, dt.INT32, dt.INT64,
         dt.UINT8, dt.UINT16, dt.UINT32, dt.UINT64, dt.BOOL8]


def chained(run, reps: int = 3, k_short: int = 1, k_long: int = 17) -> float:
    run(k_short), run(k_long)
    ts, tl = [], []
    for _ in range(reps):
        t0 = time.perf_counter(); run(k_short); ts.append(time.perf_counter() - t0)
        t0 = time.perf_counter(); run(k_long); tl.append(time.perf_counter() - t0)
    return max((float(np.median(tl)) - float(np.median(ts))) / (k_long - k_short), 1e-9)


def report(name: str, secs: float, nbytes_moved: int) -> None:
    print(json.dumps({
        "stage": name,
        "ms": round(secs * 1e3, 3),
        "gb_per_s_moved": round(nbytes_moved / secs / 1e9, 1),
    }), flush=True)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--rows", type=int, default=1 << 20)
    p.add_argument("--reps", type=int, default=3)
    p.add_argument("--cols", type=int, default=212)
    args = p.parse_args()
    n = args.rows

    table = create_random_table(cycle_dtypes(_NINE, args.cols), n, seed=42)
    cols = tuple(table.columns)
    layout = rc.compute_row_layout(table.dtypes())
    pad_to = layout.row_size_fixed
    lanes = (pad_to + 3) // 4
    blob_bytes = n * pad_to
    print(json.dumps({"rows": n, "cols": args.cols, "row_size": pad_to,
                      "lanes": lanes, "blob_mb": blob_bytes >> 20,
                      "backend": jax.default_backend()}), flush=True)

    # -- full encode ------------------------------------------------------
    @partial(jax.jit, static_argnums=(2,))
    def full_chain(c0, rest, iters: int):
        def body(_, carry):
            cs = (Column(cols[0].dtype, data=carry, validity=cols[0].validity),) + tuple(rest)
            blob = rc._to_rows_fixed(layout, cs, n)
            return carry ^ (blob[0] == 0).astype(carry.dtype)
        return lax.fori_loop(0, iters, body, c0)

    def run_full(k):
        return float(jnp.sum(full_chain(cols[0].data, cols[1:], k).astype(jnp.int32)))

    report("encode_full", chained(run_full, args.reps), 2 * blob_bytes)

    # -- fixed_section32 (planes + stack + transpose) ---------------------
    @partial(jax.jit, static_argnums=(2,))
    def f32_chain(c0, rest, iters: int):
        def body(_, carry):
            cs = (Column(cols[0].dtype, data=carry, validity=cols[0].validity),) + tuple(rest)
            f32 = rc._fixed_section32(layout, cs, {}, pad_to)
            return carry ^ (f32[0, 0] == 0).astype(carry.dtype)
        return lax.fori_loop(0, iters, body, c0)

    def run_f32(k):
        return float(jnp.sum(f32_chain(cols[0].data, cols[1:], k).astype(jnp.int32)))

    report("encode_fixed_section32", chained(run_f32, args.reps), 2 * blob_bytes)

    # -- planes + stack only (no transpose) -------------------------------
    def planes_stack(cs):
        plane_parts = [[] for _ in range(lanes)]

        def emit(byte_off, val):
            lane, sub = divmod(byte_off, 4)
            if lane < lanes:
                plane_parts[lane].append(val << jnp.uint32(8 * sub) if sub else val)

        for i, col in enumerate(cs):
            pos = layout.col_starts[i]
            for width, val in rc._col_u32_parts(col, {}, i):
                emit(pos, val)
                pos += width
        valid_t = jnp.stack([c.valid_mask() for c in cs], axis=0)
        for b in range((len(cs) + 7) // 8):
            byte = jnp.zeros((n,), jnp.uint32)
            for bit in range(8):
                c = 8 * b + bit
                if c < len(cs):
                    byte = byte | (valid_t[c].astype(jnp.uint32) << jnp.uint32(bit))
            emit(layout.validity_offset + b, byte)
        zero = jnp.zeros((n,), jnp.uint32)
        return jnp.stack([rc._or_compose(q, zero) for q in plane_parts], axis=0)

    @partial(jax.jit, static_argnums=(2,))
    def planes_chain(c0, rest, iters: int):
        def body(_, carry):
            cs = (Column(cols[0].dtype, data=carry, validity=cols[0].validity),) + tuple(rest)
            st = planes_stack(cs)
            return carry ^ (st[0, 0] == 0).astype(carry.dtype)
        return lax.fori_loop(0, iters, body, c0)

    def run_planes(k):
        return float(jnp.sum(planes_chain(cols[0].data, cols[1:], k).astype(jnp.int32)))

    report("encode_planes_stack_noT", chained(run_planes, args.reps), 2 * blob_bytes)

    # -- transpose [P, N] -> [N, P] ---------------------------------------
    x_pn = jnp.asarray(np.random.default_rng(0).integers(0, 2**32, (lanes, n), np.uint32))

    @partial(jax.jit, static_argnums=(1,))
    def t_chain(x, iters: int):
        def body(_, carry):
            y = carry.T + jnp.uint32(1)
            return y.T
        return lax.fori_loop(0, iters, body, x)

    def run_t(k):
        return float(t_chain(x_pn, k)[0, 0])

    report("transpose_PN_to_NP_x2", chained(run_t, args.reps), 4 * blob_bytes)

    # -- u32 rows -> u8 flat bitcast --------------------------------------
    x_np = jnp.asarray(np.random.default_rng(1).integers(0, 2**32, (n, lanes), np.uint32))

    @partial(jax.jit, static_argnums=(1,))
    def bc_chain(x, iters: int):
        def body(_, carry):
            b = u32_rows_to_u8_flat(carry)
            return carry ^ (b[0] == 0).astype(jnp.uint32)
        return lax.fori_loop(0, iters, body, x)

    def run_bc(k):
        return float(bc_chain(x_np, k)[0, 0])

    report("u32_to_u8_flat", chained(run_bc, args.reps), 2 * blob_bytes)

    # -- decode: full grouped uniform -------------------------------------
    blob = rc._to_rows_fixed(layout, cols, n)
    dtypes = tuple(table.dtypes())

    @partial(jax.jit, static_argnums=(1,))
    def dec_chain(b, iters: int):
        def body(_, carry):
            garrs, vt = rc._decode_grouped_uniform(layout, dtypes, carry)
            first = garrs[0].reshape(-1)[0]
            return carry.at[0].set(carry[0] ^ first.astype(carry.dtype))
        return lax.fori_loop(0, iters, body, b)

    def run_dec(k):
        return float(dec_chain(blob, k)[0])

    report("decode_grouped_full", chained(run_dec, args.reps), 2 * blob_bytes)

    # -- decode: lane32 build only (strided slices + OR) ------------------
    fixed = blob.reshape(n, pad_to)

    @partial(jax.jit, static_argnums=(1,))
    def lane_chain(f, iters: int):
        def body(_, carry):
            b = [carry[:, i::4].astype(jnp.uint32) for i in range(4)]
            lane32 = b[0] | (b[1] << jnp.uint32(8)) | (b[2] << jnp.uint32(16)) | (b[3] << jnp.uint32(24))
            return carry.at[0, 0].set(carry[0, 0] ^ (lane32[0, 0] & 1).astype(carry.dtype))
        return lax.fori_loop(0, iters, body, f)

    def run_lane(k):
        return float(lane_chain(fixed, k)[0, 0])

    report("decode_lane32_build", chained(run_lane, args.reps), 2 * blob_bytes)

    # -- decode: group takes + transposes from a prebuilt lane32 ----------
    groups, entries = rc._entry_plan(layout, dtypes)
    lane32_const = jnp.asarray(
        np.random.default_rng(2).integers(0, 2**32, (n, (pad_to + 3) // 4), np.uint32))

    @partial(jax.jit, static_argnums=(1,))
    def take_chain(l32, iters: int):
        def body(_, carry):
            acc = carry[0, 0]
            for key, count in groups.items():
                w = rc._entry_width(key)
                lane_idx = np.zeros((count,), np.int32)
                for ce in entries:
                    for k2, idx, row_byte in ce:
                        if k2 == key:
                            lane_idx[idx] = row_byte // (4 if w == 8 else w)
                if w in (4, 8):
                    g = jnp.take(carry, jnp.asarray(lane_idx), axis=1)
                    g = lax.optimization_barrier(g.T)
                    acc = acc ^ g[0, 0]
            return carry.at[0, 0].set(acc)
        return lax.fori_loop(0, iters, body, l32)

    def run_take(k):
        return float(take_chain(lane32_const, k)[0, 0])

    report("decode_group_takes_u32lanes", chained(run_take, args.reps), 2 * blob_bytes)


if __name__ == "__main__":
    main()
