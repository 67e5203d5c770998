#!/usr/bin/env python3
"""PR 34, step 1's question put to the chip: the two forms of the mixed
encode that the PARENT tree (fc974bf) holds, at the cell's own axis and on
the cell's own table (bench/data/rowconv_var_width.py, 1 Mi rows x 155
columns, 15 of them STRING): the fused one (`_jit_encode_strings_fused`,
one program) against the staged one (`_encode_strings_impl` called
directly, three programs). One form a process, because a process's peak
memory never falls:

    python3 benchmarks/calls/pr34_forms.py fused|staged [--rows N] [--seed S] [--reps R]

Run from the root of a checkout of the PARENT (the change keeps one form
and this script then says so and exits 2). Prints one JSON line: the
form, its compile seconds, each repetition's milliseconds from launch to
`block_until_ready`, the 16 waits' milliseconds (the sizes program's two
scalars and fifteen `Column.max_char_len`), the process's peak device
bytes, and a CRC of the bytes so that the two processes can be compared.
Rehearse with `JAX_PLATFORMS=cpu ... --rows 4096`.
"""
import argparse
import json
import os
import sys
import time
import zlib


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("form", choices=("fused", "staged"))
    ap.add_argument("--rows", type=int, default=1 << 20)
    ap.add_argument("--seed", type=int, default=3_400_000_007)
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()
    root = os.getcwd()
    sys.path.insert(0, os.path.join(root, "bench"))
    sys.path.insert(1, root)
    import numpy as np

    from benchlib import loader

    config = loader.read_json("configs", "rowconv-155x1m-strings.json")
    host = loader.module("data", config["data"]).host_tables(config, args.seed, args.rows)["table"]

    import jax
    import jax.numpy as jnp

    from spark_rapids_jni_tpu.columnar import Column
    from spark_rapids_jni_tpu.columnar.dtype import DType, TypeId
    from spark_rapids_jni_tpu.ops import row_conversion as rc

    if not hasattr(rc, "_jit_encode_strings_fused") or not hasattr(rc, "_encode_strings_impl"):
        print("this tree keeps one form of the encode: run from a checkout of the parent (fc974bf)", file=sys.stderr)
        return 2
    dev = jax.devices()[0]

    def build():  # as sidecar._decode_table builds them: no memo of the longest string
        cols = []
        for name, data, validity in host:
            v = None if validity is None else jnp.asarray(validity)
            d = DType(TypeId[name])
            if isinstance(data, tuple):
                cols.append(Column(d, validity=v, offsets=jnp.asarray(data[0]), chars=jnp.asarray(data[1])))
            else:
                cols.append(Column(d, data=jnp.asarray(data.view(np.dtype(d.np_dtype))), validity=v))
        return cols

    cols = build()
    jax.block_until_ready([c.offsets if c.dtype.id == TypeId.STRING else c.data for c in cols])
    layout = rc.compute_row_layout([c.dtype for c in cols])
    waits_ms = []
    for _ in range(3):
        fresh = build()
        jax.block_until_ready([c.offsets if c.dtype.id == TypeId.STRING else c.data for c in fresh])
        t0 = time.perf_counter()
        var_offs = tuple(fresh[i].offsets for i in layout.variable_cols)
        _, offsets_dev, stats = rc._jit_row_size_stats(layout, var_offs)
        total, max_size = (int(v) for v in np.asarray(stats))
        maxlens = rc._var_maxlens(layout, fresh)
        waits_ms.append(1e3 * (time.perf_counter() - t0))
    maxvar = max(rc._round_up(max_size - layout.fixed_end, 64), 8)
    call = rc._jit_encode_strings_fused if args.form == "fused" else rc._encode_strings_impl
    t0 = time.perf_counter()
    out = jax.block_until_ready(call(layout, tuple(cols), offsets_dev, total, maxlens, maxvar))
    first_s = time.perf_counter() - t0
    ms = []
    for _ in range(args.reps):
        t0 = time.perf_counter()
        out = jax.block_until_ready(call(layout, tuple(cols), offsets_dev, total, maxlens, maxvar))
        ms.append(1e3 * (time.perf_counter() - t0))
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    crc = zlib.crc32(np.asarray(out).tobytes())
    print(json.dumps({"form": args.form, "device": {"platform": dev.platform, "kind": dev.device_kind},
                      "rows": args.rows, "total_bytes": total, "max_row": max_size, "maxvar": maxvar,
                      "maxlens": list(maxlens), "first_call_s": first_s, "ms": ms, "sixteen_waits_ms": waits_ms,
                      "peak_bytes_in_use": int(peak), "crc32": crc}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
