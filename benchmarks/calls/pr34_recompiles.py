#!/usr/bin/env python3
"""PR 34, satellite 5 (written down, not mended): how many programs a
SECOND table of the same rows and schema but another byte total makes
`convert_to_rows` compile. The encode's program takes `total_bytes`,
`maxlens` and `maxvar` as static arguments, so a deployment's every batch
compiles it anew, while the benchmark's seeds permute one fixed draw and
hold all three fixed. A count, on the CPU, at a small size:

    JAX_PLATFORMS=cpu python3 benchmarks/calls/pr34_recompiles.py [--rows 4096]
"""
import argparse
import json
import os
import sys


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=4096)
    args = ap.parse_args()
    root = os.getcwd()
    sys.path.insert(0, os.path.join(root, "bench"))
    sys.path.insert(1, root)
    import numpy as np

    from benchlib import loader

    config = loader.read_json("configs", "rowconv-155x1m-strings.json")
    build = loader.module("data", config["data"]).host_tables

    import jax.numpy as jnp

    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.columnar.dtype import DType, TypeId
    from spark_rapids_jni_tpu.ops import row_conversion as rc
    from spark_rapids_jni_tpu.utils import metrics

    def device(host):
        cols = []
        for name, data, validity in host:
            d, v = DType(TypeId[name]), None if validity is None else jnp.asarray(validity)
            if isinstance(data, tuple):
                cols.append(Column(d, validity=v, offsets=jnp.asarray(data[0]), chars=jnp.asarray(data[1])))
            else:
                cols.append(Column(d, data=jnp.asarray(data.view(np.dtype(d.np_dtype))), validity=v))
        return Table(cols)

    def shorter(host, col, by):
        """The same table with `by` characters fewer in one STRING column's first long string."""
        out = list(host)
        name, (offsets, chars), validity = out[col]
        r = int(np.argmax(np.diff(offsets) >= by + 1))
        offsets = offsets.copy()
        offsets[r + 1:] -= by
        out[col] = (name, (offsets, np.delete(chars, np.s_[offsets[r]:offsets[r] + by])), validity)
        return out

    reg = metrics.registry()

    def compiles(host):
        table = device(host)
        before = reg.value("xla.backend_compiles")
        (batch,) = rc.convert_to_rows(table)
        return int(reg.value("xla.backend_compiles") - before), int(batch.child.data.shape[0])

    first = build(config, 34, args.rows)["table"]
    out = {"rows": args.rows}
    out["first_table"] = compiles(first)
    out["another_seed_same_draw"] = compiles(build(config, 35, args.rows)["table"])
    out["eight_bytes_fewer"] = compiles(shorter(first, 9, 8))      # another byte total, same maxlens and maxvar
    out["same_total_again"] = compiles(shorter(first, 19, 8))      # the same total as the line above, other column
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
