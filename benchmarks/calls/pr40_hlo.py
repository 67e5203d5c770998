#!/usr/bin/env python3
"""The scatters inside the exact float64 sum and mean, as the v5e's compiler
builds them: ``ops/aggregate._f64_sum_mean`` compiled for a described v5e (no
chip is needed), at ``tpch-sf1.q1``'s arguments (``u64[6001215]``, its
validity, ``order`` None, ``seg`` ``s32[6001215]``, the rows that are present,
4 groups; ``--rows 816 --groups 816`` is the store star's larger group-by,
compiled for 896 groups), beside the same program with the ``segment_max``
over the rows that asked "does this group hold a valid row" before the flag
came from the exponent maxima.

    JAX_PLATFORMS=cpu python3 benchmarks/calls/pr40_hlo.py [--rows N] [--groups 4]

Prints one line a program: the fusions of the optimised HLO whose body
holds a ``scatter``, each with its shape and the ``op_name`` of the
scatter; then one JSON line a program with the counts.
"""
import argparse
import functools
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def scatters(hlo: str):
    """(computation, scatter instruction line) for every scatter of the module."""
    out, comp = [], None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) ", line)
        if head and line.rstrip().endswith("{"):
            comp = head.group(1)
        if re.search(r"= \S+ scatter\(", line):
            out.append((comp, line.strip()))
    return out


def fusion_callers(hlo: str, comp: str):
    """The fusion instructions that call the computation ``comp``."""
    return [ln.strip() for ln in hlo.splitlines() if re.search(rf"calls=%?{re.escape(comp)}\b", ln)]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=6001215)
    ap.add_argument("--groups", default="4")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import spark_rapids_jni_tpu  # noqa: F401  (x64 before any array)
    from spark_rapids_jni_tpu.ops import aggregate, f64acc

    jax.config.update("jax_enable_compilation_cache", False)  # a described chip's programs cannot be read back

    @functools.partial(jax.jit, static_argnames=("num", "how"))
    def before(data, validity, order, seg, live, *, num, how):
        """The program as it was: the public chain, then a scatter over the rows for the flag."""
        bits = aggregate._in_order(data, order)
        valid = aggregate._sorted_valid(validity, order, live, data.shape[0])
        if how == "sum":
            out_bits = f64acc.segment_sum_f64bits(bits, seg, num, valid=valid)
        else:
            out_bits, _ = f64acc.segment_mean_f64bits(bits, seg, num, valid=valid)
        return out_bits, jax.ops.segment_max(valid.astype(jnp.int32), seg, num) > 0

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    rows = lambda dtype: jax.ShapeDtypeStruct((args.rows,), dtype, sharding=chip)  # noqa: E731
    for groups in [int(g) for g in args.groups.split(",")]:
        num = aggregate._static_groups(groups)
        for how in ("sum", "mean"):
            for side, fn in (("before", before), ("after", aggregate._f64_sum_mean)):
                hlo = fn.lower(rows(jnp.uint64), rows(jnp.bool_), None, rows(jnp.int32), rows(jnp.bool_),
                               num=num, how=how).compile().as_text()
                found = scatters(hlo)
                for comp, line in found:
                    callers = fusion_callers(hlo, comp) or ["(not fused)"]
                    op_name = re.search(r'op_name="([^"]*)"', line)
                    print(f"{side} {how} num={num}: {callers[0][:140]} | {op_name.group(1) if op_name else '?'}")
                print(json.dumps({"side": side, "how": how, "groups": groups, "num": num, "rows": args.rows,
                                  "scatters": len(found)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
