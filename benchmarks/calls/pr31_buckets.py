#!/usr/bin/env python3
"""The capacity each exchange of ``tpcds-sf10-web.q95-x4`` counts, at full
size, without the chip: does every seed land on one step?

    JAX_PLATFORMS=cpu python3 benchmarks/calls/pr31_buckets.py [seed ...]

A capacity is a shape, so a seed whose fullest bucket crossed a step of
``table_ops._counted_capacity`` would compile a second all-to-all program
(and everything behind it) inside a timed window. For each seed the cell's
tables are made by ``bench/data/tpcds_web.py``, placed as ``shard_table``
places them (row i on shard i // ceil(rows / world)), and the keys of the
plan's three exchanges routed on the host by the program's own function
(``distributed._hash_dest_multi`` over the int64 key, as
``exchange_sharded``'s count program does). Printed: the fullest of the
world x world buckets, the step it lands on, and what the parent's
``_tight_capacity`` (half again the even share of the SLOTS) gave.
"""
import importlib.util
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, HERE)
WORLD = 4
SEEDS = (7, 3100000019, 3100104743, 3100209469, 2920000021, 2147483659)


def _bench(kind, name):
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", os.path.join(HERE, "bench", kind, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def buckets(keys, slot, per_shard):
    """world x world counts: rows placed on shard slot // per_shard, bound
    for the shard the program's routing gives their key."""
    import jax.numpy as jnp

    from spark_rapids_jni_tpu.parallel.distributed import _hash_dest_multi

    dest = np.asarray(_hash_dest_multi([jnp.asarray(keys.astype(np.int64))], WORLD))
    sent = np.zeros((WORLD, WORLD), np.int64)
    np.add.at(sent, (slot // per_shard, dest), 1)
    return sent


def main(seeds):
    import pandas as pd

    from spark_rapids_jni_tpu.parallel.table_ops import _counted_capacity

    config = json.load(open(os.path.join(HERE, "bench", "configs", "tpcds-sf10-web.json")))
    web, q95 = _bench("data", "tpcds_web"), _bench("queries", "tpcds_q95")
    rows = int(config["tables"]["web_sales"]["rows"])
    steps = {}
    for seed in seeds:
        host = web.host_tables(config, seed, rows)
        series = lambda a: pd.Series(a[0].astype(np.float64)).where(a[1]) if isinstance(a, tuple) else pd.Series(a)  # noqa: E731
        frames = {n: pd.DataFrame({c: series(a) for c, a in cols.items()}) for n, cols in host.items()}
        ws, wr = frames["web_sales"], frames["web_returns"]
        ws1 = q95._ws1(frames)  # the filters keep the slots: a row of ws1 lies where web_sales had it
        for name, keys, slot, slots in (
                ("web_sales", ws.ws_order_number.to_numpy(), np.arange(len(ws)), len(ws)),
                ("ws1", ws1.ws_order_number.to_numpy(), ws1.index.to_numpy(), len(ws)),
                ("web_returns", wr.wr_order_number.to_numpy(), np.arange(len(wr)), len(wr))):
            per_shard = -(-slots // WORLD)
            sent = buckets(keys, slot, per_shard)
            cap = _counted_capacity(int(sent.max()), per_shard)
            tight = min(per_shard, max(3 * (-(-per_shard // WORLD)) // 2, 64))
            steps.setdefault(name, set()).add(cap)
            print(f"seed {seed} {name}: rows {int(sent.sum())} slots/shard {per_shard} fullest bucket {int(sent.max())} "
                  f"(smallest {int(sent.min())}) -> capacity {cap}, {WORLD * cap} slots a chip "
                  f"(parent: {tight}, {WORLD * tight} a chip)", flush=True)
    for name, caps in steps.items():
        print(f"{name}: {'ONE step on every seed' if len(caps) == 1 else 'SEEDS DISAGREE'} {sorted(caps)}")
    return 0 if all(len(c) == 1 for c in steps.values()) else 1


if __name__ == "__main__":
    sys.exit(main([int(a) for a in sys.argv[1:]] or SEEDS))
