"""Data-plane benchmark: slab-arena pool scaling + N-process exchange
(ISSUE 6, N-rank tier ISSUE 16).

Three stages, each emitting BENCH rows (JSON lines, the bench.py /
microbench.py discipline; ``SRJT_RESULTS`` appends them to a file):

- **pool**: arena-resident op throughput at pool sizes 1/2/4. Each
  worker is a REAL spawned sidecar process with a fixed worker-side
  op delay armed through faultinj (``--delay-ms``, default 10 — the
  stand-in for device-op latency, so the measurement is transport
  concurrency, not host CPU count). Client threads hammer
  ``SidecarPool.call_arena`` concurrently; ops/s scales with pool size
  exactly when per-request regions let arena ops overlap. Under the
  PR 5 single-buffer arena this was ~1.0x by construction (one
  ``_arena_io_lock`` serialized every worker); the premerge gate
  asserts pool 2 >= 1.5x pool 1 from these rows.
- **exchange**: 2-process distributed hash-partition exchange MB/s —
  rank 0 here, rank 1 a spawned ``parallel.shuffle --exchange-worker``
  peer, partitions crossing TCP as versioned columnar frames under
  retry + CRC. Bytes counted at the sockets this process touches
  (``shuffle.tcp.bytes_in/out``), and the distributed groupby result
  is verified bit-identical to the single-process oracle before the
  row is emitted.
- **nrank**: the same exchange at world sizes 2 and 4 (weak scaling:
  rows per rank constant), ranks 1..N-1 spawned as a fleet. Reports
  AGGREGATE MB/s — rank 0's socket bytes scaled by world (the
  all-to-all is symmetric). The premerge gate asserts world-4
  aggregate >= 2.5x world-2: growing the world grows cross-rank
  volume per rank, so a healthy data plane scales super-linearly.

All three stages are CPU functional gates, not device measurements:
this process imports ``ops`` (which initialises the JAX backend at
import) and then starts worker processes. A chip has one owner, so on
a TPU the pool refuses to start from here
(``sidecar_pool._refuse_second_chip_owner``), and a pool of more than
one worker cannot share one chip at all (ROADMAP.md C4).

Usage::

    python benchmarks/bench_pool.py                     # all stages
    python benchmarks/bench_pool.py --sizes 1,2 --ops 40 --delay-ms 20
    python benchmarks/bench_pool.py --stage exchange --exchange-rows 500000
    python benchmarks/bench_pool.py --stage nrank --nrank-worlds 2,4
"""

from __future__ import annotations

import argparse
import contextvars
import itertools
import json
import os
import sys
import tempfile
import threading
import time

os.environ.setdefault("SRJT_METRICS_ENABLED", "1")  # byte counters feed the rows

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from spark_rapids_jni_tpu import sidecar, sidecar_pool
from spark_rapids_jni_tpu.ops.copying import concatenate, slice_table
from spark_rapids_jni_tpu.parallel import shuffle
from spark_rapids_jni_tpu.utils import knobs, metrics, retry

import struct


def _emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)
    out_path = knobs.get_str("SRJT_RESULTS")
    if out_path:
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")


def _counter(name: str) -> int:
    return metrics.registry().value(name)


def _groupby_payload(n: int = 600, k: int = 16, seed: int = 3) -> bytes:
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, k, n).astype(np.int64)
    vals = rng.standard_normal(n).astype(np.float32)
    return struct.pack("<IQ", k, n) + keys.tobytes() + vals.tobytes()


# ---------------------------------------------------------------------------
# stage 1: pool scaling on arena-resident ops
# ---------------------------------------------------------------------------


def bench_pool_sizes(sizes, ops: int, threads: int, delay_ms: int,
                     startup_timeout_s: float) -> dict:
    """ops/s of ``call_arena(GROUPBY_SUM_F32)`` per pool size; returns
    {size: ops_per_s}. The worker-side ``delay`` fault (percent 100,
    unbounded) puts a fixed latency floor under every op, so overlap —
    not host parallelism — is what the ratio measures."""
    fd, cfg_path = tempfile.mkstemp(prefix="srjt-bench-delay-", suffix=".json")
    with os.fdopen(fd, "w") as f:
        json.dump({"faults": {"sidecar.worker.GROUPBY_SUM_F32": {
            "type": "delay", "percent": 100, "delayMs": int(delay_ms)}}}, f)
    payload = _groupby_payload()
    want = sidecar._dispatch(sidecar.OP_GROUPBY_SUM_F32, payload, "cpu")
    results: dict = {}
    try:
        for size in sizes:
            pool = sidecar_pool.SidecarPool(
                size=size, deadline_s=60, heartbeat_s=1e9,
                startup_timeout_s=startup_timeout_s,
                env={"SRJT_FAULTINJ_CONFIG": cfg_path},
            )
            try:
                # warm: slab creation + one arena round-trip per worker
                # (round-robin), correctness checked against the host
                with retry.enabled(max_attempts=6, base_delay_ms=1):
                    for _ in range(size):
                        assert pool.call_arena(
                            sidecar.OP_GROUPBY_SUM_F32, payload
                        ) == want, "pool warmup diverged from host oracle"
                tickets = itertools.count()
                errs: list = []

                def hammer():
                    try:
                        with retry.enabled(max_attempts=6, base_delay_ms=1):
                            while next(tickets) < ops:
                                pool.call_arena(
                                    sidecar.OP_GROUPBY_SUM_F32, payload
                                )
                    except Exception as e:  # surfaced after join
                        errs.append(e)

                ts = [threading.Thread(target=hammer) for _ in range(threads)]
                t0 = time.perf_counter()
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
                secs = time.perf_counter() - t0
                if errs:
                    raise errs[0]
            finally:
                pool.shutdown()
            results[size] = ops / secs
            _emit({
                "metric": "pool_arena_ops_per_s",
                "pool_size": size,
                "value": round(ops / secs, 2),
                "unit": "ops/s",
                "ops": ops,
                "threads": threads,
                "delay_ms": delay_ms,
                "secs": round(secs, 4),
                "vs_pool1": round(results[size] / results[sizes[0]], 3)
                if sizes[0] in results else None,
            })
    finally:
        os.unlink(cfg_path)
    return results


# ---------------------------------------------------------------------------
# stage 2: 2-process TCP exchange MB/s
# ---------------------------------------------------------------------------

def _spawn_peer(parent_addr: str, rows: int, seed: int):
    return shuffle.spawn_exchange_peer(parent_addr, rows, seed)


def bench_exchange(rows: int, seed: int = 13) -> float:
    """Time one full 2-process exchange round (partition both ways +
    result fetch), verify the distributed groupby bit-identical to the
    single-process oracle, and report MB/s over the bytes this process
    moved through its sockets."""
    full = shuffle._demo_table(rows, seed=seed)
    ref = shuffle._local_groupby_sum(full)
    lo, hi = shuffle._shard_bounds(rows, 2, 0)
    shard0 = slice_table(full, lo, hi)

    shuffle.hash_partition(shard0, 2, ["k"])  # compile excluded (bench discipline)
    ex0 = shuffle.TcpExchange(0)
    proc = None
    try:
        proc, peer_addr = _spawn_peer(ex0.address, rows, seed)
        b0 = _counter("shuffle.tcp.bytes_in") + _counter("shuffle.tcp.bytes_out")
        t0 = time.perf_counter()
        with retry.enabled(max_attempts=40, base_delay_ms=25, max_delay_ms=250):
            local0 = ex0.exchange_table(shard0, ["k"], {1: peer_addr}, epoch=0)
            res0 = shuffle._local_groupby_sum(local0)
            res1 = ex0.fetch(peer_addr, 1, 1)
        secs = time.perf_counter() - t0
        moved = (
            _counter("shuffle.tcp.bytes_in")
            + _counter("shuffle.tcp.bytes_out")
            - b0
        )
        got = concatenate(
            [res0, shuffle.Table(res1.columns, ["k", "s", "c"])]
        )
        order = np.argsort(np.asarray(got.column("k").data))
        for name in ("k", "s", "c"):
            assert np.array_equal(
                np.asarray(got.column(name).data)[order],
                np.asarray(ref.column(name).data),
            ), f"distributed groupby diverged from single-process ({name})"
    finally:
        if proc is not None and proc.poll() is None:
            try:
                proc.stdin.close()
                proc.wait(timeout=30)
            except Exception:
                proc.kill()
        ex0.close()
    mbps = moved / secs / 1e6
    _emit({
        "metric": "exchange_2proc_mb_per_s",
        "value": round(mbps, 2),
        "unit": "MB/s",
        "rows": rows,
        "bytes_moved": moved,
        "secs": round(secs, 4),
        "bit_identical": True,
    })
    return mbps


# ---------------------------------------------------------------------------
# stage 3: N-rank exchange aggregate throughput (ISSUE 16)
# ---------------------------------------------------------------------------

def bench_exchange_nrank(rows_per_rank: int, world: int,
                         seed: int = 17, delay_ms: int = 900) -> float:
    """Weak-scaling N-rank exchange: rank 0 here, ranks 1..world-1
    spawned via ``spawn_exchange_fleet`` (the cluster tier's bring-up
    path), every rank holding ``rows_per_rank`` rows. Verifies the
    distributed groupby bit-identical to the single-process oracle
    FIRST, then reports aggregate MB/s over one steady-state round —
    rank 0's measured socket bytes scaled by world, valid because the
    all-to-all is symmetric (every rank moves the same expected
    volume; the hash is uniform over the demo key space).

    Like the pool stage, a fault-injected latency floor
    (``delay_ms`` at ``exchange.serve.payload``, every rank) stands in
    for network latency so the round is LATENCY-dominated and the
    measurement is transport CONCURRENCY, not host core count: a
    world-4 rank must overlap its 3 pulls (wall = slowest peer), so
    with ~equal round walls the 3x cross-rank bytes of world 4 puts
    aggregate throughput >= 2.5x world 2 — the premerge gate. A data
    plane that serializes its pulls pays the floor world-1 times
    sequentially and fails the gate on any host."""
    from spark_rapids_jni_tpu.columnar import frames as frames_mod
    from spark_rapids_jni_tpu.utils import faultinj

    rows = rows_per_rank * world
    full = shuffle._demo_table(rows, seed=seed)
    ref = shuffle._local_groupby_sum(full)
    lo, hi = shuffle._shard_bounds(rows, world, 0)
    shard0 = slice_table(full, lo, hi)

    # compile excluded (bench discipline): warm the exact partition
    # slices + frame encodes publish() will hit inside the window.
    # The frames are deterministic, so their sizes ARE the round's
    # byte accounting — socket counters would race with peer serves
    # straddling the timed window.
    parts_w, offs_w = shuffle.hash_partition(shard0, world, ["k"])
    bounds_w = list(offs_w) + [parts_w.num_rows]
    out_bytes = 0
    for p in range(1, world):
        out_bytes += len(frames_mod.encode_table(
            slice_table(parts_w, bounds_w[p], bounds_w[p + 1])))
    in_bytes = 0  # what each peer's shard sends to rank 0 (same data)
    for r in range(1, world):
        rlo, rhi = shuffle._shard_bounds(rows, world, r)
        parts_r, offs_r = shuffle.hash_partition(
            slice_table(full, rlo, rhi), world, ["k"])
        bounds_r = list(offs_r) + [parts_r.num_rows]
        in_bytes += len(frames_mod.encode_table(
            slice_table(parts_r, bounds_r[0], bounds_r[1])))
    moved0 = out_bytes + in_bytes
    delay_cfg = {"faults": {"exchange.serve.payload": {
        "type": "delay", "percent": 100, "delayMs": int(delay_ms)}}}
    fd, cfg_path = tempfile.mkstemp(prefix="srjt-nrank-delay-", suffix=".json")
    with os.fdopen(fd, "w") as f:
        json.dump(delay_cfg, f)
    faultinj.configure(delay_cfg)  # rank 0's serves pay the same floor
    ex0 = shuffle.TcpExchange(0)
    procs = {}
    try:
        # pin all_to_all on every rank: apples-to-apples across worlds
        # (auto would switch to tree at world 4), and single-hop pulls
        # are what aggregate socket throughput should measure
        rounds = 4
        procs, peers = shuffle.spawn_exchange_fleet(
            ex0.address, rows, seed, world=world, rounds=rounds,
            extra_env_by_rank={
                r: {"SRJT_CLUSTER_TOPOLOGY": "all_to_all",
                    "SRJT_FAULTINJ_CONFIG": cfg_path}
                for r in range(1, world)
            })
        peer_map = {r: a for r, a in peers.items() if r != 0}
        # tight poll schedule: backoff quantization is a fixed cost the
        # world-4 round pays 3x as often, and it is not throughput
        with retry.enabled(max_attempts=200, base_delay_ms=10, max_delay_ms=50):
            # rounds 0-1 warm: data-dependent shapes (received
            # partitions, the world-way concat) compile once there, so
            # the timed rounds are steady-state exchange, not jit; two
            # timed rounds + min() shrugs off a scheduler hiccup
            secs = None
            for rnd in range(rounds):
                t0 = time.perf_counter()
                local0 = ex0.exchange_table(shard0, ["k"], peer_map,
                                            epoch=2 * rnd,
                                            topology="all_to_all")
                dt = time.perf_counter() - t0
                if rnd >= rounds - 2:
                    secs = dt if secs is None else min(secs, dt)
            res = {0: shuffle._local_groupby_sum(local0)}
            errs = []

            def _result(r, addr, ctx):
                try:
                    got = ctx.run(ex0.fetch, addr, 2 * rounds - 1, r)
                    res[r] = shuffle.Table(got.columns, ["k", "s", "c"])
                except Exception as e:  # surfaced after join
                    errs.append(e)

            ts = [threading.Thread(target=_result,
                                   args=(r, a, contextvars.copy_context()))
                  for r, a in peer_map.items()]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            if errs:
                raise errs[0]
        got = concatenate([res[r] for r in range(world)])
        order = np.argsort(np.asarray(got.column("k").data))
        for name in ("k", "s", "c"):
            assert np.array_equal(
                np.asarray(got.column(name).data)[order],
                np.asarray(ref.column(name).data),
            ), f"{world}-rank distributed groupby diverged ({name})"
    finally:
        for proc in procs.values():
            if proc.poll() is None:
                try:
                    proc.stdin.close()
                    proc.wait(timeout=30)
                except Exception:
                    proc.kill()
        ex0.close()
        faultinj.disable()
        os.unlink(cfg_path)
    aggregate_mbps = moved0 * world / secs / 1e6
    _emit({
        "metric": "exchange_nrank_mb_per_s",
        "value": round(aggregate_mbps, 2),
        "unit": "MB/s aggregate",
        "world": world,
        "rows_per_rank": rows_per_rank,
        "rank0_bytes_moved": moved0,
        "secs": round(secs, 4),
        "injected_delay_ms": int(delay_ms),  # latency floor: the value
        # is a concurrency ratio carrier, not raw socket speed
        "bit_identical": True,
    })
    return aggregate_mbps


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--stage", choices=["pool", "exchange", "nrank", "all"],
                    default="all")
    ap.add_argument("--sizes", default="1,2,4",
                    help="comma-separated pool sizes (default 1,2,4)")
    ap.add_argument("--ops", type=int, default=60,
                    help="arena ops per pool size (default 60)")
    ap.add_argument("--threads", type=int, default=8)
    ap.add_argument("--delay-ms", type=int, default=10,
                    help="worker-side per-op latency floor (default 10)")
    ap.add_argument("--startup-timeout", type=float, default=180.0)
    ap.add_argument("--exchange-rows", type=int, default=250_000)
    ap.add_argument("--nrank-worlds", default="2,4",
                    help="comma-separated world sizes for the nrank stage "
                         "(default 2,4)")
    ap.add_argument("--nrank-rows-per-rank", type=int, default=125_000,
                    help="rows held by each rank in the nrank stage "
                         "(weak scaling; default 125000)")
    args = ap.parse_args()

    if args.stage in ("pool", "all"):
        sizes = [int(s) for s in args.sizes.split(",") if s]
        res = bench_pool_sizes(
            sizes, args.ops, args.threads, args.delay_ms, args.startup_timeout
        )
        _emit({
            "metric": "pool_arena_scaling",
            "value": {str(s): round(res[s] / res[sizes[0]], 3) for s in sizes},
            "unit": "x vs pool 1",
            "delay_ms": args.delay_ms,
        })
    if args.stage in ("exchange", "all"):
        bench_exchange(args.exchange_rows)
    if args.stage in ("nrank", "all"):
        for world in [int(w) for w in args.nrank_worlds.split(",") if w]:
            bench_exchange_nrank(args.nrank_rows_per_rank, world)
    return 0


if __name__ == "__main__":
    sys.exit(main())
