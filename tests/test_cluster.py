"""srjt-cluster tier (ISSUE 16): N-rank membership, liveness, and
epoch-fenced recovery for the distributed data plane.

Covers the ClusterView state machine (ALIVE -> SUSPECT -> DEAD, the
miss ladder, wire generation adoption, quorum), the exchange's
generation fence (stale rejects on both sides, heal-on-resync), the
reset-mid-frame UNAVAILABLE classification, netsplit `@r<N>` rank
keying, per-peer breaker isolation, lineage recovery (failover_fetch /
recover_partition / recompute_dead_partition), the N-rank exchange
topologies (tree == all_to_all bit-identity, cluster pins all_to_all),
the plan compiler's Exchange stage, the scheduler's quorum-loss shed,
and the 4-process chaos acceptance: a rank kill -9'd mid-query under
ci/chaos_cluster.json with the distributed groupby still bit-identical
to the single-host oracle (heavy tests ride the slow tier;
ci/premerge.sh runs this file env-armed in the dedicated cluster
tier)."""

import os
import socket
import threading
import time

import numpy as np
import pytest

import spark_rapids_jni_tpu  # noqa: F401
import jax.numpy as jnp
from spark_rapids_jni_tpu import plan as P
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.columnar import dtype as dt
from spark_rapids_jni_tpu.ops.copying import concatenate, slice_table
from spark_rapids_jni_tpu.parallel import shuffle
from spark_rapids_jni_tpu.parallel.cluster import (
    ALIVE,
    DEAD,
    SUSPECT,
    ClusterView,
)
from spark_rapids_jni_tpu.plan import nodes as pn
from spark_rapids_jni_tpu.utils import (
    deadline as deadline_mod,
    faultinj,
    metrics,
    retry,
)
from spark_rapids_jni_tpu.utils.errors import (
    DataCorruption,
    FatalDeviceError,
    Overloaded,
    RetryableError,
)


def _counter(name):
    return metrics.registry().value(name)


pytestmark = pytest.mark.usefixtures("clean_state")


def _probe_err():
    return RetryableError("probe: connection refused")


# ---------------------------------------------------------------------------
# membership + liveness (the ClusterView state machine)
# ---------------------------------------------------------------------------


class TestMembership:
    def test_ctor_validation(self):
        ex = shuffle.TcpExchange(0)
        try:
            with pytest.raises(ValueError, match="must include this rank"):
                ClusterView(7, {0: ex.address, 1: "127.0.0.1:9"}, ex)
            with pytest.raises(ValueError, match="DEAD_MISSES"):
                ClusterView(0, {0: ex.address, 1: "127.0.0.1:9"}, ex,
                            suspect_misses=4, dead_misses=2)
        finally:
            ex.close()

    def test_miss_ladder_and_generation_fencing(self):
        ex = shuffle.TcpExchange(0)
        view = ClusterView(0, {0: ex.address, 1: "127.0.0.1:9"}, ex,
                           suspect_misses=2, dead_misses=4)
        deaths0 = _counter("cluster.deaths")
        trans0 = _counter("cluster.transitions")
        try:
            # construction installs generation 1 into the exchange
            assert view.generation() == 1 and ex.generation() == 1
            assert view.state(1) == ALIVE and view.state(0) == ALIVE
            view._record_miss(1, _probe_err())
            assert view.state(1) == ALIVE  # one miss is not suspicion
            view._record_miss(1, _probe_err())
            assert view.state(1) == SUSPECT
            view._record_miss(1, _probe_err())
            assert view.state(1) == SUSPECT  # dead needs the full ladder
            view._record_miss(1, _probe_err())
            assert view.state(1) == DEAD
            # death is a membership event: generation = 1 + deaths,
            # installed into the exchange fence immediately
            assert view.generation() == 2 and ex.generation() == 2
            assert view.dead_ranks() == [1]
            assert view.alive_ranks() == [0]
            assert not view.has_quorum()  # 1 alive of 2 fails > 0.5
            assert _counter("cluster.deaths") == deaths0 + 1
            assert _counter("cluster.transitions") == trans0 + 2
        finally:
            ex.close()

    def test_suspect_heals_to_alive_on_hit(self):
        ex = shuffle.TcpExchange(0)
        view = ClusterView(0, {0: ex.address, 1: "127.0.0.1:9"}, ex,
                           suspect_misses=2, dead_misses=4)
        try:
            view._record_miss(1, _probe_err())
            view._record_miss(1, _probe_err())
            assert view.state(1) == SUSPECT
            view._record_hit(1, peer_gen=1)
            assert view.state(1) == ALIVE
            # the miss count reset with the hit: one new miss is benign
            view._record_miss(1, _probe_err())
            assert view.state(1) == ALIVE
            assert view.generation() == 1
        finally:
            ex.close()

    def test_wire_generation_adoption(self):
        # a peer that already observed a death answers pings with a
        # higher generation; adopting it keeps our publishes servable
        ex = shuffle.TcpExchange(0)
        view = ClusterView(0, {0: ex.address, 1: "127.0.0.1:9"}, ex)
        try:
            view._record_hit(1, peer_gen=5)
            assert view.generation() == 5 and ex.generation() == 5
            view._record_hit(1, peer_gen=3)  # never adopt backwards
            assert view.generation() == 5
        finally:
            ex.close()

    def test_mark_dead_idempotent_and_await_dead(self):
        ex = shuffle.TcpExchange(0)
        view = ClusterView(0, {0: ex.address, 1: "127.0.0.1:9"}, ex)
        deaths0 = _counter("cluster.deaths")
        try:
            assert not view.await_dead(1, 0.05)  # alive: deadline passes
            t = threading.Timer(0.2, view.mark_dead, args=(1,))
            t.start()
            assert view.await_dead(1, 10.0)  # woken by the transition
            assert view.await_dead(1, 0.0)  # already dead: immediate
            view.mark_dead(1)  # idempotent: DEAD is terminal
            assert _counter("cluster.deaths") == deaths0 + 1
            assert view.generation() == 2
        finally:
            ex.close()

    def test_quorum_fraction(self):
        ex = shuffle.TcpExchange(0)
        addrs = {0: ex.address, 1: "127.0.0.1:9", 2: "127.0.0.1:9",
                 3: "127.0.0.1:9"}
        view = ClusterView(0, addrs, ex, quorum_fraction=0.5)
        try:
            assert view.has_quorum()
            view.mark_dead(1)
            assert view.has_quorum()  # 3 > 2
            view.mark_dead(2)
            assert not view.has_quorum()  # 2 > 2 is false
            # generation is a function of membership: 1 + deaths known
            assert view.generation() == 3
        finally:
            ex.close()

    def test_heartbeat_detects_death_and_views_converge(self):
        # two live observers, one peer killed: both detectors must walk
        # it ALIVE -> SUSPECT -> DEAD independently and land on the
        # SAME generation (generation is a function of membership, not
        # a per-observer counter)
        ex0, ex1, ex2 = (shuffle.TcpExchange(r) for r in range(3))
        addrs = {0: ex0.address, 1: ex1.address, 2: ex2.address}
        kw = dict(heartbeat_s=0.05, heartbeat_timeout_s=0.25,
                  suspect_misses=1, dead_misses=2)
        view0 = ClusterView(0, addrs, ex0, **kw)
        view1 = ClusterView(1, addrs, ex1, **kw)
        try:
            view0.start()
            view1.start()
            ex2.close()  # kill the peer: connects now refused
            t_end = time.monotonic() + 15.0
            while time.monotonic() < t_end:
                if view0.state(2) == DEAD and view1.state(2) == DEAD:
                    break
                time.sleep(0.02)
            assert view0.state(2) == DEAD, "view0 never declared death"
            assert view1.state(2) == DEAD, "view1 never declared death"
            assert view0.generation() == view1.generation() == 2
            assert ex0.generation() == ex1.generation() == 2
            # the live pair kept each other ALIVE throughout
            assert view0.state(1) == ALIVE and view1.state(0) == ALIVE
            assert view0.snapshot()["states"] == {1: ALIVE, 2: DEAD}
        finally:
            view0.stop()
            view1.stop()
            for ex in (ex0, ex1, ex2):
                ex.close()


# ---------------------------------------------------------------------------
# the epoch fence + wire failure classification
# ---------------------------------------------------------------------------


def _small_table(n=64):
    return Table(
        [Column(dt.INT64, data=jnp.arange(n, dtype=jnp.int64))], ["x"]
    )


class TestFencing:
    def test_ping_returns_generation(self):
        ex0, ex1 = shuffle.TcpExchange(0), shuffle.TcpExchange(1)
        try:
            assert ex0.ping(ex1.address, 2.0) == 0  # unfenced peer
            ex1.set_generation(7)
            assert ex0.ping(ex1.address, 2.0) == 7
            ex1.close()
            # a connect racing the close can still land in the kernel
            # backlog and be served; the refusal is eventual
            for _ in range(50):
                try:
                    ex0.ping(ex1.address, 0.5)
                except (RetryableError, OSError):
                    break
                time.sleep(0.05)
            else:
                pytest.fail("ping never failed after the peer closed")
        finally:
            ex0.close()
            ex1.close()

    def test_stale_generation_rejected_both_sides_then_heals(self):
        ex0, ex1 = shuffle.TcpExchange(0), shuffle.TcpExchange(1)
        try:
            ex1.publish(3, {0: _small_table()})
            ex1.set_generation(2)
            ex0.set_generation(1)
            refused0 = _counter("cluster.stale_generation_refused")
            rejects0 = _counter("cluster.stale_generation_rejects")
            with pytest.raises(RetryableError, match="DESYNC"):
                ex0._fetch_once(ex1.address, 3, 0)
            # the server refused undecoded, the client counted a desync
            assert _counter("cluster.stale_generation_refused") == refused0 + 1
            assert _counter("cluster.stale_generation_rejects") == rejects0 + 1
            # resync heals: same fetch, bumped fence
            ex0.set_generation(2)
            out = ex0._fetch_once(ex1.address, 3, 0)
            assert np.array_equal(
                np.asarray(out.columns[0].data), np.arange(64)
            )
            # an unfenced client never engages the fence (plain GET)
            ex0.set_generation(None)
            out = ex0._fetch_once(ex1.address, 3, 0)
            assert out.num_rows == 64
        finally:
            ex0.close()
            ex1.close()

    def test_reset_mid_frame_is_unavailable_not_corruption(self):
        # a peer that dies between the response header and the payload:
        # the header promised bytes that never arrive. No frame was
        # accepted, so nothing exists for a CRC to vouch for — the
        # fetch must classify UNAVAILABLE (the recovery path's signal),
        # never DataCorruption (ISSUE 16 satellite regression).
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.bind(("127.0.0.1", 0))
        srv.listen(1)
        addr = f"127.0.0.1:{srv.getsockname()[1]}"

        def serve_half_frame():
            conn, _ = srv.accept()
            conn.recv(shuffle._EXC_REQ.size)
            # a valid OK header promising 4096 payload bytes, then die
            conn.sendall(shuffle._EXC_RESP.pack(shuffle._EXC_OK, 4096))
            conn.close()

        t = threading.Thread(target=serve_half_frame, daemon=True)
        t.start()
        ex0 = shuffle.TcpExchange(0)
        try:
            with pytest.raises(RetryableError) as ei:
                ex0._fetch_once(addr, 0, 0)
            assert not isinstance(ei.value, DataCorruption)
            msg = str(ei.value)
            assert "UNAVAILABLE" in msg and "reset" in msg
            assert "payload" in msg  # the phase the peer died in
        finally:
            ex0.close()
            srv.close()
            t.join(5)

    def test_netsplit_rank_tag_scopes_to_tagged_rank(self, monkeypatch):
        cfg = {"faults": {"exchange.connect@r1": {
            "type": "netsplit", "percent": 100}}}
        # this process is rank 1: the partition rule fires at the
        # connect choke as the REAL refused-connect OSError subclass
        monkeypatch.setenv("SRJT_FAULTINJ_RANK", "r1")
        faultinj.configure(cfg)
        with pytest.raises(ConnectionRefusedError):
            faultinj.maybe_inject("exchange.connect")
        # ... which the fetch path classifies retryable-UNAVAILABLE
        ex0, ex1 = shuffle.TcpExchange(0), shuffle.TcpExchange(1)
        try:
            ex1.publish(0, {0: _small_table()})
            with pytest.raises(RetryableError, match="UNAVAILABLE"):
                ex0._fetch_once(ex1.address, 0, 0)
            # a foreign tag never matches: rank 2 runs the same
            # profile clean and the fetch flows
            monkeypatch.setenv("SRJT_FAULTINJ_RANK", "r2")
            faultinj.configure(cfg)
            faultinj.maybe_inject("exchange.connect")  # no raise
            out = ex0._fetch_once(ex1.address, 0, 0)
            assert out.num_rows == 64
        finally:
            ex0.close()
            ex1.close()

    def test_per_peer_breaker_isolation(self):
        # one dead peer's open breaker must not fail fetches from the
        # live peers — breakers are per-address, the facade fans out
        dead_addr = "127.0.0.1:9"
        br = shuffle.exchange_breaker(dead_addr)
        br.configure(threshold=1, cooldown_s=60.0)
        br.record_failure(cause="unavailable")
        assert not br.allow()
        ex0, ex1 = shuffle.TcpExchange(0), shuffle.TcpExchange(1)
        try:
            with pytest.raises(RetryableError, match="breaker open"):
                ex0.fetch(dead_addr, 0, 0)
            ex1.publish(0, {0: _small_table()})
            out = ex0.fetch(ex1.address, 0, 0)  # live peer unaffected
            assert out.num_rows == 64
            snap = shuffle.exchange_breaker().snapshot()
            assert len(snap) >= 2  # one machine per peer address
            assert shuffle.exchange_breaker(dead_addr) is br  # stable
        finally:
            ex0.close()
            ex1.close()


# ---------------------------------------------------------------------------
# lineage recovery
# ---------------------------------------------------------------------------


def _shard_of(full, rows, world, r):
    lo, hi = shuffle._shard_bounds(rows, world, r)
    return slice_table(full, lo, hi)


def _expected_partition(src, world, dest):
    partitioned, offsets = shuffle.hash_partition(src, world, ["k"])
    bounds = list(offsets) + [partitioned.num_rows]
    return slice_table(partitioned, bounds[dest], bounds[dest + 1])


def _assert_tables_equal(got, want, names=("k", "v")):
    assert got.num_rows == want.num_rows
    for name in names:
        assert np.array_equal(
            np.asarray(got.column(name).data),
            np.asarray(want.column(name).data),
        ), name


class TestRecovery:
    ROWS = 900
    SEED = 3

    def _view3(self, ex, **kw):
        full = shuffle._demo_table(self.ROWS, seed=self.SEED)
        addrs = {0: ex.address, 1: "127.0.0.1:9", 2: "127.0.0.1:9"}
        kw.setdefault("heartbeat_s", 0.02)
        kw.setdefault("heartbeat_timeout_s", 0.05)
        kw.setdefault("suspect_misses", 1)
        kw.setdefault("dead_misses", 1)
        view = ClusterView(
            0, addrs, ex,
            lineage=lambda r: _shard_of(full, self.ROWS, 3, r), **kw
        )
        return full, view

    def test_failover_requires_confirmed_death_and_lineage(self):
        ex = shuffle.TcpExchange(0)
        try:
            full, view = self._view3(ex)
            # not dead within the grace: the pull keeps its own error
            assert view.failover_fetch(1, 0, ["k"], 3, 0) is None
            view.mark_dead(1)
            no_lineage = ClusterView(
                0, {0: ex.address, 1: "127.0.0.1:9"}, ex,
                heartbeat_s=0.02, heartbeat_timeout_s=0.05,
                suspect_misses=1, dead_misses=1,
            )
            no_lineage.mark_dead(1)
            assert no_lineage.failover_fetch(1, 0, ["k"], 2, 0) is None
            with pytest.raises(FatalDeviceError, match="no lineage"):
                no_lineage.recover_partition(1, 0, ["k"], 2, 0)
            # confirmed dead + lineage: the recomputed partition flows
            got = view.failover_fetch(1, 0, ["k"], 3, 0)
            want = _expected_partition(
                _shard_of(full, self.ROWS, 3, 1), 3, 0)
            _assert_tables_equal(got, want)
        finally:
            ex.close()

    def test_recover_partition_republishes_idempotently(self):
        ex = shuffle.TcpExchange(0)
        try:
            full, view = self._view3(ex)
            view.mark_dead(1)
            recov0 = _counter("cluster.recoveries")
            got = view.recover_partition(1, 0, ["k"], 3, 2)
            want = _expected_partition(
                _shard_of(full, self.ROWS, 3, 1), 3, 2)
            _assert_tables_equal(got, want)
            assert _counter("cluster.recoveries") == recov0 + 1
            # the dead rank's outgoing partitions are republished under
            # the derived recovery epoch so ANY survivor can fetch them
            recovery_epoch = 2 * shuffle._RECOVERY_EPOCH_STRIDE
            with ex._published:
                assert (recovery_epoch, 0) in ex._frames
                assert (recovery_epoch, 2) in ex._frames
                assert (recovery_epoch, 1) not in ex._frames
            # idempotent per (dead_rank, epoch): later callers reuse it
            again = view.recover_partition(1, 0, ["k"], 3, 2)
            _assert_tables_equal(again, want)
            assert _counter("cluster.recoveries") == recov0 + 1
        finally:
            ex.close()

    def test_recompute_dead_partition_matches_direct(self):
        # the destination-side hole: the partition headed TO the dead
        # rank, rebuilt from every rank's lineage, must equal the same
        # partition computed directly over the whole input
        ex = shuffle.TcpExchange(0)
        try:
            full, view = self._view3(ex)
            view.mark_dead(1)
            got = view.recompute_dead_partition(1, ["k"], 3)
            want = _expected_partition(full, 3, 1)
            _assert_tables_equal(got, want)
        finally:
            ex.close()

    def test_exchange_failover_bit_identical_in_process(self):
        # world 3 with rank 1 dead from the start: both survivors'
        # pulls from it exhaust retries, rendezvous with the heartbeat
        # detector, and fail over to the lineage-recomputed copy — the
        # three-way groupby (survivors + the coordinator-recomputed
        # dead partition) must equal the single-host oracle exactly
        rows, seed, world = 1200, 5, 3
        full = shuffle._demo_table(rows, seed=seed)
        ref = shuffle._local_groupby_sum(full)
        ex0, ex2 = shuffle.TcpExchange(0), shuffle.TcpExchange(2)
        addrs = {0: ex0.address, 1: "127.0.0.1:9", 2: ex2.address}
        kw = dict(
            lineage=lambda r: _shard_of(full, rows, world, r),
            heartbeat_s=0.05, heartbeat_timeout_s=0.2,
            suspect_misses=1, dead_misses=2,
        )
        view0 = ClusterView(0, addrs, ex0, **kw)
        view2 = ClusterView(2, addrs, ex2, **kw)
        recov0 = _counter("cluster.recoveries")
        res, errs = {}, []

        def run_rank(rank, ex, view):
            try:
                peers = {r: a for r, a in addrs.items() if r != rank}
                with retry.enabled(max_attempts=20, base_delay_ms=5,
                                   max_delay_ms=50):
                    local = ex.exchange_table(
                        _shard_of(full, rows, world, rank), ["k"], peers,
                        epoch=0, cluster=view,
                    )
                res[rank] = shuffle._local_groupby_sum(local)
            except BaseException as e:  # noqa: BLE001 - surfaced below
                errs.append(e)

        try:
            view0.start()
            view2.start()
            threads = [
                threading.Thread(target=run_rank, args=(0, ex0, view0)),
                threading.Thread(target=run_rank, args=(2, ex2, view2)),
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(120)
            assert not errs, errs
            assert set(res) == {0, 2}
            # the coordinator rebuilds the dead rank's share of the
            # answer from lineage — no network, pure replay
            res[1] = shuffle._local_groupby_sum(
                view0.recompute_dead_partition(1, ["k"], world))
            got = concatenate([res[0], res[1], res[2]])
            order = np.argsort(np.asarray(got.column("k").data))
            for name in ("k", "s", "c"):
                assert np.array_equal(
                    np.asarray(got.column(name).data)[order],
                    np.asarray(ref.column(name).data),
                ), f"{name} diverged from the single-host oracle"
            # both views observed the death, agreed on the generation,
            # and at least one recovery republish happened
            assert view0.dead_ranks() == [1]
            assert view2.dead_ranks() == [1]
            assert view0.generation() == view2.generation() == 2
            assert _counter("cluster.recoveries") >= recov0 + 1
        finally:
            view0.stop()
            view2.stop()
            ex0.close()
            ex2.close()


# ---------------------------------------------------------------------------
# N-rank exchange topologies
# ---------------------------------------------------------------------------


class TestTopology:
    def test_topology_validation(self, monkeypatch):
        ex = shuffle.TcpExchange(0)
        t = shuffle._demo_table(64, seed=1)
        try:
            with pytest.raises(ValueError, match="must cover ranks"):
                ex.exchange_table(t, ["k"], {5: "127.0.0.1:9"})
            with pytest.raises(ValueError, match="power-of-two"):
                ex.exchange_table(
                    t, ["k"], {1: "x", 2: "y"}, topology="tree")
            with pytest.raises(ValueError, match="unknown exchange topology"):
                ex.exchange_table(t, ["k"], {1: "x"}, topology="ring")
            # topology=None reads the SRJT_CLUSTER_TOPOLOGY knob per
            # call: pinning "tree" at a non-power-of-two world hits the
            # tree plan's own validation (the knob layer itself rejects
            # unknown values with a warning and falls back to auto)
            monkeypatch.setenv("SRJT_CLUSTER_TOPOLOGY", "tree")
            with pytest.raises(ValueError, match="power-of-two"):
                ex.exchange_table(t, ["k"], {1: "x", 2: "y"})
        finally:
            ex.close()

    def test_cluster_pins_all_to_all_over_tree(self):
        # recovery needs single-hop lineage (a tree round forwards
        # OTHER ranks' rows), so an attached cluster pins the direct
        # plan even when tree is requested: frames land under the real
        # epoch, never the tree's derived sub-epoch namespace
        rows, seed = 400, 9
        full = shuffle._demo_table(rows, seed=seed)
        ref = shuffle._local_groupby_sum(full)
        ex0, ex1 = shuffle.TcpExchange(0), shuffle.TcpExchange(1)
        addrs = {0: ex0.address, 1: ex1.address}
        view0 = ClusterView(0, addrs, ex0)
        view1 = ClusterView(1, addrs, ex1)
        res, errs = {}, []

        def run_rank(rank, ex, view):
            try:
                peers = {r: a for r, a in addrs.items() if r != rank}
                with retry.enabled(max_attempts=20, base_delay_ms=5):
                    local = ex.exchange_table(
                        _shard_of(full, rows, 2, rank), ["k"], peers,
                        epoch=0, topology="tree", cluster=view,
                    )
                res[rank] = shuffle._local_groupby_sum(local)
            except BaseException as e:  # noqa: BLE001 - surfaced below
                errs.append(e)

        try:
            threads = [
                threading.Thread(target=run_rank, args=(0, ex0, view0)),
                threading.Thread(target=run_rank, args=(1, ex1, view1)),
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            assert not errs, errs
            with ex0._published:
                epochs = sorted({e for e, _ in ex0._frames})
            assert 0 in epochs, "all_to_all publish missing"
            assert all(e < shuffle._TREE_EPOCH_STRIDE for e in epochs), \
                "tree sub-epoch frames found despite an attached cluster"
            got = concatenate([res[0], res[1]])
            order = np.argsort(np.asarray(got.column("k").data))
            for name in ("k", "s", "c"):
                assert np.array_equal(
                    np.asarray(got.column(name).data)[order],
                    np.asarray(ref.column(name).data),
                ), name
        finally:
            ex0.close()
            ex1.close()

    def test_tree_equals_all_to_all_world4(self):
        # the two exchange plans move rows differently but must
        # aggregate identically: world-4 in-process fabric, one round
        # per plan (auto topology picks tree at a power-of-two world,
        # proven by its derived sub-epoch frames)
        rows, seed, world = 1600, 21, 4
        full = shuffle._demo_table(rows, seed=seed)
        ref = shuffle._local_groupby_sum(full)
        exs = [shuffle.TcpExchange(r) for r in range(world)]
        addrs = {r: exs[r].address for r in range(world)}

        def run_round(epoch, topology, out):
            errs = []

            def run_rank(rank):
                try:
                    peers = {r: a for r, a in addrs.items() if r != rank}
                    with retry.enabled(max_attempts=40, base_delay_ms=5,
                                       max_delay_ms=50):
                        local = exs[rank].exchange_table(
                            _shard_of(full, rows, world, rank), ["k"],
                            peers, epoch=epoch, topology=topology,
                        )
                    out[rank] = shuffle._local_groupby_sum(local)
                except BaseException as e:  # noqa: BLE001 - surfaced below
                    errs.append(e)

            threads = [threading.Thread(target=run_rank, args=(r,))
                       for r in range(world)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(180)
            assert not errs, errs
            assert set(out) == set(range(world))

        def check(out):
            got = concatenate([out[r] for r in range(world)])
            order = np.argsort(np.asarray(got.column("k").data))
            for name in ("k", "s", "c"):
                assert np.array_equal(
                    np.asarray(got.column(name).data)[order],
                    np.asarray(ref.column(name).data),
                ), name

        try:
            direct, tree = {}, {}
            run_round(0, "all_to_all", direct)
            check(direct)
            run_round(2, None, tree)  # auto: tree at world 4, no cluster
            check(tree)
            # the auto round really took the hypercube plan: its
            # coalesced frames live in the derived sub-epoch namespace
            with exs[0]._published:
                epochs = {e for e, _ in exs[0]._frames}
            assert any(e >= shuffle._TREE_EPOCH_STRIDE for e in epochs), \
                "auto topology never engaged the tree plan at world 4"
        finally:
            for ex in exs:
                ex.close()


# ---------------------------------------------------------------------------
# the plan compiler's Exchange stage
# ---------------------------------------------------------------------------


class TestPlanExchange:
    def test_exchange_node_validation(self):
        with pytest.raises(P.PlanError, match="at least one key"):
            pn.Exchange(pn.Scan("t"), (), 2)
        with pytest.raises(P.PlanError, match="world must be >= 1"):
            pn.Exchange(pn.Scan("t"), ("k",), 0)
        agg = pn.Aggregate(
            pn.Scan("t"), keys=("k",),
            aggs=(pn.AggSpec("v", "sum", "s"),),
        )
        with pytest.raises(P.PlanError, match="world must be >= 1"):
            P.insert_exchanges(agg, 0)

    def test_insert_exchanges_wraps_keyed_aggregates_only(self):
        keyed = pn.Aggregate(
            pn.Scan("fact"), keys=("f_key",),
            aggs=(pn.AggSpec("f_qty", "sum", "s"),),
        )
        out = P.insert_exchanges(keyed, 4)
        assert isinstance(out, pn.Aggregate)
        exch = out.input
        assert isinstance(exch, pn.Exchange)
        assert exch.keys == ("f_key",) and exch.world == 4
        assert isinstance(exch.input, pn.Scan)
        # a global aggregate has no partitioning to exploit: untouched
        glob = pn.Aggregate(
            pn.Scan("fact"), aggs=(pn.AggSpec("f_qty", "sum", "s"),),
        )
        out2 = P.insert_exchanges(glob, 4)
        assert isinstance(out2.input, pn.Scan)

    def test_exchange_stage_is_identity_without_binding(self):
        # the SAME distributed plan runs single-host: outside any
        # exchange binding the stage lowers to the identity, so the
        # compiled result matches the exchange-free plan exactly
        rng = np.random.default_rng(31)
        n = 256
        tables = {"fact": Table(
            [Column(dt.INT64, data=jnp.asarray(
                rng.integers(0, 16, n).astype(np.int64))),
             Column(dt.INT64, data=jnp.asarray(
                 rng.integers(-50, 50, n).astype(np.int64)))],
            ["k", "v"],
        )}
        plan = pn.Aggregate(
            pn.Scan("fact"), keys=("k",),
            aggs=(pn.AggSpec("v", "sum", "s"),),
        )
        single = P.compile_ir(plan, tables, name="cluster-single")()
        dist = P.compile_ir(
            P.insert_exchanges(plan, 4), tables, name="cluster-dist")()
        for got in (single, dist):
            assert set(got.names) == {"k", "s"}
        o1 = np.argsort(np.asarray(single.column("k").data))
        o2 = np.argsort(np.asarray(dist.column("k").data))
        for name in ("k", "s"):
            assert np.array_equal(
                np.asarray(single.column(name).data)[o1],
                np.asarray(dist.column(name).data)[o2],
            ), name


# ---------------------------------------------------------------------------
# a real TPC-DS plan across 4 ranks with one rank dead (the plan-layer
# half of the ISSUE 16 acceptance; the process-level kill -9 variant
# runs in TestClusterChaosFourRank below)
# ---------------------------------------------------------------------------


class TestDistributedPlanQuery:
    def test_q55x4_bit_identical_with_dead_rank(self):
        """The q55 plan with exchange stages inserted runs on a 4-rank
        fabric with rank 1 dead: the SAME compiled plan produces the
        single-host oracle unbound (exchange = identity), each live
        rank aggregates its key partition under an exchange binding
        (fact table sharded, dims replicated — broadcast join), the
        dead rank's exchange input is replayed from the lineage the
        stage itself installed, the coordinator rebuilds the
        destination-side hole, and merge_partials re-applies the
        plan's total-order Sort — bit-identical end to end."""
        from spark_rapids_jni_tpu.models import tpcds, tpcds_plans as tp
        from spark_rapids_jni_tpu.plan.distribute import merge_partials

        world, rows = 4, 8000
        tables = tpcds.gen_store(rows, seed=12)
        plan = P.insert_exchanges(tp.q55_plan(), world)
        sort_keys = (("ext_price", False), ("i_brand_id", True))
        # unbound, the exchange stages lower to the identity: the
        # distributed plan IS its own single-host oracle
        ref = P.compile_ir(plan, tables, name="q55x4-oracle")()
        assert ref.num_rows > 0

        fact_rows = tables["store_sales"].num_rows

        def shard_tables(r):
            lo, hi = shuffle._shard_bounds(fact_rows, world, r)
            return {
                "store_sales": slice_table(tables["store_sales"], lo, hi),
                "date_dim": tables["date_dim"],
                "item": tables["item"],
            }

        exs = {r: shuffle.TcpExchange(r) for r in (0, 2, 3)}
        addrs = {r: (exs[r].address if r in exs else "127.0.0.1:9")
                 for r in range(world)}
        kw = dict(heartbeat_s=0.05, heartbeat_timeout_s=0.2,
                  suspect_misses=1, dead_misses=2)
        views = {r: ClusterView(r, addrs, exs[r], **kw) for r in exs}
        recov0 = _counter("cluster.recoveries")
        res, errs = {}, []

        def run_rank(rank):
            try:
                peers = {r: a for r, a in addrs.items() if r != rank}
                with P.exchange_context(
                    exs[rank], peers, cluster=views[rank],
                    shard_tables=shard_tables,
                ), retry.enabled(max_attempts=20, base_delay_ms=5,
                                 max_delay_ms=50):
                    res[rank] = P.compile_ir(
                        plan, shard_tables(rank), name=f"q55x4-r{rank}")()
            except BaseException as e:  # noqa: BLE001 - surfaced below
                errs.append(e)

        try:
            for v in views.values():
                v.start()
            threads = [threading.Thread(target=run_rank, args=(r,))
                       for r in exs]
            for t in threads:
                t.start()
            for t in threads:
                t.join(300)
            assert not errs, errs
            assert set(res) == set(exs)
            # the destination-side hole: rank 1's key partition,
            # rebuilt from the lineage the exchange stage installed on
            # rank 0's view, then aggregated by the same plan shape
            hole = views[0].recompute_dead_partition(
                1, ["i_brand_id"], world)
            res[1] = P.compile_ir(
                pn.Aggregate(
                    pn.Scan("hole"), keys=("i_brand_id",),
                    aggs=(pn.AggSpec(
                        "ss_ext_sales_price", "sum", "ext_price"),),
                ),
                {"hole": hole}, name="q55x4-hole")()
            got = merge_partials(
                [res[r] for r in range(world)], sort_keys)
            assert got.num_rows == ref.num_rows
            for name in ("i_brand_id", "ext_price"):
                assert np.array_equal(
                    np.asarray(got.column(name).data),
                    np.asarray(ref.column(name).data),
                ), f"{name} diverged from the single-host oracle"
            # membership converged on one death; at least one survivor
            # recovered the dead rank's partitions from lineage
            for v in views.values():
                assert v.dead_ranks() == [1]
                assert v.generation() == 2
            assert _counter("cluster.recoveries") >= recov0 + 1
        finally:
            for v in views.values():
                v.stop()
            for ex in exs.values():
                ex.close()


# ---------------------------------------------------------------------------
# the serving layer's quorum-loss shed
# ---------------------------------------------------------------------------


class TestSchedulerQuorumShed:
    def test_scheduler_sheds_below_quorum(self):
        from spark_rapids_jni_tpu.serve.scheduler import Scheduler

        ex = shuffle.TcpExchange(0)
        view = ClusterView(0, {0: ex.address, 1: "127.0.0.1:9"}, ex)
        s = Scheduler(max_concurrent=1, queue_depth=4, name="cluster-shed")
        try:
            s.attach_cluster(view)
            h = s.submit(lambda: 7, tenant="t")
            assert h.result(30) == 7  # at quorum: admitted normally
            view.mark_dead(1)  # 1 of 2 alive: below the > 0.5 bar
            with pytest.raises(Overloaded) as ei:
                s.submit(lambda: 8, tenant="t")
            assert ei.value.cause == "cluster_degraded"
        finally:
            assert s.shutdown(drain=False, timeout_s=30.0)
            ex.close()


# ---------------------------------------------------------------------------
# the 4-process chaos acceptance (slow tier; ci/premerge.sh cluster
# tier runs it env-armed with the event log archived)
# ---------------------------------------------------------------------------


class TestClusterChaosFourRank:
    def test_four_rank_groupby_survives_rank_kill(self):
        """The ISSUE 16 acceptance: a 4-rank distributed groupby over
        the TCP exchange with ci/chaos_cluster.json armed in the
        children — rank 2 SIGKILLs itself mid-frame on its first
        payload serve (`crash` keyed ``exchange.serve.payload@r2``),
        rank 3 rides a transient netsplit, rank 1 serves with latency
        jitter — and the final answer is STILL bit-identical to the
        single-host oracle: exactly one membership death, the dead
        rank's partitions recomputed from lineage under the bumped
        generation, the destination-side hole rebuilt by the
        coordinator, zero stale bytes decoded (fence-verified before
        the decoder on every fetch)."""
        rows, seed, world = 4000, 13, 4
        cfg = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "ci", "chaos_cluster.json",
        )
        full = shuffle._demo_table(rows, seed=seed)
        ref = shuffle._local_groupby_sum(full)

        def shard(r):
            return _shard_of(full, rows, world, r)

        ex0 = shuffle.TcpExchange(0)
        procs, view = {}, None
        deaths0 = _counter("cluster.deaths")
        trans0 = _counter("cluster.transitions")
        recov0 = _counter("cluster.recoveries")
        try:
            with metrics.enabled():
                procs, peers = shuffle.spawn_exchange_fleet(
                    ex0.address, rows, seed, world=world, cluster=True,
                    extra_env_by_rank={
                        r: {"JAX_PLATFORMS": "cpu",
                            "SRJT_FAULTINJ_CONFIG": cfg}
                        for r in range(1, world)
                    },
                )
                view = ClusterView(0, dict(peers), ex0, lineage=shard)
                view.start()
                res = {}
                with deadline_mod.scope(300), retry.enabled(
                    max_attempts=40, base_delay_ms=25, max_delay_ms=250
                ):
                    local0 = ex0.exchange_table(
                        shard(0), ["k"],
                        {r: a for r, a in peers.items() if r != 0},
                        epoch=0, cluster=view,
                    )
                    res[0] = shuffle._local_groupby_sum(local0)
                    # the crash rule fired on rank 2's first payload
                    # serve: the membership layer must confirm the
                    # death (SIGKILL, no cleanup — rc != 0)
                    assert view.await_dead(2, 120), \
                        "rank 2 never declared dead"
                    assert procs[2].wait(timeout=120) != 0
                    # survivors finish their rounds and publish their
                    # partials under the bumped generation
                    for r in (1, 3):
                        got = ex0.fetch(peers[r], 1, r)
                        res[r] = Table(got.columns, ["k", "s", "c"])
                    # the destination-side hole: rank 2's share of the
                    # answer, rebuilt from lineage by the coordinator
                    res[2] = shuffle._local_groupby_sum(
                        view.recompute_dead_partition(2, ["k"], world))
                got = concatenate([res[r] for r in range(world)])
                order = np.argsort(np.asarray(got.column("k").data))
                for name in ("k", "s", "c"):
                    assert np.array_equal(
                        np.asarray(got.column(name).data)[order],
                        np.asarray(ref.column(name).data),
                    ), f"{name} diverged from the single-host oracle"
                # exactly ONE membership death (alive->suspect->dead is
                # the one allowed transition pair), generation bumped
                # once, and this rank's own failover republished the
                # dead rank's partitions at least once
                assert view.dead_ranks() == [2]
                assert view.generation() == 2 and ex0.generation() == 2
                assert _counter("cluster.deaths") == deaths0 + 1
                assert _counter("cluster.transitions") == trans0 + 2
                assert _counter("cluster.recoveries") >= recov0 + 1
        finally:
            if view is not None:
                view.stop()
            for p in procs.values():
                if p.poll() is None:
                    try:
                        p.stdin.close()
                        p.wait(timeout=20)
                    except Exception:
                        p.kill()
            ex0.close()
            shuffle.exchange_breaker().reset()
