"""srjt-durable (ISSUE 20): crash-recoverable serving.

Covers the durable query journal (framing, replay, torn-tail
truncation at EVERY byte boundary, idempotency index, degrade
posture), the spill-manifest layer (write/read/rot, dead-owner
re-attach, orphan GC), recovery resubmission through the plan rebind
path, and the cross-process kill -9 acceptance (a child coordinator is
SIGKILL'd mid-serve; a fresh process answers its journaled queries
bit-identically with zero duplicate executions of DONE work).
"""

import os
import signal
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from spark_rapids_jni_tpu import memgov
from spark_rapids_jni_tpu import plan as P
from spark_rapids_jni_tpu.columnar import Table
from spark_rapids_jni_tpu.columnar.column import Column
from spark_rapids_jni_tpu.memgov import persist
from spark_rapids_jni_tpu.memgov.catalog import BufferCatalog
from spark_rapids_jni_tpu.serve import journal as JM
from spark_rapids_jni_tpu.serve.scheduler import Scheduler
from spark_rapids_jni_tpu.utils import faultinj, metrics

_COUNTERS = (
    "journal.appends", "journal.append_failures", "journal.replays",
    "journal.replayed_records", "journal.truncated_records",
    "journal.idempotent_hits", "journal.recovered_resubmits",
    "journal.recovery_skipped", "memgov.manifests_written",
    "memgov.manifest_rot", "memgov.reattached",
    "memgov.orphans_reclaimed",
)


def _vals():
    reg = metrics.registry()
    return {n: reg.value(n) for n in _COUNTERS}


def _delta(before, after):
    return {n: after[n] - before[n] for n in _COUNTERS}


@pytest.fixture(autouse=True)
def _durability_off(monkeypatch, clean_state):
    monkeypatch.delenv("SRJT_JOURNAL_DIR", raising=False)
    monkeypatch.delenv("SRJT_SPILL_MANIFESTS", raising=False)
    monkeypatch.delenv("SRJT_OOC_DURABLE_CHECKPOINTS", raising=False)


def _tables(rows=96):
    rng = np.random.default_rng(23)
    return {
        "fact": Table(
            [Column.from_numpy(np.arange(rows, dtype=np.int64)),
             Column.from_numpy(rng.integers(0, 5, rows).astype(np.int64)),
             Column.from_numpy(rng.random(rows))],
            ["v", "k", "p"],
        ),
    }


def _mk(cut, factor=2.0):
    return P.Aggregate(
        P.Filter(P.Scan("fact"),
                 (P.pcol("v") < P.plit(cut)) & (P.pcol("p") < P.plit(factor))),
        keys=("k",), aggs=(P.AggSpec("v", "sum", "s"),),
    )


def _submit_rec(jid, idem=None, **extra):
    rec = {"jid": jid, "tenant": "t", "priority": 0, "deadline_s": None,
           "memory_bytes": None, "host_eligible": True}
    if idem is not None:
        rec["idem"] = idem
    rec.update(extra)
    return rec


# ---------------------------------------------------------------------------
# journal framing + replay
# ---------------------------------------------------------------------------


class TestJournal:
    def test_round_trip_replay(self, tmp_path, monkeypatch):
        monkeypatch.setenv("SRJT_JOURNAL_DIR", str(tmp_path))
        j = JM.active()
        assert j is not None and not j.degraded
        assert j.append_submit(_submit_rec("p-1", idem="a"))
        j.append_state("p-1", "dispatched")
        j.append_state("p-1", "done", digest=111)
        assert j.append_submit(_submit_rec("p-2", idem="b"))
        JM.reset()
        j2 = JM.active()
        assert j2.done_digest("a") == ("p-1", 111)
        inc = j2.incomplete()
        assert [r["jid"] for r in inc] == ["p-2"]
        snap = j2.snapshot()
        assert snap["truncated"] == 0 and snap["replayed"] == 4

    def test_terminal_state_is_sticky(self, tmp_path, monkeypatch):
        monkeypatch.setenv("SRJT_JOURNAL_DIR", str(tmp_path))
        j = JM.active()
        j.append_submit(_submit_rec("p-1", idem="a"))
        j.append_state("p-1", "done", digest=5)
        j.append_state("p-1", "dispatched")  # late slot write: ignored
        JM.reset()
        j2 = JM.active()
        assert j2.done_digest("a") == ("p-1", 5)
        assert j2.incomplete() == []

    def test_state_before_submit_replays(self, tmp_path, monkeypatch):
        # under concurrency a dispatch slot's state write can land
        # BEFORE the submitter's record — replay is order-insensitive
        monkeypatch.setenv("SRJT_JOURNAL_DIR", str(tmp_path))
        j = JM.active()
        j.append_state("p-1", "done", digest=9)
        j.append_submit(_submit_rec("p-1", idem="a"))
        JM.reset()
        assert JM.active().done_digest("a") == ("p-1", 9)

    def test_incomplete_dedups_by_idempotency_key(self, tmp_path, monkeypatch):
        monkeypatch.setenv("SRJT_JOURNAL_DIR", str(tmp_path))
        j = JM.active()
        j.append_submit(_submit_rec("p-1", idem="same"))
        j.append_submit(_submit_rec("p-2", idem="same"))
        j.append_submit(_submit_rec("p-3"))
        assert [r["jid"] for r in j.incomplete()] == ["p-1", "p-3"]

    def test_reopen_always_opens_fresh_segment(self, tmp_path, monkeypatch):
        monkeypatch.setenv("SRJT_JOURNAL_DIR", str(tmp_path))
        JM.active().append_submit(_submit_rec("p-1"))
        JM.reset()
        JM.active().append_submit(_submit_rec("p-2"))
        segs = sorted(p.name for p in tmp_path.glob("seg-*.jrnl"))
        assert segs == ["seg-000001.jrnl", "seg-000002.jrnl"]

    def test_segment_roll_on_threshold(self, tmp_path, monkeypatch):
        monkeypatch.setenv("SRJT_JOURNAL_DIR", str(tmp_path))
        monkeypatch.setenv("SRJT_JOURNAL_SEGMENT_BYTES", "4096")
        j = JM.active()
        for i in range(64):
            j.append_submit(_submit_rec(f"p-{i}", idem=f"k{i}", pad="x" * 128))
        assert len(list(tmp_path.glob("seg-*.jrnl"))) >= 2
        JM.reset()
        assert len(JM.active().incomplete()) == 64

    def test_open_failure_degrades_to_none(self, tmp_path, monkeypatch):
        blocker = tmp_path / "not-a-dir"
        blocker.write_bytes(b"")
        monkeypatch.setenv("SRJT_JOURNAL_DIR", str(blocker))
        before = _vals()
        assert JM.active() is None
        assert _delta(before, _vals())["journal.append_failures"] == 1

    def test_append_failure_degrades_not_raises(self, tmp_path, monkeypatch):
        monkeypatch.setenv("SRJT_JOURNAL_DIR", str(tmp_path))
        j = JM.active()
        assert j.append_submit(_submit_rec("p-1"))

        class _Sick:
            def write(self, b):
                raise OSError("disk gone")

            def close(self):
                pass

        j._file = _Sick()
        before = _vals()
        assert not j.append_submit(_submit_rec("p-2"))
        assert j.degraded
        assert _delta(before, _vals())["journal.append_failures"] == 1
        # degraded journal refuses further work without raising
        assert not j.append_state("p-1", "done", digest=1)

    def test_unserializable_record_journals_opaque(self, tmp_path, monkeypatch):
        monkeypatch.setenv("SRJT_JOURNAL_DIR", str(tmp_path))
        j = JM.active()
        assert j.append_submit(
            _submit_rec("p-1", idem="a", bindings=[object()], pf="k"))
        JM.reset()
        (rec,) = JM.active().incomplete()
        assert rec["opaque"] and "bindings" not in rec


# ---------------------------------------------------------------------------
# the torn-tail property: ANY byte prefix replays to a consistent state
# ---------------------------------------------------------------------------


class TestTornTailProperty:
    def _build(self, tmp_path, monkeypatch):
        monkeypatch.setenv("SRJT_JOURNAL_DIR", str(tmp_path))
        j = JM.active()
        j.append_submit(_submit_rec("p-A", idem="a"))
        j.append_state("p-A", "done", digest=111)
        j.append_submit(_submit_rec("p-B", idem="b"))
        j.append_state("p-B", "dispatched")
        j.append_submit(_submit_rec("p-C", idem="b"))  # duplicate idem
        j.append_state("p-B", "done", digest=222)  # the record to tear
        JM.reset()
        (seg,) = list(tmp_path.glob("seg-*.jrnl"))
        return seg

    def test_every_byte_prefix_is_consistent(self, tmp_path, monkeypatch):
        seg = self._build(tmp_path / "src", monkeypatch)
        raw = seg.read_bytes()
        torn_dir = tmp_path / "torn"
        torn_dir.mkdir()
        torn_seg = torn_dir / seg.name
        full = JM.replay(str(seg.parent))
        assert full.done_digest("b") == ("p-B", 222)
        for cut in range(len(raw) + 1):
            torn_seg.write_bytes(raw[:cut])
            st = JM.replay(str(torn_dir))
            # no invented work: every replayed jid was actually journaled
            assert set(st.records) <= {"p-A", "p-B", "p-C"}
            # no lost DONE: once A's terminal record is inside the
            # prefix it replays, at the journaled digest, at every
            # longer prefix
            da = st.done_digest("a")
            assert da in (None, ("p-A", 111))
            if "p-B" in st.records and len(st.records) == 3 and cut == len(raw):
                assert st.done_digest("b") == ("p-B", 222)
            # no duplicate dispatch: the recovery work list carries at
            # most ONE record per idempotency key
            inc = st.incomplete()
            idems = [r.get("idem") for r in inc if r.get("idem")]
            assert len(idems) == len(set(idems))
            # a jid never appears both terminal and incomplete
            inc_jids = {r["jid"] for r in inc}
            for jid, entry in st.records.items():
                if entry["state"] in JM.TERMINAL:
                    assert jid not in inc_jids

    def test_live_open_truncates_torn_tail(self, tmp_path, monkeypatch):
        seg = self._build(tmp_path, monkeypatch)
        raw = seg.read_bytes()
        seg.write_bytes(raw[: len(raw) - 3])  # tear the final record
        before = _vals()
        j = JM.active()
        d = _delta(before, _vals())
        assert d["journal.truncated_records"] == 1
        assert d["journal.replays"] == 1
        # the torn bytes are physically gone; B never reached done so
        # it is recovery work, deduplicated with its idem twin p-C
        assert os.path.getsize(seg) < len(raw)
        assert j.done_digest("b") is None
        assert [r["jid"] for r in j.incomplete()] == ["p-B"]


# ---------------------------------------------------------------------------
# torn_write chaos kind
# ---------------------------------------------------------------------------


class TestTornWriteFaultinj:
    def test_journal_append_torn_then_replay_consistent(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("SRJT_JOURNAL_DIR", str(tmp_path))
        j = JM.active()
        assert j.append_submit(_submit_rec("p-1", idem="a"))
        faultinj.configure({
            "seed": 3,
            "faults": {"journal.append": {
                "type": "torn_write", "percent": 100, "delayMs": 9}},
        })
        j.append_state("p-1", "done", digest=7)  # torn to 9 bytes
        faultinj.disable()
        before = _vals()
        JM.reset()
        j2 = JM.active()
        assert _delta(before, _vals())["journal.truncated_records"] == 1
        # the torn DONE never happened: the query is recovery work
        assert j2.done_digest("a") is None
        assert [r["jid"] for r in j2.incomplete()] == ["p-1"]

    def test_maybe_torn_inert_without_rule(self):
        assert faultinj.maybe_torn("journal.append", b"abcdef") == b"abcdef"

    def test_maybe_torn_keeps_prefix(self):
        faultinj.configure({
            "seed": 1,
            "faults": {"x": {"type": "torn_write", "percent": 100,
                             "delayMs": 4}},
        })
        assert faultinj.maybe_torn("x", b"abcdefgh") == b"abcd"
        # explicit delayMs 0: tear at the midpoint
        faultinj.configure({
            "seed": 1,
            "faults": {"x": {"type": "torn_write", "percent": 100,
                             "delayMs": 0}},
        })
        assert faultinj.maybe_torn("x", b"abcdefgh") == b"abcd"
        # keep clamps to len-1: a "torn" write never lands whole
        faultinj.configure({
            "seed": 1,
            "faults": {"x": {"type": "torn_write", "percent": 100,
                             "delayMs": 999}},
        })
        assert faultinj.maybe_torn("x", b"abcdefgh") == b"abcdefg"

    def test_manifest_torn_reads_as_rot(self, tmp_path):
        import jax

        frm = tmp_path / "k-1.frm"
        frm.write_bytes(b"\x00" * 32)
        _, treedef = jax.tree_util.tree_flatten([np.arange(3)])
        faultinj.configure({
            "seed": 2,
            "faults": {"memgov.manifest": {
                "type": "torn_write", "percent": 100, "delayMs": 20}},
        })
        assert persist.write_manifest(str(frm), "k", "partition", 32, 1,
                                      treedef)
        faultinj.disable()
        before = _vals()
        assert persist.read_manifest(str(frm)) is None
        assert _delta(before, _vals())["memgov.manifest_rot"] == 1


# ---------------------------------------------------------------------------
# manifests: write/read/re-attach/orphan GC
# ---------------------------------------------------------------------------


def _dead_pid():
    p = subprocess.Popen([sys.executable, "-c", ""])
    p.wait()
    return p.pid


def _forge_manifest(frame_path, pid, key, kind, nbytes, n_leaves, treedef):
    """Hand-frame a manifest naming an arbitrary owning PID — the test
    stand-in for 'a previous process wrote this and died'."""
    import pickle

    from spark_rapids_jni_tpu.utils import integrity

    payload = pickle.dumps(
        {"key": key, "kind": kind, "nbytes": nbytes, "n_leaves": n_leaves,
         "pid": pid, "treedef": treedef},
        protocol=pickle.HIGHEST_PROTOCOL,
    )
    frame = (persist._MAGIC
             + persist._HDR.pack(len(payload), integrity.checksum(payload))
             + payload)
    with open(persist.manifest_path(str(frame_path)), "wb") as f:
        f.write(frame)


@pytest.fixture
def _isolated_tempdir(tmp_path, monkeypatch):
    """Point the default-dir sweep at an empty sandbox so stray
    /tmp/srjt-spill-* dirs from other (dead) sessions never skew the
    counters these tests assert exactly."""
    import tempfile as _tempfile

    d = tmp_path / "sweep-sandbox"
    d.mkdir()
    monkeypatch.setattr(_tempfile, "tempdir", str(d))
    return d


class TestManifests:
    def test_round_trip(self, tmp_path):
        import jax

        frm = tmp_path / "key-1.frm"
        frm.write_bytes(b"\x00" * 16)
        leaves, treedef = jax.tree_util.tree_flatten([np.arange(4)])
        assert persist.write_manifest(str(frm), "key", "partition", 16, 1,
                                      treedef)
        man = persist.read_manifest(str(frm))
        assert man["key"] == "key" and man["kind"] == "partition"
        assert man["pid"] == os.getpid() and man["n_leaves"] == 1
        persist.remove_manifest(str(frm))
        assert persist.read_manifest(str(frm)) is None

    def test_spill_writes_manifest_when_armed(self, tmp_path, monkeypatch):
        monkeypatch.setenv("SRJT_SPILL_MANIFESTS", "1")
        monkeypatch.setenv("SRJT_SPILL_DIR", str(tmp_path))
        cat = BufferCatalog()
        h = cat.register("dur.x", [np.arange(32, dtype=np.int64)],
                         kind="partition", pinned=False)
        before = _vals()
        h.spill(to_disk=True)
        assert _delta(before, _vals())["memgov.manifests_written"] == 1
        (mf,) = list(tmp_path.glob("*.mf"))
        man = persist.read_manifest(str(mf)[: -len(".mf")])
        assert man["key"] == "dur.x"
        # re-materialization consumes frame AND sidecar
        np.testing.assert_array_equal(h.get()[0], np.arange(32))
        assert list(tmp_path.glob("*.mf")) == []
        cat.close()

    def test_off_posture_writes_no_manifest(self, tmp_path, monkeypatch):
        monkeypatch.setenv("SRJT_SPILL_DIR", str(tmp_path))
        cat = BufferCatalog()
        h = cat.register("vol.x", [np.arange(8)], kind="buffer",
                         pinned=False)
        h.spill(to_disk=True)
        assert list(tmp_path.glob("*.mf")) == []
        cat.close()
        assert list(tmp_path.glob("*")) == []

    def test_reattach_dead_owner_bit_identical(self, tmp_path, monkeypatch,
                                               _isolated_tempdir):
        spill = tmp_path / "spill"
        spill.mkdir()
        monkeypatch.setenv("SRJT_SPILL_MANIFESTS", "1")
        monkeypatch.setenv("SRJT_SPILL_DIR", str(spill))
        payload = np.arange(64, dtype=np.float64) * 1.5
        cat = BufferCatalog()
        h = cat.register("ooc.q.fp.part.0", [payload], kind="partition",
                         pinned=False)
        h.spill(to_disk=True)
        (frm,) = list(spill.glob("*.frm"))
        # forge the dead previous owner: rewrite the manifest under a
        # provably-dead pid (the child exited and was reaped)
        man = persist.read_manifest(str(frm))
        _forge_manifest(frm, _dead_pid(), man["key"], man["kind"],
                        man["nbytes"], man["n_leaves"], man["treedef"])
        # drop the live entry WITHOUT unlinking (simulates the owner's
        # death): the fresh catalog must adopt from disk alone
        with cat._lock:
            cat._entries.pop("ooc.q.fp.part.0")
        before = _vals()
        cat2 = BufferCatalog()
        report = persist.startup(cat2)
        assert report["reattached"] == 1
        assert _delta(before, _vals())["memgov.reattached"] == 1
        h2 = cat2.lookup("ooc.q.fp.part.0")
        assert h2 is not None and h2.tier == "disk"
        np.testing.assert_array_equal(h2.get()[0], payload)
        cat2.close()
        cat.close()

    def test_dead_owner_buffer_kind_reclaimed(self, tmp_path, monkeypatch,
                                              _isolated_tempdir):
        import jax

        spill = tmp_path / "spill"
        spill.mkdir()
        monkeypatch.setenv("SRJT_SPILL_MANIFESTS", "1")
        monkeypatch.setenv("SRJT_SPILL_DIR", str(spill))
        frm = spill / "ws-1.frm"
        frm.write_bytes(b"\x00" * 24)
        _, treedef = jax.tree_util.tree_flatten([np.arange(2)])
        _forge_manifest(frm, _dead_pid(), "ws", "buffer", 24, 1, treedef)
        before = _vals()
        report = persist.startup(BufferCatalog())
        assert report["orphans_reclaimed"] == 1 and report["reattached"] == 0
        assert _delta(before, _vals())["memgov.orphans_reclaimed"] == 1
        assert list(spill.glob("*")) == []

    def test_live_owner_never_touched(self, tmp_path, monkeypatch,
                                      _isolated_tempdir):
        import jax

        monkeypatch.setenv("SRJT_SPILL_MANIFESTS", "1")
        monkeypatch.setenv("SRJT_SPILL_DIR", str(tmp_path))
        frm = tmp_path / "live-1.frm"
        frm.write_bytes(b"\x00" * 24)
        _, treedef = jax.tree_util.tree_flatten([np.arange(2)])
        persist.write_manifest(str(frm), "live", "partition", 24, 1, treedef)
        report = persist.startup(BufferCatalog())
        assert report["skipped_live"] == 1
        assert frm.exists()
        frm.unlink()
        persist.remove_manifest(str(frm))

    def test_unmanifested_frame_left_alone(self, tmp_path, monkeypatch,
                                           _isolated_tempdir):
        monkeypatch.setenv("SRJT_SPILL_MANIFESTS", "1")
        monkeypatch.setenv("SRJT_SPILL_DIR", str(tmp_path))
        frm = tmp_path / "mystery-1.frm"
        frm.write_bytes(b"\x00" * 8)
        report = persist.startup(BufferCatalog())
        assert report["unprovable"] == 1
        assert frm.exists()
        frm.unlink()

    def test_default_dir_sweep_reclaims_dead_pid(self, _isolated_tempdir):
        base = _isolated_tempdir
        dead = _dead_pid()
        d = base / f"srjt-spill-{dead}"
        d.mkdir()
        (d / "a-1.frm").write_bytes(b"\x00" * 8)
        (d / "a-1.frm.mf").write_bytes(b"junk")
        (d / "stray.txt").write_bytes(b"not ours")
        live = base / f"srjt-spill-{os.getpid()}"
        live.mkdir()
        (live / "b-1.frm").write_bytes(b"\x00" * 8)
        before = _vals()
        assert persist.sweep_default_dirs() == 1
        assert _delta(before, _vals())["memgov.orphans_reclaimed"] == 1
        assert not (d / "a-1.frm").exists()
        assert (d / "stray.txt").exists()  # unknown shapes never touched
        assert (live / "b-1.frm").exists()  # own dir never touched
        (live / "b-1.frm").unlink()


# ---------------------------------------------------------------------------
# scheduler integration: journaled lifecycle, idempotency, recovery
# ---------------------------------------------------------------------------


class TestSchedulerJournal:
    def test_off_posture_no_files_no_jid(self, tmp_path):
        s = Scheduler(max_concurrent=1, name="joff")
        try:
            h = s.submit(lambda: 7, tenant="t")
            assert h.result(10) == 7
            assert h._jid is None
        finally:
            s.shutdown(drain=False, timeout_s=10)
        assert JM.active() is None

    def test_lifecycle_journaled(self, tmp_path, monkeypatch):
        monkeypatch.setenv("SRJT_JOURNAL_DIR", str(tmp_path))
        s = Scheduler(max_concurrent=1, name="jlife")
        try:
            ok = s.submit(lambda: np.arange(4), tenant="t", idempotency_key="q")
            assert np.array_equal(ok.result(10), np.arange(4))
            bad = s.submit(_boom, tenant="t")
            with pytest.raises(RuntimeError):
                bad.result(10)
        finally:
            s.shutdown(drain=False, timeout_s=10)
        JM.reset()
        st = JM.active().state
        counts = st.counts()
        assert counts.get("done") == 1 and counts.get("failed") == 1
        assert st.done_digest("q") is not None

    def test_idempotent_hit_returns_digest_answer(self, tmp_path, monkeypatch):
        monkeypatch.setenv("SRJT_JOURNAL_DIR", str(tmp_path))
        payload = np.arange(16, dtype=np.int64)
        s = Scheduler(max_concurrent=1, name="jidem")
        try:
            assert np.array_equal(
                s.submit(lambda: payload.copy(), tenant="t",
                         idempotency_key="once").result(10), payload)
        finally:
            s.shutdown(drain=False, timeout_s=10)
        JM.reset()  # the restarted coordinator
        before = _vals()
        s2 = Scheduler(max_concurrent=1, name="jidem2")
        try:
            ans = s2.submit(_boom, tenant="t",
                            idempotency_key="once").result(10)
        finally:
            s2.shutdown(drain=False, timeout_s=10)
        assert isinstance(ans, JM.DigestAnswer)
        assert ans.matches(payload) and not ans.matches(payload + 1)
        d = _delta(before, _vals())
        assert d["journal.idempotent_hits"] == 1

    def test_recover_resubmits_bit_identical(self, tmp_path, monkeypatch):
        monkeypatch.setenv("SRJT_JOURNAL_DIR", str(tmp_path))
        tabs = _tables()
        template = _mk(0)  # same structure, different literals
        oracle = P.compile_ir(_mk(40, 0.75), tabs, name="oracle")().to_pydict()
        # the pre-crash coordinator journals the submission but dies
        # before dispatching it: journal the record directly
        from spark_rapids_jni_tpu.plan.rewrites import (
            parameterized_fingerprint,
        )

        pf = parameterized_fingerprint(_mk(40, 0.75))
        j = JM.active()
        j.append_submit(_submit_rec(
            "dead-1", idem="r1", pf=pf.key,
            bindings=JM.sanitize_bindings(pf.bindings)))
        JM.reset()
        before = _vals()
        s = Scheduler(max_concurrent=1, name="jrec")
        try:
            report = JM.recover(
                s, lambda rec: (template, tabs) if rec["pf"] == pf.key
                else None)
            assert report["skipped"] == 0
            ((rec, h),) = report["resubmitted"]
            assert rec["jid"] == "dead-1"
            assert h.result(30).to_pydict() == oracle
        finally:
            s.shutdown(drain=False, timeout_s=10)
        d = _delta(before, _vals())
        assert d["journal.recovered_resubmits"] == 1
        # the resubmission itself was journaled to completion
        JM.reset()
        assert JM.active().done_digest("r1") is not None

    def test_recover_skips_unresolvable_and_opaque(self, tmp_path,
                                                   monkeypatch):
        monkeypatch.setenv("SRJT_JOURNAL_DIR", str(tmp_path))
        j = JM.active()
        j.append_submit(_submit_rec("o-1", opaque=True))
        j.append_submit(_submit_rec("o-2", pf="no-such-structure",
                                    bindings=[]))
        JM.reset()
        s = Scheduler(max_concurrent=1, name="jskip")
        try:
            report = JM.recover(s, lambda rec: None)
        finally:
            s.shutdown(drain=False, timeout_s=10)
        assert report["skipped"] == 2 and report["resubmitted"] == []

    def test_rebind_refuses_drifted_template(self):
        from spark_rapids_jni_tpu.plan.rewrites import (
            parameterized_fingerprint,
        )

        pf = parameterized_fingerprint(_mk(40))
        rec = {"pf": pf.key, "bindings": JM.sanitize_bindings(pf.bindings)}
        # a structurally-different template must refuse the rebind
        assert JM.rebind_for_record(P.Scan("fact"), rec) is None
        # binding arity drift refuses too
        assert JM.rebind_for_record(
            _mk(40), {"pf": pf.key, "bindings": []}) is None

    def test_sanitize_round_trips_value_types(self):
        pf_src = _mk(40, 0.75)
        from spark_rapids_jni_tpu.plan.rewrites import (
            fingerprint,
            parameterized_fingerprint,
        )

        pf = parameterized_fingerprint(pf_src)
        rec = {"pf": pf.key, "bindings": JM.sanitize_bindings(pf.bindings)}
        import json

        json.dumps(rec)  # journal-clean
        rebound = JM.rebind_for_record(_mk(40, 0.75), rec)
        assert rebound is not None
        assert fingerprint(rebound) == fingerprint(pf_src)


def _boom():
    raise RuntimeError("boom")


# ---------------------------------------------------------------------------
# the kill -9 acceptance: cross-process recovery, bit-identical answers
# ---------------------------------------------------------------------------

_CHILD = textwrap.dedent("""
    import os, sys, signal
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, {repo!r})
    from spark_rapids_jni_tpu import plan as P
    from spark_rapids_jni_tpu.columnar import Table
    from spark_rapids_jni_tpu.columnar.column import Column
    from spark_rapids_jni_tpu.memgov.catalog import BufferCatalog
    from spark_rapids_jni_tpu.serve.scheduler import Scheduler
    from spark_rapids_jni_tpu.serve import journal as JM
    import threading

    rows = 96
    rng = np.random.default_rng(23)
    tabs = {{"fact": Table(
        [Column.from_numpy(np.arange(rows, dtype=np.int64)),
         Column.from_numpy(rng.integers(0, 5, rows).astype(np.int64)),
         Column.from_numpy(rng.random(rows))],
        ["v", "k", "p"])}}

    def mk(cut, factor=2.0):
        return P.Aggregate(
            P.Filter(P.Scan("fact"),
                     (P.pcol("v") < P.plit(cut))
                     & (P.pcol("p") < P.plit(factor))),
            keys=("k",), aggs=(P.AggSpec("v", "sum", "s"),))

    # a durable partition checkpoint this process will never reclaim
    cat = BufferCatalog()
    ck = cat.register("ooc.child.fp.part.0",
                      [np.arange(64, dtype=np.float64) * 2.25],
                      kind="partition", pinned=False)
    ck.spill(to_disk=True)

    s = Scheduler(max_concurrent=1, name="child")
    done = s.submit(mk(40, 0.75), tabs, tenant="t", idempotency_key="done-1")
    done.result(60)
    gate = threading.Event()
    blocker = s.submit(gate.wait, 120, tenant="t")   # holds the one slot
    pending = s.submit(mk(70, 0.6), tabs, tenant="t",
                       idempotency_key="pend-1")     # journaled, queued
    open(os.path.join({outdir!r}, "ready"), "w").write("1")
    os.kill(os.getpid(), signal.SIGKILL)             # the crash
""")


class TestKillNineAcceptance:
    def test_restart_answers_journaled_queries_bit_identical(
            self, tmp_path, monkeypatch, _isolated_tempdir):
        jdir = tmp_path / "journal"
        sdir = tmp_path / "spill"
        jdir.mkdir()
        sdir.mkdir()
        env = dict(
            os.environ,
            SRJT_JOURNAL_DIR=str(jdir),
            SRJT_SPILL_DIR=str(sdir),
            SRJT_SPILL_MANIFESTS="1",
            JAX_PLATFORMS="cpu",
        )
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        child = subprocess.Popen(
            [sys.executable, "-c",
             _CHILD.format(repo=repo, outdir=str(tmp_path))],
            env=env, cwd=repo,
        )
        child.wait(timeout=300)
        assert child.returncode == -signal.SIGKILL
        assert (tmp_path / "ready").exists(), "child died before the kill"

        # -- the restarted coordinator --
        monkeypatch.setenv("SRJT_JOURNAL_DIR", str(jdir))
        monkeypatch.setenv("SRJT_SPILL_DIR", str(sdir))
        monkeypatch.setenv("SRJT_SPILL_MANIFESTS", "1")
        tabs = _tables()
        oracle_done = P.compile_ir(
            _mk(40, 0.75), tabs, name="od")().to_pydict()
        oracle_pend = P.compile_ir(
            _mk(70, 0.6), tabs, name="op")().to_pydict()

        before = _vals()
        JM.reset()
        jrn = JM.active()
        assert jrn is not None
        d = _delta(before, _vals())
        assert d["journal.replays"] == 1 and d["journal.replayed_records"] > 0

        # DONE work is never re-executed: the idempotency key answers
        # by the journaled digest, and it matches the oracle's bits
        hit = jrn.done_digest("done-1")
        assert hit is not None
        _, digest = hit
        oracle_result = P.compile_ir(_mk(40, 0.75), tabs, name="od2")()
        assert JM.result_digest(oracle_result) == digest
        assert oracle_result.to_pydict() == oracle_done

        # the dead child's durable checkpoint re-attaches; its blocked
        # lambda (unresolvable) skips; its pending plan resubmits and
        # answers bit-identically
        cat = BufferCatalog()
        report = persist.startup(cat)
        assert report["reattached"] == 1
        h = cat.lookup("ooc.child.fp.part.0")
        np.testing.assert_array_equal(
            h.get()[0], np.arange(64, dtype=np.float64) * 2.25)
        cat.close()

        template = _mk(0)
        s = Scheduler(max_concurrent=1, name="recovered")
        try:
            rep = JM.recover(
                s, lambda rec: (template, tabs) if rec.get("pf") else None)
            by_idem = {rec.get("idem"): h for rec, h in rep["resubmitted"]}
            assert "pend-1" in by_idem
            assert by_idem["pend-1"].result(60).to_pydict() == oracle_pend
        finally:
            s.shutdown(drain=False, timeout_s=30)
        # the blocker lambda journaled opaque: skipped, never invented
        assert rep["skipped"] >= 1
        d2 = _delta(before, _vals())
        assert d2["journal.recovered_resubmits"] >= 1
        assert d2["memgov.reattached"] == 1


# ---------------------------------------------------------------------------
# durable OOC checkpoints ride the knob
# ---------------------------------------------------------------------------


class TestDurableCheckpointKnob:
    def test_stats_sections_present(self):
        from spark_rapids_jni_tpu import runtime

        rep = runtime.stats_report()
        assert "durability" in rep
        assert set(rep["durability"]) == {"journal", "persist"}
        stage = metrics.stage_report("t")
        assert "partition_resumes" in stage["durability"]

    def test_memgov_catalog_factory_runs_startup(self, tmp_path, monkeypatch,
                                                 _isolated_tempdir):
        import jax

        spill = tmp_path / "spill"
        spill.mkdir()
        monkeypatch.setenv("SRJT_SPILL_MANIFESTS", "1")
        monkeypatch.setenv("SRJT_SPILL_DIR", str(spill))
        frm = spill / "seed-1.frm"
        frm.write_bytes(b"\x00" * 8)
        _, treedef = jax.tree_util.tree_flatten([np.arange(1)])
        _forge_manifest(frm, _dead_pid(), "seed", "buffer", 8, 1, treedef)
        memgov.reset()
        before = _vals()
        memgov.catalog()  # the factory hook sweeps on construction
        assert _delta(before, _vals())["memgov.orphans_reclaimed"] == 1
        assert not frm.exists()
        memgov.reset()
