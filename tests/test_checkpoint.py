"""Checkpointed resumable backups (server/checkpoint.py +
docs/data-plane.md "Checkpointed resumable backups"): crash anywhere,
resume from the last durable checkpoint.

The chaos core: the job is killed at the Nth `pbsstore.chunk.insert`
fire (deterministic — cuts and digests are fixed for a fixed seed), the
resumed run completes, the restored tree is bit-identical to the
source, AND agent bytes re-read are strictly less than half the source
size for a ~50% crash point — proving the resume skipped the committed
prefix instead of re-reading it.  Runs for both the sequential
(`pipeline_workers=0`) and the pipelined (`>=2`) writer.

The agentfs transport is the same local duck-type as
tests/test_failpoint_chaos.py — the layers under test are the walker
fast-skip, the writer splice, the checkpoint persistence, and GC
interplay, all in the real production code paths."""

import asyncio
import json
import os
import time

import numpy as np
import pytest

from agentfs_fakes import OpenReadViaCalls
from pbs_plus_tpu.agent.agentfs import _entry_map
from pbs_plus_tpu.chunker import ChunkerParams
from pbs_plus_tpu.pxar.backupproxy import LocalStore
from pbs_plus_tpu.pxar.walker import backup_tree
from pbs_plus_tpu.server import checkpoint
from pbs_plus_tpu.server.backup_job import RemoteTreeBackup
from pbs_plus_tpu.utils import failpoints
from pbs_plus_tpu.utils.failpoints import FailpointError

P = ChunkerParams(avg_size=4 << 10)


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoints.disarm_all()
    yield
    failpoints.disarm_all()


class CountingAgentFS(OpenReadViaCalls):
    """AgentFSClient duck-type over a local directory that COUNTS the
    bytes handed out by read_at — the 'agent bytes read' meter the
    resume bound is asserted against."""

    def __init__(self, root: str):
        self.root = str(root)
        self._handles: dict[int, object] = {}
        self._next = 1
        self.bytes_read = 0

    def _p(self, rel: str) -> str:
        return os.path.join(self.root, rel) if rel else self.root

    async def attr(self, rel: str) -> dict:
        return _entry_map(os.path.basename(rel), os.lstat(self._p(rel)))

    async def read_dir(self, rel: str) -> list[dict]:
        base = self._p(rel)
        return [_entry_map(name, os.lstat(os.path.join(base, name)))
                for name in sorted(os.listdir(base))]

    async def open(self, rel: str) -> int:
        h, self._next = self._next, self._next + 1
        self._handles[h] = open(self._p(rel), "rb")
        return h

    async def read_at(self, handle: int, off: int, n: int) -> bytes:
        f = self._handles[handle]
        f.seek(off)
        out = f.read(n)
        self.bytes_read += len(out)
        return out

    async def close(self, handle: int) -> None:
        self._handles.pop(handle).close()


def _make_tree(root, *, files=10, size=40_000, seed=3) -> dict[str, bytes]:
    rng = np.random.default_rng(seed)
    (root / "sub").mkdir(parents=True)
    content = {}
    for i in range(files):
        rel = f"sub/f{i:02d}.bin"
        data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        (root / rel).write_bytes(data)
        content[rel] = data
    return content


def _verify_against_source(store: LocalStore, ref, content: dict) -> None:
    r = store.open_snapshot(ref)
    for rel, want in content.items():
        e = r.lookup(rel)
        assert e is not None, f"missing {rel}"
        assert r.read_file(e) == want, f"content mismatch for {rel}"


async def _pump_backup(store: LocalStore, fs, *, interval="",
                       pipeline_workers=0, backup_id="ck"):
    """One attempt of the agent-pump backup with checkpointing/resume
    wired exactly as server/backup_job.run_backup_job wires it."""
    loop = asyncio.get_running_loop()
    resume_ctx = await loop.run_in_executor(
        None, lambda: checkpoint.open_resume(
            store, backup_type="host", backup_id=backup_id))
    kw = {"previous_reader": resume_ctx[0]} if resume_ctx else {}
    session = await loop.run_in_executor(
        None, lambda: store.start_session(
            backup_type="host", backup_id=backup_id,
            pipeline_workers=pipeline_workers, **kw))
    try:
        if resume_ctx is not None:
            session.resume_plan = resume_ctx[1]
        checkpoint.attach(session, interval)
        pump = RemoteTreeBackup(fs, session)
        res = await pump.run()
        extra = {"job": backup_id}
        if resume_ctx is not None:
            extra["resume"] = resume_ctx[1].summary()
        res.manifest = await loop.run_in_executor(
            None, session.finish, extra)
        await loop.run_in_executor(None, lambda: checkpoint.clear(
            store.datastore, "host", backup_id))
        res.snapshot = str(session.ref)
        return res, session.ref
    except BaseException:
        session.abort()
        raise


def _count_inserts(tmp_path, src, content, *, interval="2c") -> int:
    """Probe run in a scratch store WITH the same checkpoint interval as
    the chaos run (checkpoints force extra cuts, so an uncheckpointed
    probe would undercount): total pbsstore.chunk.insert fires for this
    tree, deterministic for a fixed seed/params."""
    probe = LocalStore(str(tmp_path / "ds-probe"), P)
    with failpoints.armed("pbsstore.chunk.insert", "delay", arg=0.0) as fp:
        res, ref = asyncio.run(_pump_backup(
            probe, CountingAgentFS(str(src)), backup_id="probe",
            interval=interval))
        _verify_against_source(probe, ref, content)
        return fp.hits


def _probe_crash_point(tmp_path, src, *, files, interval="2c",
                       name="probe-cp") -> tuple[int, int]:
    """(total_insert_hits, crash_at): the hit index in the MIDDLE of
    file ``files//2 + 1``'s stream, derived structurally from a probe
    run that marks the hit counter at every completed entry — never a
    magic factor.  Crashing there means the last durable checkpoint
    covers > half the source, so the resume's re-read (the in-flight
    file + the tail) is strictly under half."""
    probe = LocalStore(str(tmp_path / f"ds-{name}"), P)
    marks: list[int] = []
    with failpoints.armed("pbsstore.chunk.insert", "delay", arg=0.0) as fp:
        async def main():
            loop = asyncio.get_running_loop()
            session = await loop.run_in_executor(
                None, lambda: probe.start_session(
                    backup_type="host", backup_id="p"))
            try:
                checkpoint.attach(session, interval)
                inner = session.writer.checkpoint_hook

                def hook(w, _inner=inner):
                    marks.append(fp.hits)
                    _inner(w)
                session.writer.checkpoint_hook = hook
                pump = RemoteTreeBackup(CountingAgentFS(str(src)), session)
                await pump.run()
                await loop.run_in_executor(None, session.finish)
            except BaseException:
                session.abort()
                raise
        asyncio.run(main())
        total = fp.hits
    checkpoint.clear(probe.datastore, "host", "p")
    # entries in DFS order: root, sub, f00.. — file i completes at
    # marks[2 + i]; the midpoint between file k-1's and file k's
    # completion lands inside file k's stream
    k = files // 2 + 1
    return total, (marks[2 + k - 1] + marks[2 + k]) // 2


# ------------------------------------------------------- the chaos core


@pytest.mark.parametrize("workers", [0, 2])
def test_crash_at_nth_insert_resume_bit_identical(tmp_path, workers):
    """Kill the job at the Nth store insert (~50% point), resume, and
    prove: (1) the restored tree is bit-identical to the source,
    (2) agent bytes re-read by the resumed run are STRICTLY less than
    half the source size, (3) the checkpoint skip/ref accounting shows
    the prefix was spliced, not streamed — sequential AND pipelined."""
    src = tmp_path / "src"
    content = _make_tree(src)
    total_bytes = sum(len(v) for v in content.values())
    # checkpoint every 2 committed payload chunks — the hook fires at
    # entry boundaries, so this is effectively one checkpoint per file
    interval = "2c"
    total_inserts, crash_at = _probe_crash_point(
        tmp_path, src, files=len(content), interval=interval)
    assert total_inserts > 20, "tree too small for a meaningful crash point"

    store = LocalStore(str(tmp_path / "ds"), P)

    fs1 = CountingAgentFS(str(src))
    with failpoints.armed("pbsstore.chunk.insert", "raise", nth=crash_at):
        with pytest.raises(FailpointError):
            asyncio.run(_pump_backup(store, fs1, interval=interval,
                                     pipeline_workers=workers))
    # the crash left no published snapshot, but a durable checkpoint
    assert store.datastore.list_snapshots() == []
    ck = checkpoint.load_latest(store.datastore, "host", "ck", params=P)
    assert ck is not None, "no checkpoint survived the crash"
    assert ck.state["hwm"], "checkpoint has no high-water mark"

    # resume: disarmed, fresh agent connection, same tree
    fs2 = CountingAgentFS(str(src))
    res, ref = asyncio.run(_pump_backup(store, fs2, interval=interval,
                                        pipeline_workers=workers))
    _verify_against_source(store, ref, content)

    # the bound: the resumed run re-read strictly less than half the
    # source from the agent (the committed prefix was spliced by ref)
    assert fs2.bytes_read < total_bytes / 2, (
        f"resume re-read {fs2.bytes_read} of {total_bytes} bytes "
        f"(crash at insert {crash_at}/{total_inserts})")
    summary = res.manifest["resume"]
    assert summary["files_skipped"] > 0
    assert summary["bytes_skipped"] > total_bytes / 2
    assert summary["bytes_reread"] == fs2.bytes_read
    # splice accounting: reused chunks show up as refs, not new inserts
    assert res.manifest["stats"]["ref_chunks"] > 0
    assert res.manifest["stats"]["bytes_reffed"] > 0
    # publish cleared the group's checkpoints
    assert checkpoint.load_latest(store.datastore, "host", "ck") is None


@pytest.mark.parametrize("workers", [0, 2])
def test_resumed_snapshot_matches_uncrashed_content(tmp_path, workers):
    """The resumed snapshot's decoded tree (entries + content digests)
    equals an uncrashed backup's of the same source — resume changes
    chunk layout at the splice seams, never logical content."""
    src = tmp_path / "src"
    content = _make_tree(src, files=5)
    plain = LocalStore(str(tmp_path / "ds-plain"), P)
    _, ref_plain = asyncio.run(_pump_backup(
        plain, CountingAgentFS(str(src)), backup_id="ck"))

    total_inserts = _count_inserts(tmp_path, src, content)
    store = LocalStore(str(tmp_path / "ds"), P)
    with failpoints.armed("pbsstore.chunk.insert", "raise",
                          nth=max(4, total_inserts // 2)):
        with pytest.raises(FailpointError):
            asyncio.run(_pump_backup(store, CountingAgentFS(str(src)),
                                     interval="2c",
                                     pipeline_workers=workers))
    _, ref = asyncio.run(_pump_backup(store, CountingAgentFS(str(src)),
                                      interval="2c",
                                      pipeline_workers=workers))

    def tree(s, r):
        rd = s.open_snapshot(r)
        return [(e.path, e.kind, e.size, e.digest)
                for e in rd.entries()]

    assert tree(store, ref) == tree(plain, ref_plain)


def test_changed_files_restream_on_resume(tmp_path):
    """Stat drift between crash and resume: files whose (size, mtime_ns)
    changed must re-stream — the fast-skip only splices stat-identical
    files — and the final snapshot carries the NEW content."""
    src = tmp_path / "src"
    content = _make_tree(src)
    total_inserts = _count_inserts(tmp_path, src, content)
    store = LocalStore(str(tmp_path / "ds"), P)
    with failpoints.armed("pbsstore.chunk.insert", "raise",
                          nth=int(total_inserts * 0.7)):
        with pytest.raises(FailpointError):
            asyncio.run(_pump_backup(store, CountingAgentFS(str(src)),
                                     interval="2c"))
    # mutate the FIRST file (inside the committed prefix)
    new_data = os.urandom(50_000)
    (src / "sub/f00.bin").write_bytes(new_data)
    content["sub/f00.bin"] = new_data

    res, ref = asyncio.run(_pump_backup(store, CountingAgentFS(str(src)),
                                        interval="2c"))
    _verify_against_source(store, ref, content)
    summary = res.manifest["resume"]
    assert summary["files_skipped"] > 0          # unchanged prefix spliced
    assert summary["bytes_reread"] >= len(new_data)  # changed file streamed


def test_checkpoint_flush_fault_keeps_previous_checkpoint(tmp_path):
    """An injected fault at `backup.checkpoint.flush` (after the first
    checkpoint landed) must neither fail the backup nor corrupt the
    surviving checkpoint: the flush is atomic (tmp dir + rename), the
    failure is counted, and the previous checkpoint stays loadable."""
    src = tmp_path / "src"
    content = _make_tree(src, files=4)
    store = LocalStore(str(tmp_path / "ds"), P)
    before = checkpoint.metrics_snapshot()
    with failpoints.armed("backup.checkpoint.flush", "raise", after=1) as fp:
        res, ref = asyncio.run(_pump_backup(
            store, CountingAgentFS(str(src)), interval="2c"))
    assert fp.fires >= 1, "later flushes must have been attempted"
    _verify_against_source(store, ref, content)      # backup unharmed
    after = checkpoint.metrics_snapshot()
    assert after["write_failures"] - before["write_failures"] == fp.fires
    assert after["written"] - before["written"] == 1
    # no torn tmp dirs anywhere under the datastore
    for dirpath, dirs, _files in os.walk(str(tmp_path / "ds")):
        for d in dirs:
            assert not d.startswith(".tmp-"), f"torn dir {dirpath}/{d}"


def test_checkpoint_atomicity_crash_mid_backup_then_flush_fault(tmp_path):
    """Crash the BACKUP after checkpoint 1, with checkpoint 2's flush
    also faulted: the surviving on-disk checkpoint must be the valid
    older one (atomic replace discipline), and resume must work off it."""
    src = tmp_path / "src"
    content = _make_tree(src)
    total_inserts = _count_inserts(tmp_path, src, content)
    store = LocalStore(str(tmp_path / "ds"), P)
    with failpoints.armed("backup.checkpoint.flush", "raise", after=1):
        with failpoints.armed("pbsstore.chunk.insert", "raise",
                              nth=int(total_inserts * 0.8)):
            with pytest.raises(FailpointError):
                asyncio.run(_pump_backup(store, CountingAgentFS(str(src)),
                                         interval="2c"))
    ck = checkpoint.load_latest(store.datastore, "host", "ck", params=P)
    assert ck is not None and ck.state["seq"] == 1
    res, ref = asyncio.run(_pump_backup(store, CountingAgentFS(str(src)),
                                        interval="2c"))
    _verify_against_source(store, ref, content)
    assert res.manifest["resume"]["files_skipped"] > 0


def test_resume_source_checkpoint_protected_until_publish(tmp_path):
    """A resumed run's own checkpoints must NOT reap the checkpoint they
    are resuming from: until publish, the old checkpoint's indexes are
    the only GC protection for files the plan has not spliced yet.  A
    double-crash (crash, resume, crash again) must leave BOTH
    checkpoints on disk; the third run completes and publish clears
    everything."""
    src = tmp_path / "src"
    content = _make_tree(src)
    total_inserts, crash_at = _probe_crash_point(tmp_path, src,
                                                 files=len(content))
    store = LocalStore(str(tmp_path / "ds"), P)
    with failpoints.armed("pbsstore.chunk.insert", "raise", nth=crash_at):
        with pytest.raises(FailpointError):
            asyncio.run(_pump_backup(store, CountingAgentFS(str(src)),
                                     interval="2c"))
    first = checkpoint.load_latest(store.datastore, "host", "ck", params=P)
    assert first is not None
    first_name = os.path.basename(first.path)

    # crash the RESUMED run too, after it has written checkpoints of its
    # own (splice-phase checkpoint syncs insert ~1 meta chunk each, so
    # this nth lands in the tail's first re-streamed file)
    with failpoints.armed("pbsstore.chunk.insert", "raise", nth=12):
        with pytest.raises(FailpointError):
            asyncio.run(_pump_backup(store, CountingAgentFS(str(src)),
                                     interval="2c"))
    ckdir = os.path.dirname(first.path)
    names = sorted(n for n in os.listdir(ckdir) if n.startswith("ck-"))
    assert first_name in names, "resume reaped its own source checkpoint"
    assert len(names) >= 2, "resumed run wrote no checkpoint of its own"
    # a (cross-process) prune sweep must ALSO keep the resume source:
    # the newest checkpoint's state records resumed_from
    assert checkpoint.sweep_stale(store.datastore) == 0
    assert sorted(n for n in os.listdir(ckdir)
                  if n.startswith("ck-")) == names

    res, ref = asyncio.run(_pump_backup(store, CountingAgentFS(str(src)),
                                        interval="2c"))
    _verify_against_source(store, ref, content)
    assert not os.path.isdir(ckdir)          # publish cleared the group


def test_local_walker_resume(tmp_path):
    """The local-target path (pxar/walker.backup_tree) honors the resume
    plan too: crash, resume, bit-identical, prefix spliced."""
    src = tmp_path / "src"
    content = _make_tree(src)
    total_bytes = sum(len(v) for v in content.values())
    store = LocalStore(str(tmp_path / "ds"), P)

    def run(arm_nth=None):
        resume_ctx = checkpoint.open_resume(store, backup_type="host",
                                            backup_id="lk")
        kw = {"previous_reader": resume_ctx[0]} if resume_ctx else {}
        sess = store.start_session(backup_type="host", backup_id="lk", **kw)
        try:
            if resume_ctx:
                sess.resume_plan = resume_ctx[1]
            checkpoint.attach(sess, "2c")
            backup_tree(sess, str(src))
            man = sess.finish(
                {"resume": resume_ctx[1].summary()} if resume_ctx else None)
            checkpoint.clear(store.datastore, "host", "lk")
            return man, sess.ref
        except BaseException:
            sess.abort()
            raise

    marks: list[int] = []
    with failpoints.armed("pbsstore.chunk.insert", "delay", arg=0.0) as fp:
        probe = LocalStore(str(tmp_path / "ds-probe2"), P)
        ps = probe.start_session(backup_type="host", backup_id="lk")
        checkpoint.attach(ps, "2c")       # same forced-cut schedule
        inner = ps.writer.checkpoint_hook

        def hook(w, _inner=inner):
            marks.append(fp.hits)
            _inner(w)
        ps.writer.checkpoint_hook = hook
        backup_tree(ps, str(src))
        ps.finish()
        checkpoint.clear(probe.datastore, "host", "lk")
    k = len(content) // 2 + 1        # crash mid-file, just past half
    with failpoints.armed("pbsstore.chunk.insert", "raise",
                          nth=(marks[2 + k - 1] + marks[2 + k]) // 2):
        with pytest.raises(FailpointError):
            run()
    man, ref = run()
    _verify_against_source(store, ref, content)
    assert man["resume"]["files_skipped"] > 0
    assert man["resume"]["bytes_skipped"] > total_bytes / 2
    assert man["resume"]["bytes_reread"] < total_bytes / 2


# ------------------------------------------------- subsystem unit tests


def test_parse_interval_grammar():
    assert checkpoint.parse_interval("") == (0, 0.0)
    assert checkpoint.parse_interval("0") == (0, 0.0)
    assert checkpoint.parse_interval("256") == (256, 0.0)
    assert checkpoint.parse_interval("256c") == (256, 0.0)
    assert checkpoint.parse_interval("30s") == (0, 30.0)
    assert checkpoint.parse_interval("256c/30s") == (256, 30.0)
    assert checkpoint.parse_interval("128/2.5s") == (128, 2.5)
    with pytest.raises(ValueError):
        checkpoint.parse_interval("banana")


def test_attach_disabled_and_pbs_gated(tmp_path):
    store = LocalStore(str(tmp_path / "ds"), P)
    sess = store.start_session(backup_type="host", backup_id="g")
    try:
        assert checkpoint.attach(sess, "") is None
        assert sess.writer.checkpoint_hook is None
        # malformed interval is loud but NEVER fatal (optimization only)
        assert checkpoint.attach(sess, "5m") is None
        assert sess.writer.checkpoint_hook is None
        ck = checkpoint.attach(sess, "4c/10s")
        assert ck is not None and sess.writer.checkpoint_hook is ck

        class NoDatastore:
            datastore = None
        sess2 = store.start_session(backup_type="host", backup_id="g2")
        try:
            sess2.store = NoDatastore()      # PBS-shaped store: gated off
            assert checkpoint.attach(sess2, "4c") is None
        finally:
            sess2.abort()
    finally:
        sess.abort()


def test_checkpoint_params_mismatch_invalidates(tmp_path):
    """A chunker-params change between crash and resume must invalidate
    the checkpoint (cuts would not line up), falling back to a full
    run — exactly the LocalStore previous-snapshot guard."""
    src = tmp_path / "src"
    _make_tree(src, files=3)
    store = LocalStore(str(tmp_path / "ds"), P)
    sess = store.start_session(backup_type="host", backup_id="pm")
    ck = checkpoint.Checkpointer(sess, every_chunks=1)
    try:
        backup_tree(sess, str(src))
        ck.flush(sess.writer)
    finally:
        sess.abort()
    assert checkpoint.load_latest(store.datastore, "host", "pm",
                                  params=P) is not None
    other = ChunkerParams(avg_size=8 << 10)
    assert checkpoint.load_latest(store.datastore, "host", "pm",
                                  params=other) is None
    store2 = LocalStore(str(tmp_path / "ds"), other)
    assert checkpoint.open_resume(store2, backup_type="host",
                                  backup_id="pm") is None


def test_checkpoint_missing_chunk_invalidates(tmp_path):
    """A checkpoint whose referenced chunk vanished (GC race, disk loss)
    must be rejected as a whole — a resume must never splice a hole."""
    src = tmp_path / "src"
    _make_tree(src, files=3)
    store = LocalStore(str(tmp_path / "ds"), P)
    sess = store.start_session(backup_type="host", backup_id="mc")
    ck = checkpoint.Checkpointer(sess, every_chunks=1)
    try:
        backup_tree(sess, str(src))
        ck.flush(sess.writer)
    finally:
        sess.abort()
    loaded = checkpoint.load_latest(store.datastore, "host", "mc", params=P)
    assert loaded is not None
    victim = loaded.pidx.digest(0)
    os.unlink(store.datastore.chunks._path(victim))
    assert checkpoint.load_latest(store.datastore, "host", "mc",
                                  params=P) is None


def test_superseding_snapshot_disables_resume(tmp_path):
    """A checkpoint older than the group's newest published snapshot is
    ignored by open_resume (dedup vs that snapshot is strictly better)
    and reaped by sweep_stale."""
    src = tmp_path / "src"
    _make_tree(src, files=3)
    store = LocalStore(str(tmp_path / "ds"), P)
    sess = store.start_session(backup_type="host", backup_id="sp")
    ck = checkpoint.Checkpointer(sess, every_chunks=1)
    try:
        backup_tree(sess, str(src))
        ck.flush(sess.writer)
    finally:
        sess.abort()
    # publish a full snapshot AFTER the checkpoint
    sess2 = store.start_session(backup_type="host", backup_id="sp")
    backup_tree(sess2, str(src))
    sess2.finish()
    assert checkpoint.open_resume(store, backup_type="host",
                                  backup_id="sp") is None
    removed = checkpoint.sweep_stale(store.datastore)
    assert removed == 1
    assert checkpoint.load_latest(store.datastore, "host", "sp") is None


def test_sweep_stale_age_and_torn_tmp(tmp_path):
    src = tmp_path / "src"
    _make_tree(src, files=2)
    store = LocalStore(str(tmp_path / "ds"), P)
    sess = store.start_session(backup_type="host", backup_id="ag")
    ck = checkpoint.Checkpointer(sess, every_chunks=1)
    try:
        backup_tree(sess, str(src))
        ck.flush(sess.writer)
        ckdir = checkpoint.group_ckpt_dir(store.datastore, sess.ref)
    finally:
        sess.abort()
    tmp_dir = os.path.join(ckdir, ".tmp-00000099.1234")
    os.makedirs(tmp_dir)
    # a FRESH .tmp dir may be a live flush racing the sweep — kept
    assert checkpoint.sweep_stale(store.datastore) == 0
    assert os.path.isdir(tmp_dir)
    # aged past the TTL it is a torn write — reaped
    old_t = time.time() - 2 * 3600
    os.utime(tmp_dir, (old_t, old_t))
    assert checkpoint.sweep_stale(store.datastore) == 1
    assert not os.path.isdir(tmp_dir)
    assert checkpoint.load_latest(store.datastore, "host", "ag") is not None
    # aged out
    state_p = os.path.join(ckdir, "ck-00000001", checkpoint.STATE_JSON)
    with open(state_p) as f:
        state = json.load(f)
    state["created_unix"] -= 10 * 24 * 3600
    with open(state_p, "w") as f:
        json.dump(state, f)
    # an aged-out checkpoint is refused at LOAD time too (its GC
    # protection may already be gone), not just reaped by the sweep
    assert checkpoint.load_latest(store.datastore, "host", "ag") is None
    assert checkpoint.sweep_stale(store.datastore) == 1
    assert checkpoint.load_latest(store.datastore, "host", "ag") is None
    assert not os.path.isdir(ckdir)          # empty dir reaped


def test_ckpt_dir_invisible_to_snapshot_listing(tmp_path):
    """The hidden .ckpt dir must never surface as a snapshot."""
    src = tmp_path / "src"
    _make_tree(src, files=2)
    store = LocalStore(str(tmp_path / "ds"), P)
    sess = store.start_session(backup_type="host", backup_id="inv")
    ck = checkpoint.Checkpointer(sess, every_chunks=1)
    try:
        backup_tree(sess, str(src))
        ck.flush(sess.writer)
    finally:
        sess.abort()
    assert store.datastore.list_snapshots() == []
    assert store.datastore.last_snapshot("host", "inv") is None


def test_metrics_render_checkpoint_counters():
    """server/metrics.py renders the checkpoint counter family (no
    server needed: the module-global registry is the contract)."""
    snap = checkpoint.metrics_snapshot()
    for key in ("written", "resumes", "files_skipped", "bytes_skipped",
                "files_reread", "bytes_reread", "write_failures", "swept"):
        assert key in snap
