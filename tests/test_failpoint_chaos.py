"""Failpoint-driven chaos battery (docs/fault-injection.md): proves the
resilience wiring end to end with DETERMINISTIC fault schedules — no
sleeps-as-sync, no real network flakes.

Scenarios:
  1. mid-backup transport death (`backup.file.stream=drop@nth`) → the
     job-level retry re-runs the pump and the retried snapshot verifies
     bit-identical to the source tree, incrementally (chunks committed
     by the failed attempt dedup on the re-run);
  2. sidecar outage at stream open (`sidecar.call=drop`) → the breaker
     opens and ResilientSidecarFactory degrades to the CPU chunker,
     producing a snapshot bit-identical to a pure-CPU run; a sidecar
     dying MID-stream fails that attempt (never a mid-stream chunker
     swap — cut-point stability) and the retry degrades cleanly;
  3. store insert faults after partial progress
     (`pbsstore.chunk.insert=raise@after=N`) → the per-target breaker
     opens, the failure is clean: no published snapshot, no `.tmp`
     debris, every chunk on disk still digest-verifies.

The agentfs transport is a local duck-type (no TLS — the layers under
test are the pump, writer, store, and resilience wrap; transport auth
is tests/test_arpc.py's job, and the failpoints fire in the REAL
production code paths either way)."""

import asyncio
import glob
import os

import numpy as np
import pytest

from agentfs_fakes import OpenReadViaCalls
from pbs_plus_tpu.agent.agentfs import _entry_map
from pbs_plus_tpu.chunker import ChunkerParams
from pbs_plus_tpu.pxar.backupproxy import LocalStore
from pbs_plus_tpu.pxar.transfer import SplitReader
from pbs_plus_tpu.server import backup_job as bj
from pbs_plus_tpu.server.backup_job import RemoteTreeBackup
from pbs_plus_tpu.utils import failpoints
from pbs_plus_tpu.utils.failpoints import FailpointError
from pbs_plus_tpu.utils.resilience import (
    CircuitBreaker, CircuitOpenError, with_retry,
)

P = ChunkerParams(avg_size=4 << 10)


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoints.disarm_all()
    yield
    failpoints.disarm_all()


class LocalAgentFS(OpenReadViaCalls):
    """AgentFSClient duck-type over a local directory."""

    def __init__(self, root: str):
        self.root = str(root)
        self._handles: dict[int, object] = {}
        self._next = 1

    def _p(self, rel: str) -> str:
        return os.path.join(self.root, rel) if rel else self.root

    async def attr(self, rel: str) -> dict:
        return _entry_map(os.path.basename(rel), os.lstat(self._p(rel)))

    async def read_dir(self, rel: str) -> list[dict]:
        base = self._p(rel)
        out = []
        for name in sorted(os.listdir(base)):
            out.append(_entry_map(name, os.lstat(os.path.join(base, name))))
        return out

    async def open(self, rel: str) -> int:
        h, self._next = self._next, self._next + 1
        self._handles[h] = open(self._p(rel), "rb")
        return h

    async def read_at(self, handle: int, off: int, n: int) -> bytes:
        f = self._handles[handle]
        f.seek(off)
        return f.read(n)

    async def close(self, handle: int) -> None:
        self._handles.pop(handle).close()


def _make_tree(root, *, files=6, size=40_000, seed=3) -> dict[str, bytes]:
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


async def _agent_backup_once(store: LocalStore, src: str, counter: dict,
                             pipeline_workers: int = 0):
    """One attempt of the agent-pump backup — the run_backup_job data
    plane minus the TLS session plumbing; session abort on any failure
    (exactly backup_job.run_backup_job's discipline)."""
    counter["n"] += 1
    loop = asyncio.get_running_loop()
    session = await loop.run_in_executor(
        None, lambda: store.start_session(
            backup_type="host", backup_id="chaos",
            pipeline_workers=pipeline_workers))
    try:
        pump = RemoteTreeBackup(LocalAgentFS(src), session)
        res = await pump.run()
        res.manifest = await loop.run_in_executor(
            None, session.finish, {"job": "chaos"})
        res.snapshot = str(session.ref)
        return res, session.ref
    except BaseException:
        session.abort()
        raise


# ---------------------------------------------------------- scenario 1


def test_mid_backup_disconnect_job_retries_and_verifies(tmp_path,
                                                        monkeypatch):
    """Transport dies mid-stream on the Nth block read → attempt 1 fails
    (ConnectionError is fatal to the pump, not a per-file warning),
    attempt 2 completes and the snapshot verifies bit-identical.  The
    re-run is incremental: every chunk the failed attempt committed
    dedups as `known` on the retry."""
    monkeypatch.setattr(bj, "READ_BLOCK", 16_384)   # many reads per file
    src = tmp_path / "src"
    content = _make_tree(src)
    store = LocalStore(str(tmp_path / "ds"), P)
    breaker = CircuitBreaker(failure_threshold=5, reset_timeout_s=60.0,
                             name="agent:chaos")
    attempts = {"n": 0}

    async def main():
        with failpoints.armed("backup.file.stream", "drop", nth=7) as fp:
            res, ref = await with_retry(
                lambda: breaker.call(
                    lambda: _agent_backup_once(store, str(src), attempts)),
                attempts=2, base_delay_s=0.05, name="backup:chaos")
        assert attempts["n"] == 2, "first attempt must fail, second run"
        assert fp.fires == 1
        _verify_against_source(store, ref, content)
        # incremental by construction: chunks already in the store from
        # attempt 1 re-occur identically in attempt 2 (same content,
        # deterministic cuts) and count as dedup hits, never re-written
        stats = res.manifest["stats"]
        assert stats["new_chunks"] + stats["known_chunks"] > 0
        assert breaker.state == "closed"
        return res

    res = asyncio.run(main())
    # the drop fired mid-file: attempt 1 recorded it as that file's error
    # before failing the job (visible in the retried result's log trail
    # only via attempt 1; the final result is clean)
    assert res.errors == []


def test_mid_backup_disconnect_without_retry_is_hard_error(tmp_path,
                                                           monkeypatch):
    """attempts=1 (the ServerConfig default): the same fault is a hard,
    promptly-surfaced job failure — retry is an operator opt-in."""
    monkeypatch.setattr(bj, "READ_BLOCK", 16_384)
    src = tmp_path / "src"
    _make_tree(src)
    store = LocalStore(str(tmp_path / "ds"), P)
    attempts = {"n": 0}

    async def main():
        with failpoints.armed("backup.file.stream", "drop", nth=3):
            with pytest.raises(ConnectionResetError):
                await _agent_backup_once(store, str(src), attempts)
        assert attempts["n"] == 1

    asyncio.run(main())
    assert store.datastore.list_snapshots() == []   # nothing published


# ---------------------------------------------------------- scenario 2


def _write_reference_cpu_snapshot(tmp_path):
    """Pure-CPU snapshot of the same logical tree — the bit-identity
    yardstick for the degraded runs."""
    store = LocalStore(str(tmp_path / "ds-cpu"), P)
    ref = asyncio.run(_agent_backup_once(store, str(tmp_path / "src"),
                                         {"n": 0}))[1]
    r = store.open_snapshot(ref)
    return (list(r.meta_index.records()), list(r.payload_index.records()))


def test_sidecar_outage_at_stream_open_degrades_bit_identical(tmp_path):
    """Sidecar unreachable when the session opens: the breaker opens
    after the probe's bounded retries, every stream binds the CPU
    chunker, and the snapshot is BIT-identical (cuts + digests) to a
    pure-CPU run.  No gRPC dial ever happens (the failpoint fires
    first), so the scenario is deterministic and offline."""
    from pbs_plus_tpu.sidecar.client import ResilientSidecarFactory

    src = tmp_path / "src"
    content = _make_tree(src)
    want = _write_reference_cpu_snapshot(tmp_path)

    factory = ResilientSidecarFactory("127.0.0.1:1")
    store = LocalStore(str(tmp_path / "ds-sc"), P, chunker_factory=factory)

    async def main():
        with failpoints.armed("sidecar.call", "drop") as fp:
            res, ref = await _agent_backup_once(store, str(src), {"n": 0})
        # drop is transport-class: the probe retried (bounded), the
        # breaker opened, later streams short-circuited to CPU
        assert fp.fires >= 3
        assert factory.client.breaker.state == "open"
        return ref

    ref = asyncio.run(main())
    _verify_against_source(store, ref, content)
    r = store.open_snapshot(ref)
    got = (list(r.meta_index.records()), list(r.payload_index.records()))
    assert got == want, "degraded snapshot must be bit-identical to CPU"


def test_sidecar_death_mid_stream_fails_attempt_then_degrades(tmp_path):
    """A sidecar dying MID-stream must fail that attempt — never swap
    chunkers mid-stream (a swap after a partial carry moves every later
    cut and silently destroys dedup).  The retry reopens the session,
    finds the breaker failing, and degrades the whole rerun to CPU:
    bit-identical output again."""
    pytest.importorskip("grpc")
    from pbs_plus_tpu.sidecar import serve_sidecar
    from pbs_plus_tpu.sidecar.client import ResilientSidecarFactory

    src = tmp_path / "src"
    content = _make_tree(src)
    want = _write_reference_cpu_snapshot(tmp_path)

    server, port, _svc = serve_sidecar(params=P, use_tpu=False)
    try:
        factory = ResilientSidecarFactory(f"127.0.0.1:{port}")
        store = LocalStore(str(tmp_path / "ds-mid"), P,
                           chunker_factory=factory)
        attempts = {"n": 0}

        async def main():
            # hit arithmetic for after=4: binding the session costs 4
            # sidecar.call hits (meta: stats probe + one-time params
            # check; payload: stats probe, params check cached), the
            # first meta feed is hit 5 — so the fault lands on a LIVE
            # mid-stream Chunk call, which is never retried (stateful)
            with failpoints.armed("sidecar.call", "drop", after=4):
                return await with_retry(
                    lambda: _agent_backup_once(store, str(src), attempts),
                    attempts=2, base_delay_s=0.05, name="backup:sc-mid")

        res, ref = asyncio.run(main())
        assert attempts["n"] == 2, \
            "attempt 1 must die mid-stream, attempt 2 degrade to CPU"
        assert factory.client.breaker.state == "open"
        _verify_against_source(store, ref, content)
        r = store.open_snapshot(ref)
        got = (list(r.meta_index.records()),
               list(r.payload_index.records()))
        assert got == want
    finally:
        server.stop(grace=None)


def test_sidecar_healthy_is_used_not_degraded(tmp_path):
    """Control case: with a live sidecar and nothing armed, the factory
    binds the sidecar chunker (no silent always-CPU regression)."""
    pytest.importorskip("grpc")
    from pbs_plus_tpu.sidecar import serve_sidecar
    from pbs_plus_tpu.sidecar.client import (
        ResilientSidecarFactory, SidecarChunker,
    )

    server, port, svc = serve_sidecar(params=P, use_tpu=False)
    try:
        factory = ResilientSidecarFactory(f"127.0.0.1:{port}")
        bound = factory.bind_stream(P)
        assert isinstance(bound(P), SidecarChunker)
        assert factory.client.breaker.state == "closed"
    finally:
        server.stop(grace=None)


# ---------------------------------------------------------- scenario 3


@pytest.mark.parametrize("workers", [0, 2])
def test_store_insert_fault_opens_breaker_fails_clean(tmp_path, workers):
    """Chunk inserts start failing after 2 commits (ENOSPC class): both
    attempts fail, the per-target breaker opens (so the next enqueue
    fails fast without touching the agent), and the failure is CLEAN —
    no published snapshot, no .tmp debris, and every chunk that did
    land still digest-verifies.  Runs sequential (workers=0) and
    pipelined (workers=2: the committer must drain, release
    backpressure permits, and reap its pool on the way down)."""
    src = tmp_path / "src"
    _make_tree(src)
    store = LocalStore(str(tmp_path / "ds"), P)
    breaker = CircuitBreaker(failure_threshold=2, reset_timeout_s=60.0,
                             name="agent:chaos")
    attempts = {"n": 0}

    async def run_guarded():
        return await with_retry(
            lambda: breaker.call(
                lambda: _agent_backup_once(store, str(src), attempts,
                                           pipeline_workers=workers)),
            attempts=2, base_delay_s=0.05, name="backup:chaos")

    async def main():
        with failpoints.armed("pbsstore.chunk.insert", "raise",
                              after=2) as fp:
            with pytest.raises(FailpointError):
                await run_guarded()
            assert attempts["n"] == 2 and fp.fires >= 2
            assert breaker.state == "open"
            # one dead target cannot burn the retry budget: the next
            # run short-circuits before any agent/store work
            with pytest.raises(CircuitOpenError):
                await run_guarded()
            assert attempts["n"] == 2

    asyncio.run(main())
    # clean failure: nothing published, no partial-chunk debris,
    # every committed chunk intact (content-addressed, GC-able)
    assert store.datastore.list_snapshots() == []
    base = store.datastore.chunks.base
    leftovers = [p for p in glob.glob(os.path.join(base, "**", "*"),
                                      recursive=True)
                 if os.path.isfile(p) and ".tmp" in os.path.basename(p)]
    assert leftovers == []
    committed = list(store.datastore.chunks.iter_digests())
    assert len(committed) == 2              # exactly the pre-fault inserts
    for d in committed:
        store.datastore.chunks.get(d)       # raises if corrupt
    # staging dirs were aborted away
    stray = [p for p in glob.glob(os.path.join(
        str(tmp_path / "ds"), "**", "*.tmp.*"), recursive=True)]
    assert stray == []


def test_metrics_snapshot_exposes_failpoint_counters():
    """The /metrics contract: armed sites and cumulative hit/fire
    counters are visible (server/metrics.py renders exactly this)."""
    failpoints.reset_counters()
    with failpoints.armed("pipeline.hash", "delay", arg=0.0):
        failpoints.hit("pipeline.hash")
        snap = failpoints.snapshot()
        assert snap["armed"] == {"pipeline.hash": "delay"}
    snap = failpoints.snapshot()
    assert snap["armed"] == {}
    assert snap["counters"]["pipeline.hash"]["hits"] == 1
