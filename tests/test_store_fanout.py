"""The store stage's fan-out (pxar/storepool.py, ``_ChunkedStream.
_store_fanned``): a hash batch's novel chunks stored by the writer and
the store pool's helpers at once, against the sequential stage it
replaces where the store declares ``concurrent_insert``.

The sequential stage is had by leaving the flush no helper
(``transfer.store_helpers`` patched to 0, here only); the fan-out is
made to engage on any host by patching it to 3."""

import hashlib
import os
import sys
import threading
import time
from collections import Counter
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from benchmark.harness.loadgen import CommitLog
from pbs_plus_tpu.chunker import ChunkerParams
from pbs_plus_tpu.pxar import storepool, transfer
from pbs_plus_tpu.pxar.datastore import ChunkStore
from pbs_plus_tpu.pxar.ingestbackend import NO_CAPABILITIES
from pbs_plus_tpu.utils import failpoints, trace

SEQUENTIAL, FANNED = 0, 3


class _FixedCuts:
    """A chunker that cuts every ``avg_size`` bytes of a run: the
    stage under test is the store, and fixed cuts put the same block
    twice into one hash batch exactly where the test wants it."""

    def __init__(self, params: ChunkerParams):
        self.size = params.avg_size
        self.fed = self.cut = 0

    def feed(self, data) -> list:
        self.fed += len(data)
        cuts = []
        while self.cut + self.size <= self.fed:
            self.cut += self.size
            cuts.append(self.cut)
        return cuts

    def finalize(self) -> list:
        if self.fed > self.cut:
            self.cut = self.fed
            return [self.fed]
        return []


def _sha_batch(chunks):
    return [hashlib.sha256(c).digest() for c in chunks]


def _blocks(seed: int, n: int, size: int) -> list:
    """``n`` blocks of ``size`` bytes: even ones random, odd ones runs
    of 4-bit symbols (zstd halves them), as the dumps are."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        if i % 2:
            out.append(rng.integers(0, 16, size, dtype=np.uint8).tobytes())
        else:
            out.append(rng.bytes(size))
    return out


def _tree(base) -> dict:
    """Every file under ``.chunks/`` and a digest of its bytes."""
    root = os.path.join(str(base), ".chunks")
    out = {}
    for d, _, names in os.walk(root):
        for name in names:
            p = os.path.join(d, name)
            with open(p, "rb") as f:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def _stream(store, avg: int):
    return transfer._ChunkedStream(store, ChunkerParams(avg_size=avg),
                                   _FixedCuts, batch_hasher=_sha_batch)


def _write(store, avg: int, blocks: list):
    """One stream of ``blocks`` through the batch-hasher path, on a
    clock of the test's own thread: the stream and what was tallied."""
    counts: dict = {}
    stream = _stream(store, avg)
    with trace.clocked(trace.ThreadClock(counts=counts)):
        for b in blocks:
            stream.write(b)
        stream.finish()
    return stream, counts


def _case(kind: str, avg: int):
    """(blocks written first, the stream compared).  A hash batch holds
    16 MiB: ``per`` blocks.  ``duplicates``: two batches in which every
    odd block repeats the block before it.  ``half_known``: a batch
    written first, then two batches that alternate its blocks with new
    ones."""
    per = transfer._HASH_BATCH_BYTES // avg
    fresh = _blocks(avg + len(kind), 2 * per, avg)
    if kind == "duplicates":
        return [], [fresh[i - i % 2] for i in range(2 * per)]
    first = fresh[:per]
    new = _blocks(avg + 1, per, avg)
    return first, [b for pair in zip(first, new) for b in pair]


def _run_case(base, monkeypatch, kind: str, avg: int, helpers: int):
    monkeypatch.setattr(transfer, "store_helpers", lambda: helpers)
    store = ChunkStore(str(base), index_budget_mb=8)
    first, blocks = _case(kind, avg)
    if first:
        _write(store, avg, first)
    stream, counts = _write(store, avg, blocks)
    return stream, counts, _tree(base)


@pytest.mark.parametrize("avg", [64 << 10, 4 << 20], ids=["64k", "4m"])
@pytest.mark.parametrize("kind", ["duplicates", "half_known"])
def test_the_fan_out_stores_what_the_sequential_stage_stores(
        tmp_path, monkeypatch, kind, avg):
    """The records, the new/known counts and the chunk files (names and
    bytes) are the sequential stage's, with a block twice in one batch
    and with a batch half known to the index."""
    seq, seq_counts, seq_tree = _run_case(tmp_path / "seq", monkeypatch,
                                          kind, avg, SEQUENTIAL)
    fan, fan_counts, fan_tree = _run_case(tmp_path / "fan", monkeypatch,
                                          kind, avg, FANNED)
    assert fan.records == seq.records
    assert (fan.stats.new_chunks, fan.stats.known_chunks) \
        == (seq.stats.new_chunks, seq.stats.known_chunks)
    per = transfer._HASH_BATCH_BYTES // avg
    assert fan.stats.new_chunks == per              # either kind
    assert fan.stats.known_chunks == per
    assert fan_tree == seq_tree
    assert not [p for p in fan_tree if ".tmp." in p]
    # it engaged, and the index did the same work on either path
    assert seq_counts.get("store_pool_flushes", 0) == 0
    assert fan_counts["store_pool_flushes"] >= 2
    for key in ("index_inserts", "index_contains", "index_hits",
                "index_probe_digests"):
        assert fan_counts.get(key, 0) == seq_counts.get(key, 0), key


@pytest.mark.parametrize("kind", ["duplicates", "half_known"])
def test_the_commit_log_sees_every_chunk_once(tmp_path, monkeypatch, kind):
    """The benchmark's numerator (``CommitLog.watch`` wraps the store
    instance's ``insert`` and ``note_dedup_hit``) logs each record's
    chunk exactly once, whichever thread stored it."""
    monkeypatch.setattr(transfer, "store_helpers", lambda: FANNED)
    avg = 64 << 10
    store = ChunkStore(str(tmp_path), index_budget_mb=8)
    first, blocks = _case(kind, avg)
    if first:
        _write(store, avg, first)
    log = CommitLog()
    log.watch(store)
    stream, counts = _write(store, avg, blocks)
    assert counts["store_pool_flushes"] >= 2
    assert Counter(d for _, d in log.events) \
        == Counter(d for _, d in stream.records)


def _slow_watched(store, writer: threading.Thread, *, writer_waits=None):
    """Wrap the instance's ``insert``: every call's (thread, start, end,
    raised) into a list, the calls under way counted; a call that
    stores lingers 5 ms on the writer's thread and 50 ms on a helper's.
    With ``writer_waits`` (an Event), the writer's own calls first wait
    for it, so that the pool's helpers hit the store first, and the
    first call that raises sets it."""
    calls: list = []
    live = [0]
    lock = threading.Lock()
    insert = store.insert

    def watched(digest, data, **kw):
        me = threading.current_thread()
        if writer_waits is not None and me is writer:
            writer_waits.wait(5)
        with lock:
            live[0] += 1
        t0 = time.perf_counter()
        raised = None
        try:
            new = insert(digest, data, **kw)
            time.sleep(0.005 if me is writer else 0.05)
            return new
        except BaseException as e:
            raised = e
            if writer_waits is not None:
                writer_waits.set()
            raise
        finally:
            with lock:
                live[0] -= 1
                calls.append((me, t0, time.perf_counter(), raised))
    store.insert = watched
    return calls, live


@pytest.mark.parametrize("helpers", [SEQUENTIAL, FANNED],
                         ids=["sequential", "fanned"])
def test_a_failed_insert_reaches_the_writer_after_every_insert_returned(
        tmp_path, monkeypatch, helpers):
    """The ``pbsstore.chunk.insert`` failpoint fires once, at the third
    insert — in a helper, while the two before it still linger, where
    the flush fans out: the writer gets that error, of its own type,
    only once no insert is under way, and no chunk is taken after it;
    no staging file is left under ``.chunks/`` (none on the sequential
    path either)."""
    monkeypatch.setattr(transfer, "store_helpers", lambda: helpers)
    avg = 64 << 10
    store = ChunkStore(str(tmp_path), index_budget_mb=8)
    me = threading.current_thread()
    gate = threading.Event() if helpers else None
    calls, live = _slow_watched(store, me, writer_waits=gate)
    stream = _stream(store, avg)
    per = transfer._HASH_BATCH_BYTES // avg
    with failpoints.armed("pbsstore.chunk.insert", "raise", nth=3):
        with pytest.raises(failpoints.FailpointError):
            try:
                for b in _blocks(7, per, avg):
                    stream.write(b)
            finally:
                caught, in_flight = time.perf_counter(), live[0]
    assert in_flight == 0
    assert all(end <= caught for _, _, end, _ in calls)
    failed = [c for c in calls if c[3] is not None]
    assert len(failed) == 1
    if helpers:
        assert failed[0][0] is not me
        assert failed[0][0].name.startswith("store-helper")
        # after the failure no chunk was taken: at most one insert a
        # thread was under way beside it
        later = [c for c in calls if c[1] > failed[0][2]]
        assert len(later) <= helpers + 1
    else:
        assert failed[0][0] is me and len(calls) == 3
    assert not [p for p in _tree(tmp_path) if ".tmp." in p]


def test_the_jobs_record_counts_the_index_work_of_either_stage(
        tmp_path, monkeypatch):
    """A backup job through the pump (``tests/test_pump.py``'s session:
    hash batches of four chunks over a two-shard store with an index),
    its second half the first half's bytes again: the job's record
    holds the same ``index_*`` counts with the fan-out as without it —
    the helpers' tallies are added to the writer's clock at the join —
    and ``store_pool_*`` says how often it engaged."""
    from tests.test_pump import CountingFS, _clocked_run, _StreamSession
    sizes = {f"f{i:02d}": 600 + i % 20 for i in range(40)}
    records = {}
    for helpers in (SEQUENTIAL, FANNED):
        monkeypatch.setattr(transfer, "store_helpers", lambda h=helpers: h)
        sess = _StreamSession(tmp_path / f"h{helpers}", monkeypatch)
        _, attrs, _, _ = _clocked_run(CountingFS(sizes), sess,
                                      f"row-fan-{helpers}")
        records[helpers] = attrs
    seq, fan = records[SEQUENTIAL], records[FANNED]
    for key in ("inserts", "contains", "hits", "probe_digests",
                "probe_trips", "false_positives"):
        assert fan["index_" + key] == seq["index_" + key], key
    assert seq["index_hits"] > 0 and seq["index_inserts"] > 0
    assert seq["store_pool_flushes"] == seq["store_pool_chunks"] == 0
    assert fan["store_pool_flushes"] > 0
    assert fan["store_pool_chunks"] <= fan["index_inserts"]
    assert (fan["store_pool_s"] > 0) == (fan["store_pool_chunks"] > 0)


class _SinkDouble:
    """A ``PBSChunkSink``-like store: inserts over one connection, no
    batched probe, nothing concurrent declared."""

    def __init__(self):
        self.stored: dict = {}

    def insert(self, digest, data, *, verify=True) -> bool:
        new = digest not in self.stored
        self.stored[digest] = bytes(data)
        return new

    def touch(self, digest) -> None:
        pass

    def ingest_capabilities(self):
        return NO_CAPABILITIES


@pytest.mark.parametrize("which", ["similarity_tier", "pbs_sink"])
def test_a_store_that_declares_no_concurrent_insert_never_fans_out(
        tmp_path, monkeypatch, which):
    monkeypatch.setattr(transfer, "store_helpers", lambda: FANNED)
    made = []
    real = transfer.StoreFanOut

    def spy(*a, **kw):
        made.append(a)
        return real(*a, **kw)
    monkeypatch.setattr(transfer, "StoreFanOut", spy)
    if which == "similarity_tier":
        store = ChunkStore(str(tmp_path), index_budget_mb=8,
                           delta_tier=True)
        assert store.ingest_capabilities().presketch
    else:
        store = _SinkDouble()
    assert not store.ingest_capabilities().concurrent_insert
    avg = 64 << 10
    stream, counts = _write(store, avg, _blocks(3, 2 * (
        transfer._HASH_BATCH_BYTES // avg), avg))
    assert made == [] and "store_pool_flushes" not in counts
    assert stream.stats.new_chunks == len(stream.records)


def test_many_helpers_store_each_chunk_once_and_lose_no_count(monkeypatch):
    """More helpers than cores on a pool of their own and a short switch
    interval: every chunk is inserted once, its answer lands in its own
    slot, and the helpers' tallies and counts add up."""
    pool = ThreadPoolExecutor(max_workers=2 * (os.cpu_count() or 1) + 2)
    monkeypatch.setattr(storepool, "_pool", pool)
    seen: list = []

    def insert(digest, data, *, verify=True):
        seen.append(digest)
        trace.tally(index_inserts=1, index_contains=1)
        return digest[0] % 3 != 0
    items = [(i.to_bytes(4, "big"), b"") for i in range(3000)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        counts: dict = {}
        with trace.clocked(trace.ThreadClock(counts=counts)):
            fan = storepool.StoreFanOut(insert, items)
            fan.start(pool._max_workers)
            t0 = time.monotonic()
            fan.join()
            assert time.monotonic() - t0 < 60
    finally:
        sys.setswitchinterval(old)
        pool.shutdown(wait=True)
    assert sorted(seen) == [d for d, _ in items]
    assert fan.new == [d[0] % 3 != 0 for d, _ in items]
    writer_did = counts.get("index_inserts", 0)
    assert fan.helped + writer_did == len(items)
    assert fan.counts == {"index_inserts": fan.helped,
                          "index_contains": fan.helped}
