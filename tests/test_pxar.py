"""pxar data-plane tests: golden archive roundtrips against a LocalStore —
the reference's key test pattern (PBS-less chunk store + real split
archives, /root/reference/internal/pxarmount/commit_walk_test.go:21-120).
"""

import hashlib
import os

import numpy as np
import pytest

from pbs_plus_tpu.chunker import ChunkerParams
from pbs_plus_tpu.pxar import (
    Datastore, DynamicIndex, Entry, KIND_DIR, KIND_FILE, KIND_HARDLINK,
    KIND_SYMLINK, LocalStore, SnapshotRef, SplitReader,
)
from pbs_plus_tpu.pxar.datastore import ChunkStore
from pbs_plus_tpu.pxar.ingestbackend import (
    IngestCapabilities, InlineIngestBackend, NO_CAPABILITIES,
    StoreIngestBackend, resolve_ingest_backend)
from pbs_plus_tpu.pxar.similarityindex import SimilarityIndex
from pbs_plus_tpu.pxar.walker import backup_tree, iter_tree

P = ChunkerParams(avg_size=4 << 10)  # reference test scale: 4 KiB chunks
RNG = np.random.default_rng(42)


def _blob(n, seed=None):
    rng = np.random.default_rng(seed) if seed is not None else RNG
    return rng.integers(0, 256, n, dtype=np.uint8).tobytes()


@pytest.fixture
def tree(tmp_path):
    """A realistic source tree: nested dirs, binary + text + empty files,
    symlink, hardlink."""
    root = tmp_path / "src"
    (root / "docs").mkdir(parents=True)
    (root / "data" / "deep").mkdir(parents=True)
    (root / "docs" / "readme.txt").write_text("hello backup world\n" * 200)
    (root / "docs" / "empty").write_bytes(b"")
    (root / "data" / "big.bin").write_bytes(_blob(150_000, seed=1))
    (root / "data" / "deep" / "inner.bin").write_bytes(_blob(30_000, seed=2))
    (root / "data.txt").write_text("sibling of data dir")  # DFS-order edge
    os.symlink("docs/readme.txt", root / "link")
    os.link(root / "docs" / "readme.txt", root / "hard")
    return str(root)


def _snapshot_digests(store, ref):
    r = store.open_snapshot(ref)
    return {e.path: e.digest for e in r.entries() if e.kind == KIND_FILE}


def test_backup_restore_roundtrip(tmp_path, tree):
    store = LocalStore(str(tmp_path / "ds"), P)
    sess = store.start_session(backup_type="host", backup_id="t1")
    n = backup_tree(sess, tree)
    manifest = sess.finish()
    assert manifest["entries"] == n

    r = store.open_snapshot(sess.ref)
    by_path = {e.path: e for e in r.entries()}
    # all filesystem objects present
    assert by_path[""].kind == KIND_DIR
    assert by_path["docs"].kind == KIND_DIR
    assert by_path["link"].kind == KIND_SYMLINK
    assert by_path["link"].link_target == "docs/readme.txt"
    hard = by_path["hard"]
    rd = by_path["docs/readme.txt"]
    # hardlink pair: one is the file, the other references it
    assert {hard.kind, rd.kind} == {KIND_FILE, KIND_HARDLINK}
    # content parity for every regular file
    for e, src in iter_tree(tree):
        if src is None or not e.is_file:
            continue
        want = open(src, "rb").read()
        got = r.read_file(by_path[e.path])
        assert got == want, e.path
        assert by_path[e.path].digest == hashlib.sha256(want).digest()
    # ranged reads across chunk boundaries
    big = by_path["data/big.bin"]
    want = open(os.path.join(tree, "data/big.bin"), "rb").read()
    for off, sz in [(0, 10), (4095, 2), (5000, 60_000), (149_990, 100)]:
        assert r.read_file(big, off, sz) == want[off:off + sz]
    # metadata preserved
    st = os.stat(os.path.join(tree, "data/big.bin"))
    assert big.mode == st.st_mode & 0o7777
    assert big.mtime_ns == st.st_mtime_ns


def test_second_backup_dedups_chunks(tmp_path, tree):
    store = LocalStore(str(tmp_path / "ds"), P)
    s1 = store.start_session(backup_type="host", backup_id="t1")
    backup_tree(s1, tree)
    m1 = s1.finish()
    assert m1["stats"]["new_chunks"] > 0

    # identical second run: payload chunks all known, nothing new but meta
    s2 = store.start_session(backup_type="host", backup_id="t1",
                             backup_time=None)
    backup_tree(s2, tree)
    m2 = s2.finish()
    assert m2["previous"] == str(s1.ref)
    # mtimes unchanged → metadata stream identical too; all chunks known
    assert m2["stats"]["new_chunks"] == 0
    assert m2["stats"]["known_chunks"] > 0
    assert _snapshot_digests(store, s1.ref) == _snapshot_digests(store, s2.ref)


def test_dedup_writer_refs(tmp_path, tree):
    """write_entry_ref: in-order refs reuse whole chunks without IO;
    content parity preserved; boundary bytes re-encoded only."""
    store = LocalStore(str(tmp_path / "ds"), P)
    s1 = store.start_session(backup_type="host", backup_id="t1")
    backup_tree(s1, tree)
    s1.finish()

    prev = store.open_snapshot(s1.ref)
    prev_entries = {e.path: e for e in prev.entries()}

    s2 = store.start_session(backup_type="host", backup_id="t1")
    w = s2.writer
    changed = {"docs/readme.txt"}
    for e, src in iter_tree(tree):
        pe = prev_entries.get(e.path)
        if e.is_file and src and e.path not in changed and pe is not None \
                and pe.kind == KIND_FILE and pe.payload_offset >= 0:
            e.digest = pe.digest
            w.write_entry_ref(e, pe.payload_offset, pe.size)
        elif src is not None:
            with open(src, "rb") as f:
                w.write_entry_reader(e, f)
        else:
            w.write_entry(e)
    m2 = s2.finish()
    st = m2["stats"]
    assert st["ref_chunks"] > 0
    assert st["bytes_reffed"] > 0
    # re-encoded boundary bytes bounded by a few chunk sizes per ref run
    assert st["bytes_reencoded"] <= 6 * P.max_size

    # full content parity via the new snapshot
    r2 = store.open_snapshot(s2.ref)
    by_path = {e.path: e for e in r2.entries()}
    for e, src in iter_tree(tree):
        if src is None or not e.is_file:
            continue
        want = open(src, "rb").read()
        assert r2.read_file(by_path[e.path]) == want, e.path


def test_out_of_order_refs_fall_back(tmp_path):
    """Non-monotonic refs must stay correct (re-encode fallback — the
    payload-offset monotonicity rule, SURVEY §7 hard parts)."""
    store = LocalStore(str(tmp_path / "ds"), P)
    s1 = store.start_session(backup_type="host", backup_id="oo")
    w = s1.writer
    blobs = {f"f{i:02d}": _blob(20_000, seed=10 + i) for i in range(4)}
    root = Entry(path="", kind=KIND_DIR)
    w.write_entry(root)
    for name, data in sorted(blobs.items()):
        import io
        w.write_entry_reader(Entry(path=name, kind=KIND_FILE), io.BytesIO(data))
    s1.finish()
    prev = store.open_snapshot(s1.ref)
    pe = {e.path: e for e in prev.entries()}

    # second snapshot references files in REVERSED payload order under new
    # names that keep path order valid
    s2 = store.start_session(backup_type="host", backup_id="oo")
    w2 = s2.writer
    w2.write_entry(Entry(path="", kind=KIND_DIR))
    mapping = {}
    for i, old in enumerate(sorted(blobs, reverse=True)):
        new_name = f"r{i:02d}"
        mapping[new_name] = old
        e = Entry(path=new_name, kind=KIND_FILE)
        w2.write_entry_ref(e, pe[old].payload_offset, pe[old].size)
    s2.finish()
    r2 = store.open_snapshot(s2.ref)
    for e in r2.entries():
        if e.is_file:
            assert r2.read_file(e) == blobs[mapping[e.path]], e.path


def test_didx_roundtrip_and_corruption(tmp_path):
    recs = []
    off = 0
    for i in range(100):
        off += 1000 + i
        recs.append((off, hashlib.sha256(bytes([i])).digest()))
    idx = DynamicIndex.from_records(recs)
    p = str(tmp_path / "x.didx")
    idx.write(p)
    idx2 = DynamicIndex.parse(p)
    assert np.array_equal(idx.ends, idx2.ends)
    assert np.array_equal(idx.digests, idx2.digests)
    assert idx2.total_size == off
    # offset→chunk lookups
    assert idx2.chunk_for_offset(0) == 0
    assert idx2.chunk_for_offset(999) == 0
    assert idx2.chunk_for_offset(1000) == 1
    with pytest.raises(IndexError):
        idx2.chunk_for_offset(off)
    # header corruption detected
    raw = bytearray(open(p, "rb").read())
    raw[0] ^= 0xFF
    open(p, "wb").write(bytes(raw))
    with pytest.raises(ValueError):
        DynamicIndex.parse(p)


def test_chunkstore_integrity(tmp_path):
    ds = Datastore(str(tmp_path / "ds"))
    data = _blob(50_000, seed=3)
    digest = hashlib.sha256(data).digest()
    assert ds.chunks.insert(digest, data) is True
    assert ds.chunks.insert(digest, data) is False     # dedup hit
    assert ds.chunks.get(digest) == data
    with pytest.raises(ValueError):
        ds.chunks.insert(hashlib.sha256(b"no").digest(), data)
    # on-disk corruption detected on read
    p = ds.chunks._path(digest)
    blob = bytearray(open(p, "rb").read())
    blob[len(blob) // 2] ^= 0x01
    open(p, "wb").write(bytes(blob))
    with pytest.raises(Exception):
        ds.chunks.get(digest)


def test_snapshot_listing_and_same_second_bump(tmp_path, tree):
    store = LocalStore(str(tmp_path / "ds"), P)
    t0 = 1_700_000_000.0
    refs = []
    for _ in range(3):
        s = store.start_session(backup_type="host", backup_id="t1",
                                backup_time=t0)  # same wall time each run
        backup_tree(s, tree)
        s.finish()
        refs.append(s.ref)
    assert len({r.backup_time for r in refs}) == 3  # +1s bumps
    snaps = store.datastore.list_snapshots("host", "t1")
    assert snaps == sorted(refs, key=lambda r: r.backup_time)
    assert store.datastore.last_snapshot("host", "t1") == refs[-1]


def test_concurrent_same_second_sessions(tmp_path, tree):
    """Two sessions for the same group in the same second must stage
    independently and both publish (finish-time bump)."""
    store = LocalStore(str(tmp_path / "ds"), P)
    t0 = 1_700_000_000.0
    s1 = store.start_session(backup_type="host", backup_id="t1", backup_time=t0)
    s2 = store.start_session(backup_type="host", backup_id="t1", backup_time=t0)
    backup_tree(s1, tree)
    backup_tree(s2, tree)
    m1 = s1.finish()
    m2 = s2.finish()
    assert m1["backup_time"] != m2["backup_time"]
    snaps = store.datastore.list_snapshots("host", "t1")
    assert len(snaps) == 2
    for ref in snaps:
        r = store.open_snapshot(ref)
        assert len(list(r.entries())) == m1["entries"]


def test_abort_leaves_no_snapshot(tmp_path, tree):
    store = LocalStore(str(tmp_path / "ds"), P)
    s = store.start_session(backup_type="host", backup_id="t1")
    backup_tree(s, tree)
    s.abort()
    assert store.datastore.list_snapshots() == []
    with pytest.raises(RuntimeError):
        s.finish()


def test_batched_hasher_archives_identical(tmp_path, tree):
    """batch_hasher (the TPU digest path, here the device-batched sha256 on
    the CPU backend) yields byte-identical archives to inline hashlib."""
    from pbs_plus_tpu.ops.sha256 import sha256_chunks

    s_def = LocalStore(str(tmp_path / "a"), P)
    s1 = s_def.start_session(backup_type="host", backup_id="x")
    backup_tree(s1, tree)
    m1 = s1.finish()

    s_bat = LocalStore(str(tmp_path / "b"), P, batch_hasher=sha256_chunks)
    s2 = s_bat.start_session(backup_type="host", backup_id="x")
    backup_tree(s2, tree)
    m2 = s2.finish()

    assert m1["payload_chunks"] == m2["payload_chunks"]
    assert m1["payload_size"] == m2["payload_size"]
    r1, r2 = s_def.open_snapshot(s1.ref), s_bat.open_snapshot(s2.ref)
    recs1 = list(r1.payload_index.records())
    recs2 = list(r2.payload_index.records())
    assert recs1 == recs2                      # same cuts, same digests
    for e in r2.entries():
        if e.is_file and e.size:
            assert r2.read_file(e) == r1.read_file(r1.lookup(e.path))


def test_gc_sweep_preserves_live_chunks(tmp_path, tree):
    import time
    store = LocalStore(str(tmp_path / "ds"), P)
    s1 = store.start_session(backup_type="host", backup_id="t1")
    backup_tree(s1, tree)
    s1.finish()
    mark = time.time() + 1
    # touch all chunks referenced by live snapshots (GC phase 1)
    for ref in store.datastore.list_snapshots():
        midx, pidx = store.datastore.load_indexes(ref)
        for idx in (midx, pidx):
            for i in range(len(idx)):
                os.utime(store.datastore.chunks._path(idx.digest(i)),
                         (mark + 10, mark + 10))
    removed, freed = store.datastore.chunks.sweep(before=mark)
    assert removed == 0 and freed == 0
    r = store.open_snapshot(s1.ref)
    for e in r.entries():
        if e.is_file and e.size:
            assert len(r.read_file(e)) == e.size


def test_zip_subtree(tmp_path, tree):
    """Zip download of a snapshot subtree (reference: internal/pxar/zip.go)."""
    import io
    import zipfile
    from pbs_plus_tpu.pxar.zipdl import zip_subtree

    store = LocalStore(str(tmp_path / "ds"), P)
    s = store.start_session(backup_type="host", backup_id="z")
    backup_tree(s, tree)
    s.finish()
    r = store.open_snapshot(s.ref)
    buf = zip_subtree(r, "docs")
    zf = zipfile.ZipFile(buf)
    names = set(zf.namelist())
    assert "readme.txt" in names and "empty" in names
    assert zf.read("readme.txt") == open(
        os.path.join(tree, "docs/readme.txt"), "rb").read()
    # whole-archive zip includes nested dirs + symlink entries
    buf2 = zip_subtree(r, "")
    zf2 = zipfile.ZipFile(buf2)
    assert "data/deep/inner.bin" in zf2.namelist()
    assert "link" in zf2.namelist()
    assert zf2.read("link") == b"docs/readme.txt"    # symlink target payload
    import pytest as _pytest
    with _pytest.raises(FileNotFoundError):
        zip_subtree(r, "nope/nothere")


def test_zip_hardlinks_and_single_file(tmp_path, tree):
    import zipfile
    from pbs_plus_tpu.pxar.zipdl import zip_subtree
    store = LocalStore(str(tmp_path / "ds"), P)
    s = store.start_session(backup_type="host", backup_id="z2")
    backup_tree(s, tree)
    s.finish()
    r = store.open_snapshot(s.ref)
    zf = zipfile.ZipFile(zip_subtree(r, ""))
    want = open(os.path.join(tree, "docs/readme.txt"), "rb").read()
    # the hardlink pair: both names present, both carry the content
    assert zf.read("hard") == want and zf.read("docs/readme.txt") == want
    assert {"hard", "docs/readme.txt"} <= set(zf.namelist())
    # zipping a single file yields a properly named entry
    zf2 = zipfile.ZipFile(zip_subtree(r, "docs/readme.txt"))
    assert zf2.namelist() == ["readme.txt"]
    assert zf2.read("readme.txt") == want


# ------------------------------------------------- typed ingest backend
# (pxar/ingestbackend.py: the probe/presketch seam _ChunkedStream and
# PipelinedStream resolve once at stream open)


def test_resolve_backend_declared_capabilities(tmp_path):
    indexed = ChunkStore(str(tmp_path / "indexed"))
    be = resolve_ingest_backend(indexed)
    assert isinstance(be, StoreIngestBackend)
    assert be.capabilities == IngestCapabilities(
        probe=True, presketch=False, concurrent_insert=True)
    indexed.similarity = SimilarityIndex()
    assert be.capabilities.presketch is True      # live re-read
    # the tier's sketch pool follows the order chunks arrive in
    assert be.capabilities.concurrent_insert is False

    legacy = ChunkStore(str(tmp_path / "legacy"), index_budget_mb=0)
    assert resolve_ingest_backend(legacy).capabilities == \
        IngestCapabilities(probe=False, presketch=False,
                           concurrent_insert=True)


def test_resolve_backend_undeclared_store_is_inline():
    class Double:
        def insert(self, digest, data, *, verify=True):
            return True

    be = resolve_ingest_backend(Double())
    assert isinstance(be, InlineIngestBackend)
    assert be.capabilities == NO_CAPABILITIES
    with pytest.raises(TypeError):
        be.probe_batch([b"x" * 32])
    with pytest.raises(TypeError):
        be.presketch_batch([], [], None)


def test_pbs_sink_declares_no_capabilities():
    from pbs_plus_tpu.pxar.pbsstore import PBSChunkSink
    sink = PBSChunkSink.__new__(PBSChunkSink)
    assert sink.ingest_capabilities() == NO_CAPABILITIES


# ----------------------------------------- the stream's edges, both factories


@pytest.mark.parametrize("kind", ["cpu", "tpu"])
def test_stream_edges_under_either_chunker_factory(tmp_path, kind):
    """write, ``sync`` (every record's digest resolved and its chunk in
    the store), ``append_ref`` of an existing chunk in mid-stream (the
    splice seam restarts the scan run), write, ``flush_chunker``, write,
    ``finish``: the records are those of a whole-buffer scalar scan over
    each run on its own, under the server's factory and batch hasher for
    either chunker kind (the second run crosses a 4 MiB scan segment)."""
    from pbs_plus_tpu.chunker import chunk_bounds
    from pbs_plus_tpu.pxar.transfer import _ChunkedStream
    from pbs_plus_tpu.server.backup_job import (make_batch_hasher,
                                                make_chunker_factory)
    params = ChunkerParams(avg_size=64 << 10)
    runs = [_blob(300_000, seed=31), _blob(5 << 20, seed=32),
            _blob(200_000, seed=33)]
    spliced = _blob(70_000, seed=34)
    d_spliced = hashlib.sha256(spliced).digest()
    store = ChunkStore(str(tmp_path / "s"))
    store.insert(d_spliced, spliced, verify=False)

    want, base = [], 0
    for i, run in enumerate(runs):
        want += [(base + e, hashlib.sha256(run[s:e]).digest())
                 for s, e in chunk_bounds(run, params)]
        base += len(run)
        if i == 0:
            base += len(spliced)
            want.append((base, d_spliced))

    st = _ChunkedStream(store, params, make_chunker_factory(kind),
                        batch_hasher=make_batch_hasher(kind))
    assert st.bound_backend == kind
    st.write(runs[0])
    st.sync()
    assert st.records and st.records[-1][0] == len(runs[0])
    assert all(d and store.has(d) for _, d in st.records)
    st.append_ref(d_spliced, len(spliced))
    # two writes, so that the run's cuts are not one feed's
    st.write(runs[1][:3 << 20])
    st.write(runs[1][3 << 20:])
    st.flush_chunker()
    assert st.records[-1][0] == st.offset
    st.write(runs[2])
    assert st.finish() == want
    assert (st.stats.ref_chunks, st.stats.bytes_reffed) == (1, len(spliced))
    assert st.stats.bytes_streamed == sum(map(len, runs))
    assert st.stats.new_chunks == len(want) - 1


def test_hash_batch_that_raises_reaches_the_flushing_caller(tmp_path):
    """A ``batch_hasher`` that throws: its own error reaches the caller
    of the ``write``, ``sync`` or ``finish`` that flushed the batch, and
    ``finish`` never returns a record whose digest slot was left
    unfilled — it raises while the hasher does, and returns the scalar
    stream's records once it no longer does (the batch stays pending)."""
    from pbs_plus_tpu.pxar.transfer import _ChunkedStream, _HASH_BATCH_COUNT

    class Boom(RuntimeError):
        pass
    down = [True]

    def hasher(chunks):
        if down[0]:
            raise Boom("sha stage down")
        return [hashlib.sha256(c).digest() for c in chunks]
    store = ChunkStore(str(tmp_path / "s"))
    small = _blob(100_000, seed=41)
    # enough chunks that the count threshold flushes inside the write
    big = _blob(P.max_size * (_HASH_BATCH_COUNT + 8), seed=42)

    st = _ChunkedStream(store, P, batch_hasher=hasher)
    with pytest.raises(Boom):
        st.write(big)
    st = _ChunkedStream(store, P, batch_hasher=hasher)
    st.write(small)
    with pytest.raises(Boom):
        st.sync()
    st = _ChunkedStream(store, P, batch_hasher=hasher)
    st.write(small)
    for _ in range(2):
        with pytest.raises(Boom):
            st.finish()
    assert any(d == b"" for _, d in st.records)     # still owed
    down[0] = False
    scalar = _ChunkedStream(ChunkStore(str(tmp_path / "ref")), P)
    scalar.write(small)
    assert st.finish() == scalar.finish()
    assert all(d and store.has(d) for _, d in st.records)
