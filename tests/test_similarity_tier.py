"""Similarity-dedup tier battery (ISSUE 9, docs/data-plane.md
"Similarity tier"): resemblance index + delta-encoded chunk store.

Covers the sketch/banding oracle, the delta blob codecs, the
ChunkStore write/read integration (chain-depth bound, profitability
fallback, tier-on == tier-off snapshot bit-identity, sequential vs
pipelined parity), base resolution through the chunk cache, the
``pbsstore.delta.encode`` / ``pbsstore.delta.read`` failpoints (a
corrupt or failed delta read never serves wrong bytes and never admits
to the cache), and the GC coherence rules (a zero-grace sweep never
unlinks a base a live delta still reassembles from; the sweep discards
sketch entries BEFORE unlink)."""

import hashlib
import os
import time

import numpy as np
import pytest

from pbs_plus_tpu.chunker import ChunkerParams
from pbs_plus_tpu.pxar import chunkcache, deltablob
from pbs_plus_tpu.pxar.backupproxy import LocalStore
from pbs_plus_tpu.pxar.datastore import ChunkStore
from pbs_plus_tpu.pxar.format import KIND_DIR, KIND_FILE, Entry
from pbs_plus_tpu.pxar.similarityindex import (
    SimilarityIndex, metrics_snapshot,
)
from pbs_plus_tpu.utils import failpoints

P = ChunkerParams(avg_size=16 << 10)
RNG = np.random.default_rng(42)


def _rand(n, rng=None):
    return (rng or RNG).integers(0, 256, n, dtype=np.uint8).tobytes()


def _mutate(data: bytes, frac: float, seed: int = 0) -> bytes:
    rng = np.random.default_rng(seed)
    arr = np.frombuffer(data, dtype=np.uint8).copy()
    idx = rng.choice(len(arr), max(1, int(len(arr) * frac)), replace=False)
    arr[idx] ^= 0xFF
    return arr.tobytes()


def _dig(b: bytes) -> bytes:
    return hashlib.sha256(b).digest()


def _delta_store(tmp_path, name="ds", **kw):
    kw.setdefault("delta_tier", True)
    return ChunkStore(str(tmp_path / name), **kw)


# ---------------------------------------------------------------- index

def test_similarity_index_candidate_and_threshold():
    idx = SimilarityIndex(threshold=14)
    base = _rand(32 << 10)
    near = _mutate(base, 0.005, seed=1)
    far = _rand(32 << 10)
    s_base, s_near, s_far = (int(s) for s in
                             idx.sketch_batch([base, near, far]))
    idx.add(b"B" * 32, s_base, 0)
    got = idx.candidate(s_near)
    assert got is not None and got[0] == b"B" * 32 and got[1] == 0
    assert idx.candidate(s_far) is None


def test_similarity_index_chain_depth_reject():
    idx = SimilarityIndex(threshold=64, max_chain=2)
    idx.add(b"A" * 32, 0, 2)            # already at max depth
    m0 = metrics_snapshot()["chain_rejects"]
    assert idx.candidate(1) is None     # distance 1, but depth-blocked
    assert metrics_snapshot()["chain_rejects"] == m0 + 1
    idx.add(b"C" * 32, 0, 1)            # allowed base at depth 1
    got = idx.candidate(1)
    assert got == (b"C" * 32, 1)


def test_similarity_index_discard_and_recency():
    idx = SimilarityIndex(threshold=64)
    idx.add(b"A" * 32, 5, 0)
    assert idx.has(b"A" * 32) and idx.depth_of(b"A" * 32) == 0
    assert idx.candidate(5, exclude=b"A" * 32) is None   # self excluded
    assert idx.candidate(4) is not None
    assert idx.discard(b"A" * 32) is True
    assert idx.discard(b"A" * 32) is False
    assert idx.candidate(4) is None


def test_similarity_presketch_batch_consumed():
    idx = SimilarityIndex()
    chunks = [_rand(8 << 10) for _ in range(4)]
    digs = [_dig(c) for c in chunks]
    n = idx.presketch(digs, chunks, [False, True, False, True])
    assert n == 2                       # only the not-known chunks
    want = int(idx.sketch_batch([chunks[0]])[0])
    assert idx.take_sketch(digs[0], chunks[0]) == want
    # second take recomputes (pending consumed) and still agrees
    assert idx.take_sketch(digs[0], chunks[0]) == want


# ------------------------------------------------------------- blob fmt

def test_delta_blob_roundtrip_both_codecs():
    base = _rand(64 << 10)
    data = _mutate(base, 0.005, seed=2)
    bd = _dig(base)
    blob = deltablob.encode(data, base, bd, depth=1)
    assert blob is not None and deltablob.is_delta(blob)
    codec, depth, rsz, got_bd = deltablob.parse_header(blob)
    assert (depth, rsz, got_bd) == (1, len(data), bd)
    assert len(blob) < len(data) // 10
    assert deltablob.decode(blob, base) == data
    # pure-Python copy/insert codec round-trips independently
    patch = deltablob._patch_encode(data, base)
    assert patch is not None
    assert deltablob._patch_apply(patch, base) == data


def test_delta_blob_unprofitable_returns_none():
    base = _rand(32 << 10)
    unrelated = _rand(32 << 10, np.random.default_rng(9))
    assert deltablob.encode(unrelated, base, _dig(base), depth=1) is None


def test_delta_blob_header_guards():
    with pytest.raises(deltablob.DeltaError):
        deltablob.parse_header(b"short")
    with pytest.raises(deltablob.DeltaError):
        deltablob.parse_header(b"NOTDELTA" + b"\0" * 60)


# ------------------------------------------------------- store write path

def test_store_writes_delta_and_reads_back(tmp_path):
    store = _delta_store(tmp_path)
    base = _rand(64 << 10)
    near = _mutate(base, 0.005, seed=3)
    db, dn = _dig(base), _dig(near)
    assert store.insert(db, base, verify=False)
    assert store.insert(dn, near, verify=False)
    # the near chunk landed as a small delta blob naming its base
    assert store.chunk_size(dn) < len(near) // 10
    assert store.delta_base_of(dn) == db
    assert store.delta_base_of(db) is None
    # both read back verified, directly and through the cache
    assert store.get(db) == base and store.get(dn) == near
    cache = chunkcache.ChunkCache(64 << 20)
    assert cache.get(store, dn) == near
    # dedup hit path still answers False for a delta-stored digest
    assert store.insert(dn, near, verify=False) is False


def test_store_chain_depth_bound(tmp_path):
    store = _delta_store(tmp_path, delta_max_chain=2)
    gens = [_rand(64 << 10)]
    for g in range(4):
        gens.append(_mutate(gens[-1], 0.003, seed=10 + g))
    digs = [_dig(g) for g in gens]
    for d, g in zip(digs, gens):
        store.insert(d, g, verify=False)
    depths = []
    for d in digs:
        depth = 0
        seen = set()
        cur = d
        while True:
            b = store.delta_base_of(cur)
            if b is None:
                break
            assert b not in seen        # acyclic
            seen.add(b)
            depth += 1
            cur = b
        depths.append(depth)
    assert max(depths) <= 2             # the configured bound holds
    for d, g in zip(digs, gens):
        assert store.get(d) == g


def test_store_unprofitable_falls_back_full(tmp_path):
    store = _delta_store(tmp_path, delta_threshold=64)
    a = _rand(32 << 10)
    b = _rand(32 << 10, np.random.default_rng(8))
    m0 = metrics_snapshot()["encode_fallbacks"]
    store.insert(_dig(a), a, verify=False)
    store.insert(_dig(b), b, verify=False)   # candidate, delta loses
    assert metrics_snapshot()["encode_fallbacks"] == m0 + 1
    assert store.delta_base_of(_dig(b)) is None
    assert store.get(_dig(b)) == b
    # the fallback registered b as a fresh depth-0 base
    assert store.similarity.depth_of(_dig(b)) == 0


def test_tier_off_store_never_deltas(tmp_path):
    store = ChunkStore(str(tmp_path / "off"), delta_tier=False)
    base = _rand(64 << 10)
    near = _mutate(base, 0.005, seed=4)
    store.insert(_dig(base), base, verify=False)
    store.insert(_dig(near), near, verify=False)
    assert store.similarity is None
    assert store.delta_base_of(_dig(near)) is None
    assert store.chunk_size(_dig(near)) > len(near) // 2


def test_pbs_format_store_forces_tier_off(tmp_path):
    store = ChunkStore(str(tmp_path / "pbs"), blob_format="pbs",
                       delta_tier=True)
    assert store.similarity is None


# ------------------------------------------- snapshots: tier on == off

def _near_dup_tree(tmp_path, n_gen=4, per=96 << 10):
    src = tmp_path / "src"
    src.mkdir()
    gens = [_rand(per, np.random.default_rng(21))]
    for g in range(1, n_gen):
        gens.append(_mutate(gens[-1], 0.004, seed=30 + g))
    for i, g in enumerate(gens):
        (src / f"gen{i:02d}.bin").write_bytes(g)
    return src, gens


def _snapshot(tmp_path, name, src, *, pipeline_workers=0, **delta_kw):
    store = LocalStore(str(tmp_path / name), P,
                       pipeline_workers=pipeline_workers, **delta_kw)
    from pbs_plus_tpu.pxar.walker import backup_tree
    sess = store.start_session(backup_type="host", backup_id="b")
    backup_tree(sess, str(src))
    man = sess.finish()
    return store, sess.ref, man


def test_snapshot_bit_identical_tier_on_vs_off(tmp_path):
    src, gens = _near_dup_tree(tmp_path)
    s_off, r_off, m_off = _snapshot(tmp_path, "off", src, delta_tier=False)
    s_on, r_on, m_on = _snapshot(tmp_path, "on", src, delta_tier=True)
    # manifest stats + counts identical (the tier changes only the
    # on-disk chunk encoding, never the archive)
    for key in ("stats", "entries", "meta_chunks", "payload_chunks",
                "meta_size", "payload_size"):
        assert m_on[key] == m_off[key], key
    # index records bit-identical
    on_m, on_p = s_on.datastore.load_indexes(r_on)
    off_m, off_p = s_off.datastore.load_indexes(r_off)
    assert list(on_p.records()) == list(off_p.records())
    assert list(on_m.records()) == list(off_m.records())
    # the tier actually engaged (some chunk stored as a delta)
    chunks = s_on.datastore.chunks
    assert any(chunks.delta_base_of(on_p.digest(i)) is not None
               for i in range(len(on_p)))
    # restores bit-identical to source AND to each other (tree decode)
    rd_on = s_on.open_snapshot(r_on)
    rd_off = s_off.open_snapshot(r_off)
    assert [e.path for e in rd_on.entries()] == \
        [e.path for e in rd_off.entries()]
    for i, g in enumerate(gens):
        e = rd_on.lookup(f"gen{i:02d}.bin")
        assert rd_on.read_file(e) == g
        assert rd_off.read_file(rd_off.lookup(f"gen{i:02d}.bin")) == g


def test_sequential_vs_pipelined_tier_parity(tmp_path):
    src, gens = _near_dup_tree(tmp_path, n_gen=3)
    s_seq, r_seq, m_seq = _snapshot(tmp_path, "seq", src, delta_tier=True)
    s_pipe, r_pipe, m_pipe = _snapshot(tmp_path, "pipe", src,
                                       delta_tier=True, pipeline_workers=2)
    assert m_seq["stats"] == m_pipe["stats"]
    sm, sp = s_seq.datastore.load_indexes(r_seq)
    pm, pp = s_pipe.datastore.load_indexes(r_pipe)
    assert list(sp.records()) == list(pp.records())
    rd = s_pipe.open_snapshot(r_pipe)
    for i, g in enumerate(gens):
        assert rd.read_file(rd.lookup(f"gen{i:02d}.bin")) == g


# -------------------------------------------------- cache base resolution

def test_hot_base_decompresses_once_through_cache(tmp_path):
    store = _delta_store(tmp_path)
    base = _rand(64 << 10)
    nears = [_mutate(base, 0.004, seed=50 + i) for i in range(4)]
    db = _dig(base)
    store.insert(db, base, verify=False)
    digs = [_dig(n) for n in nears]
    for d, n in zip(digs, nears):
        store.insert(d, n, verify=False)
    assert all(store.delta_base_of(d) == db for d in digs)

    opens = []
    real_get_resolved = store.get_resolved

    def counting(digest, resolver, _chain=()):
        opens.append(digest)
        return real_get_resolved(digest, resolver, _chain)

    store.get_resolved = counting
    cache = chunkcache.ChunkCache(64 << 20)
    for d, n in zip(digs, nears):
        assert cache.get(store, d) == n
    # the base was loaded from disk exactly once; every later delta's
    # resolution was a cache hit
    assert opens.count(db) == 1
    # and the base itself now serves directly from the cache
    del opens[:]
    assert cache.get(store, db) == base
    assert opens == []


def test_cache_resolver_wired_not_none(tmp_path):
    """The cache hands a real resolver to delta-capable stores (the
    delta-discipline invariant, exercised not just linted)."""
    store = _delta_store(tmp_path)
    seen = {}
    real = store.get_resolved

    def spy(digest, resolver, _chain=()):
        seen["resolver"] = resolver
        return real(digest, resolver, _chain)

    store.get_resolved = spy
    d = _dig(b"x" * 100)
    store.insert(d, b"x" * 100, verify=False)
    chunkcache.ChunkCache(1 << 20).get(store, d)
    assert seen["resolver"] is not None


# ------------------------------------------------------------ failpoints

def test_delta_encode_failpoint_falls_back_full(tmp_path):
    store = _delta_store(tmp_path)
    base = _rand(64 << 10)
    near = _mutate(base, 0.004, seed=60)
    store.insert(_dig(base), base, verify=False)
    m0 = metrics_snapshot()["encode_fallbacks"]
    with failpoints.armed("pbsstore.delta.encode", "raise") as fp:
        assert store.insert(_dig(near), near, verify=False)
        assert fp.fires >= 1
    # insert SUCCEEDED as a full blob; bytes readable and verified
    assert store.delta_base_of(_dig(near)) is None
    assert store.get(_dig(near)) == near
    assert metrics_snapshot()["encode_fallbacks"] > m0


def test_delta_read_corrupt_never_serves_never_admits(tmp_path):
    store = _delta_store(tmp_path)
    base = _rand(64 << 10)
    near = _mutate(base, 0.004, seed=61)
    db, dn = _dig(base), _dig(near)
    store.insert(db, base, verify=False)
    store.insert(dn, near, verify=False)
    assert store.delta_base_of(dn) == db
    cache = chunkcache.ChunkCache(64 << 20)
    with failpoints.armed("pbsstore.delta.read", "corrupt"):
        with pytest.raises((IOError, deltablob.DeltaError)):
            cache.get(store, dn)
    assert not cache.contains(dn)       # never admitted
    assert cache.snapshot()["load_errors"] >= 1
    # healthy read after disarm serves the true bytes
    assert cache.get(store, dn) == near


def test_delta_read_raise_failpoint(tmp_path):
    store = _delta_store(tmp_path)
    base = _rand(32 << 10)
    near = _mutate(base, 0.004, seed=62)
    store.insert(_dig(base), base, verify=False)
    store.insert(_dig(near), near, verify=False)
    m0 = metrics_snapshot()["delta_reads"]
    with failpoints.armed("pbsstore.delta.read", "raise"):
        with pytest.raises(failpoints.FailpointError):
            store.get(_dig(near))
    assert metrics_snapshot()["delta_reads"] > m0
    assert store.get(_dig(near)) == near


# ------------------------------------------------------------ GC battery

def _publish_near_dup_snapshot(tmp_path, name="gcds"):
    """One snapshot whose payload holds near-dup files, written with the
    tier on → at least one published chunk is a delta.  Returns
    (LocalStore, ref, payload_index)."""
    src, _g = _near_dup_tree(tmp_path, n_gen=3)
    store, ref, _m = _snapshot(tmp_path, name, src, delta_tier=True)
    _midx, pidx = store.datastore.load_indexes(ref)
    return store, ref, pidx


def test_zero_grace_sweep_keeps_delta_bases(tmp_path):
    from pbs_plus_tpu.server.prune import PrunePolicy, run_prune
    store, ref, pidx = _publish_near_dup_snapshot(tmp_path)
    chunks = store.datastore.chunks
    published = {pidx.digest(i) for i in range(len(pidx))}
    deltas = {d for d in published if chunks.delta_base_of(d)}
    assert deltas, "tier never engaged — test would prove nothing"
    bases = chunks.delta_closure(published) - published
    assert bases or all(chunks.delta_base_of(d) in published
                        for d in deltas)
    # age every chunk far into the past, then zero-grace GC: only the
    # closure may survive — and every published byte must still restore
    old = time.time() - 10 * 24 * 3600
    for d in chunks.iter_digests():
        os.utime(chunks._path(d), (old, old))
    report = run_prune(store.datastore, PrunePolicy(), gc=True,
                       gc_grace_s=0.0)
    reader = store.open_snapshot(ref)
    for e in reader.entries():
        if e.is_file and e.size:
            assert len(reader.read_file(e)) == e.size
    for d in published | bases:
        assert chunks.on_disk(d), d.hex()


def test_sweep_discards_sketch_before_unlink(tmp_path):
    """Structural ordering proof: at the moment a delta-bearing store's
    sweep unlinks a chunk file, the similarity index has ALREADY
    forgotten that digest (it can never be offered as a base again)."""
    store = _delta_store(tmp_path)
    sim = store.similarity
    victims = []
    for i in range(6):
        c = _rand(16 << 10, np.random.default_rng(70 + i))
        d = _dig(c)
        store.insert(d, c, verify=False)
        victims.append(d)
    assert all(sim.has(d) for d in victims)

    real_unlink = os.unlink
    violations = []

    def checking_unlink(path):
        name = os.path.basename(path)
        if len(name) == 64:
            d = bytes.fromhex(name)
            if sim.has(d):
                violations.append(name)
        return real_unlink(path)

    old = time.time() - 3600
    for d in victims:
        os.utime(store._path(d), (old, old))
    import unittest.mock as mock
    with mock.patch("os.unlink", side_effect=checking_unlink):
        removed, _freed = store.sweep(before=time.time() - 60)
    assert removed == len(victims)
    assert violations == []
    assert not any(sim.has(d) for d in victims)


def test_sweep_failpoint_discards_nothing(tmp_path):
    """A sweep killed at the pbsstore.chunk.sweep failpoint has
    discarded no sketch entries and unlinked no files."""
    store = _delta_store(tmp_path)
    c = _rand(16 << 10)
    d = _dig(c)
    store.insert(d, c, verify=False)
    old = time.time() - 3600
    os.utime(store._path(d), (old, old))
    with failpoints.armed("pbsstore.chunk.sweep", "raise"):
        with pytest.raises(failpoints.FailpointError):
            store.sweep(before=time.time() - 60)
    assert store.similarity.has(d)
    assert store.on_disk(d)


def test_sweep_skips_pinned_base(tmp_path):
    """Base-pin protocol: while a delta commit has a base pinned, the
    sweep must leave it on disk (and keep its sketch entry) even at
    zero grace — then take it normally once unpinned."""
    store = _delta_store(tmp_path)
    c = _rand(16 << 10)
    d = _dig(c)
    store.insert(d, c, verify=False)
    old = time.time() - 3600
    os.utime(store._path(d), (old, old))
    with store._pin_lock:
        store._pinned_bases[d] = 1
    try:
        removed, _ = store.sweep(before=time.time() - 60)
        assert removed == 0
        assert store.on_disk(d) and store.similarity.has(d)
    finally:
        with store._pin_lock:
            store._pinned_bases.pop(d, None)
    os.utime(store._path(d), (old, old))
    removed, _ = store.sweep(before=time.time() - 60)
    assert removed == 1 and not store.on_disk(d)


def test_concurrent_delta_commit_vs_sweep_never_orphans(tmp_path):
    """Hammer insert-of-near-dups against zero-grace sweeps of the
    base: whatever interleaving wins, every successfully inserted
    chunk must reassemble (a swept base ⇒ the insert fell back to a
    full blob; a committed delta ⇒ the base survived)."""
    import threading
    store = _delta_store(tmp_path)
    base = _rand(32 << 10)
    db = _dig(base)
    results = []
    for round_ in range(8):
        store.insert(db, base, verify=False)
        near = _mutate(base, 0.004, seed=100 + round_)
        dn = _dig(near)
        old = time.time() - 3600
        os.utime(store._path(db), (old, old))

        def sweeper():
            store.sweep(before=time.time() - 60)

        t = threading.Thread(target=sweeper)
        t.start()
        store.insert(dn, near, verify=False)
        t.join()
        # the invariant: the just-inserted chunk always reassembles
        assert store.get(dn) == near
        results.append(store.delta_base_of(dn) is not None)
        # reset for the next round
        for dg in list(store.iter_digests()):
            os.utime(store._path(dg), (old, old))
        store.sweep(before=time.time() - 60)
    # both outcomes are legal; the test is the reassembly assert above
    assert len(results) == 8


def test_read_errors_counted_once_for_chained_failure(tmp_path):
    """One broken reassembly of a chained delta reports ONE read
    error, not one per enclosing frame."""
    store = _delta_store(tmp_path)
    gens = [_rand(32 << 10)]
    for g in range(2):
        gens.append(_mutate(gens[-1], 0.004, seed=90 + g))
    digs = [_dig(g) for g in gens]
    store.insert(digs[0], gens[0], verify=False)
    store.insert(digs[1], gens[1], verify=False)
    # force the chain gens[2] -> gens[1] -> gens[0]: with gens[0] still
    # offered, candidate() may legally pick it (flatter chain) — drop
    # it from the index so gens[1] is the only candidate
    store.similarity.discard(digs[0])
    store.insert(digs[2], gens[2], verify=False)
    assert store.delta_base_of(digs[2]) == digs[1]
    assert store.delta_base_of(digs[1]) == digs[0]
    # corrupt the MIDDLE delta's payload on disk
    p1 = store._path(digs[1])
    with open(p1, "rb") as f:
        raw = bytearray(f.read())
    raw[-1] ^= 0xFF
    with open(p1, "wb") as f:
        f.write(bytes(raw))
    m0 = metrics_snapshot()["read_errors"]
    with pytest.raises((IOError, deltablob.DeltaError)):
        store.get(digs[2])          # resolver-less recursive path
    assert metrics_snapshot()["read_errors"] == m0 + 1


def test_delta_closure_survives_tier_off_restart(tmp_path):
    """The .delta-tier marker keeps GC's base closure running on a
    store re-opened with the tier off."""
    store = _delta_store(tmp_path)
    base = _rand(64 << 10)
    near = _mutate(base, 0.004, seed=80)
    db, dn = _dig(base), _dig(near)
    store.insert(db, base, verify=False)
    store.insert(dn, near, verify=False)
    assert store.delta_base_of(dn) == db
    reopened = ChunkStore(str(tmp_path / "ds"), delta_tier=False)
    assert reopened.similarity is None
    assert reopened.delta_closure({dn}) == {dn, db}


# ------------------------- sketch persistence (ISSUE 10 satellite /
#                           ROADMAP item 3: survive restarts) ---------

def test_sketches_persist_across_restart(tmp_path):
    """The dedup-index snapshot carries the resemblance entries: a
    restarted tier-on store offers PRE-restart delta bases instead of
    waiting for organic re-inserts."""
    store = _delta_store(tmp_path)
    base = _rand(64 << 10)
    db = _dig(base)
    store.insert(db, base, verify=False)
    assert store.similarity.has(db)
    assert store.save_index_snapshot()

    reopened = _delta_store(tmp_path)
    _ = reopened.index                      # lazy boot consumes snapshot
    assert reopened.similarity.has(db), "pre-restart sketch lost"
    assert reopened.similarity.depth_of(db) == 0
    # a near-dup inserted AFTER the restart deltas against the
    # pre-restart base
    near = _mutate(base, 0.002, seed=91)
    dn = _dig(near)
    reopened.insert(dn, near, verify=False)
    assert reopened.delta_base_of(dn) == db
    assert reopened.get(dn) == near


def test_sketch_depths_persist(tmp_path):
    """Chain depths survive the roundtrip — without them a restarted
    index would hand out max-chain bases and overshoot the bound."""
    store = _delta_store(tmp_path)
    base = _rand(48 << 10)
    near = _mutate(base, 0.002, seed=92)
    db, dn = _dig(base), _dig(near)
    store.insert(db, base, verify=False)
    store.insert(dn, near, verify=False)
    assert store.similarity.depth_of(dn) == 1
    store.save_index_snapshot()
    reopened = _delta_store(tmp_path)
    _ = reopened.index
    assert reopened.similarity.depth_of(dn) == 1
    assert reopened.similarity.depth_of(db) == 0


def test_corrupt_sketch_section_degrades_to_organic(tmp_path):
    """A flipped byte anywhere in the sketch section: the exact index
    still loads from the snapshot, the tier just rebuilds organically —
    never a crash, never half-loaded sketch state."""
    store = _delta_store(tmp_path)
    base = _rand(48 << 10)
    db = _dig(base)
    store.insert(db, base, verify=False)
    store.save_index_snapshot()
    snap = os.path.join(str(tmp_path / "ds"), ".chunkindex", "snapshot")
    raw = bytearray(open(snap, "rb").read())
    raw[-7] ^= 0x01                          # inside the sketch trailer
    open(snap, "wb").write(bytes(raw))

    reopened = _delta_store(tmp_path)
    _ = reopened.index
    assert reopened.index.contains(db)       # main payload intact
    assert not reopened.similarity.has(db)   # sketches: organic rebuild
    # organic rebuild proceeds normally
    near = _mutate(base, 0.002, seed=93)
    reopened.insert(_dig(near), near, verify=False)
    assert reopened.similarity.has(_dig(near))


def test_truncated_sketch_section_degrades(tmp_path):
    store = _delta_store(tmp_path)
    base = _rand(32 << 10)
    db = _dig(base)
    store.insert(db, base, verify=False)
    store.save_index_snapshot()
    snap = os.path.join(str(tmp_path / "ds"), ".chunkindex", "snapshot")
    raw = open(snap, "rb").read()
    open(snap, "wb").write(raw[:-10])        # tear the section tail
    reopened = _delta_store(tmp_path)
    _ = reopened.index
    assert reopened.index.contains(db)
    assert not reopened.similarity.has(db)


def test_v1_snapshot_without_sketch_section_loads(tmp_path):
    """A tier-off store writes no sketch section (the v1 byte layout);
    a tier-on reopen loads the digests and leaves the tier organic."""
    store = ChunkStore(str(tmp_path / "ds"), delta_tier=False)
    data = _rand(16 << 10)
    d = _dig(data)
    store.insert(d, data, verify=False)
    store.save_index_snapshot()
    reopened = _delta_store(tmp_path)
    _ = reopened.index
    assert reopened.index.contains(d)
    assert len(reopened.similarity) == 0


def test_sweep_resaves_snapshot_with_surviving_sketches(tmp_path):
    """The post-sweep snapshot save keeps only surviving sketches — a
    swept base can never be offered by a restarted server."""
    store = _delta_store(tmp_path)
    keep = _rand(32 << 10)
    drop = _rand(32 << 10, np.random.default_rng(7))
    dk, dd = _dig(keep), _dig(drop)
    store.insert(dk, keep, verify=False)
    store.insert(dd, drop, verify=False)
    time.sleep(0.02)
    cutoff = time.time()
    time.sleep(0.05)     # fs timestamp clock may lag time.time() by ms
    store.touch(dk)                          # mark: keep survives
    store.sweep(cutoff)                      # drop is unlinked + re-saved
    reopened = _delta_store(tmp_path)
    _ = reopened.index
    assert reopened.similarity.has(dk)
    assert not reopened.similarity.has(dd)
    assert reopened.index.contains(dk)
    assert not reopened.index.contains(dd)


# -------------------------------------- batched delta-candidate preselect


def test_precandidate_batch_matches_live_candidate():
    """The vectorized per-batch candidate preselect (consumed by
    ``take_candidate``) returns exactly what a live ``candidate()``
    walk would, including depth rejects and misses."""
    rng = np.random.default_rng(21)
    live, batched = SimilarityIndex(), SimilarityIndex()
    for _ in range(300):
        d = rng.bytes(32)
        s = int(rng.integers(0, 2 ** 63))
        dp = int(rng.integers(0, 4))
        live.add(d, s, dp)
        batched.add(d, s, dp)
    digests, sketches = [], []
    entries = list(live._entries.items())
    for _ in range(48):
        base = entries[int(rng.integers(0, len(entries)))][1][0]
        s = base
        for _ in range(int(rng.integers(0, 22))):
            s ^= 1 << int(rng.integers(0, 64))
        digests.append(rng.bytes(32))
        sketches.append(s)
    with batched._lock:
        batched._precandidate_locked(digests, sketches)
    for d, s in zip(digests, sketches):
        assert batched.take_candidate(d, s, exclude=d) == \
            live.candidate(s, exclude=d)
    # consumed stashes fall back to the live walk
    assert batched.take_candidate(digests[0], sketches[0],
                                  exclude=digests[0]) == \
        live.candidate(sketches[0], exclude=digests[0])


def test_take_candidate_sees_band_adds_past_recency_window():
    """A base inserted after the preselect stays visible via its LIVE
    band bucket even after >128 unrelated inserts rotate it out of the
    recency window (the 512-chunk-batch regression: the stash must
    never see LESS than a live candidate() walk)."""
    rng = np.random.default_rng(22)
    idx = SimilarityIndex()
    sketch = 0x0123_4567_89AB_CDEF
    d_new = b"n" * 32
    with idx._lock:
        idx._precandidate_locked([d_new], [sketch])    # empty pool
    d_base = b"b" * 32
    idx.add(d_base, sketch ^ 0b101, 0)                 # post-stash add
    for _ in range(200):                               # rotate it out
        idx.add(rng.bytes(32), int(rng.integers(0, 2 ** 63)) | 1 << 63,
                0)
    assert d_base not in idx._recent
    assert idx.take_candidate(d_new, sketch, exclude=d_new) == \
        idx.candidate(sketch, exclude=d_new) == (d_base, 0)


def test_take_candidate_sees_intra_batch_adds():
    """A base inserted AFTER the preselect (an earlier chunk of the
    same batch) is still offered via the live recency re-check."""
    idx = SimilarityIndex()
    sketch = 0x5A5A_5A5A_5A5A_5A5A
    d_new = b"n" * 32
    with idx._lock:
        idx._precandidate_locked([d_new], [sketch])    # empty pool
    d_base = b"b" * 32
    idx.add(d_base, sketch ^ 0b11, 0)                  # post-stash add
    got = idx.take_candidate(d_new, sketch, exclude=d_new)
    assert got == (d_base, 0)
