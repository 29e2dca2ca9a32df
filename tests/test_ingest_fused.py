"""Fused cross-session ingest battery (ISSUE 13).

Covers the four acceptance surfaces of the fused path:

- **Ragged packing round-trip (property)**: arbitrary session counts,
  buffer splits, and content produce absolute cuts, digests, and
  similarity sketch values bit-identical to the single-session staged
  path, and padding/halo rows never leak a candidate into any row.
- **Twin parity**: the numpy host scan/digest twins and the jax device
  twins (both run on the CPU backend here) agree exactly.
- **Flush deadline**: a lone depositing session publishes within the
  collector's bounded wait even when another registered session idles.
- **Typed ingest backend**: declared capabilities resolve correctly for
  indexed stores, index-less stores, and undeclared legacy doubles.
"""

import hashlib
import threading
import time

import numpy as np
import pytest

from pbs_plus_tpu.chunker import ChunkerParams, candidates
from pbs_plus_tpu.chunker.spec import TEST_PARAMS
from pbs_plus_tpu.ops import ingest as ingest_ops
from pbs_plus_tpu.pxar import ingestbatch
from pbs_plus_tpu.pxar.datastore import ChunkStore
from pbs_plus_tpu.pxar.ingestbackend import (
    IngestCapabilities, InlineIngestBackend, NO_CAPABILITIES,
    StoreIngestBackend, resolve_ingest_backend)
from pbs_plus_tpu.pxar.ingestbatch import FusedIngestStream, IngestCollector
from pbs_plus_tpu.pxar.similarityindex import SimilarityIndex
from pbs_plus_tpu.pxar.transfer import _ChunkedStream


def _store(tmp_path, name, sim=False):
    s = ChunkStore(str(tmp_path / name))
    if sim:
        s.similarity = SimilarityIndex()
    return s


# ------------------------------------------------------- ops twins


def test_pack_rows_scan_matches_per_row_candidates():
    rng = np.random.default_rng(11)
    params = TEST_PARAMS
    rows, tails, hists, bases, expect = [], [], [], [], []
    for _ in range(7):
        n = int(rng.integers(100, 60_000))
        data = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
        histn = int(rng.integers(0, 200))
        hist = rng.integers(0, 256, histn, dtype=np.uint8).tobytes()
        # arbitrary block splits inside the row
        cut = int(rng.integers(0, n + 1))
        rows.append([data[:cut], data[cut:]])
        tails.append(hist[-63:])
        hists.append(min(histn, 63))
        bases.append(histn)
        expect.append(candidates(
            np.frombuffer(data, np.uint8), params,
            prefix=np.frombuffer(hist[-63:], np.uint8) if hist else b"",
            global_offset=histn))
    batch = ingest_ops.pack_rows(rows, tails, hists, bases)
    got = ingest_ops.scan_rows_host(batch, params)
    for e, h in zip(expect, got):
        assert np.array_equal(e, h)


def test_scan_device_twin_matches_host():
    rng = np.random.default_rng(12)
    rows = [[rng.integers(0, 256, 9000, dtype=np.uint8).tobytes()]
            for _ in range(4)]
    batch = ingest_ops.pack_rows(rows, [b""] * 4, [0] * 4,
                                 [0, 10, 0, 5])
    host = ingest_ops.scan_rows_host(batch, TEST_PARAMS)
    dev = ingest_ops.scan_rows_device(batch, TEST_PARAMS)
    assert len(host) == len(dev) == 4
    for h, d in zip(host, dev):
        assert np.array_equal(h, d)


def test_digest_twins_match_hashlib():
    rng = np.random.default_rng(13)
    chunks = [rng.integers(0, 256, int(rng.integers(1, 20_000)),
                           dtype=np.uint8).tobytes() for _ in range(16)]
    want = [hashlib.sha256(c).digest() for c in chunks]
    assert ingest_ops.digest_chunks_host(chunks) == want
    assert ingest_ops.digest_chunks_device(chunks) == want


def test_padding_rows_never_leak():
    """Candidates landing in halo slots, short-history prefixes, or the
    device pow2 pad must never surface in any row's results."""
    rng = np.random.default_rng(14)
    # rows deliberately shorter than one window + rows with zero history
    rows = [[rng.integers(0, 256, n, dtype=np.uint8).tobytes()]
            for n in (10, 63, 64, 200)]
    batch = ingest_ops.pack_rows(rows, [b""] * 4, [0] * 4, [0] * 4)
    for ends in ingest_ops.scan_rows_host(batch, TEST_PARAMS):
        # with zero history, a candidate needs a full 64-byte window
        # inside the row itself: end offsets are in (63, row_len]
        assert all(e > 63 for e in ends.tolist())
    short = ingest_ops.scan_rows_device(batch, TEST_PARAMS)
    for h, d in zip(ingest_ops.scan_rows_host(batch, TEST_PARAMS), short):
        assert np.array_equal(h, d)


# ------------------------------------------- ragged round-trip property


def test_ragged_round_trip_property(tmp_path):
    """Arbitrary session/buffer splits through the threaded collector
    == the single-session staged path: cuts, digests, sketch values."""
    rng = np.random.default_rng(15)
    n_sessions = 5
    payloads = []
    for _ in range(n_sessions):
        n = int(rng.integers(10_000, 2_000_000))
        payloads.append(rng.integers(0, 256, n, dtype=np.uint8).tobytes())

    staged_store = _store(tmp_path, "staged", sim=True)
    staged_records = []
    for p in payloads:
        st = _ChunkedStream(staged_store, TEST_PARAMS)
        off = 0
        r = np.random.default_rng(len(p))
        while off < len(p):
            step = int(r.integers(1, 300_000))
            st.write(p[off:off + step])
            off += step
        staged_records.append(st.finish())

    fused_store = _store(tmp_path, "fused", sim=True)
    coll = IngestCollector(fused_store, max_wait=0.02)
    fused_records = [None] * n_sessions
    errors = []

    def run(k):
        try:
            fu = FusedIngestStream(fused_store, TEST_PARAMS, coll)
            p = payloads[k]
            off = 0
            r = np.random.default_rng(len(p))    # same split sequence
            while off < len(p):
                step = int(r.integers(1, 300_000))
                fu.write(p[off:off + step])
                off += step
            fused_records[k] = fu.finish()
        except BaseException as e:
            errors.append(e)

    threads = [threading.Thread(target=run, args=(k,))
               for k in range(n_sessions)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    assert fused_records == staged_records
    # sketch VALUES identical: both tiers sketched the same chunk set
    a = {d: s for d, (s, _dp) in
         staged_store.similarity._entries.items()}
    b = {d: s for d, (s, _dp) in
         fused_store.similarity._entries.items()}
    assert a == b and len(a) > 0


def test_fused_stream_interface_edges(tmp_path):
    """flush_chunker/append_ref/sync mirror the staged stream: splice
    seams restart the scan run, sync resolves every record."""
    rng = np.random.default_rng(16)
    data1 = rng.integers(0, 256, 300_000, dtype=np.uint8).tobytes()
    data2 = rng.integers(0, 256, 200_000, dtype=np.uint8).tobytes()

    def drive(stream, store):
        stream.write(data1)
        stream.sync()
        assert all(d for _, d in stream.records)   # fully resolved
        # splice an existing chunk mid-stream
        d = hashlib.sha256(b"spliced").digest()
        store.insert(d, b"spliced", verify=False)
        stream.append_ref(d, len(b"spliced"))
        stream.write(data2)
        return stream.finish()

    s1 = _store(tmp_path, "a")
    r1 = drive(_ChunkedStream(s1, TEST_PARAMS), s1)
    s2 = _store(tmp_path, "b")
    r2 = drive(FusedIngestStream(s2, TEST_PARAMS,
                                 IngestCollector(s2, max_wait=0.01)), s2)
    assert r1 == r2
    assert len(r1) > 2


# ------------------------------------------------------ flush deadline


def test_flush_deadline_bounds_lone_session(tmp_path):
    """A depositing session whose fleet-mates idle still publishes
    within the collector's bounded wait — the all-deposited trigger
    cannot fire (an idle stream is registered), so the deadline must."""
    store = _store(tmp_path, "s")
    max_wait = 0.05
    coll = IngestCollector(store, max_wait=max_wait)
    idle = FusedIngestStream(store, TEST_PARAMS, coll)     # registered
    active = FusedIngestStream(store, TEST_PARAMS, coll)
    rng = np.random.default_rng(17)
    data = rng.integers(0, 256, 600_000, dtype=np.uint8).tobytes()
    t0 = time.monotonic()
    active.write(data)       # crosses the coalesce block -> deposits
    records = active.finish()
    elapsed = time.monotonic() - t0
    assert all(d for _, d in records) and len(records) > 1
    # a handful of deadline-bounded waits, not an unbounded stall; the
    # budget is generous against CI scheduler noise (deposits are
    # bounded by max_wait each, and this stream makes only a few)
    assert elapsed < 20 * max_wait, elapsed
    snap = ingestbatch.metrics_snapshot()
    # the bound held via the linger (quiescence) or the hard deadline
    assert snap["linger_flushes"] + snap["deadline_flushes"] >= 1
    idle.close()
    ref = _ChunkedStream(_store(tmp_path, "ref"), TEST_PARAMS)
    ref.write(data)
    assert ref.finish() == records


def test_failed_construction_never_leaks_registration(tmp_path):
    """A stream whose construction fails after the collector exists
    must not stay counted in the process-lifetime all-deposited
    trigger (PipelinedStream pool/committer failures, fallible
    chunker-factory binds, failed session opens)."""
    from pbs_plus_tpu.pxar.pipeline import PipelinedStream

    store = _store(tmp_path, "s")
    coll = IngestCollector(store, max_wait=0.01)

    def bad_factory(params):
        raise RuntimeError("bind failed")

    with pytest.raises(RuntimeError):
        PipelinedStream(store, TEST_PARAMS, bad_factory, workers=1,
                        collector=coll)
    assert len(coll._streams) == 0
    # a good stream still registers/deregisters cleanly
    fu = FusedIngestStream(store, TEST_PARAMS, coll)
    assert len(coll._streams) == 1
    fu.finish()
    assert len(coll._streams) == 0


def test_collector_error_poisons_batch(tmp_path):
    """A stage-level failure re-raises at every depositor instead of
    leaving unfilled record slots behind."""
    store = _store(tmp_path, "s")
    coll = IngestCollector(store, max_wait=0.01)

    class _Boom(RuntimeError):
        pass

    def explode(chunks):
        raise _Boom("sha stage down")

    fu = FusedIngestStream(store, TEST_PARAMS, coll)
    fu.write(np.random.default_rng(18).integers(
        0, 256, 100_000, dtype=np.uint8).tobytes())
    orig = ingest_ops.digest_chunks
    ingest_ops.digest_chunks = explode
    try:
        with pytest.raises(_Boom):
            fu.finish()
    finally:
        ingest_ops.digest_chunks = orig
        fu.close()


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


# ------------------------------------------------- typed ingest backend


def test_resolve_backend_declared_capabilities(tmp_path):
    indexed = ChunkStore(str(tmp_path / "indexed"))
    be = resolve_ingest_backend(indexed)
    assert isinstance(be, StoreIngestBackend)
    assert be.capabilities == IngestCapabilities(probe=True,
                                                 presketch=False)
    indexed.similarity = SimilarityIndex()
    assert be.capabilities.presketch is True      # live re-read

    legacy = ChunkStore(str(tmp_path / "legacy"), index_budget_mb=0)
    assert resolve_ingest_backend(legacy).capabilities == \
        IngestCapabilities(probe=False, presketch=False)


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


# --------------------------------------- pipelined committer deposits


def test_pipelined_stream_deposits_to_collector(tmp_path):
    from pbs_plus_tpu.pxar.pipeline import PipelinedStream

    rng = np.random.default_rng(19)
    data = rng.integers(0, 256, 1_500_000, dtype=np.uint8).tobytes()
    s1 = _store(tmp_path, "staged")
    st = _ChunkedStream(s1, TEST_PARAMS)
    st.write(data)
    want = st.finish()

    s2 = _store(tmp_path, "fusedpipe")
    coll = IngestCollector(s2, max_wait=0.01)
    base = ingestbatch.metrics_snapshot()
    ps = PipelinedStream(s2, TEST_PARAMS, workers=2, collector=coll)
    ps.write(data)
    got = ps.finish()
    assert got == want
    snap = ingestbatch.metrics_snapshot()
    assert snap["flushes"] > base["flushes"]          # really deposited
    assert snap["probe_dispatches"] > base["probe_dispatches"]


def test_session_writer_fused_wiring(tmp_path):
    """SessionWriter with a collector uses the fused payload stream and
    publishes records identical to the staged writer."""
    from pbs_plus_tpu.pxar.transfer import SessionWriter
    import io
    from pbs_plus_tpu.pxar.format import Entry, KIND_FILE

    rng = np.random.default_rng(20)
    data = rng.integers(0, 256, 400_000, dtype=np.uint8).tobytes()

    def run(store, collector):
        w = SessionWriter(store, payload_params=TEST_PARAMS,
                          ingest_collector=collector)
        w.write_entry_reader(Entry(path="f", kind=KIND_FILE,
                                   size=len(data)), io.BytesIO(data))
        midx, pidx, stats = w.finish()
        return ([pidx.digest(i) for i in range(len(pidx))],
                stats.new_chunks)

    s1 = _store(tmp_path, "w1")
    d1, n1 = run(s1, None)
    s2 = _store(tmp_path, "w2")
    d2, n2 = run(s2, IngestCollector(s2, max_wait=0.01))
    assert d1 == d2 and n1 == n2
    assert isinstance(
        SessionWriter(s2, payload_params=TEST_PARAMS,
                      ingest_collector=IngestCollector(
                          s2, max_wait=0.01)).payload,
        FusedIngestStream)
