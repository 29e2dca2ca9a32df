"""Flagship pipeline tests: DedupPipeline parity with the CPU backend,
TpuChunker drop-in behavior, verification, similarity model."""

import hashlib

import numpy as np
import pytest

from pbs_plus_tpu.chunker import ChunkerParams, CpuChunker, chunk_bounds
from pbs_plus_tpu.models import DedupConfig, DedupPipeline, SimilarityModel, VerifyPipeline
from pbs_plus_tpu.models.dedup import TpuChunker

P = ChunkerParams(avg_size=4 << 10)


def _data(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8).tobytes()


def test_pipeline_matches_cpu_backend():
    """Cut + digest bit parity (BASELINE.md config #2) and dedup accounting."""
    shared = _data(120_000, seed=1)
    streams = {
        "agent-a": shared + _data(50_000, seed=2),
        "agent-b": shared + _data(50_000, seed=3),   # 70% duplicate content
    }
    pipe = DedupPipeline(DedupConfig(params=P, segment_bytes=1 << 16,
                                     index_buckets=1 << 10))
    res = pipe.process_streams(streams)
    for name, data in streams.items():
        want = chunk_bounds(data, P)
        got = [(c.offset, c.offset + c.length) for c in res[name].chunks]
        assert got == want, name
        for c in res[name].chunks:
            assert c.digest == hashlib.sha256(
                data[c.offset:c.offset + c.length]).digest()
    # cross-stream dedup: agent-b's shared prefix chunks are not new
    assert res["agent-b"].dedup_ratio > 0.4
    assert res["agent-a"].new_bytes == res["agent-a"].total_bytes  # first seen
    # repeat run: everything known
    res2 = pipe.process_streams({"agent-a": streams["agent-a"]})
    assert res2["agent-a"].dedup_ratio == 1.0


def test_tpu_chunker_drop_in():
    """TpuChunker == CpuChunker through the streaming interface."""
    data = _data(300_000, seed=4)
    for feed in (1 << 14, 99_991):
        cpu, tpu = CpuChunker(P), TpuChunker(P)
        got_c, got_t = [], []
        for off in range(0, len(data), feed):
            seg = data[off:off + feed]
            got_c += cpu.feed(seg)
            got_t += tpu.feed(seg)
        got_c += cpu.finalize()
        got_t += tpu.finalize()
        assert got_c == got_t


# --- a stream reaches the device in scan segments (ISSUE 27) ---------------

MIB = 1 << 20
FLUSH = None        # in a list of writes: ``flush_chunker`` / ``sync`` here


def _write_sizes(pattern: str, rng) -> list:
    """The sizes of a stream's writes, as a session writer makes them:
    16-byte headers, files under 64 KiB, read-ahead blocks of exactly
    4 MiB, one write longer than two segments."""
    files = [int(n) for n in rng.integers(1, 65537, 300)]
    mix = [16, 70_000, 16, 4 * MIB, 16, 1, 16, 9 * MIB, 16, *files[:20],
           4 * MIB, 16, 333]
    return {
        "headers": [16] * 3000,
        "small_files": files,
        "blocks_4mib": [4 * MIB] * 3,           # nothing left at finalize
        "one_9mib": [9 * MIB],
        "mix": mix,
        "mix_flushed": mix[:7] + [FLUSH] + mix[7:12] + [FLUSH] + mix[12:],
        "flushed_at_a_segment_end": [16, 4 * MIB - 16, FLUSH, 5 * MIB,
                                     3 * MIB, FLUSH, 100],
    }[pattern]


class _Records:
    """A store that keeps nothing: the stream's records are the result."""

    def insert(self, digest, data, *, verify=True):
        return True

    def touch(self, digest):
        pass


def _stream_cuts(params, factory, data, sizes, end="finish"):
    from pbs_plus_tpu.pxar.transfer import _ChunkedStream
    s = _ChunkedStream(_Records(), params, chunker_factory=factory)
    off = 0
    for i, n in enumerate(sizes):
        if n is FLUSH:      # its own assertion: nothing stays un-cut
            s.flush_chunker() if i % 2 else s.sync()
            continue
        s.write(data[off:off + n])
        off += n
    assert off == len(data)
    return [end for end, _ in s.finish()]


@pytest.mark.parametrize("avg", [4 * MIB, 64 << 10], ids=["avg4m", "avg64k"])
@pytest.mark.parametrize("pattern", [
    "headers", "small_files", "blocks_4mib", "one_9mib", "mix",
    "mix_flushed", "flushed_at_a_segment_end"])
def test_cuts_do_not_depend_on_how_the_stream_is_written(pattern, avg):
    """However the writes fall on the scan segments, ``TpuChunker``'s
    cuts are ``CpuChunker``'s, and those of each run fed whole."""
    rng = np.random.default_rng([27, len(pattern)])
    sizes = _write_sizes(pattern, rng)
    data = rng.integers(0, 256, sum(n for n in sizes if n is not FLUSH),
                        dtype=np.uint8).tobytes()
    params = ChunkerParams(avg_size=avg)
    got = _stream_cuts(params, TpuChunker, data, sizes)
    assert got == _stream_cuts(params, CpuChunker, data, sizes)
    assert got[-1] == len(data)
    # each run between two flushes, as one feed of a chunker of its own
    whole, base, run = [], 0, 0
    for n in sizes + [FLUSH]:
        if n is not FLUSH:
            run += n
            continue
        if run:
            ch = TpuChunker(params)
            whole += [base + c for c in ch.feed(data[base:base + run])
                      + ch.finalize()]
        base, run = base + run, 0
    assert got == whole


def test_tpu_chunker_in_session_writer(tmp_path):
    """chunker='tpu' is a one-line writer swap; archives are identical."""
    import io
    from pbs_plus_tpu.pxar import Entry, KIND_DIR, KIND_FILE, LocalStore

    def build(base, factory):
        store = LocalStore(str(base), P, chunker_factory=factory)
        s = store.start_session(backup_type="host", backup_id="x")
        w = s.writer
        w.write_entry(Entry(path="", kind=KIND_DIR))
        w.write_entry_reader(Entry(path="f1", kind=KIND_FILE),
                             io.BytesIO(_data(100_000, seed=5)))
        w.write_entry_reader(Entry(path="f2", kind=KIND_FILE),
                             io.BytesIO(_data(60_000, seed=6)))
        m = s.finish()
        return store, s.ref, m

    _, _, m_cpu = build(tmp_path / "cpu", lambda p: CpuChunker(p))
    store_t, ref_t, m_tpu = build(tmp_path / "tpu", lambda p: TpuChunker(p))
    assert m_cpu["payload_chunks"] == m_tpu["payload_chunks"]
    assert m_cpu["payload_size"] == m_tpu["payload_size"]
    r = store_t.open_snapshot(ref_t)
    for e in r.entries():
        if e.is_file:
            seed = 5 if e.path == "f1" else 6
            assert r.read_file(e) == _data(100_000 if e.path == "f1" else 60_000,
                                           seed=seed)


def test_verify_pipeline(tmp_path):
    chunks = [_data(n, seed=n) for n in (100, 5000, 70_000)]
    expected = [hashlib.sha256(c).digest() for c in chunks]
    vp = VerifyPipeline()
    assert vp.verify_chunks(chunks, expected).ok
    bad = list(chunks)
    bad[1] = bad[1][:-1] + bytes([bad[1][-1] ^ 1])
    res = vp.verify_chunks(bad, expected)
    assert res.corrupt == [1]


def test_verify_snapshot(tmp_path):
    import io
    from pbs_plus_tpu.pxar import Entry, KIND_DIR, KIND_FILE, LocalStore
    store = LocalStore(str(tmp_path / "ds"), P)
    s = store.start_session(backup_type="host", backup_id="v")
    s.writer.write_entry(Entry(path="", kind=KIND_DIR))
    for i in range(5):
        s.writer.write_entry_reader(Entry(path=f"f{i}", kind=KIND_FILE),
                                    io.BytesIO(_data(20_000, seed=i)))
    s.finish()
    r = store.open_snapshot(s.ref)
    assert VerifyPipeline().verify_snapshot(r).ok
    # corrupt one payload chunk on disk → detected
    digest = r.payload_index.digest(0)
    p = store.datastore.chunks._path(digest)
    try:
        import zstandard
    except ImportError:
        from pbs_plus_tpu.utils import zstdshim as zstandard
    raw = zstandard.ZstdDecompressor().decompress(open(p, "rb").read(),
                                                  max_output_size=1 << 30)
    raw = bytearray(raw)
    raw[0] ^= 1
    open(p, "wb").write(zstandard.ZstdCompressor().compress(bytes(raw)))
    r2 = store.open_snapshot(s.ref)
    with pytest.raises(IOError):
        VerifyPipeline().verify_snapshot(r2)


def test_similarity_model():
    m = SimilarityModel(minhash_k=256)
    a = [hashlib.sha256(bytes([i & 0xFF, i >> 8, 1])).digest() for i in range(1500)]
    b = a[:750] + [hashlib.sha256(bytes([i & 0xFF, i >> 8, 2])).digest()
                   for i in range(750)]
    c = [hashlib.sha256(bytes([i & 0xFF, i >> 8, 3])).digest() for i in range(1500)]
    sa, sb, sc = (m.snapshot_signature(x) for x in (a, b, c))
    best, sim = m.best_previous(sa, {"b": sb, "c": sc})
    assert best == "b" and sim > 0.2
    # sketches of identical digests are identical → near-dup pairs found
    sk = m.chunk_sketches(a[:64])
    pairs = m.near_duplicates(sk, sk, max_distance=0)
    assert all(d == 0 for _, _, d in pairs)
    assert {(i, i) for i in range(64)} <= {(i, j) for i, j, _ in pairs}
