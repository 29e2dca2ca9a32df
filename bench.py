#!/usr/bin/env python3
"""Host-side counts and rates of the data plane, as ONE JSON line.

``value`` is the measured single-core CPU baseline (native C++ buzhash
scan + OpenSSL sha256 — the reference's Go hot loop equivalent; SURVEY
§6); ``detail`` carries the sections below it (pipeline, resume, read,
fleet, indexes, delta …), each with its own in-run parity gates.  No
chip number: that is ``BENCHMARK.json`` + ``benchmark/run.py`` (PERF.md).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def _machine_context() -> dict:
    """Host context recorded in EVERY bench JSON so run-to-run CPU
    numbers are comparable (round-5: the CPU fallback halved with no way
    to tell noise from regression — cpu model/cores/load make that
    call possible)."""
    ctx: dict = {
        "python": sys.version.split()[0],
        "cores": os.cpu_count(),
        "platform": sys.platform,
    }
    try:
        ctx["loadavg_1m_5m_15m"] = [round(x, 2) for x in os.getloadavg()]
    except OSError:
        pass
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.lower().startswith("model name"):
                    ctx["cpu_model"] = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    try:
        import jax
        ctx["jax"] = jax.__version__
    except Exception:
        ctx["jax"] = None
    try:
        import numpy as _np
        ctx["numpy"] = _np.__version__
    except Exception:
        pass
    return ctx


def _cpu_baseline(mib: int = 256) -> dict:
    """Single-core CPU: native buzhash candidates + greedy cuts + OpenSSL
    sha256 per chunk (sequential, as the reference's writer hot loop)."""
    import hashlib
    import numpy as np
    from pbs_plus_tpu.chunker import ChunkerParams, candidates
    from pbs_plus_tpu.chunker.spec import select_cuts

    params = ChunkerParams(avg_size=4 << 20)
    data = np.random.default_rng(0).integers(
        0, 256, mib << 20, dtype=np.uint8).tobytes()
    t0 = time.perf_counter()
    # threads=1: the DECLARED baseline is the single-core hot loop (the
    # reference's sequential Go writer); the production path uses the
    # segment-parallel scan, reported separately below
    ends = candidates(data, params, threads=1)       # native C++ scan
    cuts = select_cuts(ends, len(data), params)
    s = 0
    digests = []
    for e in cuts:
        digests.append(hashlib.sha256(data[s:e]).digest())
        s = e
    dt = time.perf_counter() - t0
    out = {"mib_s": mib / dt, "chunks": len(cuts), "seconds": dt}
    # scan-vs-scan comparison (apples to apples: the full-loop mib_s above
    # also includes select_cuts + sha256, so it cannot be the denominator
    # for the MT-scan speedup)
    t0 = time.perf_counter()
    ends_st = candidates(data, params, threads=1)
    dt_st = time.perf_counter() - t0
    t0 = time.perf_counter()
    ends_mt = candidates(data, params)               # auto multi-threaded
    dt_mt = time.perf_counter() - t0
    if not (np.array_equal(ends, ends_mt) and np.array_equal(ends, ends_st)):
        raise AssertionError("mt scan diverged from single-core scan")
    out["scan_st_mib_s"] = mib / dt_st
    out["scan_mt_mib_s"] = mib / dt_mt
    # vectorized backend (chunker/vector.py, ISSUE 6): same corpus, same
    # in-run parity discipline as the MT check — the ends array must be
    # bit-identical to the scalar scan's before the number is reported
    from pbs_plus_tpu.chunker import vector
    t0 = time.perf_counter()
    ends_vec = vector.candidates(data, params)
    dt_vec = time.perf_counter() - t0
    if not np.array_equal(ends, ends_vec):
        raise AssertionError("vectorized scan diverged from scalar scan")
    out["scan_vec_mib_s"] = mib / dt_vec
    out["scan_vec_impl"] = vector.scan_impl_name()
    out["scan_vec_vs_st"] = round(out["scan_vec_mib_s"]
                                  / out["scan_st_mib_s"], 2)
    # batched entry (vmap-across-sessions shape): 8 concurrent streams
    # through one candidates_batch dispatch, row 0 parity-checked
    rows = 8
    rsz = (mib << 20) // rows
    bufs = [data[i * rsz:(i + 1) * rsz] for i in range(rows)]
    t0 = time.perf_counter()
    batch_ends = vector.candidates_batch(bufs, params)
    dt_b = time.perf_counter() - t0
    if not np.array_equal(batch_ends[0],
                          candidates(bufs[0], params, threads=1)):
        raise AssertionError("batched vector scan diverged on row 0")
    out["scan_vec_batch_mib_s"] = mib / dt_b
    out["scan_vec_batch_rows"] = rows
    import os as _os
    out["cores"] = _os.cpu_count()
    return out


class _NullStore:
    """insert/touch sink: benchmarks the writer orchestration without
    disk/compression cost (every chunk is 'new')."""

    def insert(self, digest, data, *, verify=True):
        return True

    def touch(self, digest):
        pass


def _pipeline_bench(mib: int = 256) -> dict:
    """Writer-loop pipeline benchmark: the same stream through the
    sequential ``_ChunkedStream`` and the pipelined ``PipelinedStream``
    (scan ∥ sha256 ∥ insert, pxar/pipeline.py) against a no-op store.

    Emits ``pipelined chunk+fingerprint MiB/s`` alongside the
    single-thread ``cpu.mib_s`` figure.  The parity gate asserts
    bit-identical (end_offset, digest) records — identical chunk
    boundaries and digest sets, so dedup ratio cannot drift."""
    import numpy as np
    from pbs_plus_tpu.chunker import ChunkerParams
    from pbs_plus_tpu.pxar.pipeline import PipelinedStream
    from pbs_plus_tpu.pxar.transfer import _ChunkedStream

    params = ChunkerParams(avg_size=4 << 20)
    data = np.random.default_rng(0).integers(
        0, 256, mib << 20, dtype=np.uint8).tobytes()
    block = 8 << 20
    workers = max(1, min(8, os.cpu_count() or 1))

    def run(make):
        s = make()
        t0 = time.perf_counter()
        for i in range(0, len(data), block):
            s.write(data[i:i + block])
        rec = s.finish()
        return rec, time.perf_counter() - t0

    rec_seq, dt_seq = run(lambda: _ChunkedStream(_NullStore(), params))
    rec_pipe, dt_pipe = run(lambda: PipelinedStream(
        _NullStore(), params, workers=workers))
    if rec_seq != rec_pipe:
        raise AssertionError("pipelined records diverged from sequential")
    return {
        "metric": "pipelined chunk+fingerprint MiB/s",
        "pipelined_mib_s": round(mib / dt_pipe, 1),
        "writer_seq_mib_s": round(mib / dt_seq, 1),
        "workers": workers,
        "cores": os.cpu_count(),
        "chunks": len(rec_pipe),
        "parity": True,
    }


class _CountingTime:
    """The ``time`` module as the traced modules see it, with every
    clock read counted (a lost update between pool threads only shaves
    the count)."""

    CLOCKS = ("perf_counter", "perf_counter_ns", "thread_time", "time")

    def __init__(self):
        import time as real
        self.reads = 0
        for name in dir(real):
            if not name.startswith("_") and name not in self.CLOCKS:
                setattr(self, name, getattr(real, name))
        for name in self.CLOCKS:
            setattr(self, name, self._counted(getattr(real, name)))

    def _counted(self, clock):
        def read():
            self.reads += 1
            return clock()
        return read


def _observability_bench(mib: int = 48) -> dict:
    """Tracing overhead bench (ISSUE 12, docs/observability.md): the
    always-on span layer and the session's thread clocks must be
    invisible next to real work.  What it gates on is steady beside a
    loaded neighbour (ROADMAP D0): **unit costs** — a span's open and
    close with no subscriber, a histogram record, a clocked state
    bracket — each the least of several batches on the calling thread's
    own CPU clock, and **counts** — spans closed and clock reads made
    per chunk of one ingest, through the pipelined stream and through a
    session writer's shape (a sequential stream with a batch hasher on a
    clocked thread).  Counts times unit costs over the ingest's own CPU
    seconds is the share tracing takes (``traced_share``, gated < 3 % in
    tests/test_bench_harness.py).  The wall-clock tracing-on vs
    tracing-off ratio of the pipelined ingest is reported beside them
    and gates nothing: it swings with the host's load."""
    import hashlib

    import numpy as np
    from pbs_plus_tpu.chunker import ChunkerParams
    from pbs_plus_tpu.pxar import pipeline, transfer
    from pbs_plus_tpu.pxar.pipeline import PipelinedStream
    from pbs_plus_tpu.utils import trace

    def least_ns(fn, n: int = 5_000, batches: int = 7) -> float:
        least = float("inf")
        for _ in range(batches):
            t0 = time.thread_time()
            fn(n)
            least = min(least, time.thread_time() - t0)
        return least / n * 1e9

    def span_loop(n: int) -> None:
        for _ in range(n):
            with trace.span("job"):
                pass

    def span_hist_loop(n: int) -> None:
        for _ in range(n):
            with trace.span("job.execute", kind="bench"):
                pass

    def record_loop(n: int) -> None:
        for _ in range(n):
            trace.record("mux.write_frame", 1e-6)

    def state_loop(n: int) -> None:
        for _ in range(n):
            with trace.state("store_s"):
                pass

    def clock_loop(n: int) -> None:
        for _ in range(n):
            time.perf_counter_ns()

    span_ns = least_ns(span_loop)
    span_hist_ns = least_ns(span_hist_loop)
    record_ns = least_ns(record_loop)
    unclocked_ns = least_ns(state_loop)
    with trace.clocked(trace.ThreadClock(label="writer")):
        state_ns = least_ns(state_loop)
    clock_ns = least_ns(clock_loop, n=20_000)

    params = ChunkerParams(avg_size=256 << 10)
    data = np.random.default_rng(12).integers(
        0, 256, mib << 20, dtype=np.uint8).tobytes()
    block = 8 << 20
    workers = max(1, min(4, os.cpu_count() or 1))

    def ingest(stream, block: int = block) -> tuple[int, int]:
        for i in range(0, len(data), block):
            stream.write(data[i:i + block])
        return len(stream.finish()), -(-len(data) // block)

    def pipelined() -> tuple[int, int]:
        return ingest(PipelinedStream(_NullStore(), params, workers=workers))

    def session_writer() -> tuple[int, int]:
        """A ``chunker="tpu"`` session's payload stream as the writer's
        thread drives it: writes of a small file's size, hash batches,
        and the session's clock."""
        stream = transfer._ChunkedStream(
            _NullStore(), params,
            batch_hasher=lambda chunks: [hashlib.sha256(c).digest()
                                         for c in chunks])
        with trace.clocked(trace.ThreadClock(label="writer")):
            return ingest(stream, 64 << 10)

    def counted(run) -> dict:
        """Spans, clock reads and CPU seconds of one ingest, per chunk
        and per write."""
        spans: list = []
        clocks = _CountingTime()
        traced = (trace, transfer, pipeline)
        trace.subscribe(spans.append)
        for mod in traced:
            mod.time = clocks
        try:
            cpu0 = time.process_time()
            chunks, writes = run()
            cpu = time.process_time() - cpu0
        finally:
            for mod in traced:
                mod.time = time
            trace.unsubscribe(spans.append)
        cost_ns = len(spans) * span_hist_ns + clocks.reads * clock_ns
        return {"chunks": chunks, "writes": writes,
                "spans_per_chunk": round(len(spans) / chunks, 3),
                "clock_reads_per_chunk": round(clocks.reads / chunks, 3),
                "clock_reads_per_write": round(clocks.reads / writes, 3),
                "traced_share": round(cost_ns / (cpu * 1e9), 5)}

    pipe, writer = counted(pipelined), counted(session_writer)

    # wall clock, reported only: best-of-3 per mode, interleaved
    def ingest_once() -> float:
        t0 = time.perf_counter()
        pipelined()
        return mib / (time.perf_counter() - t0)
    on = off = 0.0
    for _ in range(3):
        with trace.disabled():
            off = max(off, ingest_once())
        on = max(on, ingest_once())
    return {
        "span_overhead_ns": round(span_ns, 1),
        "span_hist_overhead_ns": round(span_hist_ns, 1),
        "hist_record_ns": round(record_ns, 1),
        "state_overhead_ns": round(state_ns, 1),
        "state_unclocked_ns": round(unclocked_ns, 1),
        "clock_read_ns": round(clock_ns, 1),
        "pipelined": pipe,
        "session_writer": writer,
        "traced_share": max(pipe["traced_share"], writer["traced_share"]),
        "ingest_on_mib_s": round(on, 1),
        "ingest_off_mib_s": round(off, 1),
        "on_vs_off": round(on / off, 4) if off else 0.0,
        "ring_capacity": trace._ring.maxlen,
    }


def _resume_bench(mib: int = 64) -> dict | None:
    """Crash-at-50% resume benchmark (docs/data-plane.md "Checkpointed
    resumable backups"): back a tree up with per-file checkpointing,
    kill it via the `pbsstore.chunk.insert` failpoint halfway, resume,
    and report the bytes-re-read ratio plus resume wall-clock.  The
    re-read ratio is (source bytes streamed again) / (source bytes) —
    0.5 means the resume did no better than the crash point, lower is
    the checkpoint splice working."""
    import shutil
    import tempfile

    import numpy as np
    from pbs_plus_tpu.chunker import ChunkerParams
    from pbs_plus_tpu.pxar.backupproxy import LocalStore
    from pbs_plus_tpu.pxar.walker import backup_tree
    from pbs_plus_tpu.server import checkpoint
    from pbs_plus_tpu.utils import failpoints

    params = ChunkerParams(avg_size=1 << 20)
    tmp = tempfile.mkdtemp(prefix="pbs-resume-bench-")
    try:
        src = os.path.join(tmp, "src")
        os.makedirs(src)
        rng = np.random.default_rng(7)
        files = 16
        per = (mib << 20) // files
        total_bytes = files * per
        for i in range(files):
            with open(os.path.join(src, f"f{i:02d}.bin"), "wb") as f:
                f.write(rng.integers(0, 256, per, dtype=np.uint8).tobytes())

        def run(store, *, backup_id, crash_nth=None):
            resume_ctx = checkpoint.open_resume(
                store, backup_type="host", backup_id=backup_id)
            kw = {"previous_reader": resume_ctx[0]} if resume_ctx else {}
            sess = store.start_session(backup_type="host",
                                       backup_id=backup_id, **kw)
            try:
                if resume_ctx:
                    sess.resume_plan = resume_ctx[1]
                checkpoint.attach(sess, "2c")
                if crash_nth:
                    with failpoints.armed("pbsstore.chunk.insert",
                                          "raise", nth=crash_nth):
                        backup_tree(sess, src)
                        man = sess.finish()
                else:
                    backup_tree(sess, src)
                    man = sess.finish()
                checkpoint.clear(store.datastore, "host", backup_id)
                return man, resume_ctx[1] if resume_ctx else None
            except BaseException:
                sess.abort()
                raise

        # probe: total insert count for this tree (checkpointing on)
        probe = LocalStore(os.path.join(tmp, "probe"), params)
        with failpoints.armed("pbsstore.chunk.insert", "delay",
                              arg=0.0) as fp:
            run(probe, backup_id="b")
            total_inserts = fp.hits

        store = LocalStore(os.path.join(tmp, "ds"), params)
        crashed = False
        try:
            run(store, backup_id="b", crash_nth=max(2, total_inserts // 2))
        except Exception:
            crashed = True
        if not crashed:
            return {"note": "crash point never reached; resume not "
                            "measured", "total_inserts": total_inserts}
        t0 = time.perf_counter()
        man, plan = run(store, backup_id="b")
        resume_s = time.perf_counter() - t0
        reread = plan.bytes_reread if plan else total_bytes
        return {
            "source_mib": total_bytes >> 20,
            "crash_at_insert": max(2, total_inserts // 2),
            "total_inserts": total_inserts,
            "files_skipped": plan.files_skipped if plan else 0,
            "bytes_reread": reread,
            "reread_ratio": round(reread / total_bytes, 3),
            "resume_wall_s": round(resume_s, 3),
            "resume_mib_s": round((total_bytes >> 20) / resume_s, 1),
        }
    finally:
        failpoints.disarm_all()
        shutil.rmtree(tmp, ignore_errors=True)


def _read_bench(mib: int = 64, *, window_kib: int = 128,
                chunk_avg: int = 1 << 20) -> dict:
    """Read-path benchmark (docs/data-plane.md "Read path"): restore and
    windowed-read throughput through the chunk cache vs the cold
    single-chunk path.

    Workload: one `mib`-MiB file read (a) end-to-end (restore) and
    (b) in `window_kib`-KiB sequential windows (the ranged `read_at`
    pattern an agent-side restore or FUSE mount produces — ~8 windows
    per 1-MiB chunk, so the uncached path decompresses every chunk ~8x).
    Reported: cold (cache disabled) vs warm (cache + readahead) MiB/s
    and the re-decompression ratio (store loads / distinct chunks; the
    cache should pin it at ~1.0)."""
    import shutil
    import tempfile

    import numpy as np
    from pbs_plus_tpu.chunker import ChunkerParams
    from pbs_plus_tpu.pxar import chunkcache
    from pbs_plus_tpu.pxar.backupproxy import LocalStore
    from pbs_plus_tpu.pxar.format import KIND_DIR, KIND_FILE, Entry

    class _CountingStore:
        def __init__(self, inner):
            self.inner = inner
            self.loads = 0

        def get(self, digest):
            self.loads += 1
            return self.inner.get(digest)

    params = ChunkerParams(avg_size=chunk_avg)
    tmp = tempfile.mkdtemp(prefix="pbs-read-bench-")
    try:
        import io
        store = LocalStore(os.path.join(tmp, "ds"), params)
        rng = np.random.default_rng(11)
        blob = rng.integers(0, 256, mib << 20, dtype=np.uint8).tobytes()
        sess = store.start_session(backup_type="host", backup_id="rb")
        sess.writer.write_entry(Entry(path="", kind=KIND_DIR))
        sess.writer.write_entry_reader(
            Entry(path="f.bin", kind=KIND_FILE), io.BytesIO(blob))
        sess.finish()

        window = window_kib << 10

        def run(cache, *, windowed):
            reader = store.open_snapshot(sess.ref, cache=cache)
            counting = _CountingStore(store.datastore.chunks)
            reader.store = counting
            e = reader.lookup("f.bin")
            t0 = time.perf_counter()
            if windowed:
                for off in range(0, e.size, window):
                    reader.read_file(e, off, window)
            else:
                reader.read_file(e)
            dt = time.perf_counter() - t0
            cache.drain()      # settle in-flight prefetch load counts
            return mib / dt, counting.loads

        chunks = 0
        reader = store.open_snapshot(sess.ref,
                                     cache=chunkcache.ChunkCache(0))
        chunks = len(reader.payload_index)

        # cold single-chunk path: cache disabled, every window pays
        # open+read+decompress+sha per overlapping chunk
        cold_windowed_mib_s, cold_loads = run(
            chunkcache.ChunkCache(0), windowed=True)
        cold_restore_mib_s, _ = run(chunkcache.ChunkCache(0),
                                    windowed=False)

        # warm path: one cache across both passes — the first windowed
        # pass populates (each chunk loaded once), the second measures
        # steady-state serving
        cache = chunkcache.ChunkCache(max(256 << 20, 2 * (mib << 20)),
                                      readahead_chunks=4)
        _, first_pass_loads = run(cache, windowed=True)
        warm_windowed_mib_s, warm_loads = run(cache, windowed=True)
        warm_restore_mib_s, _ = run(cache, windowed=False)

        return {
            "source_mib": mib,
            "window_kib": window_kib,
            "chunk_avg": chunk_avg,
            "payload_chunks": chunks,
            "cold_windowed_mib_s": round(cold_windowed_mib_s, 1),
            "cold_restore_mib_s": round(cold_restore_mib_s, 1),
            "warm_windowed_mib_s": round(warm_windowed_mib_s, 1),
            "warm_restore_mib_s": round(warm_restore_mib_s, 1),
            "warm_vs_cold_windowed": round(
                warm_windowed_mib_s / cold_windowed_mib_s, 2),
            "warm_vs_cold_restore": round(
                warm_restore_mib_s / cold_restore_mib_s, 2),
            # store loads per distinct chunk for the windowed workload:
            # the uncached path re-decompresses ~window-per-chunk times,
            # the cache pins it at 1.0 (populating pass) / 0.0 (warm)
            "cold_redecompress_ratio": round(cold_loads / chunks, 2),
            "cached_redecompress_ratio": round(
                (first_pass_loads + warm_loads) / chunks, 2),
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _index_probe_passes() -> int:
    """Vectorized filter passes every DedupIndex of this process has
    made so far, host and device twin together (one per probe batch)."""
    from pbs_plus_tpu.utils.jaxenv import twin_counts
    return sum(twin_counts.get("index.probe", {}).values())


def _dedup_index_bench(n: int | None = None, *,
                       stat_sample: int = 20_000) -> dict:
    """Dedup-index benchmark (docs/data-plane.md "Dedup index"):
    insert throughput and batched probe rate of the cuckoo-filter
    membership front at ``n`` synthetic digests (default 10^6;
    PBS_PLUS_BENCH_INDEX_N overrides — the ISSUE 8 headline scale is
    10^7), the measured false-positive count over ``n`` non-member
    probes, resident bytes per digest, and the ratio against the
    pre-index membership path: one ``os.stat`` per digest against real
    chunk files (sampled at ``stat_sample`` files so the bench does not
    have to materialize millions of inodes)."""
    import hashlib
    import shutil
    import tempfile

    import numpy as np
    from pbs_plus_tpu.pxar.chunkindex import DedupIndex

    n = n or int(os.environ.get("PBS_PLUS_BENCH_INDEX_N", "1000000"))
    rng = np.random.default_rng(21)
    arr = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    digests = [arr[i].tobytes() for i in range(n)]

    idx = DedupIndex(budget_mb=max(1, (n * 64) >> 20))
    t0 = time.perf_counter()
    idx.insert_many(digests)
    dt_insert = time.perf_counter() - t0

    # warm pass first: the table's zero pages fault in on first touch,
    # and a long-lived server index runs steady-state — that is the
    # honest rate for the gate (the cold pass is reported too)
    t0 = time.perf_counter()
    hits = idx.probe_batch(digests)
    dt_cold = time.perf_counter() - t0
    assert all(hits), "member probe missed"
    passes0 = _index_probe_passes()
    t0 = time.perf_counter()
    hits = idx.probe_batch(digests)
    dt_probe = time.perf_counter() - t0
    probe_passes = _index_probe_passes() - passes0
    assert all(hits), "member probe missed"

    # negative-path probe rate over n NON-member probes
    neg = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    neg_digests = [neg[i].tobytes() for i in range(n)]
    t0 = time.perf_counter()
    neg_hits = idx.probe_batch(neg_digests)
    dt_neg = time.perf_counter() - t0
    assert not any(neg_hits), "exact confirm leaked a non-member"
    # false positives measured at the FILTER layer (probe_batch output
    # is exact-confirmed and can never contain one): maybe-present
    # non-members are the filter's actual misses
    maybe = idx._cuckoo.probe_host(neg)
    import numpy as _np
    fps = sum(1 for i in _np.flatnonzero(maybe)
              if not idx._cuckoo.contains_exact(neg[int(i)].tobytes()))

    # the pre-index path: one stat per digest against real chunk files
    tmp = tempfile.mkdtemp(prefix="pbs-index-bench-")
    try:
        from pbs_plus_tpu.pxar.datastore import ChunkStore
        store = ChunkStore(tmp, index_budget_mb=0)   # legacy, stat-based
        k = min(stat_sample, n)
        sample = []
        for i in range(k):
            data = arr[i].tobytes() * 4
            d = hashlib.sha256(data).digest()
            store.insert(d, data, verify=False)
            sample.append(d)
        t0 = time.perf_counter()
        present = sum(1 for d in sample if store.has(d))
        dt_stat = time.perf_counter() - t0
        assert present == k
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    batched_per_s = n / dt_probe
    stat_per_s = k / dt_stat
    # analytic per-probe FP bound DERIVED from the live filter shape:
    # 2 candidate buckets x SLOTS fingerprints of fp_bits each
    from pbs_plus_tpu.ops.cuckoo import SLOTS
    fp_bits = idx._cuckoo._table.dtype.itemsize * 8 * 2
    return {
        "digests": n,
        "insert_per_s": round(n / dt_insert, 1),
        "batched_probe_per_s": round(batched_per_s, 1),
        "batched_probe_cold_per_s": round(n / dt_cold, 1),
        "negative_probe_per_s": round(n / dt_neg, 1),
        "per_digest_stat_per_s": round(stat_per_s, 1),
        "batched_vs_stat": round(batched_per_s / stat_per_s, 1),
        "batched_probe_passes": probe_passes,
        "false_positives": int(fps),
        "fp_rate_bound": 2 * SLOTS / 2.0 ** fp_bits,
        "stat_sample": k,
        "resident_bytes_per_digest": round(idx.resident_bytes / n, 1),
        "table_bytes": idx.table_bytes,
        "n_buckets": idx.n_buckets,
    }


def _dist_index_bench(n: int | None = None, *, batch: int = 8192,
                      rounds: int = 50) -> dict:
    """Distributed dedup index benchmark (ISSUE 16, docs/dist-index.md):
    two in-process ``IndexShardServer`` nodes behind a
    ``DistIndexClient`` vs a local single-process ``DedupIndex`` on the
    SAME synthetic corpus (default 4*10^4 digests;
    PBS_PLUS_BENCH_DIST_N overrides).  Reports the ISSUE 16 gates:

    - structural wire accounting: one ``batch``-digest probe costs
      <= shards HTTP requests (counted via the METRICS delta, not
      timed);
    - batched probe p99 over ``rounds`` rotating batches vs the local
      index's p99 on identical batches, measured back-to-back within
      each round so both paths see the same machine phases (<= 3x gate
      — the fan-out amortizes the loopback round-trips across the
      whole batch);
    - live rebalance 2 -> 3 shards, then a digest-for-digest audit over
      ``/digests`` of every node: full coverage, zero multi-owned,
      zero held off-owner under the new map;
    - restore equivalence: a dist-indexed and a local-indexed
      ChunkStore fed the same chunk sequence return bit-identical
      bytes for every digest."""
    import hashlib
    import shutil
    import tempfile

    import numpy as np
    from pbs_plus_tpu.parallel.dist_index import (
        METRICS, DistIndexClient, IndexShardServer, ShardMap)
    from pbs_plus_tpu.pxar.chunkindex import DedupIndex

    n = n or int(os.environ.get("PBS_PLUS_BENCH_DIST_N", "40000"))
    batch = min(batch, n)
    rng = np.random.default_rng(16)
    arr = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    corpus = [arr[i].tobytes() for i in range(n)]

    tmp = tempfile.mkdtemp(prefix="pbs-dist-bench-")
    servers: list = []
    client = None
    try:
        # the local baseline runs the SAME spillable engine a shard
        # node runs — the ratio isolates the wire, not the index
        local = DedupIndex(budget_mb=8, spill_dir=os.path.join(tmp, "local"),
                           resident_mb=8)
        local.mark_booted()
        local.insert_many(corpus)

        for sid in ("b0", "b1"):
            idx = DedupIndex(budget_mb=8, spill_dir=os.path.join(tmp, sid),
                             resident_mb=8)
            idx.mark_booted()
            srv = IndexShardServer(sid, idx)
            srv.start()
            servers.append(srv)
        m = ShardMap([(s.shard_id, s.endpoint) for s in servers], epoch=1)
        for s in servers:
            s.install_map(m)
        client = DistIndexClient(m)
        for lo in range(0, n, batch):
            client.insert_many(corpus[lo:lo + batch])

        # structural wire accounting over one whole probe batch
        before = METRICS.snapshot()
        client.probe_batch(corpus[:batch] + corpus[:64])   # 64 intra dups
        delta = {k: v - before[k] for k, v in METRICS.snapshot().items()}

        # paired latency rounds: local and dist probe the SAME batch
        # back to back, so scheduler noise on this one-core box hits
        # both tails alike
        local.probe_batch(corpus[:batch])                  # warm passes
        client.probe_batch(corpus[:batch])
        t_local: list = []
        t_dist: list = []
        for r in range(rounds):
            lo = (r * batch) % n
            b = corpus[lo:lo + batch]
            if len(b) < batch:
                b = b + corpus[:batch - len(b)]
            t0 = time.perf_counter()
            got = local.probe_batch(b)
            t_local.append(time.perf_counter() - t0)
            assert all(got), "local member probe missed"
            t0 = time.perf_counter()
            got = client.probe_batch(b)
            t_dist.append(time.perf_counter() - t0)
            assert all(got), "dist member probe missed"
        local_p99 = float(np.percentile(t_local, 99))
        dist_p99 = float(np.percentile(t_dist, 99))

        # grow the ring under the running client: 2 -> 3
        idx3 = DedupIndex(budget_mb=8, spill_dir=os.path.join(tmp, "b2"),
                          resident_mb=8)
        idx3.mark_booted()
        s3 = IndexShardServer("b2", idx3)
        s3.start()
        servers.append(s3)
        new_map = ShardMap([(s.shard_id, s.endpoint) for s in servers],
                           epoch=2)
        reb = client.rebalance(new_map)
        holders: dict = {}
        multi_owned = 0
        misrouted = 0
        for si, s in enumerate(servers):
            for d in s.index.digests():
                if d in holders:
                    multi_owned += 1
                holders[d] = si
                if new_map.owner_of(d) != si:
                    misrouted += 1

        # restore equivalence through real stores, dist vs local index
        from pbs_plus_tpu.pxar.datastore import ChunkStore
        dist_store = ChunkStore(os.path.join(tmp, "ds"), index=client)
        local_store = ChunkStore(os.path.join(tmp, "ls"), index_budget_mb=4)
        restore_match = True
        rchunks = []
        for i in range(128):
            data = arr[i % n].tobytes() * (8 + i % 5)
            d = hashlib.sha256(data).digest()
            rchunks.append((d, data))
            dist_store.insert(d, data, verify=False)
            local_store.insert(d, data, verify=False)
        for d, data in rchunks:
            if not (dist_store.get(d) == local_store.get(d) == data):
                restore_match = False

        return {
            "digests": n,
            "batch": batch,
            "shards": 2,
            "rounds": rounds,
            "local_p99_ms": round(local_p99 * 1e3, 3),
            "dist_p99_ms": round(dist_p99 * 1e3, 3),
            "p99_ratio": round(dist_p99 / local_p99, 2),
            "wire_requests_per_batch": delta["wire_requests"],
            "batch_dedup_saved": delta["dedup_saved"],
            "rebalance": reb,
            "owners_covered": len(holders),
            "multi_owned": multi_owned,
            "misrouted": misrouted,
            "restore_match": restore_match,
        }
    finally:
        if client is not None:
            client.close()
        for s in servers:
            s.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def _digestlog_bench(n: int | None = None, *,
                     stat_sample: int = 20_000) -> dict:
    """Spillable exact-confirm tier benchmark (ISSUE 14,
    docs/data-plane.md "Spillable exact-confirm tier"): index ``n``
    synthetic digests (default 10^6; PBS_PLUS_BENCH_INDEX_N overrides —
    the slow-marked profile runs 10^7) through a DedupIndex whose
    confirm tier is deliberately SQUEEZED so the memtable really spills
    to segments, then gate the three ISSUE 14 properties:

    - peak measured resident index bytes (filter table + memtable +
      fence pointers, sampled per insert batch) <= 2x the configured
      PBS_PLUS_DEDUP_RESIDENT_MB budget;
    - batched member-probe throughput >= 5x the per-digest stat
      baseline (the pre-index membership path), even though every
      confirm now sweeps on-disk segments;
    - an all-novel probe pass performs ZERO confirm reads —
      structurally asserted via the digestlog confirm_reads counter,
      because negatives never get past the filter."""
    import shutil
    import tempfile

    import numpy as np
    from pbs_plus_tpu.pxar import digestlog as _dl
    from pbs_plus_tpu.pxar.chunkindex import DedupIndex

    n = n or int(os.environ.get("PBS_PLUS_BENCH_INDEX_N", "1000000"))
    resident_mb = max(16, (n * 24) >> 20)
    filter_mb = max(4, (n * 12) >> 20)
    batch = 1 << 18
    tmp = tempfile.mkdtemp(prefix="pbs-digestlog-bench-")
    try:
        idx = DedupIndex(budget_mb=filter_mb, spill_dir=tmp,
                         resident_mb=resident_mb)
        m0 = _dl.metrics_snapshot()
        probe_passes = 0

        def batches(seed):
            rng = np.random.default_rng(seed)
            left = n
            while left > 0:
                k = min(batch, left)
                yield rng.integers(0, 256, (k, 32), dtype=np.uint8)
                left -= k

        peak_resident = 0
        t0 = time.perf_counter()
        for arr in batches(31):
            idx.insert_many([arr[i].tobytes() for i in range(len(arr))])
            peak_resident = max(peak_resident, idx.resident_bytes)
        dt_insert = time.perf_counter() - t0
        idx.digestlog.flush()
        idx.digestlog.compact(wait=True)
        peak_resident = max(peak_resident, idx.resident_bytes)

        # member probes: every digest re-probed in index-sized batches,
        # warm best-of-2 (steady-state page cache, like the dedup-index
        # bench's warm pass).  Only the probe_batch call is timed — a
        # real writer already holds the digest bytes its hasher
        # produced; the list build here is bench scaffolding
        def probe_all(seed: int, expect: bool,
                      probe_batch_n: int = 1 << 20
                      ) -> "tuple[float, int]":
            spent = 0.0
            wrong = 0
            pending: list[bytes] = []

            def run_pending():
                nonlocal spent, wrong, probe_passes
                probe_passes -= _index_probe_passes()
                t0 = time.perf_counter()
                out = idx.probe_batch(pending)
                spent += time.perf_counter() - t0
                probe_passes += _index_probe_passes()
                wrong += sum(1 for o in out if o is not expect)
                pending.clear()

            for arr in batches(seed):
                pending.extend(arr[i].tobytes() for i in range(len(arr)))
                if len(pending) >= probe_batch_n:
                    run_pending()
            if pending:
                run_pending()
            return spent, wrong

        dt_cold, miss = probe_all(31, True)
        if miss:
            raise AssertionError(f"member confirm missed {miss}")
        dt_probe, miss = probe_all(31, True)
        dt_probe = min(dt_cold, dt_probe)
        if miss:
            raise AssertionError(f"member confirm missed {miss}")

        # all-novel probes: the filter answers every one of these
        # without a single segment read — the structural zero
        cr0 = _dl.metrics_snapshot()["confirm_reads"]
        dt_neg, novel_hits = probe_all(77, False)
        novel_confirm_reads = _dl.metrics_snapshot()["confirm_reads"] - cr0
        if novel_hits:
            raise AssertionError("novel digest answered present")

        # the pre-index membership path: one stat per digest against
        # real chunk files (sampled; same baseline as the dedup-index
        # bench)
        import hashlib
        stat_tmp = tempfile.mkdtemp(prefix="pbs-digestlog-stat-")
        try:
            from pbs_plus_tpu.pxar.datastore import ChunkStore
            store = ChunkStore(stat_tmp, index_budget_mb=0)
            k = min(stat_sample, n)
            rng = np.random.default_rng(31)
            sample = []
            seed_arr = rng.integers(0, 256, (k, 32), dtype=np.uint8)
            for i in range(k):
                data = seed_arr[i].tobytes() * 4
                d = hashlib.sha256(data).digest()
                store.insert(d, data, verify=False)
                sample.append(d)
            t0 = time.perf_counter()
            present = sum(1 for d in sample if store.has(d))
            dt_stat = time.perf_counter() - t0
            assert present == k
        finally:
            shutil.rmtree(stat_tmp, ignore_errors=True)

        m1 = _dl.metrics_snapshot()
        budget = resident_mb << 20
        probe_per_s = n / dt_probe
        stat_per_s = k / dt_stat
        out = {
            "digests": n,
            "resident_budget_mb": resident_mb,
            "filter_budget_mb": filter_mb,
            "insert_per_s": round(n / dt_insert, 1),
            "batched_probe_per_s": round(probe_per_s, 1),
            "batched_probe_cold_per_s": round(n / dt_cold, 1),
            "negative_probe_per_s": round(n / dt_neg, 1),
            "per_digest_stat_per_s": round(stat_per_s, 1),
            "batched_vs_stat": round(probe_per_s / stat_per_s, 1),
            "batched_probe_passes": probe_passes,
            "peak_resident_bytes": peak_resident,
            "resident_bytes": idx.resident_bytes,
            "resident_vs_budget": round(peak_resident / budget, 3),
            "resident_bytes_per_digest": round(peak_resident / n, 1),
            "novel_confirm_reads": int(novel_confirm_reads),
            "spills": m1["spills"] - m0["spills"],
            "compactions": m1["compactions"] - m0["compactions"],
            "segments": idx.digestlog.segment_count,
            "confirm_reads_total": m1["confirm_reads"]
            - m0["confirm_reads"],
            "memtable_entries": len(idx.digestlog._mem),
        }
        cap = _captured_digestlog_1e7()
        if cap is not None and n != cap.get("digests"):
            # the committed headline-scale profile rides along so every
            # bench JSON carries the 10^7 gates' evidence
            out["profile_1e7"] = cap
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _corpus_base(size: int, seed_dir: str) -> "bytes | None":
    """A deterministic VM-image-style base built from REAL file bytes
    under ``seed_dir`` (sorted walk, regular files only) — the
    2409.06066 point is that synthetic random bytes misrepresent both
    compressibility and near-dup structure.  None when the seed dir
    cannot supply ``size`` bytes (caller falls back to synthetic)."""
    parts: list[bytes] = []
    total = 0
    try:
        for root, dirs, files in sorted(os.walk(seed_dir)):
            dirs.sort()
            for name in sorted(files):
                p = os.path.join(root, name)
                try:
                    if os.path.islink(p) or not os.path.isfile(p):
                        continue
                    with open(p, "rb") as f:
                        data = f.read(min(4 << 20, size - total))
                except OSError:
                    continue
                if data:
                    parts.append(data)
                    total += len(data)
                if total >= size:
                    return b"".join(parts)[:size]
    except OSError:
        return None
    return None


def _mutate_generation(prev: "np.ndarray", rng, *, edit_frac: float,
                       edit_block: int = 4096) -> "np.ndarray":
    """One VM-image / rotated-log style generation (2409.06066): the
    bulk of the image is untouched (the exact tier's job), a clustered
    fraction of blocks gets small in-place patches (the similarity
    tier's job — near-dup, not novel), a couple of small inserts shift
    downstream content (the CDC resync case), and a log tail grows and
    rotates."""
    import numpy as np
    g = prev.copy()
    size = len(g)
    n_blocks = max(1, int(size * edit_frac) // edit_block)
    starts = rng.integers(0, max(1, size - edit_block), n_blocks)
    for s in np.sort(starts):
        s = int(s)
        span = int(rng.integers(edit_block // 8, edit_block // 2))
        off = int(rng.integers(0, edit_block - span))
        patch = g[s + off:s + off + span].copy()
        # small-valued xor + a sprinkle of fresh bytes: the block stays
        # resemblance-close to its previous generation (hot DB pages,
        # rewritten package files), never byte-identical
        patch ^= rng.integers(1, 16, span, dtype=np.uint8)
        sprinkle = rng.integers(0, span, max(1, span // 64))
        patch[sprinkle] = rng.integers(0, 256, len(sprinkle),
                                       dtype=np.uint8)
        g[s + off:s + off + span] = patch
    # 1-3 small inserts: downstream bytes shift, CDC must re-sync cuts
    pieces = []
    prev_end = 0
    for pos in np.sort(rng.integers(0, size, int(rng.integers(1, 4)))):
        pos = int(pos)
        pieces.append(g[prev_end:pos])
        pieces.append(rng.integers(0, 256, int(rng.integers(16, 256)),
                                   dtype=np.uint8))
        prev_end = pos
    pieces.append(g[prev_end:])
    # rotated-log tail: ~64 KiB of fresh timestamped lines per
    # generation, oldest 64 KiB rotated off the front of the tail
    lines = b"".join(
        b"%08d INFO worker-%02d request served bytes=%06d\n"
        % (int(rng.integers(0, 10**8)), int(rng.integers(0, 32)),
           int(rng.integers(0, 10**6))) for _ in range(1200))
    pieces.append(np.frombuffer(lines, dtype=np.uint8))
    out = np.concatenate(pieces)
    return out[:size + (64 << 10)]       # bounded drift per generation


def _delta_bench(mib: int = 16, *, generations: int = 6,
                 mutate_frac: float = 0.005,
                 chunk_avg: int = 64 << 10,
                 profile: str = "auto",
                 seed_dir: "str | None" = None,
                 edit_frac: float = 0.2) -> dict:
    """Similarity-tier benchmark (docs/data-plane.md "Similarity
    tier"): a near-duplicate corpus per the CDC-survey methodology
    (arXiv 2409.06066) backed up into a tier-off and a tier-on store.

    ``profile`` selects the mutation stream:

    - ``"real-corpus"``: the base image is REAL file bytes (``seed_dir``,
      default ``PBS_PLUS_BENCH_CORPUS_DIR`` or /usr/bin) and each
      generation applies VM-image / rotated-log style mutations —
      clustered block patches (near-dup chunks), small inserts (CDC
      resync), a growing log tail.  The exact tier dedups the untouched
      majority; the ≥1.5x tier-on gate then measures what a user with
      real images would see.
    - ``"synthetic"``: the legacy generator — random bytes, a scattered
      ``mutate_frac`` of them flipped per generation, which makes every
      chunk novel to the exact tier (the isolation profile).
    - ``"auto"``: real-corpus when the seed dir can supply the bytes,
      else synthetic (the documented fallback).

    Reported: dedup ratio (logical payload bytes / on-disk chunk bytes)
    for both stores, the tier-on/tier-off improvement (gated >= 1.5x in
    tests/test_bench_harness.py for both profiles), exact-tier dedup
    evidence, and the pbs_plus_delta_* counters the run produced."""
    import io
    import shutil
    import tempfile

    import numpy as np
    from pbs_plus_tpu.chunker import ChunkerParams
    from pbs_plus_tpu.pxar.backupproxy import LocalStore
    from pbs_plus_tpu.pxar.format import KIND_DIR, KIND_FILE, Entry
    from pbs_plus_tpu.pxar.similarityindex import metrics_snapshot

    params = ChunkerParams(avg_size=chunk_avg)
    rng = np.random.default_rng(17)
    per_gen = (mib << 20) // generations

    base = None
    if profile in ("auto", "real-corpus"):
        seed_dir = seed_dir or os.environ.get(
            "PBS_PLUS_BENCH_CORPUS_DIR", "/usr/bin")
        raw = _corpus_base(per_gen, seed_dir)
        if raw is not None:
            base = np.frombuffer(raw, dtype=np.uint8)
        elif profile == "real-corpus":
            raise RuntimeError(
                f"corpus seed dir {seed_dir!r} cannot supply "
                f"{per_gen} bytes")
    if base is not None:
        profile_used = f"real-corpus({seed_dir})"
        gens = [base]
        for _ in range(generations - 1):
            gens.append(_mutate_generation(gens[-1], rng,
                                           edit_frac=edit_frac))
    else:
        profile_used = "synthetic-random"
        gens = [rng.integers(0, 256, per_gen, dtype=np.uint8)]
        n_mut = max(1, int(per_gen * mutate_frac))
        for _ in range(generations - 1):
            g = gens[-1].copy()
            idx = rng.choice(per_gen, n_mut, replace=False)
            g[idx] = rng.integers(0, 256, n_mut, dtype=np.uint8)
            gens.append(g)
    logical = sum(len(g) for g in gens)

    tmp = tempfile.mkdtemp(prefix="pbs-delta-bench-")
    try:
        def chunk_disk_bytes(store):
            base = store.datastore.chunks.base
            total = 0
            for dirpath, _dirs, files in os.walk(base):
                for f in files:
                    total += os.path.getsize(os.path.join(dirpath, f))
            return total

        def run(name, **delta_kw):
            store = LocalStore(os.path.join(tmp, name), params, **delta_kw)
            sess = store.start_session(backup_type="host", backup_id="d")
            sess.writer.write_entry(Entry(path="", kind=KIND_DIR))
            for i, g in enumerate(gens):
                sess.writer.write_entry_reader(
                    Entry(path=f"gen{i:02d}.bin", kind=KIND_FILE),
                    io.BytesIO(g.tobytes()))
            man = sess.finish()
            return store, sess.ref, man

        m0 = metrics_snapshot()
        off_store, off_ref, off_man = run("off", delta_tier=False)
        t0 = time.perf_counter()
        on_store, on_ref, _on_man = run("on", delta_tier=True)
        on_wall = time.perf_counter() - t0
        m1 = metrics_snapshot()

        off_disk = chunk_disk_bytes(off_store)
        on_disk = chunk_disk_bytes(on_store)
        ratio_off = logical / off_disk
        ratio_on = logical / on_disk

        # restore parity: the tier must not change a single byte
        r_on = on_store.open_snapshot(on_ref)
        r_off = off_store.open_snapshot(off_ref)
        for i, g in enumerate(gens):
            e = r_on.lookup(f"gen{i:02d}.bin")
            if r_on.read_file(e) != g.tobytes():
                raise AssertionError("tier-on restore diverged from source")
        if [r for r in r_on.payload_index.records()] != \
                [r for r in r_off.payload_index.records()]:
            raise AssertionError("tier-on index records diverged")

        return {
            "source_mib": logical >> 20,
            "generations": generations,
            "profile": profile_used,
            "mutate_frac": mutate_frac,
            "chunk_avg": chunk_avg,
            # exact-tier evidence: on the synthetic profile every chunk
            # past gen0 is novel (known ≈ 0); on the real-corpus
            # profile the untouched majority dedups exactly and the
            # delta win is measured ON TOP of that
            "exact_known_chunks_off": off_man["stats"]["known_chunks"],
            "exact_new_chunks_off": off_man["stats"]["new_chunks"],
            "dedup_ratio_off": round(ratio_off, 2),
            "dedup_ratio_on": round(ratio_on, 2),
            "on_vs_off": round(ratio_on / ratio_off, 2),
            "disk_bytes_off": off_disk,
            "disk_bytes_on": on_disk,
            "tier_on_wall_s": round(on_wall, 3),
            "delta_probes": m1["probes"] - m0["probes"],
            "delta_hits": m1["hits"] - m0["hits"],
            "delta_bytes_saved": m1["bytes_saved"] - m0["bytes_saved"],
            "delta_chain_rejects": m1["chain_rejects"]
            - m0["chain_rejects"],
            "restore_parity": True,
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _sync_bench(mib: int = 16, *, chunk_avg: int = 64 << 10,
                mutate_frac: float = 0.005) -> dict:
    """Datastore-replication benchmark (docs/sync.md): back a ``mib``
    random file up into a source store, mirror it into an empty
    destination (the INITIAL sync — every chunk crosses the wire,
    compressed-as-stored), then mutate a contiguous ``mutate_frac``
    region (the realistic near-dup shape: localized edits / appended
    logs), back up the new generation and re-sync (the INCREMENTAL
    sync — the batched destination probes skip everything but the
    dirtied chunks).  Reported: wire bytes for both runs, their ratio
    (gated <= 10% in tests/test_bench_harness.py), probe batches,
    chunks skipped, and a third no-op re-sync proving zero transfer
    for an unchanged group."""
    import io
    import shutil
    import tempfile

    import numpy as np
    from pbs_plus_tpu.chunker import ChunkerParams
    from pbs_plus_tpu.pxar.backupproxy import LocalStore
    from pbs_plus_tpu.pxar.datastore import Datastore
    from pbs_plus_tpu.pxar.format import KIND_DIR, KIND_FILE, Entry
    from pbs_plus_tpu.pxar.syncwire import (LocalSyncDest,
                                            LocalSyncSource, run_sync)

    params = ChunkerParams(avg_size=chunk_avg)
    rng = np.random.default_rng(23)
    size = mib << 20
    gen0 = rng.integers(0, 256, size, dtype=np.uint8)

    tmp = tempfile.mkdtemp(prefix="pbs-sync-bench-")
    try:
        src = LocalStore(os.path.join(tmp, "src"), params)

        def backup(data: np.ndarray) -> None:
            sess = src.start_session(backup_type="host", backup_id="s")
            sess.writer.write_entry(Entry(path="", kind=KIND_DIR))
            sess.writer.write_entry_reader(
                Entry(path="data.bin", kind=KIND_FILE),
                io.BytesIO(data.tobytes()))
            sess.finish()

        backup(gen0)
        dst = Datastore(os.path.join(tmp, "dst"))
        source = LocalSyncSource(src.datastore)
        dest = LocalSyncDest(dst)

        t0 = time.perf_counter()
        initial = run_sync(source, dest, job_id="bench",
                           state_root=os.path.join(tmp, "dst"))
        t_init = time.perf_counter() - t0

        # generation 2: one contiguous mutate_frac region rewritten
        gen1 = gen0.copy()
        n_mut = max(1, int(size * mutate_frac))
        start = int(rng.integers(0, size - n_mut))
        gen1[start:start + n_mut] = rng.integers(0, 256, n_mut,
                                                 dtype=np.uint8)
        backup(gen1)

        t0 = time.perf_counter()
        incr = run_sync(source, dest, job_id="bench",
                        state_root=os.path.join(tmp, "dst"))
        t_incr = time.perf_counter() - t0
        resync = run_sync(source, dest, job_id="bench",
                          state_root=os.path.join(tmp, "dst"))

        return {
            "source_mib": mib,
            "chunk_avg": chunk_avg,
            "mutate_frac": mutate_frac,
            "initial_wire_bytes": initial["bytes_wire"],
            "initial_chunks": initial["chunks_transferred"],
            "initial_probe_batches": initial["probe_batches"],
            "initial_wall_s": round(t_init, 3),
            "incremental_wire_bytes": incr["bytes_wire"],
            "incremental_chunks": incr["chunks_transferred"],
            "incremental_chunks_skipped": incr["chunks_skipped"],
            "incremental_probe_batches": incr["probe_batches"],
            "incremental_wall_s": round(t_incr, 3),
            "wire_ratio": round(incr["bytes_wire"]
                                / max(1, initial["bytes_wire"]), 4),
            "resync_chunks": resync["chunks_transferred"],
            "resync_wire_bytes": resync["bytes_wire"],
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _captured_digestlog_1e7() -> dict | None:
    """The slow-marked 10^7 digestlog profile captured by an explicit
    ``PBS_PLUS_BENCH_INDEX_N=10000000`` run (ROADMAP item 3's open
    remainder, exercised in ISSUE 15's round) — committed at
    tools/bench_digestlog_1e7.json and attached to detail.digestlog so
    the headline-scale numbers ride every bench JSON without every run
    paying the multi-minute insert."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "bench_digestlog_1e7.json")
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            res = json.load(f)
        return res if res.get("digests") == 10_000_000 else None
    except Exception:
        return None


def _multiproc_bench(n_agents: int | None = None) -> dict:
    """Two-process shared-datastore soak (ISSUE 15, docs/fleet.md
    "Two-process shared datastore"): two REAL server subprocesses over
    one datastore + one DB — all jobs publish through the shared
    bounded queue, every shared chunk is written exactly once across
    processes (os.link claim; dedup accounting summed across both
    processes' /metrics), GC fires exactly once per cycle under the
    leader lease, and a SIGKILLed leader mid-sweep fails over within
    one lease TTL.  ``PBS_PLUS_BENCH_MULTIPROC_N`` overrides the
    per-process agent count."""
    import shutil
    import tempfile

    from pbs_plus_tpu.server.fleetsim import (MultiProcConfig,
                                              run_multiproc_fleet)

    n = n_agents or int(os.environ.get("PBS_PLUS_BENCH_MULTIPROC_N", "6"))
    tmp = tempfile.mkdtemp(prefix="pbs-multiproc-bench-")
    try:
        cfg = MultiProcConfig(n_agents=n, gc_ttl_s=2.0,
                              kill_slow_sweep_s=6.0)
        rep = run_multiproc_fleet(tmp, cfg)
        out = rep.to_dict()
        if rep.failures:
            out["failures"] = dict(sorted(rep.failures.items())[:5])
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _fleet_bench(n_agents: int | None = None) -> dict:
    """Loopback fleet soak (docs/fleet.md): N simulated agents speak real
    aRPC through AgentsManager admission and the fair jobs plane, one
    synthetic backup each.  Reports enqueue-to-publish p50/p99,
    session-open admission latency, mux frame throughput, admission
    verdict counts, and the maximum observed depth of every bounded
    queue.  ``PBS_PLUS_BENCH_FLEET_N`` overrides the agent count."""
    import shutil
    import tempfile

    from pbs_plus_tpu.server.fleetsim import FleetConfig, run_fleet

    n = n_agents or int(os.environ.get("PBS_PLUS_BENCH_FLEET_N", "100"))
    tmp = tempfile.mkdtemp(prefix="pbs-fleet-bench-")
    try:
        cfg = FleetConfig(n_agents=n, tenants=8, max_concurrent=8,
                          max_queued=2 * n)
        rep = run_fleet(os.path.join(tmp, "ds"), cfg)
        out = rep.to_dict()
        if rep.failures:
            out["failures"] = dict(sorted(rep.failures.items())[:5])
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _mountserve_bench(*, n_snapshots: int | None = None,
                      files_per_snapshot: int = 2,
                      file_size: int = 192 << 10,
                      chunk_avg: int = 16 << 10,
                      cache_kib: int = 256,
                      zipf_trace_len: int = 1200,
                      zipf_s: float = 1.1,
                      seed: int = 7) -> dict:
    """Mount-serve read-plane benchmark (ISSUE 20; docs/data-plane.md
    "Read path"): the sharded scan-resistant cache + adaptive readahead
    under the serving workload shape — a Zipf-hot working set of mount
    reads with full sequential restore scans barreling through the same
    cache, concurrent with backup ingest.

    The host is 1-core, so every gate is an ALGORITHMIC ratio from the
    cache counters and the shared /metrics histograms (no wall-clock
    thresholds):
    - ``zipf_hit_ratio`` vs ``lru_hit_ratio``: the same chunk trace
      replayed through the sharded segmented-LRU cache and through an
      in-bench plain-LRU reference — scan resistance must win strictly.
    - ``hot_hit_ratio_before``/``under_scan``: a promoted hot set
      probed while a full sequential scan runs concurrently through
      the same cache; degradation bounded.
    - ``seq_amplification``: store bytes loaded / distinct chunk bytes
      for one sequential restore with adaptive readahead on (~1.0 —
      readahead never reads past the index, single-flight dedups).
    - ``readahead_precision``: prefetch_used / prefetch_issued for the
      sequential scan.
    - ``ingest_published``/``readserve_completed``: a small fleetsim
      mix (tenant="readserve" readers vs backup ingest through the
      same admission/fairness lanes) — zero starvation both ways.
    """
    import shutil
    import tempfile
    import threading

    import numpy as np
    from pbs_plus_tpu.chunker import ChunkerParams
    from pbs_plus_tpu.pxar import chunkcache
    from pbs_plus_tpu.pxar.backupproxy import LocalStore
    from pbs_plus_tpu.pxar.format import KIND_DIR, KIND_FILE, Entry
    from pbs_plus_tpu.server import metrics
    from pbs_plus_tpu.server.fleetsim import (FleetConfig, run_fleet,
                                              zipf_rank)

    n_snapshots = n_snapshots or int(
        os.environ.get("PBS_PLUS_BENCH_MOUNTSERVE_N", "6"))
    params = ChunkerParams(avg_size=chunk_avg)
    rng = np.random.default_rng(seed)
    import random as _random
    prng = _random.Random(seed)
    fetch_base = metrics.HISTOGRAMS[
        "pbs_plus_chunk_cache_fetch_seconds"].snapshot()
    tmp = tempfile.mkdtemp(prefix="pbs-mountserve-bench-")
    try:
        import io
        store = LocalStore(os.path.join(tmp, "ds"), params)
        refs = []
        for si in range(n_snapshots):
            sess = store.start_session(backup_type="host",
                                       backup_id=f"ms{si:02d}")
            sess.writer.write_entry(Entry(path="", kind=KIND_DIR))
            for fi in range(files_per_snapshot):
                blob = rng.integers(0, 256, file_size,
                                    dtype=np.uint8).tobytes()
                sess.writer.write_entry_reader(
                    Entry(path=f"f{fi}.bin", kind=KIND_FILE,
                          size=len(blob)), io.BytesIO(blob))
            sess.finish()
            refs.append(sess.ref)

        chunks = store.datastore.chunks
        readers = [store.open_snapshot(r, cache=chunkcache.ChunkCache(0))
                   for r in refs]
        # every distinct payload chunk, snapshot-ordered (the sequential
        # scan); sizes for the amplification denominator
        all_digests = []
        seen = set()
        for rd in readers:
            idx = rd.payload_index
            for ci in range(len(idx)):
                d = idx.digest(ci)
                if d not in seen:
                    seen.add(d)
                    all_digests.append(d)
        sizes = {d: len(chunks.get(d)) for d in all_digests}

        # -- (1) Zipf + periodic scans: sharded SLRU vs plain-LRU replay
        # hot ranks re-referenced Zipf-style, a full one-pass scan
        # injected every ~third of the trace (the restore storms)
        trace_ = []
        scan_every = max(1, zipf_trace_len // 3)
        for t in range(zipf_trace_len):
            trace_.append(all_digests[
                zipf_rank(prng, len(all_digests), zipf_s)])
            if t and t % scan_every == 0:
                trace_.extend(all_digests)      # sequential scan burst

        budget = cache_kib << 10
        cache = chunkcache.ChunkCache(budget, shards=4,
                                      readahead_chunks=0)
        zstats = {"hits": 0, "misses": 0}
        for d in trace_:
            cache.get(chunks, d, zstats)
        zipf_hit_ratio = zstats["hits"] / len(trace_)

        lru: dict = {}
        lru_size = 0
        lru_hits = 0
        for d in trace_:
            if d in lru:
                lru_hits += 1
                lru[d] = lru.pop(d)
            elif sizes[d] <= budget:
                lru[d] = sizes[d]
                lru_size += sizes[d]
                while lru_size > budget:
                    lru_size -= lru.pop(next(iter(lru)))
        lru_hit_ratio = lru_hits / len(trace_)

        # -- (2) hot-set hit ratio under a CONCURRENT sequential scan --
        # hot set sized to fit each segment's protected region with
        # digest-shard skew (the property under test is scan eviction,
        # not capacity thrash); the scan set still dwarfs the budget
        hot = sorted({all_digests[zipf_rank(prng, len(all_digests),
                                            zipf_s)]
                      for _ in range(200)},
                     key=all_digests.index)[:max(4, len(all_digests) // 16)]
        cache2 = chunkcache.ChunkCache(2 * budget, shards=4,
                                       readahead_chunks=0)
        for _ in range(2):                      # admit, then promote
            for d in hot:
                cache2.get(chunks, d)
        before = {"hits": 0, "misses": 0}
        for d in hot:
            cache2.get(chunks, d, before)
        hot_before = before["hits"] / max(1, sum(before.values()))

        scans_done = threading.Event()

        def _scan():
            try:
                for _ in range(2):
                    for d in all_digests:       # one-pass cold scans
                        cache2.get(chunks, d)
            finally:
                scans_done.set()

        scanner = threading.Thread(target=_scan)
        scanner.start()
        under = {"hits": 0, "misses": 0}
        while not scans_done.is_set():
            for d in hot:
                cache2.get(chunks, d, under)
        scanner.join()
        for d in hot:                           # and after it passed
            cache2.get(chunks, d, under)
        hot_under_scan = under["hits"] / max(1, sum(under.values()))

        # -- (3) sequential restore: amplification + readahead precision
        class _ByteCountingStore:
            def __init__(self, inner):
                self.inner = inner
                self.bytes_read = 0
                self._lock = threading.Lock()

            def get(self, digest):
                data = self.inner.get(digest)
                with self._lock:
                    self.bytes_read += len(data)
                return data

        counting = _ByteCountingStore(chunks)
        seq_cache = chunkcache.ChunkCache(256 << 20, readahead_chunks=4,
                                          readahead_max=32)
        logical = 0
        window = 32 << 10
        for ref in refs:
            rd = store.open_snapshot(ref, cache=seq_cache)
            rd.store = counting
            for e in rd.entries():
                if not e.is_file:
                    continue
                # the paced mount-reader shape: window-sized pump with
                # the prefetch pool allowed to stay ahead (on a 1-core
                # host an unpaced read races its own readahead and the
                # precision measurement collapses into the race)
                fobj, _n = rd.file_reader(e)
                while True:
                    piece = fobj.read(window)
                    if not piece:
                        break
                    logical += len(piece)
                    seq_cache.drain()
        seq_cache.drain()
        distinct_bytes = sum(sizes.values())
        seq_snap = seq_cache.snapshot()
        seq_amplification = counting.bytes_read / max(1, distinct_bytes)
        precision = (seq_snap["prefetch_used"]
                     / max(1, seq_snap["prefetch_issued"]))

        # -- (4) read+ingest mix through the real fairness lanes -------
        fleet_cfg = FleetConfig(
            n_agents=4, tenants=2, max_concurrent=4, max_queued=64,
            file_size=32 << 10, chunk_avg=8 << 10,
            readserve_readers=8, readserve_reads=4, seed=seed)
        rep = run_fleet(os.path.join(tmp, "fleet-ds"), fleet_cfg)
        fleet = rep.to_dict()

        fetch_hist = metrics.HISTOGRAMS[
            "pbs_plus_chunk_cache_fetch_seconds"]
        return {
            "n_snapshots": n_snapshots,
            "payload_chunks": len(all_digests),
            "cache_budget_kib": cache_kib,
            "trace_len": len(trace_),
            "zipf_hit_ratio": round(zipf_hit_ratio, 4),
            "lru_hit_ratio": round(lru_hit_ratio, 4),
            "scan_resistance_gain": round(
                zipf_hit_ratio - lru_hit_ratio, 4),
            "probation_admits": cache.snapshot()["probation_admits"],
            "probation_promotions":
                cache.snapshot()["probation_promotions"],
            "hot_hit_ratio_before": round(hot_before, 4),
            "hot_hit_ratio_under_scan": round(hot_under_scan, 4),
            "hot_set_chunks": len(hot),
            "seq_amplification": round(seq_amplification, 4),
            "seq_logical_mib": round(logical / (1 << 20), 2),
            "readahead_precision": round(precision, 4),
            "readahead_window_max": seq_snap["readahead_window"],
            "fetch_p50_ms": round(1e3 * fetch_hist.quantile(
                0.50, since=fetch_base), 3),
            "fetch_p99_ms": round(1e3 * fetch_hist.quantile(
                0.99, since=fetch_base), 3),
            "ingest_published": fleet["published"],
            "ingest_failed": fleet["failed"],
            "readserve_completed": fleet["readserve_completed"],
            "readserve_failed": fleet["readserve_failed"],
            "readserve_cache_hits":
                fleet["readserve_cache"].get("hits", 0),
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main() -> None:
    from pbs_plus_tpu.utils import jaxenv
    jaxenv.configure_compile_cache()
    # this process may hold the chip from here on; the only children the
    # sections below start are fleetproc workers, which fleetsim starts
    # with JAX_PLATFORMS=cpu in their environment
    cpu = _cpu_baseline()
    try:
        pipe = _pipeline_bench()
        pipe["vs_cpu_single_thread"] = round(
            pipe["pipelined_mib_s"] / cpu["mib_s"], 2)
    except AssertionError:
        raise      # records divergence is a correctness failure, not
                   # a missing-capability note — fail the bench loudly
    except Exception as e:
        sys.stderr.write(f"[bench] pipeline bench unavailable: {e}\n")
        pipe = None
    result = {
        "metric": "chunk+fingerprint MiB/s/chip",
        "value": round(cpu["mib_s"], 1),
        "unit": "MiB/s",
        "vs_baseline": 1.0,
        "cpu_baseline_mib_s": round(cpu["mib_s"], 1),
        "detail": {"note": "CPU backend; CPU-only run", "cpu": cpu},
    }
    if pipe is not None:
        result["pipelined_mib_s"] = pipe["pipelined_mib_s"]
        result["detail"]["pipeline"] = pipe
    try:
        resume = _resume_bench()
    except Exception as e:
        sys.stderr.write(f"[bench] resume bench unavailable: {e}\n")
        resume = None
    if resume is not None:
        result["detail"]["resume"] = resume
    try:
        read = _read_bench()
    except Exception as e:
        sys.stderr.write(f"[bench] read bench unavailable: {e}\n")
        read = None
    if read is not None:
        result["detail"]["read"] = read
    try:
        mountserve = _mountserve_bench()
    except Exception as e:
        sys.stderr.write(f"[bench] mountserve bench unavailable: {e}\n")
        mountserve = None
    if mountserve is not None:
        result["detail"]["mountserve"] = mountserve
    try:
        fleet = _fleet_bench()
    except Exception as e:
        sys.stderr.write(f"[bench] fleet bench unavailable: {e}\n")
        fleet = None
    if fleet is not None:
        result["detail"]["fleet"] = fleet
    try:
        multiproc = _multiproc_bench()
    except Exception as e:
        sys.stderr.write(f"[bench] multiproc bench unavailable: {e}\n")
        multiproc = None
    if multiproc is not None:
        result["detail"]["multiproc"] = multiproc
    try:
        dedup_index = _dedup_index_bench()
    except Exception as e:
        sys.stderr.write(f"[bench] dedup index bench unavailable: {e}\n")
        dedup_index = None
    if dedup_index is not None:
        result["detail"]["dedup_index"] = dedup_index
    try:
        dist_index = _dist_index_bench()
    except Exception as e:
        sys.stderr.write(f"[bench] dist index bench unavailable: {e}\n")
        dist_index = None
    if dist_index is not None:
        result["detail"]["dist_index"] = dist_index
    try:
        dlog = _digestlog_bench()
    except Exception as e:
        sys.stderr.write(f"[bench] digestlog bench unavailable: {e}\n")
        dlog = None
    if dlog is not None:
        result["detail"]["digestlog"] = dlog
    try:
        delta = _delta_bench()
    except Exception as e:
        sys.stderr.write(f"[bench] delta tier bench unavailable: {e}\n")
        delta = None
    if delta is not None:
        result["detail"]["delta"] = delta
    try:
        sync = _sync_bench()
    except Exception as e:
        sys.stderr.write(f"[bench] sync bench unavailable: {e}\n")
        sync = None
    if sync is not None:
        result["detail"]["sync"] = sync
    try:
        obs = _observability_bench()
    except Exception as e:
        sys.stderr.write(f"[bench] observability bench unavailable: {e}\n")
        obs = None
    if obs is not None:
        result["detail"]["observability"] = obs
    result["machine"] = _machine_context()
    print(json.dumps(result))


if __name__ == "__main__":
    main()
