"""End-to-end tracing battery (ISSUE 12, docs/observability.md):
span nesting and ring semantics, the closed name registry, histogram
feeding + the one quantile implementation (property-tested against
sorted-sample truth), and context propagation across every concurrency
seam — asyncio tasks, raw threads, executor offloads, the pipelined
writer's pool, aRPC call metadata over plain-TCP loopback, and the
sync HTTP wire.  Orphan detection (a span opened but never closed)
fails the test that leaked it."""

import asyncio
import hashlib
import threading
import time

import pytest

from pbs_plus_tpu.server import metrics
from pbs_plus_tpu.utils import trace


@pytest.fixture(autouse=True)
def _clean_ring():
    """Every test starts with an empty ring and must end with zero
    open spans — the orphan-span gate of the satellite task."""
    trace.clear()
    yield
    leaked = trace.active_spans()
    trace.clear()
    assert not leaked, f"orphaned spans left open: {leaked}"


def _by_name(name):
    return [r for r in trace.recent() if r["name"] == name]


# ------------------------------------------------------------ basics


def test_span_nesting_parent_ids():
    with trace.span("job", job_id="j1", kind="backup") as root:
        with trace.span("job.queue_wait"):
            pass
        with trace.span("job.execute", kind="backup") as ex:
            with trace.span("backup.publish"):
                pass
    recs = trace.recent()
    assert [r["name"] for r in recs] == \
        ["job.queue_wait", "backup.publish", "job.execute", "job"]
    by = {r["name"]: r for r in recs}
    assert by["job"]["parent"] == ""
    assert by["job.queue_wait"]["parent"] == by["job"]["span"]
    assert by["job.execute"]["parent"] == by["job"]["span"]
    assert by["backup.publish"]["parent"] == by["job.execute"]["span"]
    assert all(r["trace"] == root.trace_id for r in recs)
    assert by["job"]["attrs"] == {"job_id": "j1", "kind": "backup"}
    assert ex.trace_id == root.trace_id


def test_span_error_status_recorded_and_exception_propagates():
    with pytest.raises(ValueError):
        with trace.span("job"):
            raise ValueError("boom")
    [rec] = trace.recent()
    assert rec["error"] == "ValueError"


def test_unregistered_names_rejected():
    with pytest.raises(ValueError):
        trace.span("not.a.span")
    with pytest.raises(ValueError):
        trace.emit("not.a.span", 0.1)
    with pytest.raises(ValueError):
        trace.record("not.a.span", 0.1)


def test_emit_is_one_shot_pre_measured():
    with trace.span("job") as root:
        trace.emit("ingest.cdc", 0.125, aggregated=True)
    cdc = _by_name("ingest.cdc")[0]
    assert cdc["parent"] == root.span_id
    assert cdc["dur_s"] == 0.125
    assert cdc["attrs"]["aggregated"] is True


def test_ring_is_bounded():
    old = trace._ring.maxlen
    trace.configure_ring(128)
    try:
        for _ in range(300):
            with trace.span("job"):
                pass
        assert len(trace.recent()) == 128
    finally:
        trace.configure_ring(old)


def test_orphan_detection_api():
    sp = trace.span("job")
    sp.__enter__()
    assert [(n, s) for n, s, _age in trace.active_spans()] == \
        [("job", sp.span_id)]
    sp.__exit__(None, None, None)
    assert not trace.active_spans()


def test_subscriber_sees_closed_spans():
    got = []
    trace.subscribe(got.append)
    try:
        with trace.span("job"):
            pass
    finally:
        trace.unsubscribe(got.append)
    assert [r["name"] for r in got] == ["job"]


def test_dump_text_and_traces_payload():
    with trace.span("job", job_id="j9"):
        with trace.span("job.execute", kind="backup"):
            pass
    text = trace.dump_text(10)
    assert "job.execute" in text and "job_id=j9" in text
    from pbs_plus_tpu.server.web import traces_payload
    data = traces_payload(None, None)
    assert [r["name"] for r in data] == ["job.execute", "job"]
    only = traces_payload("1", data[0]["trace"])
    assert len(only) == 1 and only[0]["trace"] == data[0]["trace"]
    assert traces_payload("junk", "nope") == []


# ----------------------------------------------------- propagation


def test_async_tasks_do_not_cross_contexts():
    async def main():
        async def one(jid):
            with trace.span("job", job_id=jid):
                await asyncio.sleep(0.01)
                with trace.span("job.execute", kind="backup"):
                    await asyncio.sleep(0.01)

        await asyncio.gather(one("a"), one("b"))

    asyncio.run(main())
    roots = _by_name("job")
    execs = _by_name("job.execute")
    assert len(roots) == 2 and len(execs) == 2
    assert roots[0]["trace"] != roots[1]["trace"]
    by_trace = {r["trace"]: r for r in roots}
    for e in execs:
        assert e["parent"] == by_trace[e["trace"]]["span"]


def test_thread_capture_attach_and_wrap():
    out = {}

    def worker(ctx):
        with trace.attached(ctx):
            with trace.span("ingest.sha", chunks=1):
                out["ctx"] = trace.capture()

    with trace.span("job") as root:
        ctx = trace.capture()
        t = threading.Thread(target=worker, args=(ctx,))
        t.start()
        t.join()
        # wrap(): capture-at-submit for executor seams
        def emit_here():
            trace.emit("ingest.cdc", 0.01)
        threading.Thread(target=trace.wrap(emit_here)).start()
        time.sleep(0.05)
    sha = _by_name("ingest.sha")[0]
    cdc = _by_name("ingest.cdc")[0]
    assert sha["trace"] == root.trace_id
    assert sha["parent"] == root.span_id
    assert cdc["trace"] == root.trace_id
    assert out["ctx"][0] == root.trace_id


def test_headers_roundtrip_and_malformed_ignored():
    assert trace.headers_out(None) == {}
    assert trace.parse_header(None) is None
    assert trace.parse_header("") is None
    assert trace.parse_header("zz") is None
    assert trace.parse_header("x" * 16 + "-" + "y" * 16) is None
    with trace.span("job") as sp:
        h = trace.headers_out({"other": "kept"})
        assert h["other"] == "kept"
        ctx = trace.parse_header(h[trace.TRACE_HEADER])
        assert ctx == (sp.trace_id, sp.span_id)


def test_mux_call_metadata_roundtrip_plain_tcp():
    """The aRPC seam: a client call inside a span carries its context
    in the request headers; the handler side's rpc.serve span (another
    task, the server conn) parents under the caller's span."""
    from pbs_plus_tpu.arpc import Router, Session
    from pbs_plus_tpu.arpc.mux import MuxConnection

    async def main():
        loop = asyncio.get_running_loop()
        accepted: asyncio.Future = loop.create_future()

        async def on_client(reader, writer):
            conn = MuxConnection(reader, writer, is_client=False,
                                 keepalive_s=0)
            conn.start()
            accepted.set_result(conn)

        srv = await asyncio.start_server(on_client, "127.0.0.1", 0)
        port = srv.sockets[0].getsockname()[1]
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        client = MuxConnection(reader, writer, is_client=True,
                               keepalive_s=0)
        client.start()
        sconn = await accepted

        router = Router()

        async def ping(req, ctx):
            return {"pong": True}
        router.handle("ping", ping)
        serve_task = asyncio.create_task(router.serve_connection(sconn))
        sess = Session(client)
        try:
            with trace.span("job", job_id="rpc") as root:
                resp = await sess.call("ping", {})
                assert resp.data["pong"]
            # and a call with NO ambient span must not inject a header
            resp = await sess.call("ping", {})
            assert resp.data["pong"]
        finally:
            serve_task.cancel()
            await asyncio.gather(serve_task, return_exceptions=True)
            await client.close()
            await sconn.close()
            srv.close()
            await srv.wait_closed()
        return root

    root = asyncio.run(main())
    serves = _by_name("rpc.serve")
    assert len(serves) == 2
    traced = [s for s in serves if s["trace"] == root.trace_id]
    assert len(traced) == 1
    assert traced[0]["parent"] == root.span_id
    assert traced[0]["attrs"]["method"] == "ping"
    # the uncontexted call opened its own root trace
    other = next(s for s in serves if s is not traced[0])
    assert other["trace"] != root.trace_id and other["parent"] == ""


def test_sync_http_header_crosses_the_wire(tmp_path):
    """The sync wire seam: HttpSyncSource requests carry the ambient
    context as an HTTP header; the wire server's handler thread
    attaches it, so its sync.serve spans join the caller's trace."""
    from pbs_plus_tpu.pxar.datastore import Datastore
    from pbs_plus_tpu.pxar.syncwire import HttpSyncSource, SyncWireServer

    ds = Datastore(str(tmp_path / "ds"))
    server = SyncWireServer(ds, "tok")
    port = server.start()
    try:
        src = HttpSyncSource(f"http://127.0.0.1:{port}", "tok")
        with trace.span("job", job_id="sync") as root:
            assert src.list_snapshots() == []
        src.close()
    finally:
        server.stop()
    serves = _by_name("sync.serve")
    assert len(serves) == 1
    assert serves[0]["trace"] == root.trace_id
    assert serves[0]["parent"] == root.span_id
    assert serves[0]["attrs"]["endpoint"] == "/snapshots"


def test_pipelined_stream_pool_spans_parent_under_job(tmp_path):
    """The thread-pool seam: a PipelinedStream opened under a span runs
    its batch hashing on pool threads and its probe on the committer —
    their ingest spans must join the opening span's trace."""
    from pbs_plus_tpu.chunker import ChunkerParams
    from pbs_plus_tpu.pxar.pipeline import PipelinedStream

    class NullStore:
        thread_safe = True

        def insert(self, digest, data, *, verify=True):
            return True

        def touch(self, digest):
            pass

    def hasher(chunks):
        return [hashlib.sha256(c).digest() for c in chunks]

    data = b"x" * (256 << 10)
    with trace.span("job", job_id="pipe") as root:
        s = PipelinedStream(NullStore(), ChunkerParams(avg_size=4096),
                            batch_hasher=hasher, workers=2)
        for _ in range(4):
            s.write(data)
        records = s.finish()
    assert records
    shas = _by_name("ingest.sha")
    assert shas, "no batch sha spans recorded"
    assert all(r["trace"] == root.trace_id for r in shas)
    cdcs = _by_name("ingest.cdc")
    assert cdcs and all(r["trace"] == root.trace_id for r in cdcs)


def test_sequential_stream_emits_aggregate_stage_spans(tmp_path):
    from pbs_plus_tpu.chunker import ChunkerParams
    from pbs_plus_tpu.pxar.transfer import _ChunkedStream

    class NullStore:
        def insert(self, digest, data, *, verify=True):
            return True

        def touch(self, digest):
            pass

    with trace.span("job", job_id="seq") as root:
        s = _ChunkedStream(NullStore(), ChunkerParams(avg_size=4096))
        s.write(b"y" * (128 << 10))
        s.finish()
    cdc = _by_name("ingest.cdc")
    sha = _by_name("ingest.sha")
    assert len(cdc) == 1 and len(sha) == 1
    assert cdc[0]["trace"] == root.trace_id
    assert sha[0]["attrs"]["chunks"] > 0
    assert sha[0]["attrs"]["aggregated"] is True


def test_chunkcache_fetch_span_on_miss_only():
    from pbs_plus_tpu.pxar.chunkcache import ChunkCache

    class Store:
        def get(self, digest):
            return b"chunk-bytes"

    cache = ChunkCache(1 << 20)
    digest = hashlib.sha256(b"chunk-bytes").digest()
    with trace.span("job"):
        cache.get(Store(), digest)      # miss: one fetch span
        cache.get(Store(), digest)      # hit: no new span
    fetches = _by_name("chunkcache.fetch")
    assert len(fetches) == 1
    assert fetches[0]["attrs"]["digest"] == digest.hex()[:16]


# ------------------------------------------------ histograms/quantile


def test_span_close_feeds_histogram_and_exposition():
    h = metrics.HISTOGRAMS["pbs_plus_ingest_stage_seconds"]
    before = h.snapshot().get((("stage", "probe"),), {"count": 0})
    with trace.span("ingest.probe", chunks=8):
        time.sleep(0.002)
    snap = h.snapshot()[(("stage", "probe"),)]
    assert snap["count"] == before["count"] + 1
    expo = metrics.render_histograms()
    assert 'pbs_plus_ingest_stage_seconds_bucket{le="+Inf",stage="probe"}' \
        in expo or 'stage="probe"' in expo
    assert "pbs_plus_ingest_stage_seconds_sum" in expo
    assert "pbs_plus_ingest_stage_seconds_count" in expo


def test_record_feeds_histogram_without_ring_entry():
    h = metrics.HISTOGRAMS["pbs_plus_mux_frame_write_seconds"]
    before = h.snapshot().get((), {"count": 0})
    trace.record("mux.write_frame", 3e-6)
    assert h.snapshot()[()]["count"] == before["count"] + 1
    assert trace.recent() == []


def test_quantile_property_against_sorted_truth():
    """THE quantile implementation vs sorted-sample truth: the bucketed
    estimate must land inside (or at the edges of) the bucket holding
    the true quantile — log-bucket resolution is the contract."""
    import random
    rng = random.Random(7)
    h = metrics.Histogram("t_prop", "test")
    samples = [rng.lognormvariate(-6, 2.0) for _ in range(5000)]
    samples = [min(s, 9.0) for s in samples]
    for s in samples:
        h.observe(s)
    ordered = sorted(samples)
    for q in (0.01, 0.25, 0.5, 0.9, 0.99, 1.0):
        truth = ordered[min(len(ordered) - 1,
                            int(q * len(ordered)))] if q < 1.0 \
            else ordered[-1]
        est = h.quantile(q)
        # bucket containing the truth
        import bisect
        i = bisect.bisect_left(h.buckets, truth)
        lo = h.buckets[i - 1] if i > 0 else 0.0
        hi = h.buckets[min(i, len(h.buckets) - 1)]
        assert lo <= est <= hi * 1.0000001, (q, truth, est, lo, hi)
    # monotone in q
    qs = [h.quantile(q) for q in (0.1, 0.3, 0.5, 0.7, 0.9, 0.99)]
    assert qs == sorted(qs)


def test_quantile_since_snapshot_diffs_batches():
    h = metrics.Histogram("t_diff", "test")
    for _ in range(100):
        h.observe(0.001)                 # batch 1: all ~1ms
    base = h.snapshot()
    for _ in range(100):
        h.observe(1.0)                   # batch 2: all ~1s
    # all-time median sits between the modes; diff median is batch 2
    assert h.quantile(0.5, since=base) > 0.5
    assert h.quantile(0.5) < 0.5
    assert h.quantile(0.5, since=None) > 0.0


def test_quantile_empty_and_zero():
    h = metrics.Histogram("t_empty", "test")
    assert h.quantile(0.5) == 0.0
    assert metrics.quantile_from_counts(metrics.HIST_BUCKETS,
                                        [0] * 23, 0.5) == 0.0


def test_disabled_suppresses_everything():
    with trace.disabled():
        with trace.span("job"):
            pass
        trace.emit("ingest.cdc", 0.1)
        trace.record("mux.write_frame", 1e-6)
    assert trace.recent() == []


def test_missing_attr_label_resolves_empty_not_placeholder():
    """A registered span closed without its $attr must land in the ""
    label child — the literal "$kind" placeholder never reaches the
    exposition."""
    h = metrics.HISTOGRAMS["pbs_plus_job_grant_to_publish_seconds"]
    before = h.snapshot().get((("kind", ""),), {"count": 0})
    with trace.span("job.execute"):
        pass
    snap = h.snapshot()
    assert snap[(("kind", ""),)]["count"] == before["count"] + 1
    assert (("kind", "$kind"),) not in snap


def test_enqueue_to_grant_measured_from_enqueue_timestamp():
    """The enqueue-to-grant histogram covers scheduling + pre-exec, not
    just the slot acquisition (review finding: a 30s mount must show
    up here, not only in enqueue-to-publish)."""
    from pbs_plus_tpu.server.jobs import Job, JobsManager

    async def main():
        jobs = JobsManager(max_concurrent=2, max_queued=8)

        async def pre():
            await asyncio.sleep(0.05)

        async def work():
            pass

        jobs.enqueue(Job(id="g1", kind="backup", pre_exec=pre,
                         execute=work))
        await jobs.drain()

    h = metrics.HISTOGRAMS["pbs_plus_job_enqueue_to_grant_seconds"]
    before = h.snapshot().get((("kind", "backup"),), {"count": 0,
                                                      "sum": 0.0})
    asyncio.run(main())
    after = h.snapshot()[(("kind", "backup"),)]
    assert after["count"] == before["count"] + 1
    # the 50ms pre_exec is inside the measured window
    assert after["sum"] - before["sum"] >= 0.05


# ------------------------------------------------ ingest.store (ISSUE 24)


def test_writer_insert_loop_is_one_store_span_per_hash_batch(tmp_path):
    """Each hash batch's insert loop is one ``ingest.store`` span beside
    its ``ingest.sha`` and ``ingest.probe``, with the batch's chunks,
    bytes and how many were new, and feeds the stage histogram."""
    import numpy as np

    from pbs_plus_tpu.chunker import ChunkerParams
    from pbs_plus_tpu.pxar.datastore import ChunkStore
    from pbs_plus_tpu.pxar.transfer import _ChunkedStream

    def hasher(chunks):
        return [hashlib.sha256(c).digest() for c in chunks]

    h = metrics.HISTOGRAMS["pbs_plus_ingest_stage_seconds"]
    before = h.snapshot().get((("stage", "store"),), {"count": 0})["count"]
    store = ChunkStore(str(tmp_path), n_shards=2, index_budget_mb=2)
    data = np.random.default_rng(24).integers(
        0, 256, 256 << 10, dtype=np.uint8).tobytes()
    for new_expected in (True, False):
        trace.clear()
        s = _ChunkedStream(store, ChunkerParams(avg_size=4 << 10),
                           batch_hasher=hasher)
        s.write(data)
        records = s.finish()
        stores, shas = _by_name("ingest.store"), _by_name("ingest.sha")
        assert len(stores) == len(shas) >= 1
        assert sum(r["attrs"]["chunks"] for r in stores) == len(records)
        assert sum(r["attrs"]["bytes"] for r in stores) == len(data)
        assert sum(r["attrs"]["new"] for r in stores) == \
            (len(records) if new_expected else 0)
    after = h.snapshot()[(("stage", "store"),)]["count"]
    assert after > before


def test_span_set_adds_attrs_known_late_and_is_a_noop_when_disabled():
    with trace.span("ingest.store", chunks=2) as sp:
        sp.set(new=1)
    with trace.span("job") as bare:
        bare.set(kind="backup")
    with trace.disabled():
        with trace.span("ingest.store") as off:
            off.set(new=3)
    assert _by_name("ingest.store")[0]["attrs"] == {"chunks": 2, "new": 1}
    assert _by_name("job")[0]["attrs"] == {"kind": "backup"}
    assert len(trace.recent()) == 2


# ------------------------------------------------------ thread clocks

def _life_of(clock, body):
    t = threading.Thread(target=lambda: _clocked(clock, body))
    t.start()
    t.join(30)
    assert not t.is_alive()
    return clock.seconds


def _clocked(clock, body):
    with trace.clocked(clock):
        body()


def test_thread_clock_partitions_a_threads_life():
    """The states a clocked thread passes through sum to its life, its
    CPU seconds stand beside them, and what no bracket names is the
    residue."""
    def body():
        time.sleep(0.02)                        # the residue
        with trace.state("pump_wait_s"):
            time.sleep(0.05)
        spin = time.perf_counter() + 0.03
        while time.perf_counter() < spin:       # CPU, in the residue
            pass
        st = trace.state("cdc_s")
        t0 = st.begin()
        time.sleep(0.04)
        took.append((st.end() - t0) * 1e-9)
        trace.spent("store_s")                  # since the last reading

    took: list = []
    clock = trace.ThreadClock(label="writer")
    s = _life_of(clock, body)
    # the caller's own two readings are the clock's
    assert took == [pytest.approx(s["cdc_s"], abs=1e-4)]
    states = {k: v for k, v in s.items() if k not in ("cpu_s", "life_s")}
    assert set(states) == {"pump_wait_s", "cdc_s", "store_s", trace.REST}
    assert sum(states.values()) == pytest.approx(s["life_s"], rel=0.01)
    assert s["pump_wait_s"] >= 0.05 and s["cdc_s"] >= 0.04
    assert s[trace.REST] >= 0.05
    assert 0.02 <= s["cpu_s"] <= s["life_s"]
    assert s["life_s"] >= 0.14


def test_thread_clock_keeps_its_owners_counters():
    """The batcher's way: the clock writes into the owner's dict, the
    keys it is given and no ``life_s`` until it stops; ``cpu`` brings
    the CPU seconds up to date in between."""
    stats = {"idle_s": 0.0, "mask_busy_s": 0.0, "rounds": 7}
    clock = trace.ThreadClock(stats)

    def body():
        clock.start()
        time.sleep(0.02)
        clock.spent("idle_s")
        spin = time.perf_counter() + 0.02
        while time.perf_counter() < spin:
            pass
        clock.spent("mask_busy_s")
        clock.cpu()
    t = threading.Thread(target=body)
    t.start()
    t.join(30)
    assert stats["rounds"] == 7 and "life_s" not in stats
    assert stats["idle_s"] >= 0.02 and stats["mask_busy_s"] >= 0.02
    assert 0.01 <= stats["cpu_s"] <= stats["idle_s"] + stats["mask_busy_s"]


def test_spent_and_state_without_a_clock_are_noops():
    """A thread with no clock (pipelined hash workers, local and S3
    backups, this test): nothing is kept, and ``state`` still gives its
    caller the two readings."""
    trace.spent("cdc_s")
    with trace.state("store_s") as st:
        pass
    assert st is trace.state("probe_s")         # the one no-op
    t0 = st.begin()
    assert st.end() >= t0 > 0
    clock = trace.ThreadClock()
    s = _life_of(clock, lambda: None)
    # ... and a clock reaches no thread but the one it is attached on
    trace.spent("cdc_s")
    assert set(s) == {trace.REST, "cpu_s", "life_s"}


def test_a_clock_is_detached_when_its_block_ends():
    clock = trace.ThreadClock()
    with trace.clocked(clock):
        with trace.state("sha_s"):
            pass
    before = dict(clock.seconds)
    with trace.state("sha_s"):
        time.sleep(0.001)
    trace.spent("sha_s")
    assert clock.seconds == before
