"""The device round trip, timed where it happens (ISSUE 24): the five
phase clocks of the three device ops, the compile marker, the profiler
annotations' names, and what ``/metrics`` renders of them.  Counts and
orderings only — a CPU run gives no rate."""

import subprocess
import sys
import time

import numpy as np
import pytest

from pbs_plus_tpu.chunker import ChunkerParams
from pbs_plus_tpu.ops import cuckoo, rolling_hash, sha256
from pbs_plus_tpu.utils import trace

P = ChunkerParams(avg_size=4 << 10)
KEYS = [p + "_s" for p in trace.PHASES]
STATS = {"scan": rolling_hash.stats, "sha": sha256.stats,
         "probe": cuckoo.stats}


def _bytes(n, seed):
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8)


def _index():
    index = cuckoo.CuckooIndex(n_buckets=1 << 10)
    index.insert_many([bytes([i]) * 32 for i in range(10)])
    return index


def _trip(op, index=None):
    """One round trip of ``op`` at a small shape."""
    if op == "scan":
        rolling_hash.batched_candidate_hits(
            [_bytes(50_000, 1), _bytes(60_000, 2)], [None, None],
            rolling_hash.device_tables(P), P)
    elif op == "sha":
        sha256.sha256_chunks_device([_bytes(5000, 3), _bytes(100, 4),
                                     b"abc"])
    else:
        (index or _index()).probe(_bytes(20 * 32, 5).reshape(-1, 32))


def _phases(op):
    return {k: STATS[op][k] for k in KEYS}


@pytest.fixture
def spans():
    seen = []
    trace.subscribe(seen.append)
    yield seen
    trace.unsubscribe(seen.append)


@pytest.mark.parametrize("op", ["scan", "sha", "probe"])
def test_phases_are_positive_and_sum_to_at_most_the_wall_time(op, spans):
    _trip(op)                                   # compiles, if it must
    before = _phases(op)
    t0 = time.perf_counter()
    _trip(op)
    wall = time.perf_counter() - t0
    spent = {k: STATS[op][k] - before[k] for k in KEYS}
    assert all(v >= 0.0 for v in spent.values()), spent
    assert 0.0 < sum(spent.values()) <= wall, (spent, wall)
    # the span of the trip carries the same seconds, and the same counts
    rec = [r for r in spans if r["name"] == "device." + op][-1]
    for k in KEYS:
        assert rec["attrs"][k] == pytest.approx(spent[k])
    assert sum(rec["attrs"][k] for k in KEYS) <= rec["dur_s"]
    assert rec["attrs"]["dispatches"] >= 1 and "compiled" not in rec["attrs"]


SLEEP = 0.2


def _slow(fn):
    def slowed(*a, **kw):
        time.sleep(SLEEP)
        return fn(*a, **kw)
    return slowed


@pytest.mark.parametrize("op,phase,module,attr", [
    ("scan", "unpack", rolling_hash.np, "nonzero"),
    ("scan", "device", rolling_hash, "candidate_words"),
    ("sha", "device", sha256, "_sha256_scan"),
    ("probe", "device", cuckoo, "_lookup"),
])
def test_a_sleep_lands_in_its_phase_and_no_other(op, phase, module, attr,
                                                 monkeypatch):
    index = _index()
    _trip(op, index)
    before = _phases(op)
    with monkeypatch.context() as m:
        m.setattr(module, attr, _slow(getattr(module, attr)))
        _trip(op, index)
    spent = {k: STATS[op][k] - before[k] for k in KEYS}
    assert spent[phase + "_s"] >= SLEEP, spent
    others = {k: v for k, v in spent.items() if k != phase + "_s"}
    assert all(v < SLEEP / 2 for v in others.values()), spent


def test_clocks_run_with_spans_disabled(spans):
    """The dicts are counters, not tracing."""
    before = rolling_hash.stats["dispatches"], _phases("scan")
    with trace.disabled():
        _trip("scan")
    assert rolling_hash.stats["dispatches"] == before[0] + 1
    assert rolling_hash.stats["device_s"] > before[1]["device_s"]
    assert not [r for r in spans if r["name"] == "device.scan"]


@pytest.mark.parametrize("op", ["scan", "sha", "probe"])
def test_first_seen_shape_is_marked_compiled_and_the_second_is_not(
        op, spans, monkeypatch):
    """Shape classes nothing else in the suite uses, so this process
    has not built their programs."""
    monkeypatch.setattr(rolling_hash, "_ROW_CLASSES", (3,))
    monkeypatch.setattr(rolling_hash, "_SEG_CLASSES", (3 << 15,))
    monkeypatch.setattr(sha256, "_ROW_CLASSES", (24,))
    monkeypatch.setattr(sha256, "_SLAB_CLASSES",
                        (40_000 + sha256.SLACK_BYTES,))
    monkeypatch.setattr(cuckoo, "_PROBE_CLASSES", (48,))
    index = _index()
    _trip(op, index)
    _trip(op, index)
    first, second = [r["attrs"] for r in spans
                     if r["name"] == "device." + op]
    assert first["compiled"] >= 1
    assert "compiled" not in second
    # and the log named the class, once (the scan's rows follow the
    # mesh's width)
    shape = {"scan": "seg=96 KiB", "sha": "slab=0 MiB rows=24",
             "probe": "rows=48 buckets=1024"}[op]
    assert [s for n, s in trace._warned_compiles
            if n == "device." + op and shape in s]


DOCUMENTED = {"feeder.dispatch"} | {
    f"device.{op}{suffix}" for op in ("scan", "sha", "probe")
    for suffix in [""] + ["/" + p for p in trace.PHASES]}


def test_annotations_are_entered_under_exactly_the_documented_names(
        monkeypatch):
    import jax

    from pbs_plus_tpu.models.feeder import DeviceFeeder
    entered = []

    class Recorder:
        def __init__(self, label):
            self.label = label

        def __enter__(self):
            entered.append(self.label)
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    feeder = DeviceFeeder(linger_s=0)
    feeder.candidate_hits(_bytes(50_000, 6), np.zeros(63, np.uint8), P)
    assert entered == ["feeder.dispatch", "device.scan"] + [
        "device.scan/" + p for p in trace.PHASES]
    del entered[:]
    feeder.sha256_batch([b"abc"])
    assert entered == ["feeder.dispatch", "device.sha", "device.sha/pack",
                       "device.sha/h2d"] + [
        "device.sha/" + p for p in trace.PHASES]
    del entered[:]
    _trip("probe")
    assert entered == ["device.probe"] + [
        "device.probe/" + p for p in trace.PHASES]
    assert set(entered) <= DOCUMENTED
    with open("docs/observability.md", encoding="utf-8") as f:
        doc = f.read()
    for op in ("scan", "sha", "probe"):
        assert f"`device.{op}`" in doc
    assert "`device.scan/pack`" in doc and "`feeder.dispatch`" in doc


def test_utils_trace_imports_without_jax():
    code = ("import sys\n"
            "from pbs_plus_tpu.utils import trace\n"
            "assert 'jax' not in sys.modules, 'trace imported jax'\n"
            "with trace.annotation('x'):\n    pass\n"
            "stats = trace.device_stats('t', {'n': 0})\n"
            "assert 'jax' not in sys.modules\n"
            "print(sorted(stats))\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == str(sorted(
        ["n"] + KEYS)).split()


def test_table_upload_is_counted_once_per_dirtying(spans):
    """The first probe copies the table whole, a probe after an insert
    sends the changed bucket padded to a class, a clean probe nothing:
    bytes = whole uploads × the table's bytes + the deltas' bytes.  A
    table of 65,536 buckets: the smallest that takes a change of one
    class in place."""
    index = cuckoo.CuckooIndex(n_buckets=1 << 16)
    index.insert_many([bytes([i]) * 32 for i in range(10)])
    before = dict(cuckoo.stats)
    _trip("probe", index)
    _trip("probe", index)
    assert cuckoo.stats["table_uploads"] == before["table_uploads"] + 1
    assert cuckoo.stats["table_upload_bytes"] \
        == before["table_upload_bytes"] + index._table.nbytes
    index.insert(b"\x77" * 32)
    _trip("probe", index)
    delta = 64 * cuckoo.DELTA_BUCKET_BYTES
    spent = {k: cuckoo.stats[k] - before[k] for k in before}
    assert spent["table_uploads"] == 1
    assert spent["table_delta_uploads"] == 1
    assert spent["table_delta_buckets"] == 1
    assert spent["table_delta_bytes"] == delta
    assert spent["table_upload_bytes"] == index._table.nbytes + delta
    assert spent["probes"] == 60
    # and the span of a probe that carried the table says so
    probes = [r["attrs"] for r in spans if r["name"] == "device.probe"]
    assert [a.get("table_uploads", 0) for a in probes] == [1, 0, 0]
    assert [a.get("table_delta_uploads", 0) for a in probes] == [0, 0, 1]
    assert probes[0]["table_upload_bytes"] == index._table.nbytes
    assert "table_upload_bytes" not in probes[1]
    assert probes[2]["table_upload_bytes"] \
        == probes[2]["table_delta_bytes"] == delta
    assert probes[2]["upload_s"] > 0


def test_concurrent_probes_lose_no_count():
    """Probes run on the writers' threads: what a trip adds to
    ``cuckoo.stats`` it adds under the module's own lock."""
    import threading
    index = _index()
    _trip("probe", index)
    before = dict(cuckoo.stats)

    def work():
        for _ in range(25):
            _trip("probe", index)

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert cuckoo.stats["dispatches"] == before["dispatches"] + 100
    assert cuckoo.stats["probes"] == before["probes"] + 2000
    assert cuckoo.stats["table_uploads"] == before["table_uploads"]


def test_a_trip_that_raises_keeps_what_it_counted(spans):
    """Phases and counts land in the op's dict as they close, so a
    window's deltas cut a long dispatch where the window ends; a trip
    that raises keeps them and closes its span with the error."""
    stats = trace.device_stats("t", {"rows": 0})
    try:
        with pytest.raises(RuntimeError):
            with trace.round_trip("device.scan", stats, seg_pad=7) as rt:
                with rt.phase("pack"):
                    pass
                rt.add(rows=3)
                assert stats["rows"] == 3 and stats["pack_s"] > 0.0
                with pytest.raises(KeyError):
                    rt.add(no_such_counter=1)
                raise RuntimeError("device lost")
    finally:
        del trace.DEVICE_STATS["t"]
    assert set(stats) == {"rows"} | set(KEYS)
    rec = [r for r in spans if r["name"] == "device.scan"][-1]
    assert rec["error"] == "RuntimeError"
    assert rec["attrs"]["seg_pad"] == 7 and rec["attrs"]["rows"] == 3
    assert rec["attrs"]["pack_s"] == stats["pack_s"]


def test_the_scan_waits_once_for_the_device(monkeypatch):
    """The copies in are not waited for (the wait cost the feeder's
    thread 2-4 points, PERF.md PR 24): one ``block_until_ready`` per
    scan, on the result."""
    import jax
    waits = []

    class Result:
        def __init__(self, array):
            self.array = array

        def block_until_ready(self):
            waits.append("result")
            return self.array

    words = rolling_hash.candidate_words
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waits.append("inputs") or x)
    monkeypatch.setattr(rolling_hash, "candidate_words",
                        lambda *a, **kw: Result(words(*a, **kw)))
    _trip("scan")
    assert waits == ["result"]


def test_scan_span_carries_the_bytes_its_answer_weighed(spans):
    """The answer comes home 32 positions a word (ISSUE 33):
    ``home_bytes`` is on the ``device.scan`` span and in the op's
    counters, an eighth of what went out."""
    before = dict(rolling_hash.stats)
    _trip("scan")
    scan, = [r for r in spans if r["name"] == "device.scan"]
    assert scan["attrs"]["home_bytes"] * 8 == scan["attrs"]["padded_bytes"]
    for key in ("home_bytes", "padded_bytes"):
        assert rolling_hash.stats[key] - before[key] == scan["attrs"][key]


def test_metrics_render_every_new_gauge(tmp_path):
    from pbs_plus_tpu.models import feeder as feeder_mod
    from pbs_plus_tpu.server import metrics
    from pbs_plus_tpu.server.store import Server, ServerConfig
    feeder = feeder_mod.get_feeder()
    feeder.candidate_hits(_bytes(50_000, 7), np.zeros(63, np.uint8), P)
    feeder.sha256_batch([b"abc"])
    _trip("probe")
    server = Server(ServerConfig(state_dir=str(tmp_path / "state"),
                                 cert_dir=str(tmp_path / "certs"),
                                 datastore_dir=str(tmp_path / "ds")))
    expo = metrics.MetricsRegistry(server).render() + "\n" \
        + metrics.render_histograms()
    for op in ("scan", "sha", "probe"):
        for phase in trace.PHASES:
            assert (f'pbs_plus_device_phase_seconds_total{{op="{op}",'
                    f'phase="{phase}"}}') in expo
        assert f'pbs_plus_device_dispatches_total{{op="{op}"}}' in expo
        for kind in ("payload", "padded"):
            assert (f'pbs_plus_device_bytes_total{{kind="{kind}",'
                    f'op="{op}"}}') in expo
        # the answer's bytes on their way home: the scan's alone so far
        assert ('pbs_plus_device_bytes_total{kind="home",'
                f'op="{op}"}}' in expo) == (op == "scan")
        assert (f'pbs_plus_device_dispatch_seconds_count{{op="{op}"}}'
                in expo)
    for state in ("scan", "sha", "idle", "linger"):
        assert (f'pbs_plus_feeder_thread_seconds_total{{state="{state}"}}'
                in expo)
    for outcome in ("joined", "alone"):
        assert (f'pbs_plus_feeder_linger_rounds_total{{outcome="{outcome}"}}'
                in expo)
    for kind in ("scan", "sha"):
        assert f'pbs_plus_feeder_requests_total{{kind="{kind}"}}' in expo
        assert (f'pbs_plus_feeder_dispatch_seconds_count{{kind="{kind}"}}'
                in expo)
        assert (f'pbs_plus_feeder_queue_wait_seconds_count{{kind="{kind}"}}'
                in expo)
    for name in ("device_compilations", "device_compile_seconds",
                 "device_table_delta_buckets", "feeder_rounds",
                 "feeder_scan_feeds", "feeder_scan_rows_shared"):
        assert f"pbs_plus_{name}_total " in expo, name
    for name in ("device_table_uploads", "device_table_upload_bytes",
                 "index_table_uploads"):
        for kind in ("whole", "delta"):
            assert f'pbs_plus_{name}_total{{kind="{kind}"}} ' in expo, name


# --- how many devices a scan's rows sat on (ISSUE 26) ---------------------

@pytest.mark.parametrize("rows,devices", [(1, 1), (2, 8), (5, 8)])
def test_scan_span_names_the_devices_its_rows_sat_on(rows, devices, spans):
    """On the suite's eight virtual devices a dispatch of one row stays
    on one device and one of two or more is sharded over the data mesh:
    the ``device.scan`` span says which, with the gauge's value."""
    import jax
    assert len(jax.devices()) == 8
    before = rolling_hash.stats["mesh_dispatches"]
    rolling_hash.batched_candidate_hits(
        [_bytes(30_000, 40 + i) for i in range(rows)], [None] * rows,
        rolling_hash.device_tables(P), P)
    scan, = [r for r in spans if r["name"] == "device.scan"]
    assert scan["attrs"]["devices"] == devices
    assert scan["attrs"]["rows"] == rows
    sharded = int(rows > 1)
    assert rolling_hash.stats["mesh_dispatches"] - before == sharded
    if sharded:
        assert rolling_hash.stats["mesh_shard_devices"] == devices
        assert scan["attrs"]["padded_rows"] % devices == 0
