"""Digests come from the host's SHA-256 (ISSUE 25, as measured: the
device program loses to one host thread on every batch shape): the
routing of the ``chunker="tpu"`` batch hasher and the sidecar, the host
engine's counters under threads, the device engine kept whole behind its
own entry, and a failing device dispatch that is never hashed again on
the host.  Counts only — a CPU run gives no rate."""

import hashlib
import sys
import threading

import numpy as np
import pytest

import pbs_plus_tpu.models.feeder as feeder_mod
from pbs_plus_tpu.models.dedup import device_sha256_batch
from pbs_plus_tpu.ops import sha256
from pbs_plus_tpu.utils import trace

KIB = 1 << 10
HOST_KEYS = ("host_batches", "host_rows", "host_bytes", "host_s")
DEVICE_KEYS = ("slabs", "dispatches", "rows", "bytes")


def _bytes(n, seed):
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


@pytest.fixture
def feeder(monkeypatch):
    f = feeder_mod.DeviceFeeder(linger_s=0.0)
    monkeypatch.setattr(feeder_mod, "_feeder", f)
    return f


@pytest.fixture
def spans():
    seen = []
    trace.subscribe(seen.append)
    yield seen
    trace.unsubscribe(seen.append)


# --- the routing --------------------------------------------------------------

def test_the_tpu_batch_hasher_hashes_on_the_host_and_never_enters_the_feeder(
        feeder, spans):
    chunks = [_bytes(300_000, 1), _bytes(70_000, 2), b"abc"]
    before = dict(sha256.stats)
    with trace.span("ingest.sha", chunks=len(chunks)):
        got = device_sha256_batch(chunks)
    assert got == [hashlib.sha256(c).digest() for c in chunks]
    assert feeder.stats["sha_streams"] == 0 and feeder._thread is None
    assert all(sha256.stats[k] == before[k] for k in DEVICE_KEYS)
    assert sha256.stats["host_batches"] == before["host_batches"] + 1
    assert sha256.stats["host_rows"] == before["host_rows"] + 3
    assert sha256.stats["host_bytes"] == before["host_bytes"] + 370_003
    assert sha256.stats["host_s"] > before["host_s"]
    # one host.sha span, child of the writer's ingest.sha, on its thread
    host = [r for r in spans if r["name"] == "host.sha"]
    parent = [r for r in spans if r["name"] == "ingest.sha"]
    assert len(host) == 1 and host[0]["parent"] == parent[0]["span"]
    assert host[0]["attrs"] == {"rows": 3, "bytes": 370_003}
    assert not [r for r in spans if r["name"] in ("device.sha",
                                                  "feeder.dispatch")]


@pytest.mark.parametrize("lens", [[4 << 20] * 2, [KIB] * 256],
                         ids=["few-long", "many-short"])
def test_no_batch_shape_reaches_the_device(feeder, lens):
    """A few long chunks or many equal short ones: the same engine."""
    chunks = [bytes([i % 251]) * n for i, n in enumerate(lens)]
    before = dict(sha256.stats)
    assert device_sha256_batch(chunks) == [hashlib.sha256(c).digest()
                                           for c in chunks]
    assert all(sha256.stats[k] == before[k] for k in DEVICE_KEYS)
    assert sha256.stats["host_bytes"] == before["host_bytes"] + sum(lens)
    assert feeder.stats["sha_streams"] == 0


def test_the_batch_hasher_looks_the_entry_up_at_call_time(monkeypatch):
    """What a test or the benchmark puts in ``sha256.sha256_chunks``'s
    place sees the batch."""
    seen = []
    monkeypatch.setattr(sha256, "sha256_chunks",
                        lambda chunks, **kw: seen.append(len(chunks))
                        or [b"\x00" * 32] * len(chunks))
    assert device_sha256_batch([b"abc", b"de"]) == [b"\x00" * 32] * 2
    assert seen == [2]


def test_the_sidecar_hashes_on_the_host(feeder):
    from pbs_plus_tpu.chunker import ChunkerParams, chunk_bounds
    from pbs_plus_tpu.sidecar.service import DedupService
    params = ChunkerParams(avg_size=4 * KIB)
    data = _bytes(100_000, 11)
    before = dict(sha256.stats)
    got = DedupService(params=params, use_tpu=False).chunk(
        {"stream_id": "s", "data": data, "eof": True})
    bounds = chunk_bounds(data, params)
    assert got["digests"] == [hashlib.sha256(data[s:e]).digest()
                              for s, e in bounds]
    assert sha256.stats["host_batches"] == before["host_batches"] + 1
    assert sha256.stats["host_bytes"] == before["host_bytes"] + len(data)
    assert all(sha256.stats[k] == before[k] for k in DEVICE_KEYS)
    assert feeder.stats["sha_streams"] == 0


# --- the host engine ----------------------------------------------------------

def test_both_entries_take_the_same_buffers():
    chunks = [b"", b"abc", bytearray(b"x" * 70), memoryview(b"y" * 5000),
              np.arange(200, dtype=np.uint8)]
    want = [hashlib.sha256(bytes(c)).digest() for c in chunks]
    assert sha256.sha256_chunks(chunks) == want
    assert sha256.sha256_chunks_device(chunks) == want


def test_an_empty_batch_is_no_batch(spans):
    before = dict(sha256.stats)
    assert sha256.sha256_chunks([]) == []
    assert device_sha256_batch([]) == []
    assert all(sha256.stats[k] == before[k] for k in HOST_KEYS)
    assert not spans


def test_stream_entries_hash_on_the_host_and_refuse_bounds_out_of_range():
    data = _bytes(20_000, 5)
    bounds = [(0, 55), (55, 7000), (7000, 20_000)]
    want = [hashlib.sha256(data[s:e]).digest() for s, e in bounds]
    before = dict(sha256.stats)
    assert sha256.sha256_stream_chunks(data, bounds) == want
    assert sha256.sha256_streams_chunks(
        [data, np.frombuffer(data, np.uint8)], [bounds, bounds[:1]]) \
        == [want, want[:1]]
    assert sha256.stats["host_batches"] == before["host_batches"] + 2
    assert all(sha256.stats[k] == before[k] for k in DEVICE_KEYS)
    with pytest.raises(ValueError, match="out of supported range"):
        sha256.sha256_stream_chunks(data, [(10, 5)])


def test_concurrent_host_batches_lose_no_count(feeder):
    """Eight writers hash at once, more threads than this host may have
    cores, the interpreter switching as often as it can: the host
    engine's counters are added under its own lock."""
    n_threads, rounds = 8, 200
    chunks = [_bytes(3000, 7), _bytes(100, 8)]
    want = [hashlib.sha256(c).digest() for c in chunks]
    before = dict(sha256.stats)
    wrong = []
    start = threading.Barrier(n_threads)

    def work():
        start.wait()
        for _ in range(rounds):
            if device_sha256_batch(chunks) != want:
                wrong.append(1)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    assert not wrong
    n = n_threads * rounds
    assert sha256.stats["host_batches"] == before["host_batches"] + n
    assert sha256.stats["host_rows"] == before["host_rows"] + 2 * n
    assert sha256.stats["host_bytes"] == before["host_bytes"] + 3100 * n
    assert feeder.stats["sha_streams"] == 0


# --- the device engine, kept whole ----------------------------------------------

def test_the_feeder_hashes_on_the_device(feeder):
    """The device engine's cross-session batcher, as the benchmark's
    counters test drives it."""
    before = dict(sha256.stats)
    assert feeder.sha256_batch([b"abc"]) == [hashlib.sha256(b"abc").digest()]
    assert sha256.stats["dispatches"] == before["dispatches"] + 1
    assert feeder.stats["sha_streams"] == 1
    assert all(sha256.stats[k] == before[k] for k in HOST_KEYS)


def test_the_device_engine_shards_its_rows_over_the_data_mesh():
    """The eight virtual devices of tests/conftest.py: the only place
    the program's row sharding runs, now that no fan-in hashes on the
    device (chip_smoke.py --chips 4 looks at the scan alone)."""
    import jax
    before = dict(sha256.stats)
    chunks = [_bytes(5000, 21), _bytes(100, 22), b"abc"]
    assert sha256.sha256_chunks_device(chunks) == [
        hashlib.sha256(c).digest() for c in chunks]
    assert sha256.stats["mesh_dispatches"] > before["mesh_dispatches"]
    assert sha256.stats["mesh_devices"] == len(jax.devices()) == 8
    assert sha256.stats["mesh_shard_devices"] == 8


@pytest.mark.parametrize("lens,lanes", [
    # a bucket runs for its longest chunk: 65..128 blocks share one
    ([4100, 8000], (4100 + 8000) / (64 * 126)),
    # two buckets, one program each: 9 rows x 1 step, then 3 x 63
    ([55] * 9 + [4000] * 3, (9 * 55 + 3 * 4000) / (64 * (1 + 63))),
    # many equal chunks: every lane carries data to the end
    ([KIB] * 64, 64 * KIB / (64 * 17)),
    ([0], 0.0),
])
def test_a_device_span_says_how_many_lanes_carried_data(spans, lens, lanes):
    """``lanes_busy`` of ``device.sha``: the buffer's bytes over 64 times
    the block steps its programs ran — the number a kernel PR has to
    raise (tools/sha_crossover.py reads it)."""
    sha256.sha256_chunks_device([bytes(n) for n in lens])
    device = [r for r in spans if r["name"] == "device.sha"]
    assert len(device) == 1
    assert device[0]["attrs"]["lanes_busy"] == round(lanes, 2)
    assert not [r for r in spans if r["name"] == "host.sha"]


def test_a_failing_device_dispatch_raises_and_is_not_rehashed(
        feeder, monkeypatch):
    def lost_device(reqs):
        raise RuntimeError("injected: device lost")
    monkeypatch.setattr(feeder, "_sha_digests", lost_device)
    before = dict(sha256.stats)
    with pytest.raises(RuntimeError, match="injected: device lost"):
        feeder.sha256_batch([_bytes(KIB, 1)] * 4)
    assert all(sha256.stats[k] == before[k] for k in HOST_KEYS)


def test_a_failing_program_raises_and_is_not_rehashed(monkeypatch):
    """One level down: the jitted program itself raises inside the
    device engine."""
    def broken(*a, **kw):
        raise MemoryError("injected: does not fit")
    monkeypatch.setattr(sha256, "_sha256_scan", broken)
    before = dict(sha256.stats)
    with pytest.raises(MemoryError, match="injected"):
        sha256.sha256_chunks_device([_bytes(KIB, 1)] * 4)
    assert all(sha256.stats[k] == before[k] for k in HOST_KEYS)


# --- /metrics -------------------------------------------------------------------

def test_metrics_show_the_host_engine_beside_the_device(tmp_path):
    from pbs_plus_tpu.server import metrics
    from pbs_plus_tpu.server.store import Server, ServerConfig
    sha256.sha256_chunks([b"abc"])
    sha256.sha256_chunks_device([b"abc"])
    server = Server(ServerConfig(state_dir=str(tmp_path / "state"),
                                 cert_dir=str(tmp_path / "certs"),
                                 datastore_dir=str(tmp_path / "ds")))
    expo = metrics.MetricsRegistry(server).render()
    for name, key in (("batches", "host_batches"), ("rows", "host_rows"),
                      ("bytes", "host_bytes"), ("seconds", "host_s")):
        line = [ln for ln in expo.splitlines() if ln.startswith(
            f"pbs_plus_device_sha_host_{name}_total ")]
        assert len(line) == 1, name
        assert float(line[0].split()[1]) == pytest.approx(
            float(sha256.stats[key]))
    assert 'pbs_plus_device_bytes_total{kind="payload",op="sha"}' in expo
    with open("docs/observability.md", encoding="utf-8") as f:
        assert "`host.sha`" in f.read()
