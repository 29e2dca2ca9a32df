"""Sidecar gRPC shim tests: streaming chunk parity, index, similarity,
and the SidecarChunker writer adapter."""

import hashlib

import numpy as np
import pytest

from pbs_plus_tpu.chunker import ChunkerParams, chunk_bounds
from pbs_plus_tpu.sidecar import SidecarChunker, SidecarClient, serve_sidecar

P = ChunkerParams(avg_size=4 << 10)


@pytest.fixture(scope="module")
def sidecar():
    server, port, svc = serve_sidecar(params=P, use_tpu=False)
    client = SidecarClient(f"127.0.0.1:{port}")
    yield client, svc
    client.close()
    server.stop(grace=None)


def _data(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8).tobytes()


def test_chunk_stream_parity(sidecar):
    client, _ = sidecar
    data = _data(300_000, seed=1)
    want = chunk_bounds(data, P)
    cuts, digests = [], []
    for off in range(0, len(data), 65_536):
        r = client.chunk("s1", data[off:off + 65_536])
        cuts += r["cuts"]
        digests += r["digests"]
    r = client.chunk("s1", b"", eof=True)
    cuts += r["cuts"]
    digests += r["digests"]
    assert cuts == [e for _, e in want]
    for (s, e), d in zip(want, digests):
        assert d == hashlib.sha256(data[s:e]).digest()


def test_index_roundtrip(sidecar):
    client, _ = sidecar
    digs = [hashlib.sha256(bytes([i, 42])).digest() for i in range(50)]
    assert client.probe_index(digs) == [False] * 50
    assert client.insert_index(digs) == 50
    assert client.probe_index(digs) == [True] * 50
    assert client.insert_index(digs[:10]) == 0
    st = client.stats()
    assert st["index_size"] >= 50


def test_similarity_endpoint(sidecar):
    client, _ = sidecar
    digs = [hashlib.sha256(bytes([i & 0xFF, i >> 8, 9])).digest()
            for i in range(500)]
    sig1 = client.snapshot_signature(digs)
    sig2 = client.snapshot_signature(digs)
    assert sig1 == sig2 and len(sig1) == 128


def test_stub_cached_per_method_and_timeout_plumbed(sidecar, monkeypatch):
    """Regression: _call used to rebuild the unary_unary stub on every
    RPC and hard-code timeout=300; now one stub per method is cached and
    the deadline comes from conf (PBS_PLUS_SIDECAR_TIMEOUT) or the
    constructor."""
    client, _ = sidecar
    client._stubs.clear()
    client.stats()
    client.stats()
    client.probe_index([hashlib.sha256(b"q").digest()])
    assert set(client._stubs) == {"/pbsplus.Dedup/Stats",
                                  "/pbsplus.Dedup/ProbeIndex"}
    stats_stub = client._stubs["/pbsplus.Dedup/Stats"]
    client.stats()
    assert client._stubs["/pbsplus.Dedup/Stats"] is stats_stub

    # default comes from conf; explicit constructor arg wins
    from pbs_plus_tpu.sidecar.client import SidecarClient
    from pbs_plus_tpu.utils import conf
    assert client.timeout_s == conf.env().sidecar_timeout_s == 300.0
    c2 = SidecarClient("127.0.0.1:1", timeout_s=7.5)
    assert c2.timeout_s == 7.5
    c2.close()

    # env knob: a fresh conf.env() picks the override up
    monkeypatch.setenv("PBS_PLUS_SIDECAR_TIMEOUT", "12.5")
    conf.env.cache_clear()
    try:
        c3 = SidecarClient("127.0.0.1:1")
        assert c3.timeout_s == 12.5
        c3.close()
    finally:
        conf.env.cache_clear()


def test_chunk_rpc_failure_is_not_retried(sidecar):
    """The stateful Chunk feed must never be replayed (a retry would
    double-append to the sidecar's stream carry); idempotent methods do
    retry.  Injected via the sidecar.call failpoint."""
    from pbs_plus_tpu.utils import failpoints

    client, _ = sidecar
    before = client.breaker._failures
    with failpoints.armed("sidecar.call", "drop", once=True) as fp:
        with pytest.raises(ConnectionResetError):
            client.chunk("retrytest", b"abc")
        assert fp.hits == 1              # exactly one attempt
    assert client.breaker._failures == before + 1
    # idempotent path retries through the same (one-shot) fault
    with failpoints.armed("sidecar.call", "drop", once=True) as fp:
        assert client.stats()["chunker"]["avg"] == P.avg_size
        assert fp.hits >= 2              # first attempt dropped, retried
    client.breaker._record_success()     # leave the shared fixture clean


def test_sidecar_chunker_in_writer(sidecar, tmp_path):
    client, _ = sidecar
    import io
    from pbs_plus_tpu.pxar import Entry, KIND_DIR, KIND_FILE, LocalStore
    store = LocalStore(str(tmp_path / "ds"), P,
                       chunker_factory=lambda p: SidecarChunker(p, client))
    s = store.start_session(backup_type="host", backup_id="sc")
    s.writer.write_entry(Entry(path="", kind=KIND_DIR))
    data = _data(200_000, seed=2)
    s.writer.write_entry_reader(Entry(path="f", kind=KIND_FILE), io.BytesIO(data))
    s.finish()
    r = store.open_snapshot(s.ref)
    e = [x for x in r.entries() if x.is_file][0]
    assert r.read_file(e) == data
    # chunk boundaries identical to the local CPU chunker
    want_n = len(chunk_bounds(data, P))
    assert len(list(r.payload_index.records())) == want_n


def test_auto_backend_probe_failure_is_raised(monkeypatch):
    """``--tpu auto`` asks jax for its backend; an exception there is the
    caller's — it used to become ``use_tpu = False`` in silence."""
    import jax

    from pbs_plus_tpu.sidecar.service import DedupService
    from pbs_plus_tpu.utils import jaxenv

    def broken():
        raise RuntimeError("injected: backend init failed")
    jaxenv.on_accelerator.cache_clear()
    monkeypatch.setattr(jax, "default_backend", broken)
    try:
        with pytest.raises(RuntimeError, match="backend init failed"):
            DedupService(use_tpu=None)
    finally:
        monkeypatch.undo()
        jaxenv.on_accelerator.cache_clear()
    assert DedupService(use_tpu=None).use_tpu is False    # CPU backend
