"""Sidecar gRPC service: Chunk(stream pages) → cut points + digests.

Methods (all msgpack request/response over grpc):

    /pbsplus.Dedup/Chunk        {stream_id, data, eof} →
                                {cuts: [abs offsets], digests: [32B], ...}
    /pbsplus.Dedup/ProbeIndex   {digests: [32B]} → {present: [bool]}
    /pbsplus.Dedup/InsertIndex  {digests: [32B]} → {inserted: int}
    /pbsplus.Dedup/Stats        {} → pipeline stats
    /pbsplus.Dedup/Similarity   {digests: [...]} → {signature: [u32]}

The Chunk method is stateful per stream_id (streaming CDC with carry), so
many agents multiplex one sidecar — the batch axis of the north star.
"""

from __future__ import annotations

import threading
from concurrent import futures

import grpc
import numpy as np

from ..chunker.spec import ChunkerParams
from ..models.dedup import TpuChunker
from ..models.similarity import SimilarityModel
from ..ops.cuckoo import CuckooIndex
from ..ops.sha256 import sha256_chunks
from ..utils import codec
from ..utils.log import L


import time as _time

_STREAM_IDLE_EVICT_S = 3600.0      # abandoned-stream GC


class _StreamState:
    def __init__(self, params: ChunkerParams, use_tpu: bool):
        if use_tpu:
            self.chunker = TpuChunker(params)
        else:
            from ..chunker import CpuChunker
            self.chunker = CpuChunker(params)
        self.pending = bytearray()     # bytes not yet emitted as chunks
        self.base = 0                  # stream offset of pending[0]
        self.lock = threading.Lock()   # serialize calls per stream
        self.last_used = _time.monotonic()


class DedupService:
    def __init__(self, *, params: ChunkerParams | None = None,
                 index_buckets: int = 1 << 20, use_tpu: bool | None = None):
        self.params = params or ChunkerParams(avg_size=4 << 20)
        if use_tpu is None:
            # "auto": the device chunker where jax's backend is an
            # accelerator; a backend that fails to initialise raises
            from ..utils import jaxenv
            use_tpu = jaxenv.on_accelerator()
        self.use_tpu = use_tpu
        self.index = CuckooIndex(n_buckets=index_buckets)
        self.similarity = SimilarityModel()
        self._streams: dict[str, _StreamState] = {}
        self._lock = threading.Lock()
        self.stats = {"bytes": 0, "chunks": 0, "streams": 0}

    # -- handlers ----------------------------------------------------------
    def chunk(self, req: dict) -> dict:
        sid = req["stream_id"]
        data = req.get("data", b"")
        eof = bool(req.get("eof", False))
        with self._lock:
            now = _time.monotonic()
            # GC streams abandoned by crashed clients (never sent eof)
            for k in [k for k, v in self._streams.items()
                      if now - v.last_used > _STREAM_IDLE_EVICT_S]:
                del self._streams[k]
            st = self._streams.get(sid)
            if st is None:
                st = _StreamState(self.params, self.use_tpu)
                self._streams[sid] = st
                self.stats["streams"] += 1
            st.last_used = now
        with st.lock:                       # serialize per-stream feeds
            st.pending += data
            cuts = st.chunker.feed(data) if data else []
            if eof:
                cuts += st.chunker.finalize()
            chunks: list[bytes] = []
            out_cuts: list[int] = []
            for c in cuts:
                n = c - st.base
                chunks.append(bytes(st.pending[:n]))
                del st.pending[:n]
                st.base = c
                out_cuts.append(c)
        # on this request's own thread, by the host's SHA-256: the
        # device program loses on every batch shape (ops/sha256.py)
        digests = sha256_chunks(chunks)
        with self._lock:
            self.stats["bytes"] += len(data)
            self.stats["chunks"] += len(chunks)
            if eof:
                self._streams.pop(sid, None)
        return {"cuts": out_cuts, "digests": digests,
                "sizes": [len(c) for c in chunks]}

    def probe_index(self, req: dict) -> dict:
        digests = list(req["digests"])
        return {"present": self.index.probe_confirmed(digests)}

    def insert_index(self, req: dict) -> dict:
        return {"inserted": self.index.insert_many(list(req["digests"]))}

    def get_stats(self, req: dict) -> dict:
        return {**self.stats, "index_size": len(self.index),
                "use_tpu": self.use_tpu,
                "chunker": {"avg": self.params.avg_size,
                            "min": self.params.min_size,
                            "max": self.params.max_size,
                            "seed": self.params.seed}}

    def snapshot_signature(self, req: dict) -> dict:
        sig = self.similarity.snapshot_signature(list(req["digests"]))
        return {"signature": [int(x) for x in sig]}


def _handler(fn):
    return grpc.unary_unary_rpc_method_handler(
        lambda req, ctx: codec.encode(fn(codec.decode_map(req))),
        request_deserializer=lambda b: b,
        response_serializer=lambda b: b,
    )


class _Dispatcher(grpc.GenericRpcHandler):
    def __init__(self, svc: DedupService):
        self._methods = {
            "/pbsplus.Dedup/Chunk": _handler(svc.chunk),
            "/pbsplus.Dedup/ProbeIndex": _handler(svc.probe_index),
            "/pbsplus.Dedup/InsertIndex": _handler(svc.insert_index),
            "/pbsplus.Dedup/Stats": _handler(svc.get_stats),
            "/pbsplus.Dedup/Similarity": _handler(svc.snapshot_signature),
        }

    def service(self, handler_call_details):
        return self._methods.get(handler_call_details.method)


def serve_sidecar(address: str = "127.0.0.1:0", *,
                  params: ChunkerParams | None = None,
                  use_tpu: bool | None = None,
                  max_workers: int = 8) -> tuple[grpc.Server, int, DedupService]:
    """Start the sidecar; returns (server, bound_port, service)."""
    svc = DedupService(params=params, use_tpu=use_tpu)
    server = grpc.server(
        futures.ThreadPoolExecutor(max_workers=max_workers),
        options=[("grpc.max_receive_message_length", 128 << 20),
                 ("grpc.max_send_message_length", 128 << 20)])
    server.add_generic_rpc_handlers((_Dispatcher(svc),))
    port = server.add_insecure_port(address)
    server.start()
    L.info("dedup sidecar listening on port %d (tpu=%s)", port, svc.use_tpu)
    return server, port, svc
