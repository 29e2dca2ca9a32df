"""L1 communication backend — the aRPC fabric.

Reference: internal/arpc (SURVEY §2.1) — QUIC control plane + TCP/mTLS/smux
data plane, CBOR envelopes, raw-stream upgrade, session registry keyed by
mTLS identity, per-client rate limiting.

This build: one asyncio TCP+mTLS transport carrying both planes, with an
in-process stream multiplexer (the smux analog — varint-free fixed frame
header, per-stream flow-controlled queues), msgpack envelopes (CBOR
isomorph, see utils/codec.py), the same 213 raw-stream upgrade handshake
semantics, method router with panic containment, and the AgentsManager
admission/eviction/rate-limit model.  The mTLS certificate CN remains the
routing key (identity model, SURVEY §5.8).

QUIC note: the reference's control plane rides QUIC for connection
migration + head-of-line avoidance; no QUIC stack is baked into this image,
so the control plane multiplexes over the same TCP transport (a transport
abstraction keeps the door open).  The full control/data separation
design — per-job data connections, crashed-job detection, flow control —
is docs/data-plane.md.
"""

from .mux import MuxConnection, MuxStream, MuxError
from .call import Request, Response, Session, STATUS_OK, STATUS_ERROR, STATUS_RAW_STREAM
from .router import Router, HandlerError
from .transport import connect_to_server, serve, TlsServerConfig, TlsClientConfig
from .agents_manager import (AdmissionDeadlineError, AdmissionRejected,
                             AgentsManager, ClientSession)
from .binary_stream import (send_data_from_reader, receive_data,
                            receive_data_into, MAX_FRAME, StreamLengthError)

__all__ = [
    "MuxConnection", "MuxStream", "MuxError",
    "Request", "Response", "Session",
    "STATUS_OK", "STATUS_ERROR", "STATUS_RAW_STREAM",
    "Router", "HandlerError",
    "connect_to_server", "serve", "TlsServerConfig", "TlsClientConfig",
    "AdmissionDeadlineError", "AdmissionRejected", "AgentsManager",
    "ClientSession",
    "send_data_from_reader", "receive_data", "receive_data_into", "MAX_FRAME",
    "StreamLengthError",
]
