"""Length-prefixed raw binary transfer over a mux stream.

Reference: internal/arpc/binary_stream.go:12-124 — 14-byte header
``magic(4) + version(2) + length(8)``, 1 GiB frame cap, drain-on-short-
buffer so a short consumer never desyncs the stream.
"""

from __future__ import annotations

import struct
from typing import Callable

from ..utils import conf, failpoints
from .mux import MuxError, MuxStream

MAGIC = b"TPBS"
VERSION = 1
_HDR = struct.Struct("<4sHQ")
MAX_FRAME = conf.MAX_FRAME_SIZE            # 1 GiB
_IO_CHUNK = 1 << 20


class StreamLengthError(MuxError):
    """Declared-vs-actual length violation on a framed binary transfer:
    the header promised ``declared`` bytes but the stream delivered (or
    the reader produced) only ``actual`` before EOF.  Receive-side
    violations are counted in the per-connection
    ``stats["stream_length_violations"]`` — a peer lying about stream
    lengths is an abuse signal, not a generic transport hiccup."""

    def __init__(self, msg: str, *, declared: int, actual: int):
        super().__init__(msg)
        self.declared = declared
        self.actual = actual


async def send_data_from_reader(stream: MuxStream, reader,
                                total_len: int) -> int:
    """Send exactly ``total_len`` bytes read from ``reader`` (object with
    .read(n) → bytes, or bytes-like)."""
    if total_len < 0 or total_len > MAX_FRAME:
        raise MuxError(f"frame length {total_len} exceeds cap")
    await failpoints.ahit("arpc.binary.send")
    await stream.write(_HDR.pack(MAGIC, VERSION, total_len))
    if isinstance(reader, (bytes, bytearray, memoryview)):
        data = memoryview(reader)[:total_len]
        if len(data) < total_len:
            raise StreamLengthError(
                f"reader holds {len(data)} bytes of declared {total_len}",
                declared=total_len, actual=len(data))
        # views all the way down: ``MuxStream.write`` cuts each into
        # frames and copies only bytes the caller could still change
        for sent in range(0, total_len, _IO_CHUNK):
            await stream.write(data[sent:sent + _IO_CHUNK])
        return total_len
    sent = 0
    while sent < total_len:
        block = reader.read(min(_IO_CHUNK, total_len - sent))
        if not block:
            raise StreamLengthError(
                f"reader EOF at {sent}/{total_len}",
                declared=total_len, actual=sent)
        await stream.write(block)
        sent += len(block)
    return sent


async def _read_header(stream: MuxStream) -> int:
    """The 14-byte header of one framed transfer: its declared length.
    Every receive starts here, so here is its failpoint."""
    await failpoints.ahit("arpc.binary.receive")
    hdr = await stream.readexactly(_HDR.size)
    magic, ver, length = _HDR.unpack(hdr)
    if magic != MAGIC:
        raise MuxError(f"bad binary frame magic {magic!r}")
    if ver != VERSION:
        raise MuxError(f"unsupported binary frame version {ver}")
    if length > MAX_FRAME:
        raise MuxError(f"frame length {length} exceeds cap")
    return length


def _short(stream: MuxStream, got: int, length: int) -> StreamLengthError:
    # declared-vs-actual accounting: the sender promised ``length``
    # bytes and FINed early — a lying peer, counted per connection so
    # fleet soaks can assert the abuse was SEEN, not just survived
    stream.conn.stats["stream_length_violations"] += 1
    return StreamLengthError(f"stream EOF at {got}/{length}",
                             declared=length, actual=got)


async def _drain(stream: MuxStream, got: int, length: int) -> None:
    """Read and drop what the transfer holds past ``got`` (the
    reference's drain-on-short-buffer)."""
    while got < length:
        block = await stream.read(min(_IO_CHUNK, length - got))
        if not block:
            raise _short(stream, got, length)
        got += len(block)


async def receive_data(stream: MuxStream, max_len: int) -> bytearray:
    """Receive one framed transfer into a buffer of its own: the bulk
    bytes' way.  The header says how long the transfer is, so the
    buffer is sized once — to that, or to ``max_len`` (what the caller
    asked the peer for) if the header says more, so a header that lies
    commits no more memory than an honest answer — and every DATA
    frame's payload lands in it with one copy.  What the transfer holds
    past ``max_len`` is drained and discarded."""
    length = await _read_header(stream)
    keep = min(length, max_len)
    buf = bytearray(keep)
    with memoryview(buf) as view:
        got = 0
        while got < keep:
            n = await stream.readinto(view[got:])
            if not n:
                raise _short(stream, got, length)
            got += n
    await _drain(stream, keep, length)
    return buf


async def receive_data_into(stream: MuxStream,
                            sink: Callable[[bytes], object] | bytearray,
                            *, max_len: int | None = None) -> int:
    """Receive one framed transfer.  ``sink`` is a bytearray (appended) or
    a callable per block.  If the frame exceeds ``max_len``, the excess is
    drained and discarded (reference's drain-on-short-buffer) and the
    consumed length is still returned."""
    if isinstance(sink, bytearray):
        sink = sink.extend
    length = await _read_header(stream)
    keep = length if max_len is None else min(length, max_len)
    got = 0
    while got < keep:
        block = await stream.read(min(_IO_CHUNK, keep - got))
        if not block:
            raise _short(stream, got, length)
        sink(block)
        got += len(block)
    await _drain(stream, keep, length)
    return keep
