"""aRPC tests over real TLS loopback connections with a self-contained test
PKI (reference: internal/arpc/arpc_test.go:26-120 — CA + leaf issuance
driving real TCP+TLS+smux loopback; echo, concurrency, deadline, error
mapping, raw-stream handshake, rejection, leak discipline)."""

import asyncio
import os
import threading

import pytest

from pbs_plus_tpu.arpc import (
    AgentsManager, HandlerError, MAX_FRAME, Request, Response, Router,
    Session, TlsClientConfig, TlsServerConfig, connect_to_server,
    receive_data_into, send_data_from_reader, serve,
)
from pbs_plus_tpu.arpc.call import CallError, RawStreamHandler
from pbs_plus_tpu.arpc.transport import HandshakeError
from pbs_plus_tpu.utils import mtls


@pytest.fixture(scope="module")
def pki(tmp_path_factory):
    """Test PKI: CA + server leaf + two agent leaves."""
    d = tmp_path_factory.mktemp("pki")
    cm = mtls.CertManager(str(d))
    cm.load_or_create_ca()
    cm.ensure_server_identity("server.test")
    paths = {"ca": cm.ca_cert_path, "server_cert": cm.server_cert_path,
             "server_key": cm.server_key_path}
    for name in ("agent-1", "agent-2"):
        cert, key = cm.issue(name)
        cp, kp = str(d / f"{name}.pem"), str(d / f"{name}.key")
        open(cp, "wb").write(cert)
        open(kp, "wb").write(key)
        paths[name] = (cp, kp)
    return paths


def run_async(coro):
    """Each test gets a fresh loop (leak discipline: the loop is closed and
    all tasks must have completed)."""
    return asyncio.run(coro)


def make_router():
    r = Router()

    async def echo(req, ctx):
        return req.payload

    async def fail(req, ctx):
        raise HandlerError("nope", status=418)

    async def crash(req, ctx):
        raise RuntimeError("boom")

    async def slow(req, ctx):
        await asyncio.sleep(5)
        return "late"

    async def download(req, ctx):
        size = int(req.payload["n"])
        data = bytes(range(256)) * (size // 256 + 1)

        async def pump(stream):
            await send_data_from_reader(stream, data[:size], size)
        return RawStreamHandler(pump, data={"size": size})

    r.handle("echo", echo)
    r.handle("fail", fail)
    r.handle("crash", crash)
    r.handle("slow", slow)
    r.handle("download", download)
    return r


async def start_server(pki, am: AgentsManager | None = None, port=0):
    router = make_router()
    sessions = []

    async def on_conn(conn, peer, headers):
        if am is not None:
            sess = await am.register(peer, headers, conn)
            sessions.append(sess)
            try:
                await router.serve_connection(conn, context=sess)
            finally:
                await am.unregister(sess)
        else:
            await router.serve_connection(conn)

    tls = TlsServerConfig(pki["server_cert"], pki["server_key"], pki["ca"])
    srv = await serve("127.0.0.1", port, tls, on_connection=on_conn,
                      admit=am.admit if am else None)
    return srv, srv.sockets[0].getsockname()[1], sessions


def client_tls(pki, name="agent-1"):
    cp, kp = pki[name]
    return TlsClientConfig(cp, kp, pki["ca"])


def test_echo_and_errors(pki):
    async def main():
        srv, port, _ = await start_server(pki)
        conn = await connect_to_server("127.0.0.1", port, client_tls(pki))
        s = Session(conn)
        resp = await s.call("echo", {"x": 1, "b": b"\x00\xff"})
        assert resp.data == {"x": 1, "b": b"\x00\xff"}
        with pytest.raises(CallError) as ei:
            await s.call("fail")
        assert ei.value.response.status == 418
        with pytest.raises(CallError) as ei:
            await s.call("crash")
        assert ei.value.response.status == 500
        assert "boom" in ei.value.response.message
        with pytest.raises(CallError) as ei:
            await s.call("nosuch")
        assert ei.value.response.status == 404
        await conn.close()
        srv.close()
        await srv.wait_closed()
    run_async(main())


def test_concurrent_calls(pki):
    async def main():
        srv, port, _ = await start_server(pki)
        conn = await connect_to_server("127.0.0.1", port, client_tls(pki))
        s = Session(conn)
        results = await asyncio.gather(
            *[s.call("echo", i) for i in range(50)])
        assert [r.data for r in results] == list(range(50))
        await conn.close()
        srv.close()
        await srv.wait_closed()
    run_async(main())


def test_call_timeout(pki):
    async def main():
        srv, port, _ = await start_server(pki)
        conn = await connect_to_server("127.0.0.1", port, client_tls(pki))
        s = Session(conn)
        with pytest.raises(asyncio.TimeoutError):
            await s.call("slow", timeout=0.3)
        # connection still usable after a timed-out call
        assert (await s.call("echo", "ok")).data == "ok"
        await conn.close()
        srv.close()
        await srv.wait_closed()
    run_async(main())


def test_raw_stream_download(pki):
    async def main():
        srv, port, _ = await start_server(pki)
        conn = await connect_to_server("127.0.0.1", port, client_tls(pki))
        s = Session(conn)
        for size in (0, 1, 1000, 1 << 20):
            buf = bytearray()
            resp, n = await s.call_binary_into("download", {"n": size}, buf)
            assert n == size == len(buf)
            assert resp.data == {"size": size}
            assert bytes(buf) == (bytes(range(256)) * (size // 256 + 1))[:size]
        await conn.close()
        srv.close()
        await srv.wait_closed()
    run_async(main())


def test_mtls_required(pki, tmp_path):
    """A client with a cert from a different CA is rejected at TLS."""
    async def main():
        srv, port, _ = await start_server(pki)
        rogue_dir = tmp_path / "rogue"
        rogue = mtls.CertManager(str(rogue_dir))
        rogue.load_or_create_ca()
        cert, key = rogue.issue("evil")
        cp, kp = str(rogue_dir / "c.pem"), str(rogue_dir / "k.pem")
        open(cp, "wb").write(cert)
        open(kp, "wb").write(key)
        with pytest.raises((ConnectionError, OSError, asyncio.TimeoutError,
                            asyncio.IncompleteReadError, EOFError)):
            await connect_to_server(
                "127.0.0.1", port,
                TlsClientConfig(cp, kp, pki["ca"]), timeout=5)
        srv.close()
        await srv.wait_closed()
    run_async(main())


def test_agents_manager_admission(pki):
    async def main():
        expected = {"agent-1"}

        async def is_expected(cn, der):
            return cn in expected

        am = AgentsManager(is_expected=is_expected)
        srv, port, _ = await start_server(pki, am)
        # expected host connects
        conn = await connect_to_server("127.0.0.1", port, client_tls(pki))
        await asyncio.sleep(0.1)
        assert am.get("agent-1") is not None
        # unexpected host rejected with code
        with pytest.raises(HandshakeError) as ei:
            await connect_to_server("127.0.0.1", port,
                                    client_tls(pki, "agent-2"))
        assert ei.value.code == 403
        # job session requires expect()
        with pytest.raises(HandshakeError):
            await connect_to_server(
                "127.0.0.1", port, client_tls(pki),
                headers={"X-PBS-Plus-BackupID": "job9"})
        am.expect("agent-1|job9")
        wait_task = asyncio.create_task(am.wait_session("agent-1|job9", 5))
        jconn = await connect_to_server(
            "127.0.0.1", port, client_tls(pki),
            headers={"X-PBS-Plus-BackupID": "job9"})
        sess = await wait_task
        assert sess.client_id == "agent-1|job9"
        # duplicate primary session evicts the old one (newest wins)
        old_sess = am.get("agent-1")
        conn2 = await connect_to_server("127.0.0.1", port, client_tls(pki))
        await asyncio.sleep(0.2)
        assert conn.closed                       # old client conn torn down
        new_sess = am.get("agent-1")
        assert new_sess is not old_sess and not new_sess.conn.closed
        assert old_sess.conn.closed
        await jconn.close()
        await conn2.close()
        srv.close()
        await srv.wait_closed()
    run_async(main())


def test_rate_limit(pki):
    async def main():
        async def yes(cn, der):
            return True
        am = AgentsManager(is_expected=yes, rate=5, burst=3)
        srv, port, _ = await start_server(pki, am)
        ok = rejected = 0
        for _ in range(8):
            try:
                c = await connect_to_server("127.0.0.1", port,
                                            client_tls(pki))
                ok += 1
                await c.close()
            except HandshakeError as e:
                assert e.code == 429
                rejected += 1
        assert rejected >= 1 and ok >= 3
        srv.close()
        await srv.wait_closed()
    run_async(main())


def test_frame_cap():
    from pbs_plus_tpu.arpc.mux import MuxError

    class FakeStream:
        async def write(self, b): pass
    async def main():
        with pytest.raises(MuxError):
            await send_data_from_reader(FakeStream(), b"", MAX_FRAME + 1)
    run_async(main())


def test_no_thread_leaks(pki):
    """Leak discipline (reference: TestLeak_*): after a full client/server
    cycle no extra threads survive."""
    before = threading.active_count()

    async def main():
        srv, port, _ = await start_server(pki)
        conn = await connect_to_server("127.0.0.1", port, client_tls(pki))
        s = Session(conn)
        await s.call("echo", "x")
        await conn.close()
        srv.close()
        await srv.wait_closed()
    run_async(main())
    assert threading.active_count() <= before + 1


def test_mux_write_unblocks_on_peer_rst():
    """A writer blocked on exhausted tx credit must fail fast when the
    peer resets the stream or the connection dies — not hang forever
    (advisor finding r1: raw-stream pumps when the peer dies mid-transfer)."""
    from pbs_plus_tpu.arpc.mux import INITIAL_CREDIT, MuxConnection, MuxError

    async def main():
        accepted = asyncio.Queue()

        async def on_conn(reader, writer):
            conn = MuxConnection(reader, writer, is_client=False,
                                 keepalive_s=0)
            conn.start()
            await accepted.put(conn)

        srv = await asyncio.start_server(on_conn, "127.0.0.1", 0)
        port = srv.sockets[0].getsockname()[1]
        r, w = await asyncio.open_connection("127.0.0.1", port)
        client = MuxConnection(r, w, is_client=True, keepalive_s=0)
        client.start()
        server_conn = await accepted.get()

        st = await client.open_stream()
        # exhaust the window: the peer never reads, so no grants come back
        writer_task = asyncio.create_task(
            st.write(b"\0" * (INITIAL_CREDIT * 2)))
        peer_st = await server_conn.accept_stream()
        await asyncio.sleep(0.2)          # let the writer hit the wall
        assert not writer_task.done()     # blocked on credit, as designed
        await peer_st.reset()
        with pytest.raises(MuxError):
            await asyncio.wait_for(writer_task, 5)

        # same for a full connection shutdown
        st2 = await client.open_stream()
        writer_task2 = asyncio.create_task(
            st2.write(b"\0" * (INITIAL_CREDIT * 2)))
        await asyncio.sleep(0.2)
        assert not writer_task2.done()
        await server_conn.close()
        with pytest.raises(MuxError):
            await asyncio.wait_for(writer_task2, 5)

        await client.close()
        srv.close()
        await srv.wait_closed()
    run_async(main())


def test_mux_peer_rst_after_local_close_retires_stream():
    """A stream the local side has already closed must leave the
    connection table when the peer RSTs it (advisor r2 flagged _on_rst;
    retirement on RST is owned by _dispatch's unconditional pop — this
    regression test pins the behavior regardless of owner)."""
    from pbs_plus_tpu.arpc.mux import MuxConnection

    async def main():
        accepted = asyncio.Queue()

        async def on_conn(reader, writer):
            conn = MuxConnection(reader, writer, is_client=False,
                                 keepalive_s=0)
            conn.start()
            await accepted.put(conn)

        srv = await asyncio.start_server(on_conn, "127.0.0.1", 0)
        port = srv.sockets[0].getsockname()[1]
        r, w = await asyncio.open_connection("127.0.0.1", port)
        client = MuxConnection(r, w, is_client=True, keepalive_s=0)
        client.start()
        server_conn = await accepted.get()

        st = await client.open_stream()
        await st.write(b"hi")
        await st.close()                  # local FIN; peer has not FIN'd
        peer_st = await server_conn.accept_stream()
        await peer_st.reset()             # peer answers with RST, not FIN
        await asyncio.sleep(0.2)
        assert st.sid not in client._streams, \
            "peer-RST after local close must retire the stream table entry"

        await client.close()
        await server_conn.close()
        srv.close()
        await srv.wait_closed()
    run_async(main())


# ------------------------------------------- the bulk bytes' way (PR 35)
#
# A MuxConnection over a tape in place of a socket: everything it hands
# to the transport is kept, everything the peer "sends" is fed by hand,
# so frame boundaries, grants and counters are exact.

from pbs_plus_tpu.arpc import receive_data  # noqa: E402
from pbs_plus_tpu.arpc import mux as muxmod  # noqa: E402
from pbs_plus_tpu.arpc.binary_stream import (  # noqa: E402
    MAGIC, StreamLengthError,
)
from pbs_plus_tpu.utils import failpoints  # noqa: E402

KIB, MIB = 1 << 10, 1 << 20


class _Tape:
    """A StreamWriter's surface (and its transport's) over nothing."""

    HIGH = 64 * KIB

    def __init__(self):
        self.transport = self
        self.writes: list[bytes] = []
        self.buffered = 0               # what get_write_buffer_size says
        self.drains = 0
        self.unpaused = asyncio.Event()
        self.unpaused.set()

    def get_write_buffer_limits(self):
        return (self.HIGH // 4, self.HIGH)

    def get_write_buffer_size(self):
        return self.buffered

    def write(self, data):
        self.writes.append(bytes(data))

    async def drain(self):
        self.drains += 1
        await self.unpaused.wait()

    def close(self):
        pass

    async def wait_closed(self):
        pass

    def frames(self) -> list[tuple[int, int, bytes]]:
        """(type, sid, payload) of every frame written so far."""
        wire, out, at = b"".join(self.writes), [], 0
        while at < len(wire):
            ftype, sid, ln = muxmod._HDR.unpack_from(wire, at)
            at += muxmod._HDR.size
            out.append((ftype, sid, wire[at:at + ln]))
            at += ln
        assert at == len(wire)
        return out


def _frame(ftype: int, sid: int, payload: bytes = b"") -> bytes:
    return muxmod._HDR.pack(ftype, sid, len(payload)) + payload


async def _taped(**kw):
    """(connection, its tape, the reader the test feeds)."""
    reader, tape = asyncio.StreamReader(), _Tape()
    conn = muxmod.MuxConnection(reader, tape, is_client=False,
                                keepalive_s=0, **kw)
    conn.start()
    return conn, tape, reader


async def _settle():
    for _ in range(20):
        await asyncio.sleep(0)


def _body(n: int) -> bytes:
    return bytes(i * 7 % 251 for i in range(min(n, 4096))) * (n // 4096 + 1)


def test_wire_sender_frames_are_the_parents():
    """No wire change on the way out: the transfer's 14-byte header as a
    frame of its own, DATA payloads cut at 256 KiB inside 1 MiB slices
    and at the credit, the rest of the slice after the grant — the
    boundaries PR 34's sender wrote, byte for byte."""
    total = muxmod.INITIAL_CREDIT + 300 * KIB
    body = _body(total)[:total]

    async def main():
        conn, tape, reader = await _taped()
        st = await conn.open_stream()
        send = asyncio.ensure_future(send_data_from_reader(st, body, total))
        await _settle()
        assert not send.done()          # out of credit, 14 bytes short
        reader.feed_data(_frame(muxmod.WINDOW, st.sid,
                                (MIB).to_bytes(4, "little")))
        assert await asyncio.wait_for(send, 5) == total
        frames = tape.frames()
        assert frames[0] == (muxmod.SYN, 2, b"")
        assert all(f[:2] == (muxmod.DATA, 2) for f in frames[1:])
        hdr = frames[1][2]
        assert len(hdr) == 14 and hdr[:4] == MAGIC
        assert int.from_bytes(hdr[6:], "little") == total
        assert [len(f[2]) for f in frames[2:]] == \
            [256 * KIB] * 15 + [256 * KIB - 14, 14, 256 * KIB, 44 * KIB]
        assert b"".join(f[2] for f in frames[2:]) == body
        assert conn.stats["frames_tx"] == len(frames)
        assert conn.stats["bytes_tx"] == sum(9 + len(f[2]) for f in frames)
        await conn.close()
    run_async(main())


@pytest.mark.parametrize("source", ["bytes", "bytearray", "reader"])
def test_wire_sender_same_bytes_from_every_source(source):
    """Immutable bytes go down as views, a bytearray the caller could
    still change is copied frame by frame, a reader is read block by
    block: the wire is the same."""
    import io
    total = MIB + 1
    body = _body(total)[:total]

    async def main():
        conn, tape, _ = await _taped()
        st = await conn.open_stream()
        src = {"bytes": body, "bytearray": bytearray(body),
               "reader": io.BytesIO(body)}[source]
        assert await send_data_from_reader(st, src, total) == total
        if source == "bytearray":
            src[:] = bytes(total)       # the caller's buffer, reused
        frames = tape.frames()[2:]
        assert [len(f[2]) for f in frames] == [256 * KIB] * 4 + [1]
        assert b"".join(f[2] for f in frames) == body
        await conn.close()
    run_async(main())


async def _fed_stream(conn, reader, sid=1):
    reader.feed_data(_frame(muxmod.SYN, sid))
    return await asyncio.wait_for(conn.accept_stream(), 5)


def test_mux_read_partial_across_frame_boundaries():
    """``read(n)`` is what it was: up to n of what is buffered, across
    frames; a read that takes exactly one whole frame takes the frame's
    own bytes, by reference."""
    async def main():
        conn, tape, reader = await _taped()
        st = await _fed_stream(conn, reader)
        payloads = [b"a" * 5, b"b" * 3, b"c" * 4, b"d" * 6]
        for p in payloads:
            reader.feed_data(_frame(muxmod.DATA, 1, p))
        await _settle()
        assert await st.read(2) == b"aa"
        assert await st.read(4) == b"aaab"          # over a boundary
        assert await st.read(2) == b"bb"            # a frame's tail
        whole = await st.read(4)
        assert whole == b"cccc" and whole is st_frames[2]
        assert await st.read(100) == b"dddddd"
        reader.feed_data(_frame(muxmod.DATA, 1, b"ee") +
                         _frame(muxmod.DATA, 1, b"ff") +
                         _frame(muxmod.FIN, 1))
        await _settle()
        assert await st.read() == b"eeff"           # all that is buffered
        assert await st.read(5) == b""              # EOF
        assert st._rx_len == 0 and not st._rx
        await conn.close()

    st_frames = []
    orig = muxmod.MuxStream._on_data

    def keep(self, payload):
        st_frames.append(payload)
        orig(self, payload)
    muxmod.MuxStream._on_data = keep
    try:
        run_async(main())
    finally:
        muxmod.MuxStream._on_data = orig


def test_mux_readinto_lands_frames_with_one_copy_and_counts_them():
    async def main():
        conn, tape, reader = await _taped()
        st = await _fed_stream(conn, reader)
        for p in (b"abcde", b"fgh", b"ijkl"):
            reader.feed_data(_frame(muxmod.DATA, 1, p))
        await _settle()
        buf = bytearray(7)
        with memoryview(buf) as v:
            assert await st.readinto(v) == 7
        assert bytes(buf) == b"abcdefg"
        big = bytearray(100)
        with memoryview(big) as v:
            assert await st.readinto(v) == 5        # what is buffered
        assert bytes(big[:5]) == b"hijkl"
        assert conn.stats["rx_direct_bytes"] == 12
        reader.feed_data(_frame(muxmod.FIN, 1))
        await _settle()
        with memoryview(big) as v:
            assert await st.readinto(v) == 0        # EOF
        await conn.close()
    run_async(main())


def test_mux_credit_accounting_is_the_parents():
    """``_rx_unacked`` and the WINDOW grants for a given run of reads
    are what PR 34's byte-array stream gave: a grant once a quarter of
    the credit is consumed, of all consumed since the last one —
    whether the bytes left by ``read`` or by ``readinto``."""
    quarter = muxmod.INITIAL_CREDIT // 4

    async def main():
        conn, tape, reader = await _taped()
        st = await _fed_stream(conn, reader)
        for _ in range(12):                         # 3 MiB in 256 KiB frames
            reader.feed_data(_frame(muxmod.DATA, 1, bytes(256 * KIB)))
        await _settle()
        assert st._rx_unacked == 3 * MIB

        def grants():
            return [int.from_bytes(f[2], "little")
                    for f in tape.frames() if f[0] == muxmod.WINDOW]

        assert len(await st.read(quarter - 1)) == quarter - 1
        assert grants() == [] and st._rx_unacked == 3 * MIB
        assert len(await st.read(1)) == 1
        assert grants() == [quarter]
        assert st._rx_unacked == 3 * MIB - quarter
        buf = bytearray(quarter + 300)
        with memoryview(buf) as v:
            assert await st.readinto(v[:300]) == 300
            assert grants() == [quarter]
            assert await st.readinto(v[300:]) == quarter
        assert grants() == [quarter, quarter + 300]
        assert st._rx_unacked == 3 * MIB - 2 * quarter - 300
        assert st._consumed_since_grant == 0
        await conn.close()
    run_async(main())


@pytest.mark.parametrize("over", [0, 1])
def test_mux_flow_violation_resets_at_credit_plus_slack(over):
    """A peer that writes past its credit is reset when the unread
    bytes pass INITIAL_CREDIT + the slack, not before."""
    limit = muxmod.INITIAL_CREDIT + muxmod._RX_CREDIT_SLACK

    async def main():
        conn, tape, reader = await _taped()
        st = await _fed_stream(conn, reader)
        left = limit + over
        while left:
            n = min(left, muxmod.MAX_DATA_FRAME)
            reader.feed_data(_frame(muxmod.DATA, 1, bytes(n)))
            left -= n
        await _settle()
        assert st._rx_unacked == limit + over
        assert conn.stats["flow_violations"] == over
        rsts = [f for f in tape.frames() if f[0] == muxmod.RST]
        assert len(rsts) == over
        if over:
            with pytest.raises(muxmod.MuxError):
                await st.read(1)
        else:
            assert len(await st.read(10)) == 10
        await conn.close()
    run_async(main())


def test_send_frame_makes_no_timer_on_an_unpaused_transport():
    """A frame that finds room in the transport takes the plain drain:
    no ``wait_for``, ``drain_waits`` stays 0."""
    async def main():
        conn, tape, _ = await _taped(write_deadline_s=60.0)
        st = await conn.open_stream()
        timers = []
        orig = asyncio.wait_for

        async def counted(*a, **kw):
            timers.append(a)
            return await orig(*a, **kw)
        muxmod.asyncio.wait_for = counted
        try:
            await st.write(bytes(MIB))
        finally:
            muxmod.asyncio.wait_for = orig
        assert conn.stats["frames_tx"] == 5 and tape.drains == 5
        assert conn.stats["drain_waits"] == 0 and timers == []
        await conn.close()
    run_async(main())


def test_send_frame_waits_under_the_deadline_above_the_high_water_mark():
    """A write that leaves the transport at its high-water mark takes
    the timed path and counts; when the peer drains, the frame goes on."""
    async def main():
        conn, tape, _ = await _taped(write_deadline_s=60.0)
        st = await conn.open_stream()
        tape.buffered = tape.HIGH
        tape.unpaused.clear()
        w = asyncio.ensure_future(st.write(b"x" * 10))
        await _settle()
        assert not w.done() and conn.stats["drain_waits"] == 1
        tape.buffered = 0
        tape.unpaused.set()
        await asyncio.wait_for(w, 5)
        await st.write(b"y")
        assert conn.stats["drain_waits"] == 1
        assert conn.stats["write_deadline_sheds"] == 0
        await conn.close()
    run_async(main())


def test_send_frame_sheds_a_reader_that_never_drains():
    """The slow-reader shed keeps its meaning: a frame that has to wait
    waits at most the deadline, then the connection is shed."""
    async def main():
        conn, tape, _ = await _taped(write_deadline_s=0.05)
        st = await conn.open_stream()
        tape.buffered = 10 * tape.HIGH
        tape.unpaused.clear()
        with pytest.raises(muxmod.MuxError, match="shed"):
            await asyncio.wait_for(st.write(b"x" * 10), 5)
        assert conn.stats["write_deadline_sheds"] == 1
        assert conn.stats["drain_waits"] == 1
        assert conn.closed and "write deadline" in conn.close_reason
    run_async(main())


def test_send_frame_cancelled_drain_keeps_the_next_frame_timed():
    """A drain cancelled while paused leaves the protocol paused below
    the mark: the next frame still waits under the deadline."""
    async def main():
        conn, tape, _ = await _taped(write_deadline_s=0.05)
        st = await conn.open_stream()
        tape.buffered = tape.HIGH
        tape.unpaused.clear()
        w = asyncio.ensure_future(st.write(b"x"))
        await _settle()
        w.cancel()
        with pytest.raises(asyncio.CancelledError):
            await w
        tape.buffered = tape.HIGH // 2          # between the marks
        with pytest.raises(muxmod.MuxError, match="shed"):
            await asyncio.wait_for(st.write(b"y"), 5)
        assert conn.stats["drain_waits"] == 2
    run_async(main())


def test_write_frame_corrupt_failpoint_still_corrupts_a_view():
    """``arpc.mux.write_frame=corrupt`` gets a view now and still flips
    the frame's last byte on the wire (materialised only when armed);
    the caller's bytes stay as they were."""
    body = bytes(range(200))

    async def main():
        conn, tape, _ = await _taped()
        st = await conn.open_stream()
        with failpoints.armed("arpc.mux.write_frame", "corrupt", once=True):
            await st.write(body)
        await st.write(body)
        frames = [f[2] for f in tape.frames() if f[0] == muxmod.DATA]
        assert frames[0] == body[:-1] + bytes([body[-1] ^ 1])
        assert frames[1] == body
        await conn.close()
    run_async(main())


async def _taped_transfer(reader, sid, declared, payload):
    reader.feed_data(_frame(muxmod.DATA, sid,
                            MAGIC + (1).to_bytes(2, "little")
                            + declared.to_bytes(8, "little")))
    for at in range(0, len(payload), muxmod.MAX_DATA_FRAME):
        reader.feed_data(_frame(muxmod.DATA, sid,
                                payload[at:at + muxmod.MAX_DATA_FRAME]))


@pytest.mark.parametrize("sink", ["own", "bytearray", "callable"])
def test_receive_max_len_discards_the_excess(sink):
    """Drain-on-short-buffer: what the transfer holds past ``max_len``
    is read and dropped, the consumed length returned, and the stream
    is left at the transfer's end."""
    body = _body(MIB + 5)[:MIB + 5]
    keep = 300 * KIB + 1

    async def main():
        conn, tape, reader = await _taped()
        st = await _fed_stream(conn, reader)
        await _taped_transfer(reader, 1, len(body), body)
        reader.feed_data(_frame(muxmod.DATA, 1, b"next"))
        if sink == "own":
            got = await receive_data(st, keep)
            n = len(got)
        elif sink == "bytearray":
            got = bytearray(b"head")
            n = await receive_data_into(st, got, max_len=keep)
            assert got[:4] == b"head"
            del got[:4]
        else:
            blocks = []
            n = await receive_data_into(st, blocks.append, max_len=keep)
            got = b"".join(blocks)
        assert n == keep and got == body[:keep]
        assert await st.read(10) == b"next"
        assert conn.stats["rx_direct_bytes"] == (keep if sink == "own"
                                                 else 0)
        await conn.close()
    run_async(main())


@pytest.mark.parametrize("size", [0, 1, 256 * KIB - 1, 256 * KIB, MIB + 1])
def test_receive_data_direct_bytes_are_the_bytes_received(size):
    """Every payload byte of a transfer reaches the buffer by
    ``readinto``: ``rx_direct_bytes`` is the transfer's length, and the
    buffer is sized once, to it."""
    body = _body(size)[:size]

    async def main():
        conn, tape, reader = await _taped()
        st = await _fed_stream(conn, reader)
        await _taped_transfer(reader, 1, size, body)
        buf = await receive_data(st, 2 * MIB)
        assert type(buf) is bytearray and buf == body
        assert conn.stats["rx_direct_bytes"] == size
        await conn.close()
    run_async(main())


def test_receive_data_header_that_lies_is_counted_and_commits_the_ask():
    """A transfer that ends before its declared length: the violation
    is counted, and the buffer was sized to what the caller asked for,
    not to the lie."""
    async def main():
        conn, tape, reader = await _taped()
        st = await _fed_stream(conn, reader)
        await _taped_transfer(reader, 1, 512 * MIB, b"only this")
        reader.feed_data(_frame(muxmod.FIN, 1))
        sized = []
        orig = muxmod.MuxStream.readinto

        async def seen(self, buf):
            sized.append(len(buf))
            return await orig(self, buf)
        muxmod.MuxStream.readinto = seen
        try:
            with pytest.raises(StreamLengthError) as ei:
                await receive_data(st, MIB)
        finally:
            muxmod.MuxStream.readinto = orig
        assert sized[0] == MIB
        assert (ei.value.declared, ei.value.actual) == (512 * MIB, 9)
        assert conn.stats["stream_length_violations"] == 1
        await conn.close()
    run_async(main())
