"""The backup pump's calls per file (server/backup_job.py
``RemoteTreeBackup._stream_file``): a file's first read rides on its
open, a short block is its end, and a file of one block is one call and
one item of the writer's queue.  Driven against in-memory file systems
that count every call; the real agent's side is in
tests/test_agentfs_battery.py."""

import asyncio
import threading

import pytest

from agentfs_fakes import OpenReadViaCalls
from pbs_plus_tpu.pxar.format import KIND_DIR, KIND_FILE
from pbs_plus_tpu.server import backup_job as bj
from pbs_plus_tpu.server.backup_job import RemoteTreeBackup
from pbs_plus_tpu.utils import failpoints, trace

BLOCK = 1024


@pytest.fixture(autouse=True)
def _small_block(monkeypatch):
    monkeypatch.setattr(bj, "READ_BLOCK", BLOCK)
    failpoints.disarm_all()
    yield
    failpoints.disarm_all()


def _body(name: str, size: int) -> bytes:
    return bytes((i + len(name)) % 251 for i in range(size))


class CountingFS(OpenReadViaCalls):
    """One flat directory of in-memory files; counts the calls the pump
    pays for (``open_read`` counts as the one call it is on the wire,
    the ``open``/``read_at``/``close`` it is made of here do not)."""

    def __init__(self, sizes: dict, *, honours_read: bool = True):
        self.files = {name: _body(name, size)
                      for name, size in sizes.items()}
        self.honours_read = honours_read
        self.calls: list = []           # (method, name) as the wire sees it
        self.open_handles: dict = {}
        self._next = 1
        self._inside_open_read = False
        self.fail_open: set = set()
        self.fail_read: set = set()
        self.on_read = None             # hook(name, off) before a read_at

    async def attr(self, rel):
        return {"kind": KIND_DIR, "mode": 0o755, "uid": 0, "gid": 0,
                "mtime_ns": 0, "size": 0}

    async def read_dir(self, rel):
        if rel:
            return []
        return [{"name": name, "kind": KIND_FILE, "mode": 0o644, "uid": 0,
                 "gid": 0, "mtime_ns": 0, "size": len(body)}
                for name, body in sorted(self.files.items())]

    def _note(self, method, name):
        if not self._inside_open_read:
            self.calls.append((method, name))

    async def open_read(self, rel, n):
        self.calls.append(("open_read", rel))
        self._inside_open_read = True
        try:
            return await super().open_read(rel, n)
        finally:
            self._inside_open_read = False

    async def open(self, rel):
        self._note("open", rel)
        if rel in self.fail_open:
            raise FileNotFoundError(rel)
        h, self._next = self._next, self._next + 1
        self.open_handles[h] = rel
        return h

    async def read_at(self, handle, off, n):
        rel = self.open_handles[handle]
        self._note("read_at", rel)
        if self.on_read is not None:
            await self.on_read(rel, off)
        if rel in self.fail_read:
            raise OSError(5, "Input/output error")
        return self.files[rel][off:off + n]

    async def close(self, handle):
        self._note("close", self.open_handles.pop(handle))


class RecordingWriter:
    """The session writer's surface the pump uses; keeps what it got."""

    def __init__(self):
        self.got: dict = {}

    def write_entry(self, entry):
        pass

    def write_entry_reader(self, entry, reader):
        parts = []
        while True:
            b = reader.read(300)
            if not b:
                break
            parts.append(b)
        self.got[entry.path] = b"".join(parts)


class Sess:
    def __init__(self, writer=None):
        self.writer = writer or RecordingWriter()


def _run(fs, sess=None, timeout=20):
    sess = sess or Sess()

    async def main():
        pump = RemoteTreeBackup(fs, sess)
        try:
            res = await asyncio.wait_for(pump.run(), timeout)
        except Exception as e:
            return pump, e
        return pump, res
    pump, out = asyncio.run(main())
    return pump, out, sess.writer


# size -> the calls the file costs against an agent that honours `read`
# (open_read, then read_at until a block comes back short, then close)
CALLS = {0: 1, 1: 1, BLOCK - 1: 1, BLOCK: 3, BLOCK + 1: 3, 3 * BLOCK: 5}


@pytest.mark.parametrize("size", sorted(CALLS))
def test_calls_per_file(size):
    fs = CountingFS({"f.bin": size})
    pump, res, w = _run(fs)
    assert w.got == {"f.bin": _body("f.bin", size)}
    assert len(fs.calls) == CALLS[size], fs.calls
    one_call = size < BLOCK
    assert pump.pump == {"files": 1, "one_call_files": int(one_call),
                         "calls": CALLS[size]}
    assert fs.calls[0] == ("open_read", "f.bin")
    if not one_call:
        assert fs.calls[-1] == ("close", "f.bin")
        # the one empty read left: a size that is a multiple of the block
        reads = [c for c in fs.calls if c[0] == "read_at"]
        assert len(reads) == size // BLOCK
    assert not fs.open_handles
    assert (res.files, res.bytes_total, res.errors) == (1, size, [])


@pytest.mark.parametrize("size", sorted(CALLS))
def test_calls_per_file_against_an_agent_that_ignores_read(size):
    """The bare-handle answer: read_at from 0 until a short block, then
    close — and the bytes are the same."""
    fs = CountingFS({"f.bin": size}, honours_read=False)
    pump, res, w = _run(fs)
    assert w.got == {"f.bin": _body("f.bin", size)}
    assert pump.pump == {"files": 1, "one_call_files": 0,
                         "calls": 2 + size // BLOCK + 1}
    assert len(fs.calls) == pump.pump["calls"]
    assert not fs.open_handles
    assert (res.files, res.bytes_total) == (1, size)


def test_a_tree_of_small_files_is_one_call_a_file():
    sizes = {f"f{i:03d}": (i * 37) % BLOCK for i in range(200)}
    sizes["big"] = 4 * BLOCK + 5
    fs = CountingFS(sizes)
    pump, res, w = _run(fs)
    assert w.got == fs.files
    assert pump.pump == {"files": 201, "one_call_files": 200,
                         "calls": 200 + 6}
    assert res.files == 201 and res.bytes_total == sum(sizes.values())


def test_open_failure_skips_the_file():
    fs = CountingFS({"a": 10, "b": 10, "c": 2 * BLOCK})
    fs.fail_open = {"b"}
    pump, res, w = _run(fs)
    assert sorted(w.got) == ["a", "c"]          # the writer never saw b
    assert len(res.errors) == 1 and res.errors[0].startswith("b: open: ")
    assert res.files == 2


def test_first_read_failure_fails_as_a_read_does():
    """The writer gets the file and its read raises: the error is the
    file's `read:` error and the job fails with the writer's."""
    fs = CountingFS({"a": 10, "b": 10, "c": 10})
    fs.fail_read = {"b"}
    pump, exc, w = _run(fs)
    assert isinstance(exc, RuntimeError) and "read b:" in str(exc)
    assert pump.result.errors == ["b: read: [Errno 5] Input/output error"]
    assert "a" in w.got and "b" not in w.got
    assert not fs.open_handles                  # nothing left open


def test_later_read_failure_still_fails_the_file():
    fs = CountingFS({"a": 3 * BLOCK})

    async def on_read(rel, off):
        if off == 2 * BLOCK:
            raise OSError(5, "Input/output error")
    fs.on_read = on_read
    pump, exc, w = _run(fs)
    assert isinstance(exc, RuntimeError) and "read a:" in str(exc)
    assert pump.result.errors[0].startswith("a: read: ")
    assert fs.calls[-1] == ("close", "a") and not fs.open_handles


def test_failpoint_fires_before_the_first_read():
    fs = CountingFS({"a": 10})
    with failpoints.armed("backup.file.stream", "raise", nth=1) as fp:
        pump, exc, w = _run(fs)
    assert fp.fires == 1
    assert fs.calls == []                       # no byte was asked for
    assert isinstance(exc, RuntimeError) and "read a:" in str(exc)
    assert pump.result.errors[0].startswith("a: read: ")
    assert pump.pump == {"files": 1, "one_call_files": 0, "calls": 0}


def test_failpoint_is_hit_once_per_read():
    fs = CountingFS({"a": 10, "b": BLOCK + 1})
    with failpoints.armed("backup.file.stream", "raise", nth=99) as fp:
        _run(fs)
    # a: its one read; b: the read with the open and the short one
    assert fp.hits == 3 and fp.fires == 0


def test_dropped_transport_at_the_first_read_fails_the_job():
    fs = CountingFS({"a": 10, "b": 10})
    with failpoints.armed("backup.file.stream", "drop", nth=2):
        pump, exc, w = _run(fs)
    assert isinstance(exc, ConnectionError)
    assert "a" in w.got and "b" not in w.got
    assert pump.result.errors[0].startswith("b: read: ")


def test_abort_mid_file_does_not_hang():
    """The job is cancelled while a file of many blocks is in flight and
    the writer is slow: run() ends, the writer's thread ends, the handle
    is closed."""
    fs = CountingFS({"a": 10, "big": 64 * BLOCK})

    class SlowWriter(RecordingWriter):
        def write_entry_reader(self, entry, reader):
            while reader.read(100):
                threading.Event().wait(0.01)

    async def main():
        mid = asyncio.Event()

        async def on_read(rel, off):
            if off >= 12 * BLOCK:
                mid.set()
        fs.on_read = on_read
        pump = RemoteTreeBackup(fs, Sess(SlowWriter()))
        task = asyncio.ensure_future(pump.run())
        await asyncio.wait_for(mid.wait(), 20)
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await asyncio.wait_for(task, 20)
        return pump
    before = {t.ident for t in threading.enumerate()}
    asyncio.run(main())
    assert not fs.open_handles
    left = [t for t in threading.enumerate()
            if t.name == "backup-writer" and t.ident not in before]
    assert not left


def test_writer_death_with_one_item_files_queued_does_not_wedge():
    """Files of one queue item wait behind the file the writer dies on:
    its drain must pass them (they have no block queue to empty)."""
    fs = CountingFS({f"f{i:02d}": 10 for i in range(40)})

    class Exploding(RecordingWriter):
        def write_entry_reader(self, entry, reader):
            reader.read(1)
            raise IOError("no space left on device")

    pump, exc, w = _run(fs, Sess(Exploding()))
    assert isinstance(exc, IOError) and "no space" in str(exc)


def test_pump_counters_on_the_span_and_in_the_totals():
    fs = CountingFS({"a": 10, "b": BLOCK + 1, "c": 0})
    before = dict(bj.PUMP_TOTALS)
    trace.clear()
    pump, res, w = _run(fs)
    want = {"files": 3, "one_call_files": 2, "calls": 1 + 3 + 1}
    assert pump.pump == want
    assert {k: bj.PUMP_TOTALS[k] - before[k] for k in want} == want
    spans = [r for r in trace.recent() if r["name"] == "backup.pump"]
    assert len(spans) == 1 and spans[0]["attrs"] == want


def test_pump_totals_on_metrics(tmp_path):
    from pbs_plus_tpu.server import metrics
    from pbs_plus_tpu.server.store import Server, ServerConfig
    _run(CountingFS({"a": 10, "b": BLOCK}))
    server = Server(ServerConfig(state_dir=str(tmp_path / "state"),
                                 cert_dir=str(tmp_path / "certs"),
                                 datastore_dir=str(tmp_path / "ds")))
    expo = metrics.MetricsRegistry(server).render()
    t = bj.PUMP_TOTALS
    assert (f'pbs_plus_pump_files_total{{calls="one"}} '
            f'{float(t["one_call_files"])}') in expo
    assert (f'pbs_plus_pump_files_total{{calls="several"}} '
            f'{float(t["files"] - t["one_call_files"])}') in expo
    assert f'pbs_plus_pump_calls_total {float(t["calls"])}' in expo
    assert t["files"] >= 2 and t["calls"] >= 4
