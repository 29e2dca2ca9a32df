"""The backup pump's calls per file (server/backup_job.py
``RemoteTreeBackup``).  ``_stream_file``: a file's first read rides on
its open, a short block is its end, and a file of one block is one call
and one item of the writer's queue.  ``_stream_run``: a listing's
consecutive small files are one ``read_many`` and reach the writer's
queue in one step.  Driven against in-memory file systems that count
every call; the real agent's side is in tests/test_agentfs_battery.py."""

import asyncio
import threading
import time

import pytest

from agentfs_fakes import OpenReadViaCalls
from pbs_plus_tpu.pxar.format import KIND_DIR, KIND_FILE, KIND_SYMLINK
from pbs_plus_tpu.pxar.transfer import store_helpers
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


def _counts(files=0, one_call_files=0, calls=0, batched_files=0,
            batch_calls=0) -> dict:
    return {"files": files, "one_call_files": one_call_files,
            "calls": calls, "batched_files": batched_files,
            "batch_calls": batch_calls}


class CountingFS(OpenReadViaCalls):
    """One flat directory of in-memory files; counts the calls the pump
    pays for (``open_read`` and ``read_many`` count as the one call each
    is on the wire, the ``open``/``read_at``/``close`` they are made of
    here do not).  ``knows_read_many=False``: the agent of the one-file
    path, which answers "not supported" once."""

    def __init__(self, sizes: dict, *, honours_read: bool = True,
                 knows_read_many: bool = True):
        self.files = {name: _body(name, size)
                      for name, size in sizes.items()}
        self.honours_read = honours_read
        self.knows_read_many = knows_read_many
        self.listed: dict = {}          # name -> the size the listing gives
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
                 "gid": 0, "mtime_ns": 0,
                 "size": self.listed.get(name, len(body))}
                for name, body in sorted(self.files.items())]

    def _note(self, method, name):
        if not self._inside_open_read:
            self.calls.append((method, name))

    async def _as_one_call(self, method, arg, inner):
        self._note(method, arg)
        was, self._inside_open_read = self._inside_open_read, True
        try:
            return await inner
        finally:
            self._inside_open_read = was

    async def open_read(self, rel, n):
        return await self._as_one_call(
            "open_read", rel, super().open_read(rel, n))

    async def read_many(self, paths, budget):
        return await self._as_one_call(
            "read_many", tuple(paths), super().read_many(paths, budget))

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
    assert pump.pump == _counts(files=1, one_call_files=int(one_call),
                                calls=CALLS[size])
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
    assert pump.pump == _counts(files=1, calls=2 + size // BLOCK + 1)
    assert len(fs.calls) == pump.pump["calls"]
    assert not fs.open_handles
    assert (res.files, res.bytes_total) == (1, size)


def test_a_tree_of_small_files_is_one_call_a_file():
    """Against an agent without read_many: it is asked once, answers
    "not supported", and every file goes the one-file way from there."""
    sizes = {f"f{i:03d}": (i * 37) % BLOCK for i in range(200)}
    sizes["big"] = 4 * BLOCK + 5
    fs = CountingFS(sizes, knows_read_many=False)
    pump, res, w = _run(fs)
    assert w.got == fs.files
    assert pump.pump == _counts(files=201, one_call_files=200,
                                calls=1 + 200 + 6, batch_calls=1)
    assert [c[0] for c in fs.calls].count("read_many") == 1
    assert fs.calls[6][0] == "read_many"       # after big's six
    assert res.files == 201 and res.bytes_total == sum(sizes.values())


BOTH_WAYS = pytest.mark.parametrize(
    "batched", [False, True], ids=["one_file", "read_many"])


@BOTH_WAYS
def test_open_failure_skips_the_file(batched):
    """A file that vanished between the listing and its read."""
    fs = CountingFS({"a": 10, "b": 10, "c": 2 * BLOCK},
                    knows_read_many=batched)
    fs.fail_open = {"b"}
    pump, res, w = _run(fs)
    assert sorted(w.got) == ["a", "c"]          # the writer never saw b
    assert len(res.errors) == 1 and res.errors[0].startswith("b: open: ")
    assert res.files == 2
    assert pump.pump["batched_files"] == (1 if batched else 0)
    assert pump.pump["files"] == 3


@BOTH_WAYS
def test_first_read_failure_fails_as_a_read_does(batched):
    """The writer gets the file and its read raises: the error is the
    file's `read:` error and the job fails with the writer's."""
    fs = CountingFS({"a": 10, "b": 10, "c": 10}, knows_read_many=batched)
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
    assert pump.pump == _counts(files=1)


def test_failpoint_is_hit_once_per_read():
    fs = CountingFS({"a": 10, "b": BLOCK + 1})
    with failpoints.armed("backup.file.stream", "raise", nth=99) as fp:
        _run(fs)
    # a: its one read; b: the read with the open and the short one
    assert fp.hits == 3 and fp.fires == 0


@BOTH_WAYS
def test_dropped_transport_at_the_first_read_fails_the_job(batched):
    fs = CountingFS({"a": 10, "b": 10, "c": 10}, knows_read_many=batched)
    with failpoints.armed("backup.file.stream", "drop", nth=2):
        pump, exc, w = _run(fs)
    assert isinstance(exc, ConnectionError)
    assert "a" in w.got and "b" not in w.got and "c" not in w.got
    assert pump.result.errors[0].startswith("b: read: ")
    assert len(pump.result.errors) == 1


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


@BOTH_WAYS
def test_writer_death_with_one_item_files_queued_does_not_wedge(batched):
    """Files of one queue item wait behind the file the writer dies on:
    its drain must pass them (they have no block queue to empty) — also
    when a whole read_many answer, more than the queue holds, is on its
    way in from the pool's thread."""
    fs = CountingFS({f"f{i:02d}": 10 for i in range(40)},
                    knows_read_many=batched)

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
    want = _counts(files=3, one_call_files=2, calls=1 + 3 + 1)
    assert pump.pump == want
    assert {k: bj.PUMP_TOTALS[k] - before[k] for k in want} == want
    spans = [r for r in trace.recent() if r["name"] == "backup.pump"]
    assert len(spans) == 1
    assert {k: spans[0]["attrs"][k] for k in want} == want


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
            f'{float(t["files"] - t["one_call_files"] - t["batched_files"])}'
            ) in expo
    assert f'pbs_plus_pump_calls_total {float(t["calls"])}' in expo
    assert t["files"] >= 2 and t["calls"] >= 4


# ------------------------------------------------ a run of small files

class TreeFS(CountingFS):
    """CountingFS with listings given as they are: {dir: [entry maps]}.
    A file's body is made from its path and the size ``bodies`` gives
    it, else the size its listing gives."""

    def __init__(self, dirs: dict, *, bodies: dict | None = None, **kw):
        sizes = {}
        for rel, entries in dirs.items():
            for m in entries:
                if m["kind"] == KIND_FILE:
                    path = f"{rel}/{m['name']}" if rel else m["name"]
                    sizes[path] = m["size"]
        sizes.update(bodies or {})
        super().__init__(sizes, **kw)
        self.dirs = dirs

    async def read_dir(self, rel):
        return self.dirs.get(rel, [])


def _ent(name, kind=KIND_FILE, size=0, **kw):
    return {"name": name, "kind": kind, "mode": 0o644, "uid": 0, "gid": 0,
            "mtime_ns": 7, "size": size, **kw}


class SequenceWriter(RecordingWriter):
    """Keeps the archive's order: what the writer was handed, in turn."""

    def __init__(self):
        super().__init__()
        self.seq: list = []

    def write_entry(self, entry):
        self.seq.append(("entry", entry.path, entry.kind, entry.link_target))

    def write_entry_ref(self, entry, off, size):
        self.seq.append(("ref", entry.path, off, size, entry.digest))

    def write_entry_reader(self, entry, reader):
        super().write_entry_reader(entry, reader)
        self.seq.append(("file", entry.path, self.got[entry.path]))


class _Plan:
    """A resume plan that splices one path."""

    class _Src:
        digest, payload_offset, size = b"\x11" * 32, 4096, 33

    def __init__(self, spliced):
        self.spliced = spliced
        self.reread = [0, 0]

    def skip_ref(self, path, size, mtime_ns):
        return self._Src if path == self.spliced else None

    def note_reread(self, nbytes, *, files=0):
        self.reread[0] += nbytes
        self.reread[1] += files


def _mixed_tree():
    return {
        "": [_ent("a", size=10), _ent("b", size=0), _ent("c", size=BLOCK - 20),
             _ent("cache.tmp", size=5),             # excluded
             _ent("d", size=33),                    # spliced by the plan
             _ent("e", size=20),
             _ent("f", size=BLOCK),                 # a file of one block
             _ent("g", size=30, nlink=2, dev=1, ino=77),
             _ent("h", size=30, nlink=2, dev=1, ino=77),    # second link
             _ent("i", size=40),
             _ent("lnk", KIND_SYMLINK, target="a"),
             _ent("m", size=50), _ent("n", size=60),
             _ent("sub", KIND_DIR),
             _ent("y", size=70), _ent("z", size=80)],
        "sub": [_ent("p", size=5), _ent("q", size=6), _ent("r", size=7)],
    }


def _run_mixed(batched):
    fs = TreeFS(_mixed_tree(), knows_read_many=batched)
    sess = Sess(SequenceWriter())
    sess.resume_plan = _Plan("d")

    async def main():
        pump = RemoteTreeBackup(fs, sess, exclusions=["*.tmp"])
        return pump, await asyncio.wait_for(pump.run(), 20)
    pump, res = asyncio.run(main())
    return fs, pump, res, sess


def test_a_mixed_listing_archives_as_the_one_file_path_does():
    """Small files, a file of one block, a subdirectory, a symlink, a
    second hard link, an excluded name and a spliced file inside the
    runs: the same items in the same order with the same bytes."""
    fs1, pump1, res1, sess1 = _run_mixed(False)
    fs2, pump2, res2, sess2 = _run_mixed(True)
    assert sess2.writer.seq == sess1.writer.seq
    paths = [s[1] for s in sess2.writer.seq]
    assert paths == ["", "a", "b", "c", "d", "e", "f", "g", "h", "i", "lnk",
                     "m", "n", "sub", "sub/p", "sub/q", "sub/r", "y", "z"]
    assert sess2.writer.seq[4][0] == "ref"
    assert sess2.writer.seq[8][2:] == ("h", "g")        # hard link to g
    assert (res2.files, res2.bytes_total, res2.entries, res2.errors) == \
        (res1.files, res1.bytes_total, res1.entries, res1.errors)
    assert sess2.resume_plan.reread == sess1.resume_plan.reread
    # the runs: a b c | e | g | i | m n | sub: p q r | y z
    runs = [c[1] for c in fs2.calls if c[0] == "read_many"]
    assert runs == [("a", "b", "c"), ("m", "n"),
                    ("sub/p", "sub/q", "sub/r"), ("y", "z")]
    assert pump2.pump == _counts(files=14, one_call_files=3, calls=4 + 3 + 3,
                                 batched_files=10, batch_calls=4)
    assert pump1.pump == _counts(files=14, one_call_files=13,
                                 calls=1 + 13 + 3, batch_calls=1)


def test_sixty_four_small_files_in_one_directory_are_one_call():
    fs = CountingFS({f"f{i:02d}": 1 + i % 9 for i in range(64)})
    pump, res, w = _run(fs)
    assert w.got == fs.files
    assert len(fs.calls) == 1 and fs.calls[0][0] == "read_many"
    assert pump.pump == _counts(files=64, calls=1, batched_files=64,
                                batch_calls=1)
    assert pump.pump["calls"] / pump.pump["files"] < 0.02
    assert (res.files, res.bytes_total) == (64, sum(map(len,
                                                        fs.files.values())))


def test_the_budget_splits_a_run():
    """Listed sizes add up to one block a run at most."""
    third = BLOCK // 3
    fs = CountingFS({f"f{i}": third for i in range(7)})
    pump, res, w = _run(fs)
    assert w.got == fs.files
    runs = [c[1] for c in fs.calls if c[0] == "read_many"]
    assert runs == [("f0", "f1", "f2"), ("f3", "f4", "f5")]
    # the seventh is a run of one: the one-file way
    assert fs.calls[-1] == ("open_read", "f6")
    assert pump.pump == _counts(files=7, one_call_files=1, calls=3,
                                batched_files=6, batch_calls=2)


def test_the_file_count_splits_a_run(monkeypatch):
    """A directory of empty files is a call per READ_MANY_FILES, not one
    of any length."""
    monkeypatch.setattr(bj, "READ_MANY_FILES", 16)
    fs = CountingFS({f"f{i:03d}": 0 for i in range(100)})
    pump, res, w = _run(fs)
    assert w.got == fs.files
    runs = [c[1] for c in fs.calls if c[0] == "read_many"]
    assert [len(r) for r in runs] == [16] * 6 + [4]
    assert pump.pump == _counts(files=100, calls=7, batched_files=100,
                                batch_calls=7)


@pytest.mark.parametrize("grown", ["f0", "f2", "f3"])
def test_a_file_that_grew_since_the_listing(grown):
    """The agent serves what fits the budget and nothing of the file
    that passes it: that file starts the next run, and a first file left
    unserved goes the one-file way — whole, at its new length."""
    fs = CountingFS({f"f{i}": 100 for i in range(4)})
    fs.listed = {grown: 100}
    fs.files[grown] = _body(grown, BLOCK + 50)
    pump, res, w = _run(fs)
    assert w.got == fs.files
    assert list(w.got) == ["f0", "f1", "f2", "f3"]
    assert res.errors == [] and res.files == 4
    assert res.bytes_total == 300 + BLOCK + 50
    methods = [(c[0], c[1]) for c in fs.calls]
    if grown == "f0":
        # unserved first: its own three calls, then the rest as a run
        assert methods[0] == ("read_many", ("f0", "f1", "f2", "f3"))
        assert methods[1] == ("open_read", "f0")
        assert methods[-1] == ("read_many", ("f1", "f2", "f3"))
        assert pump.pump["batched_files"] == 3
    elif grown == "f2":
        assert methods[0] == ("read_many", ("f0", "f1", "f2", "f3"))
        assert methods[1] == ("read_many", ("f2", "f3"))
        assert methods[2] == ("open_read", "f2")
        assert methods[-1] == ("open_read", "f3")   # a run of one
        assert pump.pump["batched_files"] == 2
    else:
        assert methods[0] == ("read_many", ("f0", "f1", "f2", "f3"))
        assert methods[1] == ("open_read", "f3")    # a run of one
        assert pump.pump["batched_files"] == 3
    assert pump.pump["files"] == 4


def test_per_file_errors_keep_their_sides_inside_a_run():
    """An open error is recorded and skipped, a read error hands the
    writer a reader that raises; the neighbours of both are served."""
    fs = CountingFS({"a": 10, "b": 10, "c": 10, "d": 10, "e": 10})
    fs.fail_open = {"b"}
    fs.fail_read = {"d"}
    pump, exc, w = _run(fs)
    assert isinstance(exc, RuntimeError) and "read d:" in str(exc)
    assert pump.result.errors[0].startswith("b: open: ")
    assert pump.result.errors[1] == "d: read: [Errno 5] Input/output error"
    assert list(w.got) == ["a", "c"]        # the writer died on d
    assert len(fs.calls) == 1
    assert pump.pump == _counts(files=5, calls=1, batched_files=3,
                                batch_calls=1)
    assert not fs.open_handles


def test_failpoint_is_hit_once_per_file_of_a_run_and_in_order():
    fs = CountingFS({f"f{i}": 10 for i in range(6)})
    with failpoints.armed("backup.file.stream", "raise", nth=4) as fp:
        pump, exc, w = _run(fs)
    assert fp.hits == 6 and fp.fires == 1
    # the fourth file's read fails with it, and only that one's
    assert isinstance(exc, RuntimeError) and "read f3:" in str(exc)
    assert len(pump.result.errors) == 1
    assert pump.result.errors[0].startswith("f3: read: ")
    assert list(w.got) == ["f0", "f1", "f2"]


def test_not_supported_falls_back_once_and_stays_there():
    dirs = {"": [_ent("a", size=1), _ent("b", size=2), _ent("sub", KIND_DIR),
                 _ent("y", size=3), _ent("z", size=4)],
            "sub": [_ent("p", size=5), _ent("q", size=6)]}
    fs = TreeFS(dirs, knows_read_many=False)
    pump, res, w = _run(fs)
    assert w.got == fs.files
    assert [c[0] for c in fs.calls] == ["read_many"] + ["open_read"] * 6
    assert pump.pump == _counts(files=6, one_call_files=6, calls=7,
                                batch_calls=1)


def test_dropped_transport_inside_read_many_fails_the_job():
    fs = CountingFS({"a": 10, "b": 10})

    async def read_many(paths, budget):
        raise ConnectionResetError("session lost")
    fs.read_many = read_many
    pump, exc, w = _run(fs)
    assert isinstance(exc, ConnectionResetError)
    assert w.got == {}


def test_abort_mid_run_does_not_hang():
    """The job is cancelled while a read_many answer of more items than
    the queue holds is on its way to a slow writer: run() ends, the
    writer's thread ends, and no pool thread is left putting."""
    fs = CountingFS({f"f{i:03d}": 10 for i in range(120)})
    started = threading.Event()

    class SlowWriter(RecordingWriter):
        def write_entry_reader(self, entry, reader):
            started.set()
            while reader.read(100):
                pass
            threading.Event().wait(0.05)

    async def main():
        pump = RemoteTreeBackup(fs, Sess(SlowWriter()))
        task = asyncio.ensure_future(pump.run())
        loop = asyncio.get_running_loop()
        await asyncio.wait_for(loop.run_in_executor(None, started.wait), 20)
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await asyncio.wait_for(task, 20)
        return pump
    before = {t.ident for t in threading.enumerate()}
    pump = asyncio.run(main())
    left = [t for t in threading.enumerate()
            if t.name == "backup-writer" and t.ident not in before]
    assert not left
    # the pool's thread gave up: a put on the full queue would block
    assert pump._abort.is_set()
    pump._put_rest([object()] * 50)             # returns at once


def test_batch_counters_on_the_span_and_in_the_totals():
    fs = CountingFS({"a": 10, "b": 20, "c": 0, "d": 2 * BLOCK})
    before = dict(bj.PUMP_TOTALS)
    trace.clear()
    pump, res, w = _run(fs)
    want = _counts(files=4, calls=1 + 4, batched_files=3, batch_calls=1)
    assert pump.pump == want
    assert {k: bj.PUMP_TOTALS[k] - before[k] for k in want} == want
    spans = [r for r in trace.recent() if r["name"] == "backup.pump"]
    assert len(spans) == 1
    assert {k: spans[0]["attrs"][k] for k in want} == want


def test_batch_totals_on_metrics(tmp_path):
    from pbs_plus_tpu.server import metrics
    from pbs_plus_tpu.server.store import Server, ServerConfig
    _run(CountingFS({"a": 10, "b": 10, "c": BLOCK}))
    server = Server(ServerConfig(state_dir=str(tmp_path / "state"),
                                 cert_dir=str(tmp_path / "certs"),
                                 datastore_dir=str(tmp_path / "ds")))
    expo = metrics.MetricsRegistry(server).render()
    t = bj.PUMP_TOTALS
    assert (f'pbs_plus_pump_files_total{{calls="batched"}} '
            f'{float(t["batched_files"])}') in expo
    assert (f'pbs_plus_pump_files_total{{calls="several"}} '
            f'{float(t["files"] - t["one_call_files"] - t["batched_files"])}'
            ) in expo
    assert (f'pbs_plus_pump_batch_calls_total '
            f'{float(t["batch_calls"])}') in expo
    assert t["batched_files"] >= 2 and t["batch_calls"] >= 1


# ------------------------------------------------ the session's clocks

RECORD_KEYS = ({"job"} | set(bj.PUMP_TOTALS)
               | {"writer_" + k for k in bj.WRITER_STATES}
               | {"writer_cpu_s", "writer_life_s"}
               | {"pump_" + k for k in bj.PUMP_WAITS}
               | {"pump_life_s", "loop_cpu0", "loop_cpu1"}
               | {"index_" + k for k in bj.INDEX_COUNTS}
               | {"index_table_bytes", "index_table_shards"}
               | {"store_pool_" + k for k in bj.STORE_POOL_COUNTS})


class _StreamSession:
    """A session whose writer is the real one: a ``SessionWriter`` over
    a chunk store in ``base``, hash batches of four chunks through a
    batch hasher, as a ``chunker="tpu"`` session's payload stream goes
    (``_flush_hashes``: hash, probe, store)."""

    def __init__(self, base, monkeypatch, insert_delay=0.0):
        import hashlib

        from pbs_plus_tpu.chunker import ChunkerParams
        from pbs_plus_tpu.pxar import transfer
        from pbs_plus_tpu.pxar.datastore import ChunkStore
        monkeypatch.setattr(transfer, "_HASH_BATCH_COUNT", 4)
        store = ChunkStore(str(base), n_shards=2, index_budget_mb=2)
        # each insert's (start, end): the store stage may run a hash
        # batch's inserts on several threads at once
        self.spans: list = []
        insert = store.insert

        def slow_insert(digest, data, **kw):
            t0 = time.perf_counter()
            time.sleep(insert_delay)
            try:
                return insert(digest, data, **kw)
            finally:
                self.spans.append((t0, time.perf_counter()))
        store.insert = slow_insert
        self.writer = transfer.SessionWriter(
            store, payload_params=ChunkerParams(avg_size=1 << 10),
            batch_hasher=lambda chunks: [hashlib.sha256(c).digest()
                                         for c in chunks])


def _pump_record(pump):
    recs = [r for r in trace.job_records()
            if r["attrs"]["job"] == pump.log.scope["job_id"]]
    assert len(recs) == 1
    return recs[0]


def _clocked_run(fs, sess, job_id):
    from pbs_plus_tpu.utils.log import L

    async def main():
        pump = RemoteTreeBackup(fs, sess,
                                job_log=L.with_scope(job_id=job_id))
        await asyncio.wait_for(pump.run(), 60)
        return pump
    pump = asyncio.run(main())
    attrs = _pump_record(pump)["attrs"]
    writer = {k: attrs["writer_" + k] for k in bj.WRITER_STATES}
    waits = {k: attrs["pump_" + k] for k in bj.PUMP_WAITS}
    # the writer's states partition its life, and its CPU is inside it
    assert sum(writer.values()) == pytest.approx(attrs["writer_life_s"],
                                                 rel=0.01)
    assert 0 < attrs["writer_cpu_s"] <= attrs["writer_life_s"]
    assert sum(waits.values()) <= attrs["pump_life_s"]
    return pump, attrs, writer, waits


def _others(states: dict, *driven) -> dict:
    return {k: v for k, v in states.items() if k not in driven}


def test_a_slow_agent_is_the_writers_pump_wait_and_the_pumps_rpc_wait(
        tmp_path, monkeypatch):
    """Every call to the agent takes 50 ms: the pump is suspended on it
    for that long and the writer waits for the pump as long, less the
    little it has to do with one file while the pump asks for the next;
    no other state of either moves by a tenth of it."""
    fs = CountingFS({f"f{i:02d}": 600 for i in range(20)})
    delay, calls = 0.05, []
    read_many, open_read = fs.read_many, fs.open_read

    async def slow(call, *a):
        calls.append(call.__name__)
        await asyncio.sleep(delay)
        return await call(*a)
    fs.read_many = lambda *a: slow(read_many, *a)
    fs.open_read = lambda *a: slow(open_read, *a)
    pump, attrs, writer, waits = _clocked_run(
        fs, _StreamSession(tmp_path, monkeypatch), "row-slow-agent")
    injected = delay * len(calls)
    assert len(calls) >= 10
    assert waits["rpc_wait_s"] >= injected
    assert writer["pump_wait_s"] >= injected - sum(
        _others(writer, "pump_wait_s").values())
    assert max(_others(writer, "pump_wait_s").values()) <= injected / 10
    assert max(_others(waits, "rpc_wait_s").values()) <= injected / 10


def test_a_slow_store_is_the_writers_store_and_the_pumps_put_wait(
        tmp_path, monkeypatch):
    """Every insert into the chunk store takes 60 ms: the writer's
    thread is in its store state while any insert it began, or one of
    its hash batch's inserts on the store pool's helpers, is under way,
    and the pump, one item ahead of it, is suspended on the writer's
    queue (at the end, on its join) as long; no other state of either
    moves by a tenth of it.  (A batch's four inserts run at once where
    the host has the cores: 60 ms keeps the wall time they take where
    20 ms one after the other had it.)"""
    monkeypatch.setattr(bj, "QUEUE_DEPTH", 1)
    sess = _StreamSession(tmp_path, monkeypatch, insert_delay=0.06)
    fs = CountingFS({f"f{i:02d}": 600 + i for i in range(60)})
    pump, attrs, writer, waits = _clocked_run(fs, sess, "row-slow-store")
    # the wall time in which at least one insert was under way
    injected, reach = 0.0, 0.0
    for t0, t1 in sorted(sess.spans):
        injected += max(0.0, t1 - max(t0, reach))
        reach = max(reach, t1)
    assert len(sess.spans) >= 20
    assert injected >= 0.06 * len(sess.spans) / (store_helpers() + 1)
    assert writer["store_s"] >= injected
    assert waits["put_wait_s"] + waits["join_wait_s"] >= injected
    assert waits["put_wait_s"] >= 0.8 * injected
    assert max(_others(writer, "store_s").values()) <= injected / 10
    assert waits["rpc_wait_s"] <= injected / 10


def test_the_pumps_record_carries_every_clock_and_the_jobs_row_id(
        tmp_path, monkeypatch):
    trace.clear()
    pump, attrs, writer, waits = _clocked_run(
        CountingFS({"a": 10, "b": 3 * BLOCK}),
        _StreamSession(tmp_path, monkeypatch), "row-7")
    assert set(attrs) == RECORD_KEYS and attrs["job"] == "row-7"
    rec = _pump_record(pump)
    assert attrs["pump_life_s"] == pytest.approx(rec["dur_s"], abs=0.005)
    assert attrs["loop_cpu1"] >= attrs["loop_cpu0"] > 0
    # the record is the ring's backup.pump span, kept a second time
    assert rec in trace.recent()
    # a pump with no job's logger still closes a record
    _run(CountingFS({"a": 10}))
    assert trace.job_records()[-1]["attrs"]["job"] == ""


def test_job_records_outlive_the_rings_churn():
    """A window's rpc.serve closes evict a job's span from the ring; the
    table of job records keeps it, and keeps 256 of them."""
    trace.clear()
    _run(CountingFS({"a": 10}))
    for _ in range(10_000):
        with trace.span("rpc.serve"):
            pass
    assert not [r for r in trace.recent() if r["name"] == "backup.pump"]
    kept = trace.job_records()
    assert len(kept) == 1 and kept[0]["attrs"]["files"] == 1
    for i in range(300):
        trace.emit("backup.pump", 0.001, job=f"j{i}")
    kept = trace.job_records()
    assert len(kept) == 256 and kept[-1]["attrs"]["job"] == "j299"
    assert trace.job_records(2) == kept[-2:]
    trace.clear()
    assert trace.job_records() == []


def test_the_log_the_traces_endpoint_and_metrics_show_the_same_clocks(
        tmp_path, monkeypatch, caplog):
    """One backup, three surfaces: the line at the job's end in its log,
    the record on ``GET /api2/json/d2d/traces`` and the process totals
    on ``/metrics``."""
    import logging

    from pbs_plus_tpu.server import metrics
    from pbs_plus_tpu.server.store import Server, ServerConfig
    from pbs_plus_tpu.server.web import traces_payload
    server = Server(ServerConfig(state_dir=str(tmp_path / "state"),
                                 cert_dir=str(tmp_path / "certs"),
                                 datastore_dir=str(tmp_path / "ds")))
    registry = metrics.MetricsRegistry(server)

    def series(expo: str) -> dict:
        out = {}
        for line in expo.splitlines():
            if line.startswith(("pbs_plus_writer_thread_seconds_total{",
                                "pbs_plus_pump_wait_seconds_total{",
                                "pbs_plus_loop_cpu_seconds_total",
                                "pbs_plus_index_",
                                "pbs_plus_store_pool_")):
                name, value = line.rsplit(" ", 1)
                out[name] = float(value)
        return out
    before = series(registry.render())
    with caplog.at_level(logging.INFO, logger="pbs_plus_tpu"):
        pump, attrs, writer, waits = _clocked_run(
            CountingFS({f"f{i}": 700 for i in range(12)}),
            _StreamSession(tmp_path / "chunks", monkeypatch), "row-3")
    # the job's log
    lines = [r for r in caplog.records
             if r.getMessage().startswith("session clocks: ")]
    assert len(lines) == 1 and lines[0].scope["job_id"] == "row-3"
    logged = {k: float(v) for k, v in (
        kv.split("=") for kv in lines[0].getMessage().split(": ")[1].split())}
    clocks = {k: v for k, v in attrs.items()
              if k.startswith(("writer_", "pump_", "loop_", "index_",
                               "store_pool_"))}
    assert set(logged) == set(clocks)
    assert logged == pytest.approx(clocks, abs=1e-6)
    # the endpoint: the ring's span, and the table's record
    assert [r for r in traces_payload(trace_id=None)
            if r["name"] == "backup.pump" and r["attrs"] == attrs]
    assert traces_payload(1, jobs="1")[0]["attrs"] == attrs
    # /metrics: the process totals moved by this job's clocks
    after = series(registry.render())
    moved = {name: after[name] - before.get(name, 0.0) for name in after}
    for state in bj.WRITER_STATES + ("cpu_s",):
        name = ('pbs_plus_writer_thread_seconds_total{state="%s"}'
                % state[:-2])
        assert moved[name] == pytest.approx(attrs["writer_" + state],
                                            abs=1e-6)
    for on, key in (("agent", "rpc_wait_s"), ("writer", "put_wait_s"),
                    ("join", "join_wait_s")):
        name = 'pbs_plus_pump_wait_seconds_total{on="%s"}' % on
        assert moved[name] == pytest.approx(attrs["pump_" + key], abs=1e-6)
    assert after["pbs_plus_loop_cpu_seconds_total"] == attrs["loop_cpu1"]
    # and by what the index did for it (ISSUE 36)
    assert attrs["index_probe_trips"] > 0 and attrs["index_inserts"] > 0
    by_result = {r: moved['pbs_plus_index_probe_digests_total{result="%s"}'
                       % r] for r in ("hit", "false_positive", "miss")}
    assert by_result == {
        "hit": attrs["index_hits"],
        "false_positive": attrs["index_false_positives"],
        "miss": attrs["index_probe_digests"] - attrs["index_hits"]
        - attrs["index_false_positives"]}
    assert moved["pbs_plus_index_table_upload_bytes_total"] \
        == attrs["index_table_upload_bytes"]
    assert moved["pbs_plus_index_upload_seconds_total"] \
        == pytest.approx(attrs["index_upload_s"], abs=1e-6)
    # and by what the store pool did for it
    for key, name in (("chunks", "chunks_total"), ("flushes", "flushes_total"),
                      ("s", "seconds_total")):
        assert moved["pbs_plus_store_pool_" + name] == pytest.approx(
            attrs["store_pool_" + key], abs=1e-6)


# ------------------------------------------------ blocks served as views

@pytest.mark.parametrize("kind", [bytes, bytearray, memoryview])
def test_the_reader_serves_views_of_its_block_never_copies(kind):
    """``_QueuePumpReader.read`` hands the writer the block it was
    given — whole as it is, in part as views of it — whatever bytes-like
    object the agentfs read was received into."""
    import queue
    body = _body("blk", 1000)
    first, second = kind(body[:600]), kind(body[600:])
    fq: queue.Queue = queue.Queue()
    fq.put(second)
    fq.put(bj._SENTINEL)
    reader = bj._QueuePumpReader(fq, first=first)
    a, b = reader.read(250), reader.read(250)
    assert (a, b) == (body[:250], body[250:500])
    for part in (a, b):
        assert type(part) is memoryview
        assert part.obj is (first.obj if kind is memoryview else first)
    c = reader.read(250)                    # the block's rest, short
    assert c == body[500:600] and len(c) == 100
    whole = reader.read(4096)               # a whole block: itself
    assert whole is second
    assert not reader.read(10) and not reader.read(10)


def test_views_reach_the_writer_byte_exact_through_a_real_stream(
        tmp_path, monkeypatch):
    """Blocks that arrive as bytearrays and as views of one buffer (what
    ``read_at`` and ``read_many`` hand on since PR 35) go through the
    real ``SessionWriter`` — entry digest, chunk buffer, batch hasher,
    store — and read back byte for byte."""
    import hashlib

    class ViewFS(CountingFS):
        async def read_at(self, handle, off, n):
            return bytearray(await super().read_at(handle, off, n))

        async def read_many(self, paths, budget):
            got = await super().read_many(paths, budget)
            buf = bytearray(b"".join(g for g in got if isinstance(g, bytes)))
            view, at, out = memoryview(buf), 0, []
            for g in got:
                if isinstance(g, bytes):
                    out.append(view[at:at + len(g)])
                    at += len(g)
                else:
                    out.append(g)
            return out

    sizes = {"a": 10, "b": 0, "c": 700, "d": 3 * BLOCK + 7, "e": BLOCK}
    fs = ViewFS(sizes)
    sess = _StreamSession(tmp_path, monkeypatch)
    digests = {}
    real = sess.writer.write_entry_reader

    def keep(entry, reader, **kw):
        digests[entry.path] = real(entry, reader, **kw)
        return digests[entry.path]
    sess.writer.write_entry_reader = keep
    pump, res, _ = _run(fs, sess)
    assert not isinstance(res, Exception), res
    assert res.errors == [] and res.files == len(sizes)
    assert pump.pump["batched_files"] == 3          # a, b, c came as views
    assert digests == {name: hashlib.sha256(_body(name, size)).digest()
                       for name, size in sizes.items()}
    assert res.bytes_total == sum(sizes.values())
