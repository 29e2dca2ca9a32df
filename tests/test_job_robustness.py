"""Job-robustness regressions (advisor findings r1): a dying archive
writer must never wedge its async producers, and snapshot refs from
untrusted API input must be validated before touching paths or argv."""

import asyncio

import pytest

from agentfs_fakes import OpenReadViaCalls
from pbs_plus_tpu.pxar.datastore import parse_snapshot_ref
from pbs_plus_tpu.pxar.format import KIND_DIR, KIND_FILE
from pbs_plus_tpu.server import backup_job as bj
from pbs_plus_tpu.server.backup_job import RemoteTreeBackup


class _FakeAgentFS(OpenReadViaCalls):
    """Serves one directory containing one very large file (many blocks)."""

    def __init__(self, blocks: int, block: bytes):
        self.blocks = blocks
        self.block = block
        self.closed = []

    async def attr(self, rel):
        return {"kind": KIND_DIR, "mode": 0o755, "uid": 0, "gid": 0,
                "mtime_ns": 0, "size": 0}

    async def read_dir(self, rel):
        if rel:
            return []
        return [{"name": "big.bin", "kind": KIND_FILE, "mode": 0o644,
                 "uid": 0, "gid": 0, "mtime_ns": 0,
                 "size": self.blocks * len(self.block)}]

    async def open(self, rel):
        return 7

    async def read_at(self, handle, off, n):
        idx = off // len(self.block)
        if idx >= self.blocks:
            return b""
        return self.block

    async def close(self, handle):
        self.closed.append(handle)


class _ExplodingWriter:
    """Dies on the first file body — like ENOSPC during a chunk insert."""

    def write_entry(self, entry):
        pass

    def write_entry_reader(self, entry, reader):
        reader.read(1)                      # consume a byte, then die
        raise IOError("no space left on device")


class _FakeSession:
    writer = _ExplodingWriter()


def test_writer_death_does_not_wedge_large_file_producer(monkeypatch):
    """advisor r1 (backup_job.py): on writer failure the per-file block
    queues must be drained/marked dead — previously any file larger than
    QUEUE_DEPTH * READ_BLOCK hung the job forever."""
    monkeypatch.setattr(bj, "READ_BLOCK", 1024)
    fs = _FakeAgentFS(blocks=4096, block=b"x" * 1024)   # 4 MiB ≫ queue

    async def main():
        pump = RemoteTreeBackup(fs, _FakeSession())
        with pytest.raises(IOError, match="no space"):
            await asyncio.wait_for(pump.run(), timeout=20)
        assert fs.closed                    # producer exited its finally

    asyncio.run(main())


def test_parse_snapshot_ref_accepts_valid():
    ref = parse_snapshot_ref("host/web-01/2026-07-29T01:02:03Z")
    assert ref.backup_type == "host"
    assert ref.backup_id == "web-01"
    assert parse_snapshot_ref("/vm/100/2026-01-01T00:00:00Z").backup_id == "100"


@pytest.mark.parametrize("bad", [
    "",
    "host/a",                               # too few components
    "host/a/b/c",                           # too many
    "host/../2026-01-01T00:00:00Z",         # traversal id
    "../etc/passwd",
    "host/./t",
    "host//t",                              # empty component
    "bogus/a/2026-01-01T00:00:00Z",         # invalid backup type
    "host/a/..",
    "host/.hidden/t",                       # leading dot
    "host/a b/t",                           # whitespace / argv-unsafe
])
def test_parse_snapshot_ref_rejects(bad):
    with pytest.raises(ValueError):
        parse_snapshot_ref(bad)
