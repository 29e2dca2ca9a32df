"""agentfs — the read-only remote-FS protocol the agent serves during a
backup job.

Reference: internal/agent/agentfs/server.go:16-99 (handlers OpenFile/Attr/
Xattr/ReadDir/ReadAt/Lseek/Close/StatFS, handle table, panic-safe wrapper)
and the wire DTOs at internal/agent/agentfs/types/types.go:7-155.

Methods (msgpack payloads over aRPC; file reads use the raw-stream path so
bytes land directly in caller buffers — the reference's CallBinaryWithMeta
hot loop, SURVEY §3.2):

    agentfs.stat_fs   {}                          → {total, free, files}
    agentfs.attr      {path}                      → entry map
    agentfs.read_dir  {path, start?, max?}        → {entries: [entry map],
                                                     next?: name token}
    agentfs.read_link {path}                      → {target}
    agentfs.xattrs    {path}                      → {xattrs: {name: bytes}}
    agentfs.open      {path}                      → {handle}
    agentfs.open      {path, read: n}             → 213 raw stream of the
                                                    first n bytes, with
                                                    {handle, n, eof}; at
                                                    eof the file is closed
                                                    and handle is 0
    agentfs.read_at   {handle, off, n}            → 213 raw stream
    agentfs.read_many {paths, budget}             → 213 raw stream of whole
                                                    files back to back, with
                                                    {files: [{n} | {status,
                                                    message}]} for a prefix
                                                    of paths; no handle
    agentfs.lseek     {handle, off, whence}       → {pos}
    agentfs.close     {handle}                    → {}
"""

from __future__ import annotations

import bisect
import os
import stat as statmod
from typing import Any

from ..arpc.call import (
    STATUS_ERROR, STATUS_NOT_FOUND, STATUS_RAW_STREAM, CallError,
    RawStreamHandler, Response,
)
from ..arpc.router import HandlerError, Router
from ..arpc.binary_stream import send_data_from_reader
from ..pxar.format import read_xattrs
from ..utils.log import L

MAX_READ = 32 << 20
MAX_HANDLES = 512          # open-fd ceiling per snapshot session: a leaky
                           # or compromised server must not exhaust the
                           # agent's fd table
READDIR_PAGE = 4096        # entries per read_dir response; larger dirs
                           # page via the `start` continuation token


def _entry_map(name: str, st: os.stat_result, link_target: str = "") -> dict:
    m = st.st_mode
    if statmod.S_ISDIR(m):
        kind = "d"
    elif statmod.S_ISLNK(m):
        kind = "l"
    elif statmod.S_ISREG(m):
        kind = "f"
    elif statmod.S_ISFIFO(m):
        kind = "p"
    elif statmod.S_ISSOCK(m):
        kind = "s"
    elif statmod.S_ISBLK(m):
        kind = "b"
    else:
        kind = "c"
    return {
        "name": name, "kind": kind, "mode": statmod.S_IMODE(m),
        "uid": st.st_uid, "gid": st.st_gid, "size": st.st_size,
        "mtime_ns": st.st_mtime_ns, "nlink": st.st_nlink,
        "ino": st.st_ino, "dev": st.st_dev, "rdev": st.st_rdev,
        "target": link_target,
    }


class AgentFSServer:
    """Serves one snapshot root read-only.  Register on a job-session
    router; the server side walks it to build the archive."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        self._realroot = os.path.realpath(self.root)
        self._handles: dict[int, Any] = {}
        self._next_handle = 1
        self.stats = {"reads": 0, "bytes": 0, "opens": 0,
                      "open_reads": 0, "closed_at_eof": 0,
                      "read_many": 0, "read_many_files": 0}

    def _resolve(self, rel: str) -> str:
        rel = rel.strip("/")
        p = os.path.normpath(os.path.join(self.root, rel)) if rel else self.root
        if p != self.root and not p.startswith(self.root + os.sep):
            raise HandlerError(f"path escapes root: {rel!r}", status=400)
        return p

    def _within_realroot(self, rp: str) -> bool:
        """THE containment predicate — every gate (metadata pre-checks
        and _open's post-open fd check) must use this one definition or
        they drift apart."""
        return rp == self._realroot or \
            rp.startswith(self._realroot + os.sep)

    def _check_contained(self, p: str, rel: str, *,
                         follow_final: bool) -> None:
        """Refuse paths whose symlink resolution leaves the snapshot root.

        follow_final=True when the operation itself follows the final
        component (listdir); False when it operates on the node itself
        (lstat/readlink/lgetxattr) — there only the PARENT traversal can
        escape.  Best-effort for metadata; content reads get the stronger
        post-open fd gate in _open."""
        target = p if (follow_final or p == self.root) \
            else (os.path.dirname(p) or p)
        if not self._within_realroot(os.path.realpath(target)):
            raise HandlerError(f"symlink escapes root: {rel!r}", status=400)

    def register(self, router: Router) -> None:
        router.handle("agentfs.stat_fs", self._stat_fs)
        router.handle("agentfs.attr", self._attr)
        router.handle("agentfs.read_dir", self._read_dir)
        router.handle("agentfs.read_link", self._read_link)
        router.handle("agentfs.xattrs", self._xattrs)
        router.handle("agentfs.open", self._open)
        router.handle("agentfs.read_at", self._read_at)
        router.handle("agentfs.read_many", self._read_many)
        router.handle("agentfs.lseek", self._lseek)
        router.handle("agentfs.close", self._close)

    # -- handlers ----------------------------------------------------------
    async def _stat_fs(self, req, ctx):
        sv = os.statvfs(self.root)
        return {"total": sv.f_blocks * sv.f_frsize,
                "free": sv.f_bavail * sv.f_frsize,
                "files": sv.f_files}

    async def _attr(self, req, ctx):
        p = self._resolve(req.payload["path"])
        self._check_contained(p, req.payload["path"], follow_final=False)
        try:
            st = os.lstat(p)
        except OSError as e:
            raise HandlerError(f"lstat: {e}", status=404)
        target = ""
        if statmod.S_ISLNK(st.st_mode):
            try:
                target = os.readlink(p)
            except OSError:
                pass
        return _entry_map(os.path.basename(p), st, target)

    async def _read_dir(self, req, ctx):
        p = self._resolve(req.payload["path"])
        self._check_contained(p, req.payload["path"], follow_final=True)
        try:
            names = sorted(os.listdir(p))
        except NotADirectoryError:
            raise HandlerError("not a directory", status=400)
        except OSError as e:
            raise HandlerError(f"listdir: {e}", status=404)
        # paging: resume strictly after the `start` name so one response
        # never has to carry a 100k-entry directory (the continuation is
        # a name, not an index — stable under concurrent unlinks)
        start = req.payload.get("start", "")
        if not isinstance(start, str):
            raise HandlerError("start must be a name string", status=400)
        if start:
            names = names[bisect.bisect_right(names, start):]
        try:
            page = int(req.payload.get("max", READDIR_PAGE))
        except (TypeError, ValueError):
            raise HandlerError("max must be an integer", status=400)
        # clamp BOTH ends: max<=0 must not read as "empty directory" on
        # the client (no next token would end its loop early)
        page = max(1, min(page, READDIR_PAGE))
        names, more = names[:page], len(names) > page
        entries = []
        for name in names:
            try:
                st = os.lstat(os.path.join(p, name))
            except OSError:
                continue          # raced unlink — skip
            target = ""
            if statmod.S_ISLNK(st.st_mode):
                try:
                    target = os.readlink(os.path.join(p, name))
                except OSError:
                    pass
            e = _entry_map(name, st, target)
            # piggyback xattrs (POSIX ACLs travel as system.* xattrs) so
            # the server needs no per-file RPC to preserve them
            if not statmod.S_ISLNK(st.st_mode):
                x = read_xattrs(os.path.join(p, name))
                if x:
                    e["xattrs"] = x
            entries.append(e)
        out = {"entries": entries}
        if more and names:
            out["next"] = names[-1]
        return out

    async def _read_link(self, req, ctx):
        p = self._resolve(req.payload["path"])
        self._check_contained(p, req.payload["path"], follow_final=False)
        try:
            return {"target": os.readlink(p)}
        except OSError as e:
            raise HandlerError(f"readlink: {e}", status=404)

    async def _xattrs(self, req, ctx):
        p = self._resolve(req.payload["path"])
        self._check_contained(p, req.payload["path"], follow_final=False)
        return {"xattrs": read_xattrs(p)}

    def _open_regular(self, rel: str):
        """Open ``rel`` for reading behind every gate a content read
        has: both ``_open`` and ``_read_many`` come through here, so a
        byte is never read that one of them would have refused."""
        p = self._resolve(rel)
        # O_NONBLOCK: an open() on a fifo blocks until a writer appears —
        # a raced or hostile path must not hang the agent's event loop
        try:
            fd = os.open(p, os.O_RDONLY | getattr(os, "O_NONBLOCK", 0))
        except OSError as e:
            raise HandlerError(f"open: {e}", status=404)
        try:
            st = os.fstat(fd)
            if not statmod.S_ISREG(st.st_mode):
                raise HandlerError("not a regular file", status=400)
            # containment is checked on the OPENED fd (not a pre-open
            # realpath, which a concurrent rename could invalidate): an
            # in-tree symlink pointing outside the snapshot root must not
            # hand the peer arbitrary agent files.  /proc/self/fd gives
            # the fully-resolved path of what was actually opened.
            proc = f"/proc/self/fd/{fd}"
            rp = os.path.realpath(proc) if os.path.exists(proc) \
                else os.path.realpath(p)
            if not self._within_realroot(rp):
                raise HandlerError(f"symlink escapes root: {rel!r}",
                                   status=400)
            f = os.fdopen(fd, "rb", buffering=0)
        except HandlerError:
            os.close(fd)
            raise
        except OSError as e:
            os.close(fd)
            raise HandlerError(f"open: {e}", status=400)
        self.stats["opens"] += 1
        return f

    async def _open(self, req, ctx):
        # `read`: the file's first read rides on its open (the backup
        # pump's one call for a file of one block).  A peer that does
        # not send it gets the bare handle.
        n = req.payload.get("read")
        if n is not None and not (isinstance(n, int)
                                  and 0 <= n <= MAX_READ):
            raise HandlerError(f"read size {n!r} out of range", status=400)
        if len(self._handles) >= MAX_HANDLES:
            raise HandlerError(
                f"too many open handles ({MAX_HANDLES})", status=429)
        f = self._open_regular(req.payload["path"])
        if n is None:
            return {"handle": self._keep(f)}
        # every gate above has passed before a byte is read
        try:
            data = os.pread(f.fileno(), n, 0)
        except OSError as e:
            f.close()
            raise HandlerError(f"pread: {e}", status=500)
        self.stats["open_reads"] += 1
        self.stats["bytes"] += len(data)
        # only regular files are opened, so a short pread is the end:
        # the agent closes the file itself and no handle is left for a
        # crashed server to leak
        eof = len(data) < n
        if eof:
            f.close()
            self.stats["closed_at_eof"] += 1
        return self._raw_bytes(
            data, {"handle": 0 if eof else self._keep(f),
                   "n": len(data), "eof": eof})

    def _keep(self, f) -> int:
        h = self._next_handle
        self._next_handle += 1
        self._handles[h] = f
        return h

    @staticmethod
    def _raw_bytes(data: bytes, meta: dict) -> RawStreamHandler:
        async def pump(stream):
            await send_data_from_reader(stream, data, len(data))
        return RawStreamHandler(pump, data=meta)

    def _file(self, handle: int):
        f = self._handles.get(handle)
        if f is None:
            raise HandlerError(f"bad handle {handle}", status=400)
        return f

    async def _read_at(self, req, ctx):
        f = self._file(req.payload["handle"])
        off = int(req.payload["off"])
        n = int(req.payload["n"])
        if n < 0 or n > MAX_READ:
            raise HandlerError(f"read size {n} out of range", status=400)
        try:
            data = os.pread(f.fileno(), n, off)
        except OSError as e:
            raise HandlerError(f"pread: {e}", status=500)
        self.stats["reads"] += 1
        self.stats["bytes"] += len(data)
        return self._raw_bytes(data, {"n": len(data)})

    async def _read_many(self, req, ctx):
        """A run of small files in one answer (the backup pump's one
        call for a directory's consecutive small files): each file of
        ``paths`` in turn is opened as ``_open`` opens it, read whole —
        a short pread is the end — and closed, until the next would take
        the bytes past ``budget``.  That file and those after it are not
        served and have no record; a file that fails answers with its
        own error and the run goes on.  No handle outlives the call."""
        paths, budget = req.payload.get("paths"), req.payload.get("budget")
        if not (isinstance(budget, int) and 1 <= budget <= MAX_READ):
            raise HandlerError(f"budget {budget!r} out of range", status=400)
        if not (isinstance(paths, list)
                and all(isinstance(rel, str) for rel in paths)):
            raise HandlerError("paths must be a list of strings", status=400)
        self.stats["read_many"] += 1
        files, parts, left = [], [], budget
        for rel in paths:
            try:
                f = self._open_regular(rel)
            except HandlerError as e:
                files.append({"status": e.status, "message": str(e)})
                continue
            try:
                # one byte more than may be sent tells a file that ends
                # inside the budget from one that passes it
                data = os.pread(f.fileno(), left + 1, 0)
            except OSError as e:
                files.append({"status": STATUS_ERROR,
                              "message": f"pread: {e}"})
                continue
            finally:
                f.close()
            if len(data) > left:
                break       # it grew since the listing: ends the prefix
            left -= len(data)
            parts.append(data)
            files.append({"n": len(data)})
        self.stats["read_many_files"] += len(parts)
        self.stats["bytes"] += budget - left
        return self._raw_bytes(b"".join(parts), {"files": files})

    async def _lseek(self, req, ctx):
        f = self._file(req.payload["handle"])
        try:
            pos = f.seek(int(req.payload["off"]), int(req.payload.get("whence", 0)))
        except OSError as e:
            raise HandlerError(f"lseek: {e}", status=400)
        return {"pos": pos}

    async def _close(self, req, ctx):
        f = self._handles.pop(int(req.payload["handle"]), None)
        if f is not None:
            try:
                f.close()
            except OSError:
                pass
        return {}

    def close_all(self) -> None:
        for f in self._handles.values():
            try:
                f.close()
            except OSError:
                pass
        self._handles.clear()


class FirstReadError(RuntimeError):
    """``open_read``: the file opened and its first read failed.  The
    peer has closed the file; the caller ends the file as it ends a
    failed ``read_at``, not as a failed open."""


class AgentFSClient:
    """Server-side client of agentfs (reference: the arpcfs FUSE backend's
    RPC surface, internal/server/vfs/arpcfs — here consumed directly by the
    archive writer instead of through kernel FUSE: one fewer kernel
    crossing than the reference's hot loop)."""

    def __init__(self, session):
        self.s = session            # arpc.Session

    async def stat_fs(self) -> dict:
        return (await self.s.call("agentfs.stat_fs")).data

    async def attr(self, path: str) -> dict:
        return (await self.s.call("agentfs.attr", {"path": path})).data

    async def read_dir(self, path: str) -> list[dict]:
        entries: list[dict] = []
        start = ""
        while True:
            payload = {"path": path}
            if start:
                payload["start"] = start
            d = (await self.s.call("agentfs.read_dir", payload)).data
            entries.extend(d["entries"])
            start = d.get("next", "")
            if not start:
                return entries

    async def read_link(self, path: str) -> str:
        return (await self.s.call("agentfs.read_link", {"path": path})).data["target"]

    async def xattrs(self, path: str) -> dict:
        return (await self.s.call("agentfs.xattrs", {"path": path})).data["xattrs"]

    async def open(self, path: str) -> int:
        return (await self.s.call("agentfs.open", {"path": path})).data["handle"]

    async def open_read(self, path: str,
                        n: int) -> tuple[int, bytearray, bool]:
        """Open ``path`` and read its first ``n`` bytes in one call:
        ``(handle, data, eof)``.  At ``eof`` the agent has closed the
        file and ``handle`` is 0.  An agent that predates the ``read``
        key ignores it and answers 200 with a bare handle: that comes
        back as ``(handle, b"", False)`` and the caller goes on with
        ``read_at`` — what the peer answered decides, nothing else.
        ``data`` is the buffer the bytes were received into, as is
        ``read_at``'s; ``read_many``'s files are views of one."""
        try:
            resp, buf = await self.s.call_binary(
                "agentfs.open", {"path": path, "read": n}, n)
        except CallError as e:
            if e.response.status == STATUS_ERROR:
                raise FirstReadError(e.response.message) from e
            raise
        if resp.status != STATUS_RAW_STREAM:
            return resp.data["handle"], b"", False
        return resp.data["handle"], buf, bool(resp.data["eof"])

    async def read_many(self, paths: list[str],
                        budget: int) -> "list | None":
        """Read a run of small files whole in one call, at most
        ``budget`` bytes in all.  One item for each of ``paths``: the
        file's bytes; or the exception ``open_read`` would have raised
        for it (a ``CallError`` for a refused open, a ``FirstReadError``
        for a failed read); or None for a file the agent did not serve —
        it would have passed the budget, and so has every path after it.
        None instead of the list: the agent does not know the method
        (404 from the router; a file's own 404 is in its item, never in
        the call's status) and the caller reads file by file — what the
        peer answered decides, nothing else."""
        try:
            resp, buf = await self.s.call_binary(
                "agentfs.read_many", {"paths": paths, "budget": budget},
                budget)
        except CallError as e:
            if e.response.status == STATUS_NOT_FOUND:
                return None
            raise
        out: list = []
        view, off = memoryview(buf), 0
        for rec in resp.data["files"]:
            if "n" in rec:
                out.append(view[off:off + rec["n"]])
                off += rec["n"]
            elif rec["status"] == STATUS_ERROR:
                out.append(FirstReadError(rec["message"]))
            else:
                out.append(CallError(Response(rec["status"],
                                              rec["message"])))
        return out + [None] * (len(paths) - len(out))

    async def read_at(self, handle: int, off: int, n: int) -> bytearray:
        return (await self.s.call_binary(
            "agentfs.read_at", {"handle": handle, "off": off, "n": n},
            n))[1]

    async def close(self, handle: int) -> None:
        await self.s.call("agentfs.close", {"handle": handle})
