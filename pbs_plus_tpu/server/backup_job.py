"""The backup job — the reference's most important path (SURVEY §3.2),
TPU-first redesign.

Reference flow: scheduler → preExecute (queued task log, pre-script,
target_status probe, FUSE mount of agentfs) → execute (exec
proxmox-backup-client against the mount; pbc reads cross kernel-FUSE +
aRPC per read) → post-process logs → cleanup (unmount, kill agent child).

This build owns the archive writer (SURVEY §2.9: no pbc exec), so the hot
loop loses two kernel crossings: the server walks agentfs directly over
aRPC and streams file content straight into the DedupWriter (whose chunker
backend is the pluggable CPU/TPU pipeline).  Dataflow:

    agent pread ← aRPC raw stream ← [async prefetcher] → bounded queue →
    [writer thread: CDC chunker → chunk store] → DIDX + manifest

The async side prefetches up to ``queue_depth`` file blocks ahead (the
reference's readahead/buffer-pool role); the writer thread runs the
synchronous dedup writer without blocking the event loop.  A file's
first read rides on its open (``agentfs.open`` with ``read``), and a
block shorter than ``READ_BLOCK`` is the file's end: a file of one block
is one call and one queue item.  A listing's consecutive small files
cross the wire together: one ``agentfs.read_many`` for the run, and its
files reach the writer's queue in one step (docs/data-plane.md "The
pump").
"""

from __future__ import annotations

import asyncio
import fnmatch
import os
import queue
import threading
import time
import uuid
from dataclasses import dataclass, field
from typing import Callable, Optional

from ..agent.agentfs import AgentFSClient, FirstReadError
from ..arpc import Session
from ..arpc.agents_manager import AgentsManager
from ..chunker import ChunkerParams, CpuChunker
from ..pxar.backupproxy import BackupSession, LocalStore
from ..pxar.format import (
    Entry, KIND_BLOCKDEV, KIND_DEVICE, KIND_DIR, KIND_FIFO, KIND_FILE,
    KIND_HARDLINK, KIND_SOCKET, KIND_SYMLINK,
)
from ..utils import failpoints, trace
from ..utils.log import L
from ..utils.resilience import CircuitBreaker, with_retry
from . import checkpoint, database

READ_BLOCK = 8 << 20          # agentfs read granularity
READ_MANY_FILES = 256         # files a read_many at most: paths of any
                              # length and their records stay far inside
                              # aRPC's envelope limit, and a directory of
                              # empty files is still a call per 256
QUEUE_DEPTH = 8               # prefetched blocks in flight

_SENTINEL = object()
_ABORTED = object()

# Process-wide totals of the pump, rendered on /metrics beside the
# device batcher's (server/metrics.py): files it began to stream, those
# that crossed the wire in one call of their own, its agentfs calls
# (open_read, read_at, close, read_many), the files whose bytes came in
# a read_many answer, and those answers.  A job's own counts are the
# attrs of its backup.pump span.  Written on the event loop's thread
# alone.
PUMP_TOTALS = {"files": 0, "one_call_files": 0, "calls": 0,
               "batched_files": 0, "batch_calls": 0}

# A session's own clocks (docs/observability.md "The session's clocks").
# The writer thread's life, partitioned by state through the shared
# thread clock (utils/trace.py): waiting for the pump, at the chunker
# (the gather and the stand at the device), the hash batch, the index
# probe, the similarity sketch, the store, and everything else on the
# thread; beside them its own CPU seconds.
WRITER_STATES = ("pump_wait_s", "cdc_s", "sha_s", "probe_s", "presketch_s",
                 "store_s", trace.REST)
# The pump's awaits, wall sums: suspended on the agent (every agentfs
# call), on the writer (a queue put is an executor hop even when the
# queue has room) and on the writer's join at the end.
PUMP_WAITS = ("rpc_wait_s", "put_wait_s", "join_wait_s")
# Process-wide totals of both, rendered on /metrics like PUMP_TOTALS and
# written where those are; ``loop_cpu_s`` is the event loop thread's CPU
# clock (``time.thread_time`` on it) as last read at a pump's end.
CLOCK_TOTALS = {**{"writer_" + k: 0.0 for k in WRITER_STATES + ("cpu_s",)},
                **{"pump_" + k: 0.0 for k in PUMP_WAITS},
                "loop_cpu_s": 0.0}


# What the bulk bytes' way did on the job's aRPC connection, the
# server's end of it (arpc/mux.py ``MuxConnection.stats``): frames sent
# and those of them that had to wait for the peer under the write
# deadline's timer, bytes received and those of them that went from
# their frames into the pump's buffers with one copy.  On the job's
# record as ``mux_*``, totalled here for /metrics.
MUX_COUNTS = ("frames_tx", "drain_waits", "bytes_rx", "rx_direct_bytes")
MUX_TOTALS = dict.fromkeys(MUX_COUNTS, 0)

# What the dedup index did for the session, counted on the writer's own
# thread where it happens (``trace.tally`` in pxar/chunkindex.py and
# ops/cuckoo.py; docs/observability.md "The index"): batched probes
# (trips), the digests they asked and, on a device host, what those were
# padded to; the probes' confirmed hits and the filter positives the
# exact tier rejected; the store's scalar asks (contains) and the digests
# it inserted; the times a probe found the table changed and brought the
# device's copy up to date — whole (``table_uploads``) or by the buckets
# that changed (``table_delta_uploads``, ``table_delta_buckets``) — with
# the bytes and the wall seconds of both kinds; and the seconds of the
# lookups' device phase.  On the job's record as ``index_*`` (with
# ``index_table_bytes``, the table's size at the job's end, and
# ``index_table_shards``, the devices its device copy then lay on),
# totalled here for /metrics.
INDEX_COUNTS = ("probe_trips", "probe_digests", "probe_padded", "hits",
                "false_positives", "contains", "inserts", "table_uploads",
                "table_upload_bytes", "upload_s", "device_s",
                "table_delta_uploads", "table_delta_buckets")
INDEX_TOTALS = {"index_" + k: 0 for k in INDEX_COUNTS}

# The store stage's fan-out (pxar/storepool.py ``StoreFanOut``), counted
# on the writer's thread at each flush's join: the novel chunks a helper
# thread of the store pool stored in the writer's place, the flushes
# that fanned out, and the helpers' summed seconds inside ``insert``
# (docs/observability.md "The session's clocks").  On the job's record
# as ``store_pool_*``, totalled here for /metrics.
STORE_POOL_COUNTS = ("chunks", "flushes", "s")
STORE_POOL_TOTALS = {"store_pool_" + k: 0 for k in STORE_POOL_COUNTS}


def _get_abortable(q: "queue.Queue", abort: "threading.Event | None"):
    """Blocking queue get that returns _ABORTED instead of waiting
    forever once ``abort`` is set (producers cancelled mid-flight never
    send their sentinels).  The single polling idiom for every
    writer-side wait in this module."""
    with trace.state("pump_wait_s"):
        while True:
            try:
                return q.get(timeout=0.25)
            except queue.Empty:
                if abort is not None and abort.is_set():
                    return _ABORTED


def match_exclusion(rel: str, patterns: list[str]) -> bool:
    """THE exclusion semantic, shared by every target kind (agent pump,
    local walk, s3 pull): plain fnmatch, anchored '/'-patterns matched
    against '/'+rel, and directory-prefix patterns ('cache/')."""
    for pat in patterns:
        p = pat.strip()
        if not p:
            continue
        anchored = p
        if p.startswith("/"):
            p = p[1:]
        if fnmatch.fnmatch(rel, p) or fnmatch.fnmatch("/" + rel, anchored):
            return True
        if p.endswith("/") and (rel + "/").startswith(p):
            return True
    return False


def validate_chunker_kind(kind: str) -> None:
    """Cheap syntactic validation (no clients constructed — web CRUD path)."""
    if kind in ("", "cpu", "scalar", "vector", "tpu") \
            or kind.startswith("sidecar:"):
        return
    raise ValueError(f"unknown chunker backend {kind!r} "
                     "(want cpu | scalar | vector | tpu | "
                     "sidecar:<host:port>)")


def validate_pipeline_workers(n) -> int:
    """Validate the per-job pipelined-writer worker count (web CRUD
    path).  0 = the sequential writer; 1..64 = pxar/pipeline.py with
    that many hash workers (insert always runs on one ordered committer
    stage, so cut/digest output is identical for every value)."""
    n = int(n)
    if not 0 <= n <= 64:
        raise ValueError(f"pipeline_workers {n} out of range 0..64")
    return n


def make_batch_hasher(kind: str):
    """Batched digest backend matching the chunker backend: the tpu path
    hashes emitted chunks a batch at a time through ops/sha256 (on the
    host's SHA-256 since PR 25, counted there); cpu/sidecar use the
    writer's inline hashlib path."""
    if kind == "tpu":
        def hasher(chunks):
            # imported lazily on the writer thread (never on the event
            # loop): jax initialises whatever backend the process was
            # started with
            from ..models.dedup import device_sha256_batch
            return device_sha256_batch(chunks)
        return hasher
    return None


def resolve_cpu_scan_backend(cpu_backend: str | None = None) -> str:
    """CPU scan implementation for cpu-kind chunkers: explicit
    ``cpu_backend`` (ServerConfig.chunker_backend) wins, empty falls
    back to ``PBS_PLUS_CHUNKER_BACKEND`` (conf.Env.chunker_backend),
    default scalar.  Unknown values degrade to scalar with a warning —
    a typo'd env var must not take the fleet down."""
    from ..utils import conf
    backend = cpu_backend or conf.env().chunker_backend or "scalar"
    if backend in ("scalar", "cpu"):
        return "scalar"
    if backend == "vector":
        return "vector"
    L.warning("unknown chunker backend %r (want scalar | vector); "
              "using the scalar scan", backend)
    return "scalar"


def make_chunker_factory(kind: str, *, cpu_backend: str | None = None):
    """The one-line config change (BASELINE.json):
    chunker = cpu | scalar | vector | tpu | sidecar:<host:port>.

    ``cpu_backend`` selects the scan implementation for the cpu kinds
    (''/'cpu'): 'vector' routes through chunker/vector.py's
    ``ResilientVectorFactory`` (self-test-gated, pinned per stream at
    bind_stream time, degrades to scalar like sidecar degrades to CPU);
    anything else keeps the scalar ``CpuChunker``.  Explicit kinds
    'scalar'/'vector' pin the implementation regardless of conf."""
    if kind == "tpu":
        def factory(p):
            # invoked inside start_session, which job code runs off the
            # event loop — the jax import never stalls the server loop
            from ..models.dedup import TpuChunker
            return TpuChunker(p)
        return factory
    if kind.startswith("sidecar:"):
        # breaker-gated factory: degrades to the CPU chunker when the
        # sidecar is unreachable, decided per stream at OPEN time only
        # (sidecar/client.py ResilientSidecarFactory docstring)
        from ..sidecar.client import ResilientSidecarFactory
        return ResilientSidecarFactory(kind.split(":", 1)[1])
    if kind == "scalar":
        return lambda p: CpuChunker(p)
    if kind == "vector" or (kind in ("", "cpu")
                            and resolve_cpu_scan_backend(cpu_backend)
                            == "vector"):
        from ..chunker.vector import ResilientVectorFactory
        return ResilientVectorFactory()
    if kind not in ("", "cpu"):
        raise ValueError(f"unknown chunker backend {kind!r} "
                         "(want cpu | scalar | vector | tpu | "
                         "sidecar:<host:port>)")
    return lambda p: CpuChunker(p)


@dataclass
class BackupResult:
    snapshot: str = ""
    entries: int = 0
    bytes_total: int = 0
    files: int = 0
    errors: list[str] = field(default_factory=list)
    manifest: dict = field(default_factory=dict)


class _QueuePumpReader:
    """File-like .read(n) fed by a thread-safe queue of blocks (async
    producer / sync writer-thread consumer).  ``first`` is the block that
    came with the open; with no queue it is the whole file.  A block is
    any bytes-like object the producer no longer writes to — the buffer
    an agentfs read was received into — and ``read`` serves it whole or
    as views of it, never as copies."""

    def __init__(self, q: "queue.Queue | None",
                 abort: "threading.Event | None" = None, *,
                 first=b""):
        self._q = q
        self._abort = abort
        self._buf = first
        self._eof = q is None
        # set by the writer thread when it dies: the async producer checks
        # it before each fq.put so a >64 MB file can't wedge the job on a
        # dead consumer (advisor finding r1)
        self.dead = False

    def read(self, n: int = -1):
        while not self._buf and not self._eof:
            item = _get_abortable(self._q, self._abort)
            if item is _ABORTED:
                # producer was cancelled mid-file; no sentinel will
                # ever come — fail the writer instead of hanging
                self._eof = True
                raise RuntimeError("backup aborted mid-file")
            if item is _SENTINEL:
                self._eof = True
                break
            if isinstance(item, Exception):
                self._eof = True
                raise item
            self._buf = item
        if not self._buf:
            return b""
        if n < 0 or n >= len(self._buf):
            out, self._buf = self._buf, b""
        else:
            view = memoryview(self._buf)
            out, self._buf = view[:n], view[n:]
        return out


class RemoteTreeBackup:
    """Walks an agentfs tree in archive (DFS) order and streams it into a
    BackupSession writer."""

    def __init__(self, client: AgentFSClient, session: BackupSession, *,
                 exclusions: list[str] | None = None,
                 job_log=None):
        self.fs = client
        self.session = session
        self.exclusions = exclusions or []
        self.log = job_log or L
        self.result = BackupResult()
        # checkpoint resume (server/checkpoint.py): files the crashed
        # run fully committed splice via write_entry_ref with ZERO agent
        # reads — only the tail of the tree re-streams
        self.resume = getattr(session, "resume_plan", None)
        # this job's share of PUMP_TOTALS
        self.pump = dict.fromkeys(PUMP_TOTALS, 0)
        # the session's clocks: the writer thread's, and the pump's waits
        self.writer_clock = trace.ThreadClock(
            dict.fromkeys(WRITER_STATES, 0.0), label="writer",
            counts=dict.fromkeys([*INDEX_TOTALS, *STORE_POOL_TOTALS], 0))
        self.waits = dict.fromkeys(PUMP_WAITS, 0.0)
        # until the agent answers that it does not know read_many
        self._batching = True
        self._wq: queue.Queue = queue.Queue(maxsize=QUEUE_DEPTH)
        self._writer_exc: BaseException | None = None
        self._seen_inodes: dict[tuple[int, int], str] = {}
        # set when run() is cancelled (job kill): the writer thread must
        # exit without waiting for sentinels a dead producer never sends
        self._abort = threading.Event()

    def _excluded(self, rel: str) -> bool:
        return match_exclusion(rel, self.exclusions)

    @staticmethod
    def _to_entry(rel: str, m: dict) -> Entry:
        kind = m["kind"]
        return Entry(
            path=rel, kind=kind, mode=m["mode"], uid=m["uid"], gid=m["gid"],
            mtime_ns=m["mtime_ns"],
            size=m["size"] if kind == KIND_FILE else 0,
            link_target=m.get("target", ""),
            rdev=m.get("rdev", 0),
            xattrs={k: bytes(v) for k, v in m.get("xattrs", {}).items()},
        )

    def _mux_counts(self) -> dict:
        """The job's connection's counters now; none where the agent
        file system is not an aRPC client's (the tests' fakes)."""
        conn = getattr(getattr(self.fs, "s", None), "conn", None)
        stats = getattr(conn, "stats", {})
        return {k: stats[k] for k in MUX_COUNTS if k in stats}

    def _index_table(self) -> dict:
        """The dedup index's filter table now: its size and the devices
        its device copy lies on; 0 where the session's store has none
        (the tests' fakes, a remote index)."""
        chunks = getattr(getattr(self.session, "writer", None), "store",
                         None)
        index = getattr(chunks, "_index", None)
        return {"index_table_" + k: int(getattr(index, "table_" + k, 0))
                for k in ("bytes", "shards")}

    async def run(self) -> BackupResult:
        with trace.span("backup.pump") as sp:
            t0, loop_cpu0 = time.perf_counter(), time.thread_time()
            mux0 = self._mux_counts()
            try:
                return await self._run()
            finally:
                # one record a job: the pump's counts, the writer's
                # clock (its thread is joined by now) with what the
                # index and the store pool did for that thread, the
                # pump's waits and the loop thread's CPU clock at both
                # ends
                clocks = {
                    **{"writer_" + k: v
                       for k, v in self.writer_clock.seconds.items()},
                    **{"pump_" + k: v for k, v in self.waits.items()},
                    "pump_life_s": time.perf_counter() - t0,
                    "loop_cpu0": loop_cpu0, "loop_cpu1": time.thread_time()}
                mux = {k: v - mux0[k] for k, v in self._mux_counts().items()}
                index = {**self.writer_clock.counts, **self._index_table()}
                sp.set(job=self.log.scope.get("job_id", ""), **self.pump,
                       **clocks, **{"mux_" + k: v for k, v in mux.items()},
                       **index)
                for k, v in self.pump.items():
                    PUMP_TOTALS[k] += v
                for k, v in mux.items():
                    MUX_TOTALS[k] += v
                for k, v in clocks.items():
                    if k in CLOCK_TOTALS:       # the states and the waits
                        CLOCK_TOTALS[k] += v
                CLOCK_TOTALS["loop_cpu_s"] = clocks["loop_cpu1"]
                for k in INDEX_TOTALS:
                    INDEX_TOTALS[k] += index[k]
                for k in STORE_POOL_TOTALS:
                    STORE_POOL_TOTALS[k] += index[k]
                self.log.info("session clocks: %s", " ".join(
                    f"{k}={v}" if isinstance(v, int) else f"{k}={v:.6f}"
                    for k, v in {**clocks, **index}.items()))

    async def _run(self) -> BackupResult:
        # hand the job's trace context to the writer thread: ingest
        # stage spans emitted there parent under the job span
        self._tctx = trace.capture()
        writer_thread = threading.Thread(
            target=self._writer_loop, name="backup-writer", daemon=True)
        writer_thread.start()
        try:
            root_attr = await self._rpc(self.fs.attr(""))
            await self._put(("entry", self._to_entry("", root_attr), None))
            await self._walk("")
        except BaseException as e:
            await self._put(e if isinstance(e, Exception) else RuntimeError(str(e)))
            raise
        finally:
            # the sync abort flag ALWAYS lands, even if the awaits below
            # are interrupted by task cancellation — the writer thread
            # then self-drains and exits instead of blocking forever
            self._abort.set()
            closer = asyncio.ensure_future(self._close_writer(writer_thread))
            try:
                await asyncio.shield(closer)
            except asyncio.CancelledError:
                # finish the join before propagating so no caller ever
                # observes run() "done" with the writer still streaming
                if not closer.done():
                    try:
                        await closer
                    except (asyncio.CancelledError, Exception) as e:
                        self.log.debug(
                            "writer close raced job cancel: %s", e)
                raise
        if self._writer_exc is not None:
            raise self._writer_exc
        return self.result

    async def _close_writer(self, writer_thread: threading.Thread) -> None:
        await self._put(_SENTINEL)
        await self._hop(writer_thread.join, wait="join_wait_s")

    async def _rpc(self, call):
        """Await one agentfs call: the pump is suspended on the agent."""
        t = time.perf_counter()
        try:
            return await call
        finally:
            self.waits["rpc_wait_s"] += time.perf_counter() - t

    async def _hop(self, fn, *args, wait: str = "put_wait_s") -> None:
        """Run a blocking call of the writer's side on a pool thread:
        the pump is suspended on the writer (a put that finds room
        costs the hop all the same)."""
        t = time.perf_counter()
        try:
            await asyncio.get_running_loop().run_in_executor(None, fn, *args)
        finally:
            self.waits[wait] += time.perf_counter() - t

    async def _put(self, item) -> None:
        await self._hop(self._wq.put, item)

    async def _put_many(self, items: list) -> None:
        """Hand the writer several items in order with one hop at most:
        what the queue has room for goes in from here, the rest from
        one executor call."""
        for i, item in enumerate(items):
            try:
                self._wq.put_nowait(item)
            except queue.Full:
                await self._hop(self._put_rest, items[i:])
                return

    def _put_rest(self, items: list) -> None:
        # an aborted writer stops taking (its one drain empties the
        # queue once): give up then, not block a pool thread for good
        for item in items:
            while not self._abort.is_set():
                try:
                    self._wq.put(item, timeout=0.25)
                    break
                except queue.Full:
                    pass

    async def _walk(self, rel: str) -> None:
        try:
            entries = await self._rpc(self.fs.read_dir(rel))
        except ConnectionError:
            # transport death fails the JOB (the job-level retry may
            # re-run it); swallowing it as a per-dir error would grind
            # through every remaining path against a dead session
            raise
        except Exception as e:
            self.result.errors.append(f"{rel}: {e}")
            return
        # consecutive small files of this listing, read with one call
        run: list[tuple[str, Entry]] = []
        run_bytes = 0
        for m in entries:
            child = f"{rel}/{m['name']}" if rel else m["name"]
            if self._excluded(child):
                continue
            kind = m["kind"]
            e = self._to_entry(child, m)
            item = self._item_without_read(child, m, e)
            if item is None and kind == KIND_FILE and self._batching \
                    and e.size < READ_BLOCK:
                # the bytes in flight are what one block is, and the
                # request and its answer stay small
                if run_bytes + e.size > READ_BLOCK \
                        or len(run) == READ_MANY_FILES:
                    await self._stream_run(run)
                    run, run_bytes = [], 0
                run.append((child, e))
                run_bytes += e.size
            else:
                # anything else goes to the writer after the run
                await self._stream_run(run)
                run, run_bytes = [], 0
                if item is not None:
                    await self._put(item)
                    if kind == KIND_DIR:
                        await self._walk(child)
                elif kind == KIND_FILE:
                    await self._stream_file(child, e)
            self.result.entries += 1
        await self._stream_run(run)

    def _item_without_read(self, child: str, m: dict, e: Entry):
        """The writer's item for an entry that costs no read of file
        content, or None: a regular file whose bytes are to be read (or
        a kind the archive does not carry)."""
        seen_inodes = self._seen_inodes
        kind = m["kind"]
        if kind == KIND_FILE:
            key = (m.get("dev", 0), m.get("ino", 0))
            if m.get("nlink", 1) > 1 and key in seen_inodes:
                e.kind = KIND_HARDLINK
                e.link_target = seen_inodes[key]
                e.size = 0
                return ("entry", e, None)
            if m.get("nlink", 1) > 1:
                seen_inodes[key] = child
            src_e = (self.resume.skip_ref(child, e.size, e.mtime_ns)
                     if self.resume is not None else None)
            if src_e is None:
                return None
            # digest rides along from the checkpoint entry so
            # verification sees the whole-file sha256 (the mount commit
            # engine's ref discipline)
            e.digest = src_e.digest
            # spliced files count as completed files, same as the local
            # walker's skip branch
            self.result.files += 1
            return ("ref", e, (src_e.payload_offset, src_e.size))
        if kind == KIND_SYMLINK:
            # multiply-linked symlinks are hardlink entries here too
            # (same rsync -H parity as pxar/walker.py's local walk)
            key = (m.get("dev", 0), m.get("ino", 0))
            if m.get("nlink", 1) > 1 and key in seen_inodes:
                e.kind = KIND_HARDLINK
                e.link_target = seen_inodes[key]
            elif m.get("nlink", 1) > 1:
                seen_inodes[key] = child
        elif kind not in (KIND_DIR, KIND_FIFO, KIND_SOCKET, KIND_DEVICE,
                          KIND_BLOCKDEV):
            return None
        return ("entry", e, None)

    async def _stream_run(self, run: list[tuple[str, Entry]]) -> None:
        """A run of small files: one ``read_many``, and every file it
        served becomes what a one-call file is, a queue item that holds
        the whole file, all of them handed to the writer in one step.
        A file's own error ends that file as ``_stream_file`` ends it;
        what the agent left unserved starts the next run, and a run of
        one — or a first file left unserved — goes the one-file way, so
        every turn of the loop takes a file off the run."""
        pump = self.pump
        while len(run) > 1 and self._batching:
            pump["calls"] += 1
            pump["batch_calls"] += 1
            answer = await self._rpc(self.fs.read_many(
                [rel for rel, _ in run], READ_BLOCK))
            if answer is None:
                self._batching = False
                break
            if answer[0] is None:
                await self._stream_file(*run[0])
                run = run[1:]
                continue
            items, taken, dropped = [], 0, None
            for (rel, entry), got in zip(run, answer):
                if got is None:
                    break
                taken += 1
                pump["files"] += 1
                if not isinstance(got, Exception):
                    pump["batched_files"] += 1
                # the sides _stream_file gives open_read's errors
                side = "read" if isinstance(got, FirstReadError) else "open"
                try:
                    await self._before_handing_on()
                except Exception as e:
                    got, side = e, "read"
                if not isinstance(got, Exception):
                    items.append(("file", entry,
                                  _QueuePumpReader(None, first=got)))
                    self.result.bytes_total += len(got)
                    self.result.files += 1
                    if self.resume is not None:
                        self.resume.note_reread(len(got), files=1)
                    continue
                self.result.errors.append(f"{rel}: {side}: {got}")
                if side == "read":
                    items.append(("file", entry,
                                  self._failed_reader(rel, got)))
                    if isinstance(got, ConnectionError):
                        dropped = got
                        break
            await self._put_many(items)
            if dropped is not None:
                raise dropped
            run = run[taken:]
        for rel, entry in run:
            await self._stream_file(rel, entry)

    def _failed_reader(self, rel: str, e: Exception) -> _QueuePumpReader:
        """The writer gets a file whose first read failed all the same,
        as one whose read raises."""
        failed: queue.Queue = queue.Queue(maxsize=1)
        failed.put_nowait(RuntimeError(f"read {rel}: {e}"))
        return _QueuePumpReader(failed, self._abort)

    @staticmethod
    async def _before_handing_on() -> None:
        """The one site of the file-stream failpoint: before each read
        of ``_stream_file``, and once for each file of a ``read_many``
        answer before it is handed to the writer."""
        await failpoints.ahit("backup.file.stream")

    async def _stream_file(self, rel: str, entry: Entry) -> None:
        """Prefetch file blocks over aRPC into the writer queue.  The
        first block comes with the open, and a block shorter than
        READ_BLOCK is the end (the agent opens regular files only): a
        file of one block is one call and one item of the writer's
        queue, with no block queue, sentinel or close of its own."""
        pump = self.pump
        pump["files"] += 1
        handle, fq, reader, off = 0, None, None, 0
        try:
            while True:
                await self._before_handing_on()
                pump["calls"] += 1
                if reader is None:
                    try:
                        handle, block, eof = await self._rpc(
                            self.fs.open_read(rel, READ_BLOCK))
                    except (ConnectionError, FirstReadError):
                        raise
                    except Exception as e:
                        self.result.errors.append(f"{rel}: open: {e}")
                        return
                    if eof:
                        pump["one_call_files"] += 1
                    else:
                        fq = queue.Queue(maxsize=QUEUE_DEPTH)
                    reader = _QueuePumpReader(fq, self._abort, first=block)
                    await self._put(("file", entry, reader))
                else:
                    block = await self._rpc(
                        self.fs.read_at(handle, off, READ_BLOCK))
                    eof = len(block) < READ_BLOCK
                    if block:
                        await self._hop(fq.put, block)
                off += len(block)
                self.result.bytes_total += len(block)
                if self.resume is not None:
                    self.resume.note_reread(len(block))
                if eof or reader.dead:
                    # dead: the writer died; its drain empties fq
                    break
        except Exception as e:
            # the writer's file fails with the read; a dead transport
            # fails the job too (the job-level retry re-runs
            # incrementally — committed chunks are already in the store)
            if reader is None:
                await self._put(
                    ("file", entry, self._failed_reader(rel, e)))
            elif fq is not None:
                await self._hop(fq.put, RuntimeError(f"read {rel}: {e}"))
            self.result.errors.append(f"{rel}: read: {e}")
            if isinstance(e, ConnectionError):
                raise
            return
        finally:
            if fq is not None:
                await self._hop(fq.put, _SENTINEL)
            if handle:
                pump["calls"] += 1
                try:
                    await self._rpc(self.fs.close(handle))
                except Exception as e:
                    self.log.debug("agentfs close failed for %s: %s", rel, e)
        self.result.files += 1
        if self.resume is not None:
            self.resume.note_reread(0, files=1)

    def _drain_reader(self, reader) -> None:
        """Unblock the async producer of a dropped/aborted file: mark the
        reader dead (producer stops reading ahead) and consume its block
        queue until the producer's closing sentinel so any in-flight
        fq.put is released (advisor finding r1: the S3 writer drained its
        file queue on error; this path previously did not).  Under abort
        (producer cancelled) the sentinel may never come — bounded
        timeout-gets instead of waiting forever."""
        if reader is None or reader._eof:
            # _eof ⇒ the producer's closing sentinel was already consumed
            # (nothing more will arrive; a blocking get would never return)
            return
        reader.dead = True
        while True:
            item = _get_abortable(reader._q, self._abort)
            if item is _ABORTED or item is _SENTINEL or \
                    isinstance(item, BaseException):
                return

    def _nowait_drain_all(self, current) -> None:
        """Abort path: free every blocked executor-thread put without
        waiting for producers that were cancelled mid-flight."""
        def drain_q(q: "queue.Queue | None") -> None:
            while q is not None:        # None: a file of one queue item
                try:
                    q.get_nowait()
                except queue.Empty:
                    return
        if current is not None:
            current.dead = True
            drain_q(current._q)
        while True:
            try:
                item = self._wq.get_nowait()
            except queue.Empty:
                return
            if isinstance(item, tuple) and item[0] == "file":
                item[2].dead = True
                drain_q(item[2]._q)

    def _writer_loop(self) -> None:
        # fresh thread: attach the job's trace context so the writer's
        # ingest-stage spans/emits parent under the job span, and the
        # session's clock, which the stream writer's states reach from
        # below (pxar/transfer.py)
        trace.name_os_thread("backup-writer")   # its line in a profile
        with trace.attached(getattr(self, "_tctx", None)), \
                trace.clocked(self.writer_clock):
            self._writer_loop_body()

    def _writer_loop_body(self) -> None:
        w = self.session.writer
        current = None
        try:
            while True:
                item = _get_abortable(self._wq, self._abort)
                if item is _ABORTED:
                    self._nowait_drain_all(current)
                    return
                if item is _SENTINEL:
                    return
                if isinstance(item, BaseException):
                    return
                tag, entry, reader = item
                if tag == "entry":
                    w.write_entry(entry)
                elif tag == "ref":
                    # checkpoint fast-skip: splice the previous payload
                    # range (reader is the (old_offset, size) pair)
                    w.write_entry_ref(entry, reader[0], reader[1])
                else:
                    current = reader
                    w.write_entry_reader(entry, reader)
                    current = None
        except BaseException as e:
            self._writer_exc = e
            # drain so no producer ever blocks on a dead consumer: the
            # in-flight file first, then every dropped item in _wq
            self._drain_reader(current)
            while True:
                item = _get_abortable(self._wq, self._abort)
                if item is _ABORTED:
                    self._nowait_drain_all(None)
                    return
                if item is _SENTINEL or isinstance(item, BaseException):
                    return
                if isinstance(item, tuple) and item[0] == "file":
                    self._drain_reader(item[2])


def crashed_backup_job_ids(db: database.Database,
                           tasks: list[dict]) -> list[str]:
    """Which of the tasks found 'running' at startup (they died with the
    previous process) should be re-enqueued as resumable backups: backup
    tasks whose job row still exists and is enabled, deduped in task
    order.  The policy half of Server._cleanup_orphaned_tasks, split out
    so the startup self-heal is testable without the server's TLS
    stack."""
    out: list[str] = []
    for t in tasks:
        if t.get("kind") != "backup":
            continue
        row = db.get_backup_job(t["job_id"])
        if row is not None and row.enabled:
            out.append(row.id)
    return list(dict.fromkeys(out))


async def run_target_backup(row: database.BackupJobRow, *,
                            db: database.Database,
                            agents: AgentsManager,
                            store: LocalStore,
                            on_pump=None,
                            breaker_factory: Callable[
                                [], CircuitBreaker] | None = None,
                            attempts: int = 1,
                            checkpoint_interval: str = "") -> BackupResult:
    """Dispatch by target kind (reference: Target(agent|local|s3),
    internal/server/database/types.go) — agent targets stream over aRPC,
    local targets walk the server's own filesystem, s3 targets pull a
    bucket tree through the SigV4 client.

    Agent targets get the resilience wrap — applied HERE, at the single
    kind-dispatch point, so callers need not duplicate the kind
    defaulting: ``breaker_factory`` lazily yields the per-target circuit
    (JobsManager.breaker — one dead agent must not burn the scheduler's
    whole retry budget) and ``attempts > 1`` enables the job-level
    retry, which the dedup store makes cheap — chunks committed by a
    failed attempt are already present, so the re-run is incremental by
    construction.  ``CircuitOpenError``/cancellation are never retried
    (utils/resilience.py).

    ``checkpoint_interval`` (conf: ``PBS_PLUS_CHECKPOINT_INTERVAL``)
    arms durable checkpoints on agent and local targets backed by a
    local datastore — a crashed or retried attempt then resumes from the
    last checkpoint instead of byte zero (server/checkpoint.py); s3
    pulls and PBS push sessions are not checkpointed."""
    target = db.get_target(row.target)
    kind = (target or {}).get("kind", "agent")
    if kind == "local":
        return await run_local_backup(row, db=db, store=store,
                                      target=target,
                                      checkpoint_interval=checkpoint_interval)
    if kind == "s3":
        return await run_s3_backup(row, db=db, store=store, target=target)
    if kind != "agent":
        # a typo'd kind must fail HERE, not as a misleading
        # "agent not connected" from the fall-through
        raise RuntimeError(f"unknown target kind {kind!r} "
                           "(want agent | local | s3)")

    async def once() -> BackupResult:
        return await run_backup_job(row, db=db, agents=agents, store=store,
                                    on_pump=on_pump,
                                    checkpoint_interval=checkpoint_interval)

    breaker = breaker_factory() if breaker_factory is not None else None
    guarded = once if breaker is None else (lambda: breaker.call(once))
    if attempts <= 1 and breaker is None:
        return await once()
    return await with_retry(guarded, attempts=max(1, attempts),
                            base_delay_s=0.5, max_delay_s=5.0,
                            name=f"backup:{row.id}")


async def run_local_backup(row: database.BackupJobRow, *, db, store,
                           target: dict | None,
                           checkpoint_interval: str = "") -> BackupResult:
    """Local-path target: snapshot (btrfs/lvm/freeze fall-through) and
    walk the server's own filesystem — no agent involved (reference:
    local targets back up paths on the PBS host itself)."""
    from ..agent.snapshots import SnapshotManager
    from ..pxar.walker import backup_tree

    src = row.source_path or (target or {}).get("root_path", "")
    if not src or not os.path.isdir(src):
        raise RuntimeError(f"local source {src!r} is not a directory")
    result = BackupResult()
    exclusions = row.exclusions + db.list_exclusions(row.id)
    backup_id = row.backup_id or row.target

    def excluded(rel: str) -> bool:
        return match_exclusion(rel, exclusions)

    def run_sync() -> None:
        snaps = SnapshotManager()
        snap = snaps.create(src)
        try:
            with trace.span("backup.session_open"):
                resume_ctx = checkpoint.open_resume(
                    store, backup_type="host", backup_id=backup_id,
                    namespace=row.namespace or "")
                kw = {"previous_reader": resume_ctx[0]} if resume_ctx \
                    else {}
                session = store.start_session(
                    backup_type="host", backup_id=backup_id,
                    namespace=row.namespace or None,
                    pipeline_workers=row.pipeline_workers, **kw)
            try:
                if resume_ctx is not None:
                    session.resume_plan = resume_ctx[1]
                checkpoint.attach(session, checkpoint_interval)
                counters = {"files": 0, "bytes": 0}
                n = backup_tree(
                    session, snap.snapshot_path, exclude=excluded,
                    on_error=lambda p, e: result.errors.append(
                        f"{p}: {e}"),
                    counters=counters)
                result.entries = n
                result.files = counters["files"]
                result.bytes_total = counters["bytes"]
                extra = {"job": row.id, "errors": result.errors[:100]}
                if resume_ctx is not None:
                    extra["resume"] = resume_ctx[1].summary()
                with trace.span("backup.publish"):
                    result.manifest = session.finish(extra)
                result.snapshot = str(session.ref)
                # the published snapshot supersedes the group's
                # checkpoints — reap them now instead of waiting for
                # prune's sweep (store may be a PBSStore when the job
                # row says store='pbs': no local datastore, nothing to
                # clear)
                if getattr(store, "datastore", None) is not None:
                    checkpoint.clear(store.datastore, "host", backup_id,
                                     row.namespace or "")
            except BaseException:
                session.abort()
                raise
        finally:
            snaps.cleanup(snap)

    await asyncio.get_running_loop().run_in_executor(
        None, trace.wrap(run_sync))
    return result


async def run_s3_backup(row: database.BackupJobRow, *, db, store,
                        target: dict | None) -> BackupResult:
    """S3 target: pull the bucket/prefix tree through the SigV4 client
    (reference: vfs/s3fs backup source)."""
    import aiohttp

    from .s3 import S3Client, S3Config, backup_s3_tree

    cfg = (target or {}).get("config") or {}
    for k in ("endpoint", "bucket", "access_key", "secret_key"):
        if not cfg.get(k):
            raise RuntimeError(f"s3 target missing config key {k!r}")
    result = BackupResult()
    session = await asyncio.get_running_loop().run_in_executor(
        None, lambda: store.start_session(
            backup_type="host", backup_id=row.backup_id or row.target,
            namespace=row.namespace or None,
            pipeline_workers=row.pipeline_workers))
    try:
        async with aiohttp.ClientSession() as http:
            client = S3Client(http, S3Config(
                endpoint=cfg["endpoint"], bucket=cfg["bucket"],
                access_key=cfg["access_key"],
                secret_key=cfg["secret_key"],
                prefix=cfg.get("prefix", ""),
                region=cfg.get("region", "us-east-1")))
            counters = {"files": 0, "bytes": 0}
            n = await backup_s3_tree(
                client, session,
                exclusions=row.exclusions + db.list_exclusions(row.id),
                counters=counters)
        result.entries = n
        result.files = counters["files"]
        result.bytes_total = counters["bytes"]
        result.manifest = await asyncio.get_running_loop().run_in_executor(
            None, session.finish, {"job": row.id})
        result.snapshot = str(session.ref)
        return result
    except BaseException:
        session.abort()
        raise


async def run_backup_job(row: database.BackupJobRow, *,
                         db: database.Database,
                         agents: AgentsManager,
                         store: LocalStore,
                         job_suffix: str | None = None,
                         on_pump=None,
                         checkpoint_interval: str = "") -> BackupResult:
    """End-to-end agent backup: ask the agent to open a job session, walk
    its agentfs, stream into a datastore session, publish the snapshot."""
    job_id = job_suffix or f"{row.id}-{uuid.uuid4().hex[:8]}"
    target = db.get_target(row.target)
    if target is None:
        raise RuntimeError(f"unknown target {row.target!r}")
    hostname = target["hostname"] or row.target
    log = L.with_scope(job_id=row.id, backup_id=job_id)

    control = agents.get(hostname)
    if control is None:
        raise RuntimeError(f"agent {hostname!r} not connected")
    control_sess = Session(control.conn)

    # target_status probe over the control plane (reference: job.go:489-543)
    st = await control_sess.call(
        "target_status", {"path": row.source_path})
    if not st.data.get("ok"):
        raise RuntimeError(f"target path unavailable: {st.data}")
    db.touch_target_online(row.target)

    # announce + request the job data session (reference: Expect + "backup")
    client_id = f"{hostname}|{job_id}"
    agents.expect(client_id)
    try:
        resp = await control_sess.call(
            "backup", {"job_id": job_id, "source": row.source_path},
            timeout=120)
        log.info("agent accepted backup (snapshot=%s)",
                 resp.data.get("snapshot_method"))
        loop = asyncio.get_running_loop()
        with trace.span("backup.session_open"):
            job_sess_info = await agents.wait_session(client_id, timeout=60)
            fs = AgentFSClient(Session(job_sess_info.conn))

            # checkpoint resume (datastore-backed stores only): a valid
            # checkpoint from a crashed or retried run becomes the
            # writer's `previous`, and its plan fast-skips committed
            # unchanged files.  Executor offloads are trace.wrap-ped so
            # spans opened on the worker thread (ingest stage emits,
            # store work) stay parented under this job's trace.
            resume_ctx = await loop.run_in_executor(
                None, trace.wrap(lambda: checkpoint.open_resume(
                    store, backup_type="host",
                    backup_id=row.backup_id or row.target,
                    namespace=row.namespace or "")))
            session_kw = ({"previous_reader": resume_ctx[0]}
                          if resume_ctx else {})
            # start_session can do network I/O (PBSStore: TLS connect,
            # session establish, previous-index downloads) — keep it off
            # the event loop
            session = await loop.run_in_executor(
                None, trace.wrap(lambda: store.start_session(
                    backup_type="host",
                    backup_id=row.backup_id or row.target,
                    namespace=row.namespace or None,
                    pipeline_workers=row.pipeline_workers,
                    **session_kw)))
        try:
            if resume_ctx is not None:
                session.resume_plan = resume_ctx[1]
                log.info("resuming from checkpoint %s: %d skippable "
                         "files", resume_ctx[1].summary()["checkpoint"],
                         len(resume_ctx[1]))
            # attach scans the group's .ckpt dir — datastore I/O stays
            # off the event loop like the session/resume calls around it
            await loop.run_in_executor(
                None, lambda: checkpoint.attach(session,
                                                checkpoint_interval))
            pump = RemoteTreeBackup(
                fs, session,
                exclusions=row.exclusions + db.list_exclusions(row.id),
                job_log=log)
            if on_pump is not None:
                on_pump(pump.result)     # live-progress metrics hook
            # crashed-job detection: race the pump against the job
            # session's disconnect (reference: arpcfs crashed-agent
            # pattern — control plane up, job session severed)
            disc = agents.watch_disconnect(job_sess_info)
            pump_task = asyncio.ensure_future(pump.run())
            try:
                await asyncio.wait({pump_task, disc},
                                   return_when=asyncio.FIRST_COMPLETED)
                if not pump_task.done():
                    pump_task.cancel()
                    await asyncio.gather(pump_task, return_exceptions=True)
                    raise RuntimeError(
                        "agent job session lost mid-backup "
                        f"({job_sess_info.client_id})")
                result = await pump_task
            finally:
                agents.unwatch_disconnect(job_sess_info, disc)
                if not disc.done():
                    disc.cancel()
                # outer cancellation (job kill, server stop) must not
                # orphan the pump: its writer would keep streaming into
                # a session about to be aborted
                if not pump_task.done():
                    pump_task.cancel()
                    await asyncio.gather(pump_task, return_exceptions=True)
            extra = {"job": row.id, "errors": pump.result.errors[:100]}
            if resume_ctx is not None:
                extra["resume"] = resume_ctx[1].summary()

            def _publish():
                with trace.span("backup.publish"):
                    return session.finish(extra)
            manifest = await loop.run_in_executor(
                None, trace.wrap(_publish))
            if getattr(store, "datastore", None) is not None:
                # published snapshot supersedes the group's checkpoints
                await loop.run_in_executor(
                    None, lambda: checkpoint.clear(
                        store.datastore, "host",
                        row.backup_id or row.target, row.namespace or ""))
            result.snapshot = str(session.ref)
            result.manifest = manifest
            log.info("backup complete: %d entries, %d bytes, snapshot %s",
                     result.entries, result.bytes_total, result.snapshot)
            return result
        except BaseException:
            session.abort()
            raise
    finally:
        agents.unexpect(client_id)
        # the server owns the client end of the job data session — close
        # it so a fork-isolated agent child sees EOF and can wind down
        # even when the daemon (and its "cleanup" RPC) is gone
        try:
            sess_info = agents.get(client_id)
            if sess_info is not None:
                await sess_info.conn.close()
        except Exception as e:
            log.debug("job data session close failed: %s", e)
        # tear down the agent-side job session (reference: "cleanup" RPC)
        try:
            await control_sess.call("cleanup", {"job_id": job_id}, timeout=15)
        except Exception as e:
            log.warning("agent cleanup RPC failed (agent may leak a "
                        "snapshot): %s", e)
