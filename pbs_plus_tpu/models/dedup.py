"""DedupPipeline — batched chunk + fingerprint + index probe on TPU.

The TPU-native equivalent of the reference's commit/backup hot loop
(SURVEY §3.4: "the walk's per-entry decode and the library's chunk+hash of
new payload — exactly what moves to TPU"; BASELINE.json north star).

Dataflow per step (B agent streams at once — the batch axis IS the agent
fan-in, SURVEY §2.10):

    host pages → device stream buffer uint8[B, S]
      ├─ rolling-hash kernel → candidate mask, 32 positions a word (device)
      ├─ greedy min/max cut selection over sparse candidates  (host, O(B·S/avg))
      ├─ block-gather + SHA-256 scan → digests uint8[N, 32]   (device)
      ├─ cuckoo probe → maybe-present bool[N]                 (device)
      └─ authoritative confirm + index insert                 (host)

Only the two dense passes touch every byte, and both stay on device; host
work is proportional to the number of chunks, not bytes.

Streams are processed in fixed-shape segments with 63-byte history halos so
jit caches stay small and results are bit-identical to the streaming CPU
chunker (same spec, same shared greedy pass).

When a stream's bytes reach the device: ``DedupPipeline`` takes whole
streams and scans them in ``segment_bytes`` rows.  A streaming session
(``TpuChunker``, the ``chunker="tpu"`` backend) is written to a few
bytes or a few MiB at a time — a tree of 1,024 files is some 2,100
writes — and a device round trip costs milliseconds whatever it carries,
so the chunker gathers the writes and sends a stream to the device in
full ``SCAN_SEGMENT`` rows (4 MiB), plus one request for the rest at
every flush and at the end: about sixty round trips for that tree's 235
MiB, where one per write made 2,100 (PERF.md section 6, PR 27).
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field

import numpy as np

from ..chunker.spec import WINDOW, ChunkerParams, select_cuts
from ..ops import sha256
from ..ops.cuckoo import CuckooIndex
from ..ops.rolling_hash import (batched_candidate_hits, device_tables,
                                segment_class)
from ..ops.sha256 import sha256_streams_chunks
from ..utils.conf import STREAM_BUFFER_SIZE

# What one scan request of a stream carries at most, and what a stream
# collects before it asks for a scan (TpuChunker): upstream's stream
# buffer.  One value for every stream and deployment; as a segment class
# of the scan, a full segment travels without padding.
SCAN_SEGMENT = STREAM_BUFFER_SIZE
assert segment_class(SCAN_SEGMENT) == SCAN_SEGMENT, \
    "the scan segment must be one of the scan's padded segment lengths"


@dataclass(frozen=True)
class DedupConfig:
    params: ChunkerParams = field(default_factory=lambda: ChunkerParams(avg_size=4 << 20))
    segment_bytes: int = 64 << 20        # device segment per stream per step
    index_buckets: int = 1 << 20         # initial cuckoo table (4M slots)


@dataclass
class ChunkRecord:
    offset: int          # absolute offset in the stream
    length: int
    digest: bytes
    is_new: bool         # not in the chunk index before this step


@dataclass
class StreamResult:
    chunks: list[ChunkRecord] = field(default_factory=list)

    @property
    def total_bytes(self) -> int:
        return sum(c.length for c in self.chunks)

    @property
    def new_bytes(self) -> int:
        return sum(c.length for c in self.chunks if c.is_new)

    @property
    def dedup_ratio(self) -> float:
        t = self.total_bytes
        return 1.0 - (self.new_bytes / t) if t else 0.0


class DedupPipeline:
    """Batched multi-stream dedup.  Feed segments for many streams, collect
    per-stream ChunkRecords.  Digests/cuts are bit-identical to the CPU
    path (tests/test_models.py::test_pipeline_matches_cpu_backend)."""

    def __init__(self, config: DedupConfig | None = None, *,
                 index: CuckooIndex | None = None):
        self.config = config or DedupConfig()
        self.params = self.config.params
        self.index = index if index is not None else CuckooIndex(
            n_buckets=self.config.index_buckets)
        self._tables = device_tables(self.params)
        self.stats = {"bytes_in": 0, "chunks": 0, "new_chunks": 0,
                      "device_steps": 0, "batched_rows": 0, "max_batch": 0}

    # (streaming consumers use TpuChunker below — the drop-in chunker
    # backend; this class is the batched whole-stream pipeline)
    def process_streams(self, streams: dict[str, bytes | np.ndarray],
                        ) -> dict[str, StreamResult]:
        """Chunk + fingerprint + probe complete streams (each stream fully
        in memory).  The batch axis is cross-stream INSIDE each device
        dispatch: segments from different streams stack into one
        ``[B, S]`` candidate kernel (histories are raw stream bytes, so
        every segment of every stream is independent), and every stream's
        chunks share one bucketed SHA dispatch set."""
        names = sorted(streams)
        arrs = {n: (np.frombuffer(streams[n], dtype=np.uint8)
                    if not isinstance(streams[n], np.ndarray) else streams[n])
                for n in names}
        out: dict[str, StreamResult] = {}
        # 1) candidate masks: all segments of all streams, grouped by
        # padded size, stacked [B, S_pad] per dispatch
        seg = self.config.segment_bytes
        tasks_by_pad: dict[int, list[tuple[str, int, int]]] = {}
        for n in names:
            a = arrs[n]
            self.stats["bytes_in"] += len(a)
            for off in range(0, len(a), seg):
                S = min(seg, len(a) - off)
                tasks_by_pad.setdefault(segment_class(S), []).append(
                    (n, off, S))
        ends_parts: dict[str, list[np.ndarray]] = {n: [] for n in names}
        for _, batch in sorted(tasks_by_pad.items()):
            # one call per padded size; the op splits it into as many
            # dispatches as the device's memory budget asks for
            hits_rows = batched_candidate_hits(
                [arrs[n][off:off + S] for n, off, S in batch],
                [arrs[n][off - (WINDOW - 1):off] if off else None
                 for n, off, S in batch],
                self._tables, self.params)
            self.stats["device_steps"] += 1
            self.stats["batched_rows"] += len(batch)
            self.stats["max_batch"] = max(self.stats["max_batch"],
                                          len(batch))
            for (n, off, S), hits in zip(batch, hits_rows):
                valid = hits + off >= WINDOW - 1
                ends_parts[n].append(hits[valid] + 1 + off)
        all_cuts: dict[str, list[int]] = {}
        for n in names:
            ends = np.sort(np.concatenate(ends_parts[n])) \
                if ends_parts[n] else np.empty(0, np.int64)
            all_cuts[n] = select_cuts(ends, len(arrs[n]), self.params)
        # 2) hash all chunks — ONE cross-stream bucketed dispatch set
        bounds_by_stream: dict[str, list[tuple[int, int]]] = {}
        for n in names:
            s = 0
            bounds = []
            for e in all_cuts[n]:
                bounds.append((s, e))
                s = e
            bounds_by_stream[n] = bounds
        digest_lists = sha256_streams_chunks(
            [arrs[n] for n in names], [bounds_by_stream[n] for n in names])
        digests_by_stream = dict(zip(names, digest_lists))
        # 3) probe (one cross-stream device probe) + ordered host insert
        all_digs = [d for n in names for d in digests_by_stream[n]]
        maybe_all = self.index.probe_confirmed(all_digs) if all_digs else []
        maybe_iter = iter(maybe_all)
        batch_seen: set[bytes] = set()
        for n in names:
            res = StreamResult()
            for (s, e), d in zip(bounds_by_stream[n], digests_by_stream[n]):
                present = next(maybe_iter) or d in batch_seen
                is_new = not present
                if is_new:
                    self.index.insert(d)
                    batch_seen.add(d)
                res.chunks.append(ChunkRecord(s, e - s, d, is_new))
                self.stats["chunks"] += 1
                self.stats["new_chunks"] += int(is_new)
            out[n] = res
        return out


class DeviceDispatchError(RuntimeError):
    """A device dispatch made for a ``chunker="tpu"`` session failed.
    The session ends and its job fails with this name in ``last_error``;
    the work is never re-run on the host behind the operator's back."""

    def __init__(self, what: str, cause: BaseException):
        super().__init__(f"DeviceDispatchError: {what} failed: "
                         f"{type(cause).__name__}: {cause}")


def device_sha256_batch(chunks: list) -> list:
    """The ``chunker="tpu"`` batch hasher: the host's SHA-256
    (``ops.sha256.sha256_chunks``, looked up at call time) on the
    writer's own thread, so eight sessions hash side by side and none
    waits in the feeder's queue.  The device's SHA-256 program loses to
    one host thread on every batch shape measured (ops/sha256.py;
    PERF.md section 6, PR 25) — a session's 16 MiB of 4 MiB chunks by a
    hundred times — and held the feeder's one thread for half of every
    second while the scans waited.  The scan stays on the device, and a
    scan dispatch that fails still fails the job (DeviceDispatchError)."""
    return sha256.sha256_chunks(chunks)


class TpuChunker:
    """chunker-interface adapter: feed/finalize returning absolute cut
    offsets, computed by the device kernel.  Drop-in for CpuChunker in
    transfer writers (``chunker="tpu"`` — the one-line config change from
    BASELINE.json).

    A stream goes to the device in full scan segments, not once per
    write: ``feed`` keeps the write with the stream's un-scanned bytes
    and returns no cut until they fill ``SCAN_SEGMENT``; then it submits
    exactly one segment (a longer write is sliced into several) through
    the process-wide DeviceFeeder, which coalesces concurrent streams'
    segments into ``[B, S]`` batched dispatches (the production batch
    axis — models/feeder.py).  ``finalize`` scans what is left, as one
    request at its own segment class, and only then forces the last cut.
    The cuts are those of a whole-stream scan however the writes fall:
    candidates are position-local (63 bytes of history) and
    ``select_cuts`` is handed the bytes scanned so far as the stream's
    length, so a cut is only ever returned later than a per-write scan
    would have returned it, never elsewhere.

    Memory: the un-scanned bytes are references to the writes
    themselves, which the writer's ``_ChunkBuffer`` holds anyway (up to
    ``max_size``) — under one segment a stream; a segment that spans
    writes is joined into one array for the time of its scan."""

    # per-session bound-backend label (transfer._ChunkedStream picks it
    # up at bind time; rendered in job stats and /metrics)
    backend_name = "tpu"

    def __init__(self, params: ChunkerParams):
        self.params = params
        self._tail = np.zeros(WINDOW - 1, dtype=np.uint8)
        self._seen = 0                  # bytes scanned
        # writes not yet scanned, in order: under SCAN_SEGMENT bytes
        # whenever ``feed`` returns
        self._parts: deque[np.ndarray] = deque()
        self._pending = 0
        self._req_feeds = 1             # writes the request under way holds
        self._chunk_start = 0
        self._cand: list[int] = []
        self._cand_drained = 0
        self._finalized = False

    def _candidates(self, data: np.ndarray) -> np.ndarray:
        """Absolute candidate ends for the bytes of ``data``, which
        directly follow the bytes already scanned: one request to the
        feeder, of at most ``SCAN_SEGMENT`` bytes."""
        from .feeder import get_feeder
        try:
            hits = get_feeder().candidate_hits(data, self._tail, self.params,
                                               feeds=self._req_feeds)
        except Exception as e:
            raise DeviceDispatchError("candidate scan", e) from e
        valid = hits + self._seen >= WINDOW - 1
        return hits[valid] + 1 + self._seen

    def _take(self, n: int) -> tuple[np.ndarray, int]:
        """The first ``n`` un-scanned bytes as one array (no copy where
        one write holds them all), and how many writes they came from."""
        out, got = [], 0
        while got < n:
            part = self._parts.popleft()
            if len(part) > n - got:
                self._parts.appendleft(part[n - got:])
                part = part[:n - got]
            out.append(part)
            got += len(part)
        self._pending -= n
        return (out[0] if len(out) == 1 else np.concatenate(out)), len(out)

    def _scan(self, n: int) -> None:
        """The first ``n`` un-scanned bytes go to the device, as one
        request."""
        # ``_candidates`` keeps its one argument (the benchmark's control
        # wraps it), so the count rides on the chunker
        seg, self._req_feeds = self._take(n)
        self._cand.extend(self._candidates(seg).tolist())
        self._seen += n
        # the history of the next request is the end of this one
        self._tail = np.concatenate([self._tail, seg[-(WINDOW - 1):]]
                                    )[-(WINDOW - 1):]

    def feed(self, data: bytes) -> list[int]:
        if self._finalized:
            raise RuntimeError("chunker already finalized")
        if not data:
            return []
        self._parts.append(np.frombuffer(data, dtype=np.uint8))
        self._pending += len(data)
        if self._pending < SCAN_SEGMENT:
            return []
        while self._pending >= SCAN_SEGMENT:
            self._scan(SCAN_SEGMENT)
        return self._drain(final=False)

    def finalize(self) -> list[int]:
        if self._finalized:
            return []
        self._finalized = True
        if self._pending:
            self._scan(self._pending)
        return self._drain(final=True)

    def _drain(self, final: bool) -> list[int]:
        pending = np.array(self._cand[self._cand_drained:], dtype=np.int64)
        cuts = select_cuts(pending, self._seen, self.params,
                           start=self._chunk_start, final=final)
        if cuts:
            self._chunk_start = cuts[-1]
            # advance the drained pointer past consumed candidates
            k = self._cand_drained
            while k < len(self._cand) and self._cand[k] <= self._chunk_start:
                k += 1
            self._cand_drained = k
        return cuts
