"""The store stage's helper threads: a hash batch's novel chunks stored
several at once (ROADMAP S8 2).

A chunk's insert is a shard lock, a scalar index ask, zstd, a file's
write and its rename, and the index's insert.  zstd and the file calls
release the interpreter lock, so a writer that hands a batch's novel
chunks to a few threads and stores with them finishes the batch sooner.
The stream writer (pxar/transfer.py ``_ChunkedStream._store_fanned``)
does so only on a store that declares ``concurrent_insert``
(pxar/ingestbackend.py): the sharded ``ChunkStore`` with the similarity
tier off.  A remote PBS sink, the similarity tier and the pipelined
writer keep one insert at a time.

One pool serves the process.  Its threads are helpers: the writer
stores too, from the same list, so a writer whose helpers are busy with
other sessions' batches is no slower than it was alone.
"""

from __future__ import annotations

import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from ..utils import trace

# Threads that store one hash batch's novel chunks at once, the writer
# among them, on a host with the cores for it.  Past this width the
# index's scalar work, which holds the interpreter lock, bounds the
# gain: on a 13-core TPU v5e host a 64 KiB chunk's store cost the writer
# 1.43, 0.88, 0.71 and 0.66 ms at 1, 2, 4 and 8 threads
# (tools/store_fanout_cost.py; PERF.md).  The rest of the host's cores
# belong to the other sessions' writers, the event loop and the batcher.
_STORE_THREADS = 4
_pool_lock = threading.Lock()
_pool: "ThreadPoolExecutor | None" = None   # guarded-by: _pool_lock


def store_helpers() -> int:
    """Helper threads one flush may engage beside its writer, and the
    threads of the process's pool: a rule on the host's cores."""
    return max(0, min(_STORE_THREADS, os.cpu_count() or 1) - 1)


def _helper_pool() -> ThreadPoolExecutor:
    """The process's pool, made at the first flush that fans out
    (importing this module starts no thread)."""
    global _pool
    with _pool_lock:
        if _pool is None:
            _pool = ThreadPoolExecutor(max_workers=max(1, store_helpers()),
                                       thread_name_prefix="store-helper")
        return _pool


class StoreFanOut:
    """One flush's novel chunks, stored by its writer and by up to
    ``helpers`` threads of the pool at once: each takes the next chunk
    of the list until none is left ("caller runs").  ``join`` (the
    writer) takes part, then waits until every insert begun has
    returned, and raises the first exception one raised; after an
    exception no further chunk is taken.  ``insert`` is the store's
    own, called once a chunk as ``insert(digest, chunk, verify=False)``.
    What the helpers' inserts counted on their threads
    (``trace.tally``: the index's asks and inserts) is gathered in
    ``counts`` for the writer to add to its own clock, with ``helped``
    (chunks a helper stored) and ``helper_s`` (their seconds inside
    ``insert``)."""

    __slots__ = ("_insert", "_items", "_ctx", "_cv", "_next", "_busy",
                 "new", "error", "helped", "helper_s", "counts")

    def __init__(self, insert, items: "list[tuple[bytes, object]]"):
        self._insert = insert
        self._items = items
        self._ctx = trace.capture()
        self._cv = threading.Condition()
        self._next = 0                      # guarded-by: self._cv
        self._busy = 0                      # guarded-by: self._cv
        self.new = [False] * len(items)     # guarded-by: self._cv
        self.error: "BaseException | None" = None   # guarded-by: self._cv
        self.helped = 0                     # guarded-by: self._cv
        self.helper_s = 0.0                 # guarded-by: self._cv
        self.counts: dict = {}              # guarded-by: self._cv

    def start(self, helpers: int) -> None:
        try:
            pool = _helper_pool()
            for _ in range(helpers):
                pool.submit(self._help)
        except RuntimeError:
            # the interpreter is shutting down: the writer stores alone
            pass

    def _take_locked(self) -> int:
        i = self._next
        if self.error is not None or i >= len(self._items):
            return -1
        self._next = i + 1
        self._busy += 1
        return i

    def _take(self) -> int:
        with self._cv:
            return self._take_locked()

    def _run(self, i: int, tallied: "dict | None") -> None:
        """Store chunk ``i`` and the next ones taken; ``tallied`` is a
        helper's own counts, None on the writer's thread."""
        while i >= 0:
            digest, chunk = self._items[i]
            t0 = time.perf_counter()
            err = None
            try:
                new = self._insert(digest, chunk, verify=False)
            except BaseException as e:      # re-raised by join()
                err, new = e, False
            dt = time.perf_counter() - t0
            with self._cv:
                self._busy -= 1
                self.new[i] = new
                if err is not None and self.error is None:
                    self.error = err
                if tallied is not None:
                    self.helped += 1
                    self.helper_s += dt
                    for key, n in tallied.items():
                        self.counts[key] = self.counts.get(key, 0) + n
                    tallied.clear()
                i = self._take_locked()
                if not self._busy:
                    self._cv.notify_all()

    def _help(self) -> None:
        # a clock of its own, for what the inserts tally on this thread;
        # a helper that starts after the writer took every chunk stores
        # nothing
        tallied: dict = {}
        with trace.attached(self._ctx), \
                trace.clocked(trace.ThreadClock(counts=tallied)):
            self._run(self._take(), tallied)

    def _wait_locked(self) -> None:
        while self._busy:
            self._cv.wait()

    def cancel(self) -> None:
        """Take no further chunk, and wait for those begun (the writer
        failed on its own)."""
        with self._cv:
            self._next = len(self._items)
            self._wait_locked()

    def join(self) -> None:
        self._run(self._take(), None)
        with self._cv:
            self._wait_locked()
            if self.error is not None:
                raise self.error
