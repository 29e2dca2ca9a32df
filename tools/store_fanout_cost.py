#!/usr/bin/env python3
"""What a hash batch's store stage costs the writer as it fans out over
1, 2, 4 and 8 threads (ROADMAP S8 2; pxar/storepool.py).

    python3 tools/store_fanout_cost.py [MiB] [rounds]

A ``_ChunkedStream`` at ``index-at-size``'s chunking (``--chunk-size
64``: 64 KiB average, hash batches of 16 MiB) writes ``MiB`` (default
96) of dump-like bytes — blocks of random bytes and of 4-bit symbols,
half each, so zstd halves half of them — into a ``ChunkStore`` whose
dedup index has a 2 GiB table holding 2,621,440 digests, as the cell's
does after its preload (a fresh table would pay a page fault an insert),
on the writer's thread clock.  A width is the threads that store one
batch's novel chunks, the writer among them
(``storepool._STORE_THREADS``; the pool is made anew for each).  Every
run writes bytes of its own into the one store, so every chunk is new.
For each width, medians over ``rounds`` (default 3, the widths taken in
turn): the writer's ``store_s`` a chunk in ms, its whole life a MiB in
ms, and the share of chunks a helper stored.  One JSON line on stdout.
It runs in no cell.

Only host work is measured: the chunk files go where ``TMPDIR`` says
(where the benchmark keeps its datastore), and jax is held to the CPU
so that nothing here reaches for a chip.  ``os.cpu_count()`` is in the
line: the rule that sizes the pool reads it.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

WIDTHS = (1, 2, 4, 8)
BLOCK = 1 << 20

PRELOAD = 2_621_440


def _data(seed: int, mib: int) -> list:
    rng = np.random.default_rng(seed)
    out = []
    for i in range(mib):
        if i % 2:
            out.append(rng.integers(0, 16, BLOCK, dtype=np.uint8).tobytes())
        else:
            out.append(rng.bytes(BLOCK))
    return out


def _store(work: str):
    from pbs_plus_tpu.pxar.datastore import ChunkStore
    store = ChunkStore(work, index_budget_mb=2048, n_shards=16)
    raw = np.random.default_rng(38).bytes(32 * PRELOAD)
    store.index.insert_many([raw[i:i + 32]
                             for i in range(0, len(raw), 32)])
    return store


def _one(store, width: int, blocks: list) -> dict:
    from pbs_plus_tpu.chunker import ChunkerParams
    from pbs_plus_tpu.pxar import storepool, transfer
    from pbs_plus_tpu.utils import trace
    storepool._STORE_THREADS = width
    if storepool._pool is not None:
        storepool._pool.shutdown(wait=True)
        storepool._pool = None
    stream = transfer._ChunkedStream(
        store, ChunkerParams(avg_size=64 << 10),
        batch_hasher=lambda cs: [hashlib.sha256(c).digest() for c in cs])
    clock = trace.ThreadClock(label="writer")
    t0 = time.perf_counter()
    with trace.clocked(clock):
        for b in blocks:
            stream.write(b)
        stream.finish()
    wall = time.perf_counter() - t0
    chunks = stream.stats.new_chunks
    return {"store_ms_per_chunk":
            1e3 * clock.seconds.get("store_s", 0.0) / chunks,
            "life_ms_per_mib": 1e3 * wall / len(blocks),
            "helped_pct": 100.0 * clock.counts.get(
                "store_pool_chunks", 0) / chunks,
            "chunks": chunks}


def main() -> int:
    mib = int(sys.argv[1]) if len(sys.argv) > 1 else 96
    rounds = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    work = tempfile.mkdtemp(prefix="store-fanout-")
    try:
        t0 = time.perf_counter()
        store = _store(work)
        preload_s = time.perf_counter() - t0
        runs: dict = {w: [] for w in WIDTHS}
        for r in range(rounds):
            for w in WIDTHS:
                runs[w].append(_one(store, w, _data(100 * r + w, mib)))
    finally:
        shutil.rmtree(work, ignore_errors=True)
    out = {"cpu_count": os.cpu_count(), "mib": mib, "rounds": rounds,
           "preload_s": preload_s, "widths": {}}
    for w, rs in runs.items():
        row = {k: statistics.median(r[k] for r in rs)
               for k in ("store_ms_per_chunk", "life_ms_per_mib",
                         "helped_pct")}
        row["store_ms_per_chunk_all"] = [r["store_ms_per_chunk"]
                                         for r in rs]
        row["chunks"] = rs[0]["chunks"]
        out["widths"][str(w)] = row
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
