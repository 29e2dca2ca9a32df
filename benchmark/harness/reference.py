"""The plain reference, and the comparison that decides ``correct``.

The reference is a copy of the repository's scalar specification
(``pbs_plus_tpu/chunker/spec.py``: buzhash32 over a sliding window of 64
bytes, nibble subtables from splitmix64, greedy min/max cut selection)
written out in plain numpy, with ``hashlib`` for SHA-256.  It imports
nothing of the program and takes nothing the program computed: its
inputs are the bytes of the streams a job published (read back chunk by
chunk) and the source trees the benchmark itself made from the seed.

What is compared, for every job the window enqueued — each an exact
comparison, limit 0:

- ``cut_mismatches``     streams (payload, metadata) whose published chunk
                         ends differ from the reference's cuts over the
                         same bytes
- ``digest_mismatches``  chunks whose published digest is not the SHA-256
                         of the chunk's bytes
- ``content_mismatches`` files of the source trees whose bytes are not in
                         the published payload stream where the archive
                         says they are (a missing file counts)
- ``new_known_gap``      |new - reference's| + |known - reference's|,
                         summed over all jobs: a chunk is new exactly
                         when no earlier chunk of the datastore had its
                         digest
- ``spliced_chunks``     chunks taken by reference from a previous
                         snapshot: every job is a first generation
- ``jobs_not_published`` jobs that ended other than in success
- ``restore_mismatch``   1 unless one snapshot, drawn from the seed and
                         restored through the program's restore job, is
                         byte-identical to its tree
- ``uncounted_commit_bytes``  the rate's numerator held to the result:
                         bytes of the chunks the store took from ``t0`` to
                         the last publish, less the bytes of the streams
                         those jobs published (plus commits of a digest
                         no index holds) — 0 when the count saw all the
                         work and nothing twice

``control_cuts`` is the control: the reference with the last doubling
pass of the window left out (a 32-byte window, the cheaper scan that
would tempt a later PR).  Put in the program's place it has to come out
not correct; ``benchmark/control.py`` runs it on the chip.
"""

from __future__ import annotations

import filecmp
import hashlib
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

WINDOW = 64
MAGIC_BASE = 0x5BC0FFEE
TABLE_SEED = 0x7069_7861_7274_7075
_M64 = (1 << 64) - 1
SCAN_BLOCK = 64 << 10          # cache-resident: 7x the rate of 8 MiB blocks

LIMITS = {"cut_mismatches": 0, "digest_mismatches": 0,
          "content_mismatches": 0, "new_known_gap": 0,
          "spliced_chunks": 0, "jobs_not_published": 0,
          "restore_mismatch": 0, "uncounted_commit_bytes": 0}


# -- the specification, in plain numpy ---------------------------------------

def _splitmix64(state: int) -> tuple[int, int]:
    state = (state + 0x9E3779B97F4A7C15) & _M64
    z = state
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _M64
    return state, (z ^ (z >> 31)) & _M64


def buzhash_table(seed: int = TABLE_SEED) -> np.ndarray:
    """T[x] = A[x >> 4] ^ B[x & 15], A and B sixteen splitmix64 words."""
    s, words = seed, []
    for _ in range(32):
        s, v = _splitmix64(s)
        words.append(v & 0xFFFFFFFF)
    a = np.array(words[:16], dtype=np.uint32)
    b = np.array(words[16:], dtype=np.uint32)
    x = np.arange(256)
    return (a[x >> 4] ^ b[x & 0xF]).astype(np.uint32)


def _rotl(x: np.ndarray, r: int) -> np.ndarray:
    r &= 31
    if r == 0:
        return x
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def _window_hashes(block: np.ndarray, table: np.ndarray,
                   window: int) -> np.ndarray:
    """h(i) = XOR_{k<window} rotl32(T[b[i-k]], k mod 32) at every
    position of ``block`` (positions short of a whole window are
    garbage and the caller drops them), by doubling."""
    h = table[block]
    m = 1
    while m < window:
        shifted = np.zeros_like(h)
        shifted[m:] = h[:-m]
        h = h ^ _rotl(shifted, m)
        m *= 2
    return h


def candidate_ends(stream: np.ndarray, avg: int, *,
                   window: int = WINDOW) -> np.ndarray:
    """Sorted end offsets (cut after byte i -> i + 1) of every position
    whose window hash matches, over the whole stream, block by block
    with a halo of ``window - 1`` bytes."""
    table = buzhash_table()
    mask = np.uint32(avg - 1)
    magic = np.uint32(MAGIC_BASE & (avg - 1))
    out = []
    halo = window - 1
    for lo in range(0, len(stream), SCAN_BLOCK):
        start = max(0, lo - halo)
        h = _window_hashes(stream[start:lo + SCAN_BLOCK], table, window)
        hit = np.nonzero((h & mask) == magic)[0] + start
        out.append(hit[(hit >= lo) & (hit >= halo)] + 1)
    return np.concatenate(out) if out else np.empty(0, np.int64)


def select_cuts(cand: np.ndarray, total_len: int, avg: int) -> list[int]:
    """Greedy: from chunk start s, cut at the first candidate end e with
    avg/4 <= e - s <= 4*avg; with none before s + 4*avg, cut there; the
    tail is the last chunk."""
    lo, hi = avg // 4, avg * 4
    cuts: list[int] = []
    s, idx = 0, 0
    while True:
        idx = int(np.searchsorted(cand, s + lo, side="left"))
        if idx < len(cand) and cand[idx] <= s + hi \
                and cand[idx] <= total_len:
            s = int(cand[idx])
        elif s + hi <= total_len:
            s = s + hi
        else:
            break
        cuts.append(s)
    if s < total_len:
        cuts.append(total_len)
    return cuts


def reference_cuts(stream: np.ndarray, avg: int) -> list[int]:
    return select_cuts(candidate_ends(stream, avg), len(stream), avg)


def control_cuts(stream: np.ndarray, avg: int) -> list[int]:
    """The control: one doubling pass fewer (a 32-byte window)."""
    return select_cuts(candidate_ends(stream, avg, window=WINDOW // 2),
                       len(stream), avg)


# -- what a job published ------------------------------------------------------

class Published:
    """One job's snapshot as the program published it: per stream the
    chunk ends and digests, the chunks' bytes, the archive's file table
    and the manifest's counters."""

    def __init__(self, job_id: str, tree_path: str, streams: dict,
                 files: dict, stats: dict):
        self.job_id = job_id
        self.tree_path = tree_path
        self.streams = streams        # name -> (ends, digests, get_chunk)
        self.files = files            # relative path -> (offset, size)
        self.stats = stats            # new_chunks, known_chunks, ref_chunks


def read_published(server, job) -> Published:
    """Through the program's reader: the indexes, a chunk getter, the
    entries with their payload offsets, and the manifest's counters."""
    from pbs_plus_tpu.pxar.datastore import parse_snapshot_ref
    ref = parse_snapshot_ref(job.snapshot)
    reader = server.datastore.open_snapshot(ref)
    chunks = server.datastore.datastore.chunks
    streams = {}
    for name, ix in (("payload", reader.payload_index),
                     ("meta", reader.meta_index)):
        streams[name] = ([int(e) for e in ix.ends],
                         [ix.digest(i) for i in range(len(ix.ends))],
                         chunks.get)
    files = {e.path: (e.payload_offset, e.size)
             for e in reader.entries() if e.is_file}
    man = server.datastore.datastore.load_manifest(ref)
    return Published(job.job_id, job.tree_path, streams, files,
                     dict(man["stats"]))


def compare_job(pub: Published, avgs: dict, cuts_of=reference_cuts) -> dict:
    """One job against the reference; returns its counts and the digests
    in publication order (for the new/known reckoning)."""
    out = {"cut_mismatches": 0, "digest_mismatches": 0,
           "content_mismatches": 0, "chunks": 0, "bytes": 0,
           "digests": []}
    payload = None
    for name, (ends, digests, get_chunk) in pub.streams.items():
        parts = []
        for d in digests:
            try:
                part = get_chunk(d)
            except Exception:       # the store refuses its own chunk
                part = b""
            parts.append(part)
            if hashlib.sha256(part).digest() != d:
                out["digest_mismatches"] += 1
        stream = np.frombuffer(b"".join(parts), dtype=np.uint8)
        if cuts_of(stream, avgs[name]) != ends:
            out["cut_mismatches"] += 1
        out["chunks"] += len(ends)
        out["bytes"] += len(stream)
        out["digests"] += digests
        if name == "payload":
            payload = stream
    for dirpath, _, names in os.walk(pub.tree_path):
        for n in names:
            full = os.path.join(dirpath, n)
            rel = os.path.relpath(full, pub.tree_path)
            where = pub.files.get(rel)
            with open(full, "rb") as f:
                data = f.read()
            if where is None or where[1] != len(data) or \
                    payload[where[0]:where[0] + where[1]].tobytes() != data:
                out["content_mismatches"] += 1
    return out


def same_tree(a: str, b: str) -> bool:
    """Byte for byte: the same relative paths, each with equal content."""
    def files(root):
        return sorted(os.path.relpath(os.path.join(dp, f), root)
                      for dp, _, fs in os.walk(root) for f in fs)
    names = files(a)
    return names == files(b) and all(
        filecmp.cmp(os.path.join(a, n), os.path.join(b, n), shallow=False)
        for n in names)


def compare(published: list[Published], *, known_before: set, avgs: dict,
            jobs_not_published: int, restore_mismatch: int,
            uncounted_commit_bytes: int = 0,
            cuts_of=reference_cuts, threads: int = 8) -> dict:
    """Every number compared, beside its limit, and the verdict."""
    with ThreadPoolExecutor(max_workers=threads) as pool:
        per_job = list(pool.map(
            lambda p: compare_job(p, avgs, cuts_of), published))
    values = {k: sum(j[k] for j in per_job)
              for k in ("cut_mismatches", "digest_mismatches",
                        "content_mismatches")}
    # new/known: publication order within a job, any order across jobs —
    # which job stores a shared chunk first is a race, the totals are not
    seen = set(known_before)
    ref_new = 0
    for j in per_job:
        for d in j["digests"]:
            if d not in seen:
                seen.add(d)
                ref_new += 1
    ref_known = sum(j["chunks"] for j in per_job) - ref_new
    new = sum(p.stats["new_chunks"] for p in published)
    known = sum(p.stats["known_chunks"] for p in published)
    values["new_known_gap"] = abs(new - ref_new) + abs(known - ref_known)
    values["spliced_chunks"] = sum(p.stats["ref_chunks"] for p in published)
    values["jobs_not_published"] = jobs_not_published
    values["restore_mismatch"] = restore_mismatch
    values["uncounted_commit_bytes"] = uncounted_commit_bytes
    compared = {k: {"value": values[k], "limit": LIMITS[k]} for k in LIMITS}
    return {"correct": all(values[k] <= LIMITS[k] for k in LIMITS),
            "compared": compared,
            "seen": {"jobs": len(published),
                     "chunks": sum(j["chunks"] for j in per_job),
                     "bytes": sum(j["bytes"] for j in per_job),
                     "new": new, "known": known,
                     "reference_new": ref_new,
                     "reference_known": ref_known}}
