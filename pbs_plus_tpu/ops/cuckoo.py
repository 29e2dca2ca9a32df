"""On-device chunk-index probe: vmap'd cuckoo lookups.

Reference role: the server's chunk-index lookup — "only globally-novel
chunks ever hit the datastore" (BASELINE.json north star; the reference
does this inside the pxar library's dedup store, consumed at
/root/reference/internal/pxarmount/commit_orchestrate.go:236-242).

Design: cuckoo-filter style two-choice hashing.  The device table holds
64-bit fingerprints (digest words 0..1) in ``uint32[n_buckets, SLOTS, 2]``;
bucket₁ = digest word 2 masked, bucket₂ = bucket₁ ^ mix(fingerprint).
Lookups are a fully-parallel gather+compare per digest (vmap over the
batch).  Inserts run on a host-side numpy mirror (single-writer, matching
the reference's async single-writer index update queue, SURVEY §2.10) with
cuckoo eviction + table growth; ``device_table`` re-uploads after a batch
of inserts.  The host dict stays authoritative — a 64-bit-fingerprint
false positive (~2⁻⁶⁴ per probe) is confirmed against it before a chunk
upload is skipped.

What that re-upload costs at a deployment's table (PERF.md, PR 36, cell
``index-at-size.serial``: a 2 GiB table in HBM, 64 KiB chunks, ~211
digests a probe, one TPU v5e and its 13-core host): a probe with a clean
table is 1.6-1.8 ms of host clock, 0.9 ms of it round the program; the
host twin answers the same 211 digests from the mirror in 0.10 ms; and
the table's copy after an insert takes 0.22 s — the host's threads
first relay ``uint32[NB, 4, 2]`` out into the device's tiling
(``Transpose`` in a profile: 150 MB of trace a copy; the same bytes
sent flat need none) — which every flush of a volume of new chunks
pays: 67 copies a 1,085 MiB volume, a third of the writer thread's life
(ROADMAP S7).  ``_lookup`` is one program a (table
shape, probe class); ``warm_lookups`` builds them from shapes alone,
ahead of the writers (``DedupIndex._warm_lookups``).
"""

from __future__ import annotations

import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import jaxenv, trace
from ..utils.log import L

jaxenv.watch_compiles()

SLOTS = 4
_MIX = np.uint32(0x9E3779B1)
_MAX_KICKS = 500
BUCKET_BYTES = SLOTS * 2 * 4        # uint32[SLOTS, 2] per bucket
# padded digest counts of a device probe: 64, 256, 1024, … (powers of four)
_PROBE_CLASSES = tuple(1 << k for k in range(6, 31, 2))


# device probes, mirror of rolling_hash.stats: ``probes`` digests asked in
# ``dispatches`` lookups (``bytes`` of digests, ``padded_bytes`` after
# padding to a probe class), the table copied to the device for a probe
# ``table_uploads`` times — whole, after any insert — and the five phase
# clocks; the table's upload is inside ``h2d_s``.  Probes run on the
# writers' threads, several at once: a trip adds to them under the lock.
stats = trace.device_stats("probe", {
    "dispatches": 0, "probes": 0, "bytes": 0, "padded_bytes": 0,
    "table_uploads": 0, "table_upload_bytes": 0})
_stats_lock = threading.Lock()


def buckets_for_bytes(budget_bytes: int, *, minimum: int = 1 << 10) -> int:
    """Largest power-of-two bucket count whose table fits the budget
    (the PBS_PLUS_DEDUP_INDEX_MB sizing rule in pxar/chunkindex.py)."""
    nb = minimum
    while nb * 2 * BUCKET_BYTES <= budget_bytes:
        nb *= 2
    return nb


def _digest_words(digests: np.ndarray | jax.Array):
    """digests uint8[N,32] → (fp0, fp1, idx) uint32[N] each."""
    if isinstance(digests, np.ndarray):
        w = digests.reshape(-1, 8, 4).astype(np.uint32)
        word = (w[..., 0] << 24) | (w[..., 1] << 16) | (w[..., 2] << 8) | w[..., 3]
        return word[:, 0], word[:, 1], word[:, 2]
    w = digests.reshape(-1, 8, 4).astype(jnp.uint32)
    word = (w[..., 0] << np.uint32(24)) | (w[..., 1] << np.uint32(16)) \
        | (w[..., 2] << np.uint32(8)) | w[..., 3]
    return word[:, 0], word[:, 1], word[:, 2]


def lookup_host(table: np.ndarray, digests: np.ndarray) -> np.ndarray:
    """numpy twin of ``_lookup`` over the host mirror: table
    uint32[NB, SLOTS, 2]; digests uint8[N, 32] → bool[N].  CPU-only
    hosts probe this path directly — no device round-trip, no jit — and
    the device/numpy parity gate in tests/test_dedupindex.py pins the
    two implementations bit-identical.

    Hot-path formulation: digest words come from a big-endian u32 view
    (one vectorized byteswap of 3 words/digest instead of 4 shifts + 3
    ors over all 8), and the (fp0, fp1) pair compares as ONE u64 per
    slot via a view of the table — half the gathers and compares of the
    naive twin."""
    nb = table.shape[0]
    if not digests.flags.c_contiguous:
        digests = np.ascontiguousarray(digests)
    w = digests.view(">u4")             # [N, 8] big-endian words
    fp0 = w[:, 0].astype(np.uint32)
    fp1 = w[:, 1].astype(np.uint32)
    bidx = w[:, 2].astype(np.uint32)
    fp0 = np.where((fp0 == 0) & (fp1 == 0), np.uint32(0x5A5A5A5A), fp0)
    mask = np.uint32(nb - 1)
    b1 = bidx & mask
    b2 = b1 ^ ((fp0 * _MIX) & mask)
    # little-endian slot memory [fp0, fp1] == u64 fp0 | fp1<<32
    t64 = table.view(np.uint64).reshape(nb, SLOTS)
    fpc = fp0.astype(np.uint64) | (fp1.astype(np.uint64) << np.uint64(32))
    hit = (t64[b1] == fpc[:, None]).any(axis=1)
    hit |= (t64[b2] == fpc[:, None]).any(axis=1)
    return hit


@functools.partial(jax.jit, static_argnames=())
def _lookup(table: jax.Array, digests: jax.Array) -> jax.Array:
    """table uint32[NB, SLOTS, 2]; digests uint8[N,32] → bool[N]."""
    nb = table.shape[0]
    fp0, fp1, bidx = _digest_words(digests)
    fp0 = jnp.where((fp0 == 0) & (fp1 == 0), jnp.uint32(0x5A5A5A5A), fp0)
    mask = jnp.uint32(nb - 1)
    b1 = bidx & mask
    b2 = b1 ^ ((fp0 * _MIX) & mask)
    s1 = table[b1]                      # [N, SLOTS, 2]
    s2 = table[b2]
    hit1 = jnp.any((s1[..., 0] == fp0[:, None]) & (s1[..., 1] == fp1[:, None]), axis=1)
    hit2 = jnp.any((s2[..., 0] == fp0[:, None]) & (s2[..., 1] == fp1[:, None]), axis=1)
    return hit1 | hit2


# ``_lookup`` built ahead of a writer's first probe, by (buckets, probe
# class): a table of another shape and every probe class is a program of
# its own, and one that compiles inside a probe stops a backup for as
# long as it takes.  A shape nobody built ahead still compiles at the
# probe, as before (``round_trip`` warns).
_programs: dict = {}


def _build_lookups(n_buckets: int, classes) -> None:
    for rows in classes:
        key = (n_buckets, rows)
        if key in _programs:
            continue
        try:
            _programs[key] = _lookup.lower(
                jax.ShapeDtypeStruct((n_buckets, SLOTS, 2), jnp.uint32),
                jax.ShapeDtypeStruct((rows, 32), jnp.uint8)).compile()
        except Exception as e:      # the probe then compiles for itself
            L.warning("device.probe rows=%d buckets=%d not built ahead: %s",
                      rows, n_buckets, e)


def warm_lookups(n_buckets: int, classes) -> threading.Thread:
    """Build (or load from the persistent cache) the lookup programs of a
    table of ``n_buckets`` at the probe classes ``classes``, from shapes
    alone — no table, no lock — on a thread of its own, which is
    returned: the caller's thread goes on."""
    t = threading.Thread(target=_build_lookups, args=(n_buckets, classes),
                         name="index-warm", daemon=True)
    t.start()
    return t


def _probe_class(n: int) -> int:
    """The padded digest count of a probe of ``n`` digests."""
    return next(c for c in _PROBE_CLASSES if c >= n)


def probe_classes_upto(n: int) -> tuple:
    """The probe classes batches of 1..``n`` digests are padded to."""
    return tuple(c for c in _PROBE_CLASSES if c <= _probe_class(n))


class CuckooIndex:
    """Chunk-presence index: device-probe, host-authoritative."""

    def __init__(self, n_buckets: int = 1 << 16, seed: int = 0):
        if n_buckets & (n_buckets - 1):
            raise ValueError("n_buckets must be a power of two")
        self.n_buckets = n_buckets
        self._table = np.zeros((n_buckets, SLOTS, 2), dtype=np.uint32)
        self._device_table: jax.Array | None = None
        self._dirty = True
        self._known: set[bytes] = set()       # authoritative
        self._rng = np.random.default_rng(seed)
        # filter-only mode (the spillable exact tier, pxar/digestlog.py):
        # membership truth lives OUTSIDE this object, `_known` stays
        # empty, `_n_fp` counts resident fingerprints for the growth
        # trigger, and growth rebuilds stream every live digest back
        # from the attached source instead of an in-RAM set
        self._n_fp = 0
        self._digest_source = None

    # -- host authoritative ----------------------------------------------
    def __len__(self) -> int:
        return len(self._known)

    def contains_exact(self, digest: bytes) -> bool:
        return digest in self._known

    def _fp_bucket(self, digest: bytes) -> tuple[int, int, int, int]:
        d = np.frombuffer(digest, dtype=np.uint8)[None]
        fp0, fp1, bidx = _digest_words(d)
        fp0, fp1, bidx = int(fp0[0]), int(fp1[0]), int(bidx[0])
        if fp0 == 0 and fp1 == 0:
            fp0 = 0x5A5A5A5A
        mask = self.n_buckets - 1
        b1 = bidx & mask
        b2 = b1 ^ ((fp0 * int(_MIX)) & 0xFFFFFFFF & mask)
        return fp0, fp1, b1, b2

    def insert(self, digest: bytes) -> bool:
        """Insert; returns False if already present."""
        if digest in self._known:
            return False
        self._known.add(digest)
        fp0, fp1, b1, b2 = self._fp_bucket(digest)
        self._insert_fp(fp0, fp1, b1, b2)
        self._dirty = True
        return True

    def discard(self, digest: bytes) -> bool:
        """Remove a digest (GC sweep coherence: a swept chunk must leave
        the filter).  Returns False if it was never present.  The table
        slot is zeroed when the fingerprint is found in either bucket; a
        fingerprint shared with ANOTHER digest (same fp+bucket pair,
        ~2⁻⁶⁴) keeps its own slot, and at worst a removal turns into a
        false NEGATIVE for that twin — which is safe: a false negative
        re-stores a chunk that exists, never skips one that doesn't."""
        if digest not in self._known:
            return False
        self._known.discard(digest)
        fp0, fp1, b1, b2 = self._fp_bucket(digest)
        for b in (b1, b2):
            row = self._table[b]
            for s in range(SLOTS):
                if row[s, 0] == fp0 and row[s, 1] == fp1:
                    row[s] = (0, 0)
                    self._dirty = True
                    return True
        # fingerprint not in the mirror (dropped during an eviction
        # overflow before a growth rebuild): the authoritative set is
        # already updated, so membership answers stay correct
        self._dirty = True
        return True

    def discard_many(self, digests) -> int:
        n = 0
        for d in digests:
            if self.discard(d):
                n += 1
        return n

    def probe_host(self, digests: np.ndarray) -> np.ndarray:
        """Batched maybe-present over the host mirror (numpy, no device):
        digests uint8[N,32] → bool[N].  The CPU-only probe path of
        ``probe``; confirm hits via ``contains_exact`` before skipping
        an upload."""
        return lookup_host(self._table, digests)

    def _insert_fp(self, fp0: int, fp1: int, b1: int, b2: int,
                   *, grow: bool = True) -> bool:
        for b in (b1, b2):
            row = self._table[b]
            for s in range(SLOTS):
                if row[s, 0] == 0 and row[s, 1] == 0:
                    row[s] = (fp0, fp1)
                    return True
        # eviction chain
        b = b1
        cur = np.array([fp0, fp1], dtype=np.uint32)
        for _ in range(_MAX_KICKS):
            s = int(self._rng.integers(0, SLOTS))
            victim = self._table[b, s].copy()
            self._table[b, s] = cur
            cur = victim
            vfp0 = int(cur[0])
            mask = self.n_buckets - 1
            b = b ^ ((vfp0 * int(_MIX)) & 0xFFFFFFFF & mask)
            row = self._table[b]
            for s2 in range(SLOTS):
                if row[s2, 0] == 0 and row[s2, 1] == 0:
                    row[s2] = cur
                    return True
        if not grow:
            # mid-rebuild overflow: the rebuild loop doubles and retries
            # from a fresh source pass (the displaced fingerprint is
            # re-placed there — its digest is in the source)
            return False
        self._grow()
        # nothing left to re-place: _grow()'s rebuild covered every
        # digest (the in-RAM set, or the attached source — callers add
        # the digest to the source BEFORE inserting its fingerprint)
        return True

    def _grow(self) -> None:
        self.n_buckets *= 2
        self._rebuild_bulk()

    # -- filter-only surface (spillable exact tier) ------------------------
    def attach_digest_source(self, source) -> None:
        """Enter filter-only mode: ``source()`` must yield every LIVE
        digest (pxar/digestlog.py's merged view) — growth rebuilds
        stream it instead of an in-RAM ``_known`` set."""
        self._digest_source = source

    def maybe_contains(self, digest: bytes) -> bool:
        """Scalar filter lookup (maybe-present; the caller confirms a
        positive against the exact tier before any dedup skip)."""
        fp0, fp1, b1, b2 = self._fp_bucket(digest)
        for b in (b1, b2):
            row = self._table[b]
            for s in range(SLOTS):
                if row[s, 0] == fp0 and row[s, 1] == fp1:
                    return True
        return False

    def insert_fp(self, digest: bytes) -> None:
        """Insert ONE fingerprint (filter-only mode; caller already
        recorded the digest in the exact tier, so a growth rebuild
        finds it in the source)."""
        self._n_fp += 1
        if self._n_fp > self.n_buckets * SLOTS * 0.85:
            self._grow()
        else:
            fp0, fp1, b1, b2 = self._fp_bucket(digest)
            self._insert_fp(fp0, fp1, b1, b2)
        self._dirty = True

    def insert_fp_many(self, digests: "list[bytes]") -> None:
        """Bulk fingerprint insert (filter-only mode): group-wise free
        slot placement, eviction chains only for the overflow tail —
        the ``insert_many`` machinery without the membership set."""
        if not digests:
            return
        self._n_fp += len(digests)
        grew = False
        while self._n_fp > self.n_buckets * SLOTS * 0.85:
            self.n_buckets *= 2
            grew = True
        if grew or self._table.shape[0] != self.n_buckets:
            self._rebuild_bulk()       # source already holds the batch
        else:
            arr = np.frombuffer(b"".join(digests),
                                dtype=np.uint8).reshape(-1, 32)
            nb = self.n_buckets
            for i in self._place_bulk(arr):
                fp0, fp1, b1, b2 = self._fp_bucket(digests[int(i)])
                self._insert_fp(fp0, fp1, b1, b2)
                if self.n_buckets != nb:
                    break              # the growth rebuild placed the rest
        self._dirty = True

    def discard_fp(self, digest: bytes) -> None:
        """Zero the fingerprint slot (filter-only mode).  A twin digest
        sharing the fp+bucket pair degrades to a safe false negative,
        exactly like ``discard``."""
        self._n_fp = max(0, self._n_fp - 1)
        fp0, fp1, b1, b2 = self._fp_bucket(digest)
        for b in (b1, b2):
            row = self._table[b]
            for s in range(SLOTS):
                if row[s, 0] == fp0 and row[s, 1] == fp1:
                    row[s] = (0, 0)
                    self._dirty = True
                    return
        self._dirty = True

    def insert_many(self, digests: list[bytes]) -> int:
        """Bulk insert, vectorized: one numpy pass computes every
        fingerprint/bucket pair, free slots are allocated group-wise on
        the host mirror, and only the overflow tail (buckets whose free
        slots ran out) falls back to per-digest eviction chains.  A 1M
        preload (PBSStore ``previous`` known-digest warm-up) builds in
        one pass instead of a million Python round-trips."""
        digests = list(digests)          # accept any iterable, like insert
        for d in digests:
            if len(d) != 32:
                raise ValueError(f"digest must be 32 bytes, got {len(d)}")
        fresh = [d for d in digests if d not in self._known]
        if not fresh:
            return 0
        # in-batch dedupe, preserving first occurrence
        seen: set[bytes] = set()
        uniq = [d for d in fresh if not (d in seen or seen.add(d))]
        self._known.update(uniq)
        # grow proactively so the bulk placement isn't done at a load
        # factor where eviction chains dominate
        while len(self._known) > self.n_buckets * SLOTS * 0.85:
            self.n_buckets *= 2
        arr = np.frombuffer(b"".join(uniq), dtype=np.uint8).reshape(-1, 32)
        if self._table.shape[0] != self.n_buckets:
            self._rebuild_bulk()            # re-places every known digest
        else:
            nb = self.n_buckets
            for i in self._place_bulk(arr):
                fp0, fp1, b1, b2 = self._fp_bucket(uniq[int(i)])
                self._insert_fp(fp0, fp1, b1, b2)
                if self.n_buckets != nb:
                    # _insert_fp grew the table, and the rebuild placed
                    # every known digest — the rest of the tail included
                    break
        self._dirty = True
        return len(uniq)

    def _fp_buckets_vec(self, arr: np.ndarray):
        """uint8[N,32] → (fp0, fp1, b1, b2) uint32[N] each (the
        vectorized twin of ``_fp_bucket``)."""
        fp0, fp1, bidx = _digest_words(arr)
        fp0 = np.where((fp0 == 0) & (fp1 == 0),
                       np.uint32(0x5A5A5A5A), fp0).astype(np.uint32)
        mask = np.uint32(self.n_buckets - 1)
        b1 = bidx & mask
        b2 = b1 ^ ((fp0 * _MIX) & mask)
        return fp0, fp1, b1, b2

    def _place_bulk(self, arr: np.ndarray) -> np.ndarray:
        """Place digests uint8[N,32] into free slots of the host mirror
        without eviction; returns the indices (into ``arr``) that did not
        fit and need the eviction-chain fallback."""
        fp0, fp1, b1, b2 = self._fp_buckets_vec(arr)
        remaining = np.ones(arr.shape[0], dtype=bool)
        for bk in (b1, b2):
            idx = np.flatnonzero(remaining)
            if not idx.size:
                break
            order = np.argsort(bk[idx], kind="stable")
            sel_i = idx[order]              # arr-indices sorted by bucket
            bs = bk[sel_i]
            # rank of each entry within its equal-bucket run
            new_grp = np.r_[True, bs[1:] != bs[:-1]]
            starts = np.flatnonzero(new_grp)
            rank = np.arange(bs.size) - np.repeat(
                starts, np.diff(np.r_[starts, bs.size]))
            free = (self._table[bs, :, 0] == 0) & \
                   (self._table[bs, :, 1] == 0)          # [n, SLOTS]
            cfree = np.cumsum(free, axis=1)
            fits = cfree[:, -1] > rank
            # the (rank+1)-th free slot of the bucket, for entries that fit
            slot = np.argmax((cfree == (rank + 1)[:, None]) & free, axis=1)
            put = sel_i[fits]
            self._table[bs[fits], slot[fits], 0] = fp0[put]
            self._table[bs[fits], slot[fits], 1] = fp1[put]
            remaining[put] = False
        return np.flatnonzero(remaining)

    def _rebuild_bulk(self) -> None:
        """Zero the mirror at the current ``n_buckets`` and re-place
        every known digest with the vectorized path (bulk twin of
        ``_grow``).  In filter-only mode the digests stream from the
        attached source in bounded batches — 10⁹ fingerprints rebuild
        without ever materializing the digest set in RAM.  A placement
        overflow mid-rebuild doubles the table and retries from a fresh
        source pass (no nested-grow recursion)."""
        while True:
            self._table = np.zeros((self.n_buckets, SLOTS, 2),
                                   dtype=np.uint32)
            if self._place_all():
                return
            self.n_buckets *= 2

    def _place_all(self) -> bool:
        if self._known or self._digest_source is None:
            src = iter(self._known)
        else:
            src = self._digest_source()
            self._n_fp = 0             # recounted as the stream places
        batch: list[bytes] = []
        for d in src:
            batch.append(d)
            if len(batch) == (1 << 19):
                if not self._place_batch(batch):
                    return False
                batch.clear()
        return self._place_batch(batch) if batch else True

    def _place_batch(self, known: "list[bytes]") -> bool:
        if self._digest_source is not None and not self._known:
            self._n_fp += len(known)
        arr = np.frombuffer(b"".join(known), dtype=np.uint8).reshape(-1, 32)
        for i in self._place_bulk(arr):
            fp0, fp1, b1, b2 = self._fp_bucket(known[int(i)])
            if not self._insert_fp(fp0, fp1, b1, b2, grow=False):
                return False
        return True

    # -- device probe -----------------------------------------------------
    def device_table(self) -> jax.Array:
        if self._dirty or self._device_table is None:
            self._device_table = jnp.asarray(self._table)
            self._dirty = False
        return self._device_table

    def probe(self, digests: np.ndarray | jax.Array) -> np.ndarray:
        """digests uint8[N,32] → bool[N] (maybe-present; exact-confirm via
        contains_exact on hits if false positives matter).  The batch is
        padded on the host to a probe class, so the lookup compiles for
        a handful of batch sizes and not for every N."""
        with trace.round_trip("device.probe", stats,
                              lock=_stats_lock) as rt:
            with rt.phase("pack"):
                arr = np.asarray(digests, dtype=np.uint8)
                n = arr.shape[0]
                n_pad = _probe_class(n)
                padded = np.zeros((n_pad, 32), dtype=np.uint8)
                padded[:n] = arr
            rt.shape = f"rows={n_pad} buckets={self.n_buckets}"
            mine = {"index_probe_padded": n_pad}
            with rt.phase("h2d"):
                # the table too, whole, after any insert: its own seconds
                # apart from the digests' copy
                carried = self._dirty or self._device_table is None
                t0 = time.perf_counter()
                table = self.device_table().block_until_ready()
                if carried:
                    upload_s = time.perf_counter() - t0
                    nbytes = self._table.nbytes
                    rt.add(table_uploads=1, table_upload_bytes=nbytes)
                    rt.attrs["upload_s"] = upload_s
                    mine.update(index_table_uploads=1,
                                index_table_upload_bytes=nbytes,
                                index_upload_s=upload_s)
                dd = jnp.asarray(padded).block_until_ready()
            rt.add(dispatches=1, probes=n, bytes=arr.nbytes,
                   padded_bytes=padded.nbytes)
            lookup = _programs.get((self.n_buckets, n_pad), _lookup)
            with rt.phase("device"):
                dhit = lookup(table, dd).block_until_ready()
            trace.tally(index_device_s=rt.attrs["device_s"], **mine)
            with rt.phase("d2h"):
                hit = np.asarray(dhit)
            with rt.phase("unpack"):
                return hit[:n]

    def probe_confirmed(self, digests: list[bytes]) -> list[bool]:
        arr = np.frombuffer(b"".join(digests), dtype=np.uint8).reshape(-1, 32)
        maybe = self.probe(arr)
        return [bool(m) and (d in self._known) for m, d in zip(maybe, digests)]
