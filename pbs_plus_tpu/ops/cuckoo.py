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
cuckoo eviction + table growth.  The host dict stays authoritative — a
64-bit-fingerprint false positive (~2⁻⁶⁴ per probe) is confirmed against
it before a chunk upload is skipped.

The device's copy of the table stays resident between probes and is
kept in step with the mirror in place: every write to a row of the
mirror marks its bucket, and the next probe sends the marked buckets'
indices and rows (36 bytes a bucket, padded to a class) to a program
that writes them into the resident table, which it is given to reuse
(``donate_argnums``) — no second table is allocated.  The table goes
whole only at the first probe, after a rebuild (growth, any change of
its shape), or where the change, padded to its class, has more than a
1,024th of the table's buckets (``_goes_whole``).  Why (PERF.md, cell
``index-at-size.serial``: a 2 GiB table in HBM, ~211 digests a probe,
one TPU v5e and its 13-core host): the whole copy took 0.22 s — the
host's threads relay ``uint32[NB, 4, 2]`` out into the device's tiling
— and every flush of a volume of new chunks paid it, a third of the
writer thread's life, to change ~211 buckets of 32 bytes (ROADMAP S7).
A probe with a clean table is 1.6-1.8 ms of host clock, 0.9 ms of it
round the program.  ``_lookup`` is one program a (table shape, probe
class) and ``_scatter`` one a (table shape, change class);
``warm_lookups`` builds both from shapes alone, ahead of the writers
(``DedupIndex._warm_lookups``).

A table that one device cannot hold beside the program lies by bucket
range over all of the host's devices (``table_devices``): the whole copy
is one ``device_put`` of the mirror, sharded; a change goes to the shards
that own its buckets, each written in place (``_scatter_sharded``); and a
lookup asks every shard for both of a digest's buckets, each answering
for the rows it holds, and sums the partial hits over the shards
(``_lookup_sharded``).  Their programs are built ahead as the one
device's, keyed by (table shape, class, shards).
"""

from __future__ import annotations

import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils import jaxenv, trace
from ..utils.log import L

jaxenv.watch_compiles()

SLOTS = 4
_MIX = np.uint32(0x9E3779B1)
_MAX_KICKS = 500
BUCKET_BYTES = SLOTS * 2 * 4        # uint32[SLOTS, 2] per bucket
# padded digest counts of a device probe: 64, 256, 1024, … (powers of four);
# a change of the table is padded to the same classes of buckets
_PROBE_CLASSES = tuple(1 << k for k in range(6, 31, 2))
DELTA_BUCKET_BYTES = BUCKET_BYTES + 4   # a changed bucket's row and index
# a bucket written in place costs what the whole copy spends on ~660
# buckets (one TPU v5e, a 2 GiB table: 2.2 µs a step of ``_scatter``'s
# loop against 0.1 ns a byte of the copy; PERF.md, section 6)
_STEP_BUCKETS = 1024

# Where the device's table lies.  On one device where the table and the
# program's working set beside it fit that device's memory; else by bucket
# range over every device of the host, where a shard and the working set
# fit the least of them; else on none, and ``TableTooLarge`` says so when
# the index is built (``DedupIndex``), not in a backup.  Read from the
# devices' own ``memory_stats()["bytes_limit"]``; a backend that reports
# none (the CPU's) holds any table on one device.  The working set: the
# scan's programs, the probes' digests and an update's rows — 0.03 GB
# beside the 2 GiB table, 0.17 GB with no large table (PERF.md, section
# 4) — with room for a compile's scratch.  On TPU v5e (15.75 GiB a chip):
# 64 MiB, 2 GiB and 8 GiB tables lie on one device, on one chip and on
# four; 16 GiB (2^29 buckets) on four chips lies 4 GiB a chip, and on one
# chip is refused.
WORKING_SET_BYTES = 2 << 30
_AXIS = "index"
_SHARDED = P(_AXIS, None, None)


class TableTooLarge(ValueError):
    """No set of the host's devices holds the index's table."""


# device probes, mirror of rolling_hash.stats: ``probes`` digests asked in
# ``dispatches`` lookups (``bytes`` of digests, ``padded_bytes`` after
# padding to a probe class); the table brought up to date for a probe
# ``table_uploads`` times whole and ``table_delta_uploads`` times by its
# changed buckets (``table_delta_buckets`` of them, ``table_delta_bytes``
# sent, padding included), ``table_upload_bytes`` the bytes of both;
# ``table_shards`` the devices the table went to at its last whole copy
# (a gauge); and the five phase clocks — the table's update is inside
# ``h2d_s``.  Probes run on the writers' threads, several at once: a trip
# adds to them under the lock.
stats = trace.device_stats("probe", {
    "dispatches": 0, "probes": 0, "bytes": 0, "padded_bytes": 0,
    "table_uploads": 0, "table_upload_bytes": 0, "table_delta_uploads": 0,
    "table_delta_buckets": 0, "table_delta_bytes": 0, "table_shards": 0})
_stats_lock = threading.Lock()


def shards_for(n_buckets: int, limits) -> int:
    """How many of the host's devices a table of ``n_buckets`` lies on:
    1, or all of them (``len(limits)``); ``limits`` are their
    ``bytes_limit``s, None where the backend reports none."""
    table = n_buckets * BUCKET_BYTES
    if not limits or None in limits \
            or table + WORKING_SET_BYTES <= limits[0]:
        return 1
    n = len(limits)
    if n > 1 and n_buckets % n == 0 \
            and table // n + WORKING_SET_BYTES <= min(limits):
        return n
    raise TableTooLarge(
        f"the dedup index's table is {table:,} bytes ({n_buckets:,} "
        f"buckets); with {WORKING_SET_BYTES:,} bytes of working set beside "
        f"it, no set of this host's {n} device(s) holds it (bytes_limit "
        f"{', '.join(f'{b:,}' for b in limits)})")


_placements: dict = {}


def table_devices(n_buckets: int) -> tuple:
    """The devices the table of ``n_buckets`` lies on, decided once a
    table shape (``shards_for``); raises ``TableTooLarge``."""
    devices = _placements.get(n_buckets)
    if devices is None:
        every = jax.devices()
        limits = [(d.memory_stats() or {}).get("bytes_limit")
                  for d in every]
        devices = _placements[n_buckets] = \
            tuple(every[:shards_for(n_buckets, limits)])
    return devices


@functools.lru_cache(maxsize=None)
def _mesh(devices: tuple) -> Mesh:
    """A 1-D mesh over ``devices`` whose axis holds the table's buckets."""
    return Mesh(np.array(devices), (_AXIS,))


def _mesh_of(table: jax.Array) -> "Mesh | None":
    """The mesh a sharded table lies on; None for one device's."""
    sharding = table.sharding
    return sharding.mesh if isinstance(sharding, NamedSharding) else None


def _key(n_buckets: int, k: int, shards: int) -> tuple:
    """A program's key: the one device's as it always was."""
    return (n_buckets, k) if shards == 1 else (n_buckets, k, shards)


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


@functools.partial(jax.jit, donate_argnums=0)
def _scatter(table: jax.Array, idx: jax.Array, rows: jax.Array) -> jax.Array:
    """table uint32[NB, SLOTS, 2], given to be reused; idx int32[K],
    padded by repeating the last (the same row set twice); rows
    uint32[K, SLOTS, 2] → the table with those buckets' rows replaced,
    written in place.  One row a step: on a TPU the table lies with the
    buckets minor (``{0,2,1}``), and a ``scatter`` of whole rows has the
    compiler relay it out first — 32 times its size, refused at 2 GiB —
    where a row's ``dynamic_update_slice`` writes it where it lies.
    Eight steps an iteration: on one TPU v5e, at a 2 GiB table, 65,536
    rows took 147 ms where one step an iteration took 207 (PERF.md,
    section 6)."""
    def put(i, t):
        row = jax.lax.dynamic_slice_in_dim(rows, i, 1)
        return jax.lax.dynamic_update_slice_in_dim(t, row, idx[i], axis=0)
    return jax.lax.fori_loop(0, idx.shape[0], put, table, unroll=8)


def _lookup_shard(table_shard: jax.Array, digests: jax.Array,
                  n_buckets: int, axis_name: str) -> jax.Array:
    """``_lookup`` on one shard of a table of ``n_buckets`` split by
    bucket range over ``axis_name``: table_shard uint32[NB/n, SLOTS, 2];
    digests uint8[N, 32], the same on every shard → bool[N], the hits in
    the rows this shard holds (a bucket outside them reads a clipped row
    and is masked out); the caller sums the shards'."""
    rows_here = table_shard.shape[0]
    base = jax.lax.axis_index(axis_name) * rows_here
    fp0, fp1, bidx = _digest_words(digests)
    fp0 = jnp.where((fp0 == 0) & (fp1 == 0), jnp.uint32(0x5A5A5A5A), fp0)
    mask = jnp.uint32(n_buckets - 1)
    b1 = bidx & mask
    b2 = b1 ^ ((fp0 * _MIX) & mask)

    def check(b):
        local = b.astype(jnp.int32) - base
        here = (local >= 0) & (local < rows_here)
        rows = table_shard[jnp.clip(local, 0, rows_here - 1)]
        hit = jnp.any((rows[..., 0] == fp0[:, None]) &
                      (rows[..., 1] == fp1[:, None]), axis=1)
        return hit & here

    return check(b1) | check(b2)


@functools.partial(jax.jit, static_argnames="mesh")
def _lookup_sharded(table: jax.Array, digests: jax.Array, *,
                    mesh: Mesh) -> jax.Array:
    """``_lookup`` of a table split by bucket range over ``mesh``; the
    digests lie whole on every device, the answer too."""
    nb = table.shape[0]

    def body(shard, dg):
        part = _lookup_shard(shard, dg, nb, _AXIS)
        return jax.lax.psum(part.astype(jnp.int32), _AXIS) > 0
    return shard_map(body, mesh=mesh, in_specs=(_SHARDED, P()),
                     out_specs=P())(table, digests)


@functools.partial(jax.jit, donate_argnums=0, static_argnames="mesh")
def _scatter_sharded(table: jax.Array, idx: jax.Array, rows: jax.Array, *,
                     mesh: Mesh) -> jax.Array:
    """``_scatter`` on every shard of a table split by bucket range over
    ``mesh``, given to be reused: idx int32[n·K] and rows
    uint32[n·K, SLOTS, 2] split as the table is, K a shard — the indices
    local to the shard, each shard's padded by repeating its last (a
    shard with no change rewrites its first row as the mirror has it)."""
    return shard_map(_scatter.__wrapped__, mesh=mesh,
                     in_specs=(_SHARDED, P(_AXIS), _SHARDED),
                     out_specs=_SHARDED)(table, idx, rows)


# ``_lookup`` and ``_scatter`` (their sharded twins for a table that lies
# on several devices) built ahead of a writer's first probe, by (buckets,
# class[, shards]): a table of another shape and every class is a program
# of its own, and one that compiles inside a probe stops a backup for as
# long as it takes.  A shape nobody built ahead still compiles at the
# probe, as before (``round_trip`` warns).
_programs: dict = {}
_scatters: dict = {}


def _goes_whole(n_buckets: int, k: int) -> bool:
    """True where a change of ``k`` buckets would cost more written in
    place than the whole table's copy: more than a ``_STEP_BUCKETS``-th
    of its buckets (a 2 GiB table takes up to 65,536 in place, a 64 MiB
    one up to 2,048)."""
    return k * _STEP_BUCKETS > n_buckets


def _build_lookups(n_buckets: int, classes) -> None:
    try:
        devices = table_devices(n_buckets)
    except TableTooLarge as e:      # the probe raises it
        L.warning("device.probe buckets=%d: %s", n_buckets, e)
        return
    shards = len(devices)
    if shards == 1:
        def arg(dims, dtype, spec=None):
            return jax.ShapeDtypeStruct(dims, dtype)
        lookup, scatter = _lookup.lower, _scatter.lower
    else:
        mesh = _mesh(devices)

        def arg(dims, dtype, spec=P()):
            return jax.ShapeDtypeStruct(dims, dtype,
                                        sharding=NamedSharding(mesh, spec))
        lookup = functools.partial(_lookup_sharded.lower, mesh=mesh)
        scatter = functools.partial(_scatter_sharded.lower, mesh=mesh)
    table = arg((n_buckets, SLOTS, 2), jnp.uint32, _SHARDED)
    for k in classes:
        key = _key(n_buckets, k, shards)
        try:
            if key not in _programs:
                _programs[key] = lookup(
                    table, arg((k, 32), jnp.uint8)).compile()
            # a change of k buckets a shard
            if key not in _scatters \
                    and not _goes_whole(n_buckets // shards, k):
                _scatters[key] = scatter(
                    table, arg((shards * k,), jnp.int32, P(_AXIS)),
                    arg((shards * k, SLOTS, 2), jnp.uint32,
                        _SHARDED)).compile()
        except Exception as e:      # the probe then compiles for itself
            L.warning("device.probe rows=%d buckets=%d shards=%d not built "
                      "ahead: %s", k, n_buckets, shards, e)


def warm_lookups(n_buckets: int, classes) -> threading.Thread:
    """Build (or load from the persistent cache) the lookup programs of a
    table of ``n_buckets`` at the probe classes ``classes``, and the
    programs that write a change of as many buckets into it (those of the
    classes that do not go whole), from shapes alone — no table, no lock
    — on a thread of its own, which is returned: the caller's thread goes
    on."""
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
        # what the device's copy lacks: the whole table (no copy yet, or
        # the mirror was rebuilt), else the buckets in _marks[:_n_marks],
        # noted after each write to a row (``_mark``); the probe that
        # brings the copy up to date (``_sync``) and the lookup reading it
        # run under the same lock, since the update gives the old array
        # away
        self._lock = threading.Lock()
        self._whole = True                    # guarded-by: self._lock
        self._marks = np.zeros(0, np.int32)   # guarded-by: self._lock
        self._n_marks = 0                     # guarded-by: self._lock
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
                    self._mark(b)
                    return True
        # fingerprint not in the mirror (dropped during an eviction
        # overflow before a growth rebuild): the authoritative set is
        # already updated, so membership answers stay correct
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
                    self._mark(b)
                    return True
        # eviction chain: every bucket on it is written
        b = b1
        cur = np.array([fp0, fp1], dtype=np.uint32)
        chain = []
        for _ in range(_MAX_KICKS):
            s = int(self._rng.integers(0, SLOTS))
            victim = self._table[b, s].copy()
            self._table[b, s] = cur
            chain.append(b)
            cur = victim
            vfp0 = int(cur[0])
            mask = self.n_buckets - 1
            b = b ^ ((vfp0 * int(_MIX)) & 0xFFFFFFFF & mask)
            row = self._table[b]
            for s2 in range(SLOTS):
                if row[s2, 0] == 0 and row[s2, 1] == 0:
                    row[s2] = cur
                    chain.append(b)
                    self._mark(chain)
                    return True
        self._mark(chain)
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
                    self._mark(b)
                    return

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
            self._mark(bs[fits])
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
            self._mark_whole()
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
    def _mark(self, buckets) -> None:
        """Note buckets whose rows of the mirror were just written, for
        the device's copy.  Called after the write: ``_sync`` takes the
        marks before it reads the rows, so a write it missed stays marked
        for the next probe.  Once the marks alone would send the table
        whole, it goes whole, and nothing is noted until it has."""
        with self._lock:
            if self._whole:
                return
            b = np.asarray(buckets, dtype=np.int32).ravel()
            n = self._n_marks + b.size
            if _goes_whole(self.n_buckets, n):
                self._whole, self._marks, self._n_marks = \
                    True, np.zeros(0, np.int32), 0
                return
            if n > self._marks.size:
                grown = np.empty(max(n, 2 * self._marks.size, 64), np.int32)
                grown[:self._n_marks] = self._marks[:self._n_marks]
                self._marks = grown
            self._marks[self._n_marks:n] = b
            self._n_marks = n

    def _mark_whole(self) -> None:
        """The mirror is rebuilt: the next probe sends it whole."""
        with self._lock:
            self._whole, self._marks, self._n_marks = \
                True, np.zeros(0, np.int32), 0

    def _sync(self) -> tuple:
        """Bring the device's copy of the table up to the mirror; the
        caller holds ``_lock``.  Returns what went: ``(None, 0, 0)``
        where nothing had changed, ``("delta", bytes, buckets)`` where
        the changed buckets were written into the resident table, and
        ``("whole", bytes, 0)``."""
        if not self._whole and self._device_table is not None:
            if not self._n_marks:
                return None, 0, 0
            idx = np.unique(self._marks[:self._n_marks])
            self._n_marks = 0
            sent = self._write_delta(idx)
            if sent:
                return "delta", sent, idx.size
        devices = table_devices(self.n_buckets)
        # cleared before the mirror is read: a row written while it is
        # copied is marked for the next probe.  The CPU backend may take
        # an aligned array's memory as its own instead of copying it, and
        # the mirror is written after the copy
        self._whole, self._n_marks = False, 0
        self._device_table = None           # not two tables at once
        src = np.array(self._table) if jax.default_backend() == "cpu" \
            else self._table
        if len(devices) == 1:
            self._device_table = jnp.asarray(src)
        else:       # each device its own range of buckets, and no more
            self._device_table = jax.device_put(
                src, NamedSharding(_mesh(devices), _SHARDED))
        return "whole", self._table.nbytes, 0

    def _write_delta(self, idx: np.ndarray) -> int:
        """Write the buckets ``idx`` (sorted, distinct) of the mirror into
        the device's table in place, each into the shard that holds it;
        returns the bytes sent, or 0 where the change, padded to its class
        a shard, costs more than the whole copy (``_goes_whole``) and
        nothing was written."""
        table = self._device_table
        mesh = _mesh_of(table)
        if mesh is None:
            k = _probe_class(idx.size)
            if _goes_whole(self.n_buckets, k):
                return 0
            pad = np.full(k, idx[-1], dtype=np.int32)
            pad[:idx.size] = idx
            rows = self._table[pad]
            scatter = _scatters.get((self.n_buckets, k), _scatter)
            self._device_table = scatter(table, pad, rows)
            return pad.nbytes + rows.nbytes
        # the host routes each shard its own buckets, as local indices
        shards = mesh.size
        per = self.n_buckets // shards
        parts = np.split(idx, np.searchsorted(idx, per * np.arange(1, shards)))
        k = _probe_class(max(p.size for p in parts))
        if _goes_whole(per, k):
            return 0
        local = np.zeros((shards, k), dtype=np.int32)
        for s, p in enumerate(parts):
            if p.size:
                local[s] = p[-1] - s * per
                local[s, :p.size] = p - s * per
        base = per * np.arange(shards, dtype=np.int32)[:, None]
        rows = self._table[(local + base).ravel()]
        local = local.ravel()
        scatter = _scatters.get(_key(self.n_buckets, k, shards)) \
            or functools.partial(_scatter_sharded, mesh=mesh)
        self._device_table = scatter(
            table, jax.device_put(local, NamedSharding(mesh, P(_AXIS))),
            jax.device_put(rows, table.sharding))
        return local.nbytes + rows.nbytes

    @property
    def table_shards(self) -> int:
        """The devices the device's copy of the table lies on now; 0
        where there is none (a CPU host probes the mirror)."""
        table = self._device_table
        return 0 if table is None else len(table.sharding.device_set)

    def device_table(self) -> jax.Array:
        """The device's copy of the table, brought up to date.  The next
        update after a change writes into this array and takes it over:
        what the caller holds is valid until the next probe."""
        with self._lock:
            self._sync()
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
            # the table's update gives the old array away: no other
            # thread's may come between it and the lookup that reads it
            with self._lock:
                with rt.phase("h2d"):
                    # the table's update, where it changed: its own
                    # seconds apart from the digests' copy
                    t0 = time.perf_counter()
                    kind, nbytes, changed = self._sync()
                    table = self._device_table.block_until_ready()
                    if kind is not None:
                        upload_s = time.perf_counter() - t0
                        rt.attrs["upload_s"] = upload_s
                        mine.update(index_table_upload_bytes=nbytes,
                                    index_upload_s=upload_s)
                    mesh = _mesh_of(table)
                    shards = 1 if mesh is None else mesh.size
                    rt.attrs["shards"] = shards
                    if kind == "whole":
                        rt.add(table_uploads=1, table_upload_bytes=nbytes)
                        with _stats_lock:
                            stats["table_shards"] = shards
                        mine["index_table_uploads"] = 1
                    elif kind == "delta":
                        rt.shape = (f"delta={_probe_class(changed)} "
                                    f"buckets={self.n_buckets}")
                        rt.add(table_delta_uploads=1,
                               table_delta_buckets=changed,
                               table_delta_bytes=nbytes,
                               table_upload_bytes=nbytes)
                        mine.update(index_table_delta_uploads=1,
                                    index_table_delta_buckets=changed)
                    if mesh is None:
                        dd = jnp.asarray(padded).block_until_ready()
                    else:   # the digests whole on every shard
                        dd = jax.device_put(padded, NamedSharding(
                            mesh, P())).block_until_ready()
                rt.shape = f"rows={n_pad} buckets={self.n_buckets}"
                rt.add(dispatches=1, probes=n, bytes=arr.nbytes,
                       padded_bytes=padded.nbytes)
                lookup = _programs.get(
                    _key(self.n_buckets, n_pad, shards),
                    _lookup if mesh is None
                    else functools.partial(_lookup_sharded, mesh=mesh))
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
