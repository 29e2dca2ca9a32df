"""Batched buzhash candidate computation on TPU.

Implements chunker/spec.py's position-local closed form

    h(i) = XOR_{k=0}^{63} rotl32(T[b[i-k]], k mod 32)

with log2(W)=6 shift/rotate/XOR doubling passes over whole streams at once:

    H_1(i)    = T[b[i]]
    H_{2m}(i) = H_m(i) ^ rotl_{m mod 32}(H_m(i-m))

Fully parallel over batch and sequence: the VPU evaluates every position's
window hash with ~6 fused elementwise passes; no sequential rolling state
(the CPU chunkers and this kernel are bit-identical —
tests/test_ops.py::test_candidate_mask_matches_cpu).

Bit parity gate: BASELINE.md config #2.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..chunker import observe
from ..chunker.spec import WINDOW, ChunkerParams, buzhash_subtables
from ..chunker.spec import select_cuts
from ..utils import jaxenv, trace

jaxenv.watch_compiles()

# multi-chip dispatch evidence and padding occupancy (test/metrics
# probe): mesh_* move whenever a batched dispatch is sharded over the
# data mesh; mesh_shard_devices is how many distinct devices the last
# sharded input's shards really sat on; home_bytes is what the answers
# weighed on their way back (padded_bytes / 8: a bit a position).  The
# five phase clocks
# (pack_s … unpack_s: seconds of the calling thread inside each step of
# a round trip, docs/observability.md) come with the registration.
stats = trace.device_stats("scan", {
    "mesh_dispatches": 0, "mesh_devices": 0, "mesh_shard_devices": 0,
    "dispatches": 0, "rows": 0, "padded_rows": 0, "bytes": 0,
    "padded_bytes": 0, "home_bytes": 0})


def _rotl(x: jax.Array, r: int) -> jax.Array:
    r &= 31
    if r == 0:
        return x
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def device_tables(params: ChunkerParams) -> jax.Array:
    """uint32[2, 16] — the A/B nibble subtables as one device array."""
    a, b = buzhash_subtables(params.seed)
    return jnp.asarray(np.stack([a, b]))


def _table_lookup(data: jax.Array, tables: jax.Array) -> jax.Array:
    """T[b] = A[b>>4] ^ B[b&15] as 32 unrolled selects — no gather.

    The nibble decomposition (chunker/spec.py buzhash_table) turns the
    lookup into compare/select/xor chains that XLA can fuse into one
    pass, where a 256-entry table would be an element gather.  On-chip
    rates of the two: not measured.
    """
    hi = data >> np.uint8(4)
    lo = data & np.uint8(0xF)
    acc = jnp.zeros(data.shape, dtype=jnp.uint32)
    for i in range(16):
        iv = np.uint8(i)
        acc = acc ^ jnp.where(hi == iv, tables[0, i], jnp.uint32(0)) \
                  ^ jnp.where(lo == iv, tables[1, i], jnp.uint32(0))
    return acc


def _candidate_mask_impl(data: jax.Array, tables: jax.Array, mask: int,
                         magic: int, history: jax.Array | None = None) -> jax.Array:
    """Candidate boolean mask for batched streams.

    data:    uint8[B, S] — batch of stream segments
    tables:  uint32[2, 16] — nibble subtables (device_tables(params))
    history: optional uint8[B, W-1] — the 63 bytes preceding each segment
             (for segment-parallel / streaming use).  Without it, the first
             W-1 positions of each stream are masked invalid.

    Returns bool[B, S]: True where a chunk cut candidate ends at that byte.
    """
    if data.ndim == 1:
        data = data[None]
        squeeze = True
    else:
        squeeze = False
    B, S = data.shape
    hlen = 0
    if history is not None:
        hlen = history.shape[-1]
        if hlen != WINDOW - 1:
            raise ValueError(f"history must be {WINDOW-1} bytes")
        data = jnp.concatenate([history, data], axis=-1)
    h = _table_lookup(data, tables)            # uint32[B, hlen+S]
    m = 1
    while m < WINDOW:
        shifted = jnp.pad(h[:, :-m], ((0, 0), (m, 0)))
        h = h ^ _rotl(shifted, m)
        m *= 2
    hit = (h & jnp.uint32(mask)) == jnp.uint32(magic)
    # positions with an incomplete 64-byte window are invalid
    pos = jnp.arange(hlen + S, dtype=jnp.int32)
    hit = hit & (pos >= WINDOW - 1)[None, :]
    hit = hit[:, hlen:]
    return hit[0] if squeeze else hit


_candidate_mask_jit = jax.jit(_candidate_mask_impl)


def candidate_mask(data: jax.Array, tables: jax.Array, mask: int,
                   magic: int, *, history: jax.Array | None = None) -> jax.Array:
    """Jitted public entry (see _candidate_mask_impl for the contract)."""
    return _candidate_mask_jit(data, tables, jnp.uint32(mask),
                               jnp.uint32(magic), history)


# positions a word of the packed answer holds
WORD_BITS = 32
_BIT = np.arange(WORD_BITS, dtype=np.uint32)


def _candidate_words_impl(data: jax.Array, tables: jax.Array, mask: int,
                          magic: int, history: jax.Array) -> jax.Array:
    """``_candidate_mask_impl``'s ``hit[B, S]`` bit-packed in the same
    program: ``uint32[B, S/32]``, bit ``j`` of word ``w`` is position
    ``j * S/32 + w``.  Strided, not 32 neighbours a word: the 32 bits of
    a word are then 32 slices of the row OR-ed elementwise, the minor
    axis stays S/32 wide, and nothing is reduced across lanes.  Exact
    and of static shape (every segment class divides by 32); row-local,
    so rows sharded over a mesh stay where they are."""
    hit = _candidate_mask_impl(data, tables, mask, magic, history)
    B, S = hit.shape
    bits = hit.reshape(B, WORD_BITS, S // WORD_BITS).astype(jnp.uint32) \
        << _BIT[None, :, None]
    return jnp.bitwise_or.reduce(bits, axis=1)


_candidate_words_jit = jax.jit(_candidate_words_impl)


def candidate_words(data: jax.Array, tables: jax.Array, mask: int,
                    magic: int, history: jax.Array) -> jax.Array:
    """Jitted entry of the batched scan (``_dispatch_hits``): the
    candidate mask of ``data[B, S]``, 32 positions a word."""
    return _candidate_words_jit(data, tables, jnp.uint32(mask),
                                jnp.uint32(magic), history)


def unpack_words(words: np.ndarray, n: int) -> np.ndarray:
    """Ascending positions below ``n`` of the bits set in one row of
    ``candidate_words``' answer (host side): the few words that are not
    zero, then their bits."""
    # through a bool array: numpy scans one several times faster
    at = np.flatnonzero(words != 0)
    k, bit = np.nonzero((words[at, None] >> _BIT) & np.uint32(1))
    pos = bit * len(words) + at[k]
    pos = pos[pos < n]
    pos.sort()
    return pos


# The whole jit key of the batched scan is (padded rows, padded segment
# length), and both come from these two short lists.
_ROW_CLASSES = (1, 4, 16, 64)
_SEG_CLASSES = tuple(1 << k for k in range(16, 31, 2))     # 64 KiB … 1 GiB
# device bytes one scanned byte costs while the program runs: input +
# the uint32 hash array and its shifted copy (the chip's compiler
# reports temp = 8.04x input at [8, 4 MiB] and [64, 4 MiB] —
# tests/test_tpu_compile.py) + the packed answer, an eighth of a byte
_SCAN_BYTES_PER_BYTE = 10
# share of a device's memory one scan dispatch may take: the hash
# staging buffers and the index table live there too
_SCAN_MEMORY_SHARE = 4
_ASSUMED_DEVICE_BYTES = 16 << 30      # backends that report no limit (CPU)


def _class_for(n: int, classes: tuple) -> int:
    return next(c for c in classes if c >= n)


def segment_class(n: int) -> int:
    """Padded length a segment of ``n`` bytes is scanned at."""
    if n > _SEG_CLASSES[-1]:
        raise ValueError(f"scan segment of {n} bytes exceeds "
                         f"{_SEG_CLASSES[-1]}")
    return _class_for(n, _SEG_CLASSES)


@functools.cache
def scan_budget_bytes() -> int:
    """Device bytes one scan dispatch may occupy on each device (decided
    once per process, like ``parallel.mesh.data_mesh``)."""
    ms = jax.devices()[0].memory_stats() or {}
    return ms.get("bytes_limit", _ASSUMED_DEVICE_BYTES) // _SCAN_MEMORY_SHARE


def dispatch_rows(seg_bytes: int, n_devices: int = 1) -> int:
    """Largest row class whose ``[rows, seg_bytes]`` scan fits the
    per-device budget when the rows spread over ``n_devices``."""
    cap = scan_budget_bytes() // (seg_bytes * _SCAN_BYTES_PER_BYTE) \
        * n_devices
    fits = [c for c in _ROW_CLASSES if c <= cap]
    if not fits:
        raise ValueError(f"a {seg_bytes}-byte scan segment does not fit "
                         f"the device budget of {scan_budget_bytes()} bytes")
    return fits[-1]


def batched_candidate_hits(bufs: list, hists: list, tables: jax.Array,
                           params: ChunkerParams) -> list[np.ndarray]:
    """THE pack/dispatch/unpack step for cross-stream candidate batching:
    stack variable-length segments (with optional per-row 63-byte history)
    into class-padded ``[B_pad, S_pad]`` candidate_words dispatches — as
    many as the device budget splits the rows into — and return each
    row's raw hit indices (0-based positions, unfiltered — callers apply
    their own window-validity/offset arithmetic).

    Shared by the production DeviceFeeder (models/feeder.py) and the
    whole-stream DedupPipeline so their padding/history handling cannot
    diverge (the bit-parity guarantee hangs on this one implementation).
    """
    # backend observability: every batched device scan lands here (the
    # feeder AND the whole-stream pipeline), so this is the one "tpu"
    # scan-bytes accounting point (chunker/observe.py)
    observe.add_scan_bytes("tpu", sum(len(b) for b in bufs))
    S_pad = segment_class(max(len(b) for b in bufs))
    # multi-chip: any coalesced batch (≥2 rows) shards over the data
    # mesh, padded up to mesh width — each chip computes ≤ceil(B/n)
    # rows instead of one chip computing B, so latency drops even when
    # some chips get zero rows.  Single-row dispatches stay local.
    mesh = None
    if len(bufs) >= 2:
        from ..parallel.mesh import data_mesh
        mesh = data_mesh()
    rows = dispatch_rows(S_pad, mesh.size if mesh is not None else 1)
    out: list[np.ndarray] = []
    for lo in range(0, len(bufs), rows):
        out.extend(_dispatch_hits(bufs[lo:lo + rows], hists[lo:lo + rows],
                                  S_pad, mesh, tables, params))
    return out


def _dispatch_hits(bufs: list, hists: list, S_pad: int, mesh,
                   tables: jax.Array, params: ChunkerParams,
                   ) -> list[np.ndarray]:
    B_pad = _class_for(len(bufs), _ROW_CLASSES)
    if mesh is not None:
        n = mesh.size
        B_pad = ((max(B_pad, n) + n - 1) // n) * n
    # ``devices``: how many the rows really sit on, so a span tells a
    # sharded dispatch from an unsharded one on a several-chip host
    with trace.round_trip("device.scan", stats, seg_pad=S_pad, devices=1,
                          shape=f"rows={B_pad} seg={S_pad >> 10} KiB") as rt:
        with rt.phase("pack"):
            buf = np.zeros((B_pad, S_pad), dtype=np.uint8)
            hist = np.zeros((B_pad, WINDOW - 1), dtype=np.uint8)
            for i, (b, h) in enumerate(zip(bufs, hists)):
                buf[i, :len(b)] = b
                if h is not None:
                    hist[i] = h
        with rt.phase("h2d"):
            if mesh is not None:
                # straight from the host to each device's shard: going
                # through a one-device array first would compile a
                # slicing program per shape
                from jax.sharding import NamedSharding, PartitionSpec as P
                rows = NamedSharding(mesh, P("data", None))
                dbuf = jax.device_put(buf, rows)
                dhist = jax.device_put(hist, rows)
            else:
                dbuf, dhist = jnp.asarray(buf), jnp.asarray(hist)
            # no wait here: the copies' end is not this phase's boundary
            # but the calls' return, and what of them is still in flight
            # overlaps the launch and counts as ``device``
            # (docs/observability.md "Device round trips")
        if mesh is not None:
            stats["mesh_dispatches"] += 1
            stats["mesh_devices"] = mesh.size
            stats["mesh_shard_devices"] = len(
                {s.device for s in dbuf.addressable_shards})
            rt.attrs["devices"] = stats["mesh_shard_devices"]
        rt.add(dispatches=1, rows=len(bufs), padded_rows=B_pad,
               bytes=sum(len(b) for b in bufs), padded_bytes=buf.size)
        with rt.phase("device"):
            dwords = candidate_words(dbuf, tables, params.mask, params.magic,
                                     dhist).block_until_ready()
        with rt.phase("d2h"):
            words = np.asarray(dwords)
        rt.add(home_bytes=words.nbytes)
        with rt.phase("unpack"):
            return [unpack_words(words[i], len(b))
                    for i, b in enumerate(bufs)]


def candidate_ends_host(data: bytes | np.ndarray, params: ChunkerParams,
                        *, device=None) -> np.ndarray:
    """Convenience: run the device kernel on one stream and return sorted
    absolute candidate end offsets (same contract as chunker.cpu.candidates
    with no prefix).  Host round-trip included — for parity tests and
    small inputs; the pipeline keeps everything on device."""
    arr = np.frombuffer(data, dtype=np.uint8) if not isinstance(data, np.ndarray) else data
    tables = device_tables(params)
    n = len(arr)
    # pad to a segment class so the jit cache sees few shapes
    S = segment_class(n)
    if S != n:
        padded = np.zeros(S, dtype=np.uint8)
        padded[:n] = arr
        arr = padded
    hit = candidate_mask(jnp.asarray(arr)[None], tables, params.mask,
                         params.magic)[0]
    return (np.nonzero(np.asarray(hit)[:n])[0] + 1).astype(np.int64)


def chunk_stream_device(data: bytes | np.ndarray, params: ChunkerParams,
                        ) -> list[int]:
    """Device candidates + the shared host-side greedy pass → cut offsets.
    (Candidate density is ~1 per avg_size, so the greedy pass is O(n/avg)
    host work — negligible.)"""
    n = len(data)
    ends = candidate_ends_host(data, params)
    return select_cuts(ends, n, params)
