"""Whole-chunk SHA-256: the host's, and the batched program on TPU.

``sha256_chunks`` hashes with the host's SHA-256 (hashlib / OpenSSL, on
the calling thread, interpreter lock released), and every caller that
wants digests — the ``chunker="tpu"`` batch hasher, the sidecar,
verification — goes through it.
``sha256_chunks_device`` is the jax program described below; as measured
it loses to one host thread on every batch shape (PERF.md section 6,
PR 25: 16 MiB/s at a hash batch's 4-6 chunks, 755 MB/s at its best, 512
equal chunks, against 1,565 MB/s), so only the feeder's ``sha256_batch``
(its cross-session batcher), the kernel's tests, ``chip_smoke.py`` and
``tools/sha_crossover.py`` — the measurement a kernel PR has to beat
before digests go back to the device — run it.  There is no rule between
the two: one comes, from the batch's shape, with the first kernel that
wins a batch.

The device program.  SHA-256 is strictly sequential per chunk (64-byte
block chain), so TPU throughput comes from batching: a loop over block
index advances N chunk states in lockstep on the VPU; variable chunk
lengths are handled by masking (finished chunks freeze), and the
standard SHA padding (0x80 + zeros + 64-bit bit length) is applied on
device.  Blocks are gathered per step straight from a device-resident
staging buffer — the padded [T, N, 64] block tensor is never
materialized.

Chunks are packed on the host into a staging buffer of one of a few
lengths and hashed in one dispatch per length bucket (next power of two
of the block count, so padding waste is <50% per bucket).  The loop's
trip count is a run-time argument: the compiled program's key is only
(staging-buffer class, row class).

Digest parity vs hashlib/OpenSSL is a correctness gate
(tests/test_ops.py::test_sha256_matches_hashlib, over both entries).

Reference role: the chunk fingerprinting inside RemoteDedupWriter
(/root/reference/internal/pxarmount/commit_orchestrate.go:177) and the
server-side sha256 verification pool
(/root/reference/internal/server/verification/job.go:765-1273).
"""

from __future__ import annotations

import hashlib
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import jaxenv, trace
from .rolling_hash import _class_for

jaxenv.watch_compiles()

_K = np.array([
    0x428a2f98, 0x71374491, 0xb5c0fbcf, 0xe9b5dba5,
    0x3956c25b, 0x59f111f1, 0x923f82a4, 0xab1c5ed5,
    0xd807aa98, 0x12835b01, 0x243185be, 0x550c7dc3,
    0x72be5d74, 0x80deb1fe, 0x9bdc06a7, 0xc19bf174,
    0xe49b69c1, 0xefbe4786, 0x0fc19dc6, 0x240ca1cc,
    0x2de92c6f, 0x4a7484aa, 0x5cb0a9dc, 0x76f988da,
    0x983e5152, 0xa831c66d, 0xb00327c8, 0xbf597fc7,
    0xc6e00bf3, 0xd5a79147, 0x06ca6351, 0x14292967,
    0x27b70a85, 0x2e1b2138, 0x4d2c6dfc, 0x53380d13,
    0x650a7354, 0x766a0abb, 0x81c2c92e, 0x92722c85,
    0xa2bfe8a1, 0xa81a664b, 0xc24b8b70, 0xc76c51a3,
    0xd192e819, 0xd6990624, 0xf40e3585, 0x106aa070,
    0x19a4c116, 0x1e376c08, 0x2748774c, 0x34b0bcb5,
    0x391c0cb3, 0x4ed8aa4a, 0x5b9cca4f, 0x682e6ff3,
    0x748f82ee, 0x78a5636f, 0x84c87814, 0x8cc70208,
    0x90befffa, 0xa4506ceb, 0xbef9a3f7, 0xc67178f2,
], dtype=np.uint32)

_H0 = np.array([
    0x6a09e667, 0xbb67ae85, 0x3c6ef372, 0xa54ff53a,
    0x510e527f, 0x9b05688c, 0x1f83d9ab, 0x5be0cd19,
], dtype=np.uint32)

MAX_CHUNK_BYTES = (1 << 29) - 64   # uint32 bit-length arithmetic bound
SLACK_BYTES = 4096                 # readable bytes past the last chunk (unroll <= 64)


def _rotr(x: jax.Array, r: int) -> jax.Array:
    return (x >> np.uint32(r)) | (x << np.uint32(32 - r))


def _compress_unrolled(state: jax.Array, words: jax.Array,
                       active: jax.Array) -> jax.Array:
    """One SHA-256 compression, all 64 rounds unrolled: state uint32[N,8],
    words uint32[N,16], active bool[N] (False → state unchanged).  This is
    the TPU variant — maximal ILP, no inner-loop overhead."""
    W = [words[:, i] for i in range(16)]
    for i in range(16, 64):
        s0 = _rotr(W[i - 15], 7) ^ _rotr(W[i - 15], 18) ^ (W[i - 15] >> np.uint32(3))
        s1 = _rotr(W[i - 2], 17) ^ _rotr(W[i - 2], 19) ^ (W[i - 2] >> np.uint32(10))
        W.append(W[i - 16] + s0 + W[i - 7] + s1)
    a, b, c, d, e, f, g, h = [state[:, i] for i in range(8)]
    for i in range(64):
        S1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + S1 + ch + jnp.uint32(_K[i]) + W[i]
        S0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = S0 + maj
        h, g, f, e, d, c, b, a = g, f, e, d + t1, c, b, a, t1 + t2
    new = state + jnp.stack([a, b, c, d, e, f, g, h], axis=1)
    return jnp.where(active[:, None], new, state)


def _compress_rolled(state: jax.Array, words: jax.Array,
                     active: jax.Array) -> jax.Array:
    """Same compression as a 64-step inner scan with a 16-word shift-
    register message schedule.  The XLA CPU backend livelocks its HLO
    pass pipeline on the unrolled round graph (confirmed on this image at
    any batch size); this compact form compiles fine and is the CPU
    variant.  Bit-identical output (tests/test_ops.py)."""
    def round_step(carry, k):
        a, b, c, d, e, f, g, h, W = carry
        w_t = W[:, 0]
        S1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + S1 + ch + k + w_t
        S0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = S0 + maj
        # schedule: W[t+16] = W[t] + s0(W[t+1]) + W[t+9] + s1(W[t+14])
        s0 = _rotr(W[:, 1], 7) ^ _rotr(W[:, 1], 18) ^ (W[:, 1] >> np.uint32(3))
        s1 = _rotr(W[:, 14], 17) ^ _rotr(W[:, 14], 19) ^ (W[:, 14] >> np.uint32(10))
        w_new = W[:, 0] + s0 + W[:, 9] + s1
        W = jnp.concatenate([W[:, 1:], w_new[:, None]], axis=1)
        return (t1 + t2, a, b, c, d + t1, e, f, g, W), None

    init = tuple(state[:, i] for i in range(8)) + (words,)
    out, _ = jax.lax.scan(round_step, init, jnp.asarray(_K))
    new = state + jnp.stack(out[:8], axis=1)
    return jnp.where(active[:, None], new, state)


def _compress(state: jax.Array, words: jax.Array, active: jax.Array) -> jax.Array:
    if jax.default_backend() == "cpu":
        return _compress_rolled(state, words, active)
    return _compress_unrolled(state, words, active)


def _sha256_scan_impl(stream: jax.Array, starts: jax.Array, lengths: jax.Array,
                      n_blocks, unroll: int | None = None) -> jax.Array:
    """stream uint8[S]; starts/lengths int32[N] → digests uint32[N,8].
    ``n_blocks`` is how many 64-byte block steps to run: at least
    ``(max(lengths) + 8) // 64 + 1``.  It may be a traced int32 scalar —
    the loop then reads its trip count at run time, so one compiled
    program serves every chunk length (a static trip count made one
    program per length bucket; on the TPU each is a compile of over a
    minute).  Padded slots (length 0) hash the empty string; the caller
    discards them.  The caller leaves SLACK_BYTES after the last chunk
    byte: a row slice that holds chunk bytes then never clamps, and one
    that starts past its chunk's end may — it is masked out entirely.

    Blocks are fetched per step as contiguous rows via vmap'd
    dynamic_slice rather than an element gather (on-chip rates of the
    two: not measured), ``unroll`` blocks per step to amortize loop
    overhead.  CPU defaults to unroll=1 (its compress is an inner scan;
    big unrolled bodies blow up the CPU pass pipeline)."""
    if unroll is None:
        unroll = 16 if jax.default_backend() != "cpu" else 1
    unroll = max(1, unroll)
    n_steps = (n_blocks + unroll - 1) // unroll
    N = starts.shape[0]
    L = lengths
    nblocks = (L + 8) // 64 + 1                      # data + pad + bitlen
    bitlen_lo = (L.astype(jnp.uint32) << np.uint32(3))
    j = jnp.arange(64, dtype=jnp.int32)
    widx = jnp.arange(16, dtype=jnp.int32)
    row = unroll * 64
    if row > SLACK_BYTES:
        raise ValueError(f"unroll {unroll} reads past the stream's slack")

    def step(ti, state):
        offs = starts + ti * row
        rows = jax.vmap(
            lambda o: jax.lax.dynamic_slice(stream, (o,), (row,)))(offs)
        for u in range(unroll):
            t = ti * unroll + u
            raw = rows[:, u * 64:(u + 1) * 64]       # uint8[N,64]
            local = t * 64 + j                       # int32[64]
            lcl = local[None, :]
            Lb = L[:, None]
            byte = jnp.where(lcl < Lb, raw, jnp.uint8(0))
            byte = jnp.where(lcl == Lb, jnp.uint8(0x80), byte)
            q = byte.reshape(N, 16, 4).astype(jnp.uint32)
            words = (q[..., 0] << np.uint32(24)) | (q[..., 1] << np.uint32(16)) \
                | (q[..., 2] << np.uint32(8)) | q[..., 3]
            is_last = (t == nblocks - 1)[:, None]    # bool[N,1]
            words = jnp.where(is_last & (widx == 14)[None, :],
                              jnp.uint32(0), words)
            words = jnp.where(is_last & (widx == 15)[None, :],
                              bitlen_lo[:, None], words)
            state = _compress(state, words, t < nblocks)
        return state

    # derive the init carry from the inputs so it inherits their varying
    # manual axes under shard_map (loop carry-in/out types must match,
    # including the varying-axis annotation)
    vma_seed = (stream[0].astype(jnp.uint32)
                + starts[0].astype(jnp.uint32)) * jnp.uint32(0)
    init = jnp.broadcast_to(jnp.asarray(_H0), (N, 8)).astype(jnp.uint32) \
        + vma_seed
    return jax.lax.fori_loop(0, n_steps, step, init)


# jitted entry for standalone use; inside shard_map call _sha256_scan_impl
# directly (a nested jit inside shard_map deadlocks the CPU backend)
_sha256_scan = jax.jit(_sha256_scan_impl, static_argnames=("unroll",))


# multi-chip dispatch evidence, padding occupancy and the five phase
# clocks, mirror of rolling_hash.stats: ``slabs`` staging buffers went to
# the device, ``dispatches`` programs ran over them (one per length
# bucket).  ``host_*``: what ``sha256_chunks`` hashed on the host —
# batches, chunks, bytes and the calling threads' seconds.  The device's
# counters are written on one thread (the feeder's); the host's on every
# writer's, under ``_host_lock``.
stats = trace.device_stats("sha", {
    "mesh_dispatches": 0, "mesh_devices": 0, "mesh_shard_devices": 0,
    "slabs": 0, "dispatches": 0, "rows": 0, "padded_rows": 0, "bytes": 0,
    "padded_bytes": 0,
    "host_batches": 0, "host_rows": 0, "host_bytes": 0, "host_s": 0.0})
_host_lock = threading.Lock()

# The whole jit key of ``_sha256_scan`` is (staging-buffer length, padded
# row count), and both come from these two short lists — a flush whose
# size was seen before compiles nothing.  A staging buffer is filled up
# to SLAB_BYTES / the largest row class; only a single chunk larger than
# SLAB_BYTES takes a longer one.
_ROW_CLASSES = (8, 64, 512, 4096)
SLAB_BYTES = 64 << 20
_SLAB_CLASSES = tuple(n + SLACK_BYTES for n in
                      (16 << 20, SLAB_BYTES, 256 << 20, 1 << 30))


def _hash_slab(views: list, unroll: int | None) -> list[bytes]:
    """Pack ``views`` (uint8 arrays) back to back into ONE class-sized
    staging buffer, upload it once, and hash it in one dispatch per
    length bucket (next power of two of the block count: lanes of a
    dispatch run in lockstep, so a bucket wastes under half its steps on
    the shorter chunks).  Every bucket runs the same compiled program."""
    with trace.round_trip("device.sha", stats) as rt:
        with rt.phase("pack"):
            lens = np.array([len(v) for v in views], dtype=np.int64)
            total = int(lens.sum())
            slab = np.zeros(_class_for(total + SLACK_BYTES, _SLAB_CLASSES),
                            dtype=np.uint8)
            starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
            for v, off in zip(views, starts):
                slab[off:off + len(v)] = v
            nblocks = (lens + 8) // 64 + 1
            buckets: dict[int, list[int]] = {}
            for i, nb in enumerate(nblocks):
                buckets.setdefault(int(nb - 1).bit_length(), []).append(i)
        rt.attrs["slab_bytes"] = len(slab)
        # the mean number of lanes that carry data while the programs
        # run: each runs for its longest chunk's block count, whatever
        # its rows — what a kernel PR has to raise, or make matter less
        rt.attrs["lanes_busy"] = round(total / (64 * sum(
            int(nblocks[idxs].max()) for idxs in buckets.values())), 2)
        # multi-chip: rows shard over the data mesh, the buffer is
        # replicated (per-row slices are local reads); host arrays go
        # straight to their devices — through a one-device array they
        # would compile a slicing program per shape
        from ..parallel.mesh import data_mesh
        mesh = data_mesh()
        with rt.phase("h2d"):
            if mesh is not None and _ROW_CLASSES[0] % mesh.size == 0:
                from jax.sharding import NamedSharding, PartitionSpec as P
                row_sharding = NamedSharding(mesh, P("data"))
                ds = jax.device_put(slab, NamedSharding(mesh, P()))
            else:
                row_sharding = None
                ds = jnp.asarray(slab)
            ds.block_until_ready()
        rt.add(slabs=1, bytes=total, padded_bytes=len(slab))
        out: list[bytes | None] = [None] * len(views)
        for _, idxs in sorted(buckets.items()):
            with rt.phase("pack"):
                n_pad = _class_for(len(idxs), _ROW_CLASSES)
                bs = np.zeros(n_pad, dtype=np.int32)
                bl = np.zeros(n_pad, dtype=np.int32)
                bs[:len(idxs)] = starts[idxs]
                bl[:len(idxs)] = lens[idxs]
            rt.shape = f"slab={len(slab) >> 20} MiB rows={n_pad}"
            with rt.phase("h2d"):
                if row_sharding is None:
                    dbs, dbl = jnp.asarray(bs), jnp.asarray(bl)
                else:
                    dbs = jax.device_put(bs, row_sharding)
                    dbl = jax.device_put(bl, row_sharding)
                # the phase boundaries, here and below: one wait for the
                # copies and one for the program per dispatch of up to
                # 4096 chunks, so that each is timed apart
                jax.block_until_ready((dbs, dbl))
            if row_sharding is not None:
                stats["mesh_dispatches"] += 1
                stats["mesh_devices"] = mesh.size
                stats["mesh_shard_devices"] = len(
                    {s.device for s in dbs.addressable_shards})
            rt.add(dispatches=1, rows=len(idxs), padded_rows=n_pad)
            with rt.phase("device"):
                ddig = _sha256_scan(ds, dbs, dbl,
                                    np.int32(nblocks[idxs].max()),
                                    unroll=unroll)
                # pbslint: disable=no-hostsync-in-hot-loop
                ddig.block_until_ready()
            with rt.phase("d2h"):
                # deliberate batched sync: ONE device→host transfer per
                # dispatch (the digests must land on the host), not a
                # per-chunk sync
                # pbslint: disable=no-hostsync-in-hot-loop
                dig = np.asarray(ddig)
            with rt.phase("unpack"):
                for k, i in enumerate(idxs):
                    out[i] = dig[k].astype(">u4").tobytes()
    return out  # type: ignore[return-value]


def sha256_chunks_device(chunks: list, *,
                         unroll: int | None = None) -> list[bytes]:
    """SHA-256 of each chunk buffer (bytes-like or uint8 array), in input
    order, by the jax program.  Chunks are packed in order into as few
    staging buffers as hold them."""
    views = [c if isinstance(c, np.ndarray) else np.frombuffer(c, np.uint8)
             for c in chunks]
    if any(len(v) > MAX_CHUNK_BYTES for v in views):
        raise ValueError("chunk length out of supported range")
    out: list[bytes] = []
    lo = 0
    while lo < len(views):
        hi, used = lo, 0
        while hi < len(views) and hi - lo < _ROW_CLASSES[-1] and (
                hi == lo or used + len(views[hi]) <= SLAB_BYTES):
            used += len(views[hi])
            hi += 1
        out.extend(_hash_slab(views[lo:hi], unroll))
        lo = hi
    return out


def sha256_chunks(chunks: list) -> list[bytes]:
    """SHA-256 of each chunk buffer (bytes-like or uint8 array), in input
    order: ``hashlib.sha256`` per chunk on the calling thread (OpenSSL
    releases the interpreter lock while it hashes, so writers' batches
    run side by side on the host's cores)."""
    if not chunks:
        return []
    total = sum(len(c) for c in chunks)
    with trace.span("host.sha", rows=len(chunks), bytes=total):
        t0 = time.perf_counter()
        out = [hashlib.sha256(c).digest() for c in chunks]
        took = time.perf_counter() - t0
    with _host_lock:
        stats["host_batches"] += 1
        stats["host_rows"] += len(chunks)
        stats["host_bytes"] += total
        stats["host_s"] += took
    return out


def sha256_stream_chunks(stream, bounds: list[tuple[int, int]]) -> list[bytes]:
    """SHA-256 of ``stream[s:e]`` for each (s, e) in bounds, in input
    order.  ``stream`` may be bytes / numpy uint8 / a jax uint8 array
    (which is brought to the host)."""
    if isinstance(stream, (bytes, bytearray, memoryview)):
        stream = np.frombuffer(stream, dtype=np.uint8)
    stream = np.asarray(stream)
    if any(e < s or e - s > MAX_CHUNK_BYTES for s, e in bounds):
        raise ValueError("chunk length out of supported range")
    return sha256_chunks([stream[s:e] for s, e in bounds])


def sha256_streams_chunks(streams: list, bounds_per_stream: list,
                          ) -> list[list[bytes]]:
    """Cross-stream digesting: every stream's chunks in one batch.
    Returns per-stream digest lists in input order."""
    arrs = [np.frombuffer(s, dtype=np.uint8)
            if isinstance(s, (bytes, bytearray, memoryview)) else np.asarray(s)
            for s in streams]
    flat = sha256_chunks([a[lo:hi]
                          for a, bounds in zip(arrs, bounds_per_stream)
                          for lo, hi in bounds])
    out: list[list[bytes]] = []
    k = 0
    for bounds in bounds_per_stream:
        out.append(flat[k:k + len(bounds)])
        k += len(bounds)
    return out
