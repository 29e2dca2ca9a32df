"""Fused batched ingest op — ragged cross-session packing (ISSUE 13).

The ingest hot path is four separately-batched stages — CDC candidate
scan, SHA-256, dedup-index probe, similarity presketch — each dispatched
per session, so a fleet of N agents pays O(N * stages) kernel/host
dispatches per flush.  This module is the *mechanism* half of the fix
(the cross-session policy lives in ``pxar/ingestbatch.py``): pack many
sessions' pending stream windows into ONE ragged batch — row offsets +
lengths over a single packed buffer, the Ragged Paged Attention shape
(PAPERS.md 2604.15464) — and run the scan and digest stages as one
batched pass each.

Packing layout (``pack_rows``)::

    [ pad | tail_0 | row_0 | pad | tail_1 | row_1 | ... | pow2 pad ]
            '------ 63 B ------'

Every row owns a fixed ``WINDOW - 1``-byte halo slot holding its
stream's real scan tail right-aligned (zero-filled when the stream has
less history).  Because the buzhash is position-local over a 64-byte
window (chunker/spec.py), one flat scan over the packed buffer computes
every row's candidates with bit-exact per-stream context; positions
whose window crosses a row seam or exceeds the row's real history are
masked out afterwards (``_split_ends``), so padding and halo bytes can
never leak a candidate into a row's results.

Twins (the ``ops/cuckoo.lookup_host`` discipline):

- **host** — ``chunker.cpu.candidates`` over the packed buffer (native
  AVX-512 kernel when available, blocked numpy otherwise; bit-identical
  by the chunker parity gates) + one hashlib pass for digests.
- **device** — ``ops/rolling_hash.candidate_mask`` over the packed
  buffer (one jitted dispatch; pow2-padded so jit cache keys stay
  bounded) + ``ops/sha256.sha256_chunks`` (the host's SHA-256 too since
  PR 25: the device's program loses to it).  Picked when jax's backend
  is an accelerator (``utils.jaxenv.pick_twin``, which counts the
  choice); parity is pinned on the CPU backend in
  tests/test_ingest_fused.py.

``stats`` counts batched-stage dispatches — one per entry into a
batched stage implementation (the pack/dispatch/unpack boundary);
packing accounting (rows/bytes/padding → occupancy) lives on
``RaggedBatch`` and is accumulated once, by the collector's metrics.
bench ``_ingest_fusion_bench`` gates the dispatch-per-chunk ratio
against the per-session staged path.
"""

from __future__ import annotations

import hashlib
import threading

import numpy as np

from ..chunker.cpu import candidates as _host_candidates
from ..chunker.spec import WINDOW, ChunkerParams
from ..utils import jaxenv

HALO = WINDOW - 1

# batched-stage dispatch accounting (reset-free cumulative).  ONLY the
# dispatch counters live here; packing accounting (rows/bytes/padding/
# occupancy) has one source of truth — the per-batch fields on
# ``RaggedBatch``, accumulated by pxar/ingestbatch.py's collector
# metrics and rendered by server/metrics.py.
stats = {
    "scan_dispatches": 0,          # guarded-by: _stats_lock
    "sha_dispatches": 0,           # guarded-by: _stats_lock
}
# dispatches arrive from concurrent flusher threads (one per collector,
# but a server can host several stores); dict += is not GIL-atomic
_stats_lock = threading.Lock()


def _bump(counter: str) -> None:
    with _stats_lock:
        stats[counter] += 1


class RaggedBatch:
    """One packed ragged batch of stream windows (module docstring).

    ``buf``       uint8[total] — the packed scan buffer
    ``starts``    int64[N] — packed offset of each row's first byte
    ``lens``      int64[N] — row payload lengths (all > 0)
    ``hist_lens`` int64[N] — real history bytes available to each row,
                  clamped to ``HALO`` (positions needing more are invalid)
    ``bases``     int64[N] — absolute stream offset of each row's first
                  byte (candidate ends are returned in this coordinate)
    ``padding_bytes`` — non-payload bytes in ``buf`` (halo slots + any
                  alignment pad): the packing-overhead half of the
                  occupancy metric
    """

    __slots__ = ("buf", "starts", "lens", "hist_lens", "bases",
                 "padding_bytes")

    def __init__(self, buf, starts, lens, hist_lens, bases,
                 padding_bytes: int):
        self.buf = buf
        self.starts = starts
        self.lens = lens
        self.hist_lens = hist_lens
        self.bases = bases
        self.padding_bytes = padding_bytes


def pack_rows(rows: "list[list]", tails: "list[bytes]",
              hist_lens: "list[int]", bases: "list[int]") -> RaggedBatch:
    """Pack N stream windows into one ragged scan buffer.

    ``rows[i]`` is a list of bytes-like blocks (a stream's unscanned
    window, kept as blocks so the only copy is the pack itself);
    ``tails[i]`` holds up to ``HALO`` bytes of real preceding stream
    context; ``hist_lens[i]`` is the run history length (clamped to
    ``HALO`` here); ``bases[i]`` the absolute stream offset of the
    row's first byte.  Zero-length rows are the caller's job to filter.
    """
    n = len(rows)
    lens = np.empty(n, dtype=np.int64)
    for i, blocks in enumerate(rows):
        lens[i] = sum(len(b) for b in blocks)
        if lens[i] <= 0:
            raise ValueError("pack_rows: empty row (caller filters)")
    starts = np.empty(n, dtype=np.int64)
    cursor = 0
    for i in range(n):
        cursor += HALO
        starts[i] = cursor
        cursor += int(lens[i])
    total = cursor
    buf = np.zeros(total, dtype=np.uint8)
    for i, blocks in enumerate(rows):
        tail = tails[i][-HALO:] if tails[i] else b""
        if tail:
            s = int(starts[i])
            buf[s - len(tail):s] = np.frombuffer(tail, dtype=np.uint8)
        off = int(starts[i])
        for b in blocks:
            nb = len(b)
            buf[off:off + nb] = np.frombuffer(b, dtype=np.uint8)
            off += nb
    payload = int(lens.sum())
    return RaggedBatch(
        buf, starts, lens,
        np.minimum(np.asarray(hist_lens, dtype=np.int64), HALO),
        np.asarray(bases, dtype=np.int64),
        total - payload)


def _split_ends(batch: RaggedBatch, packed_ends: np.ndarray) -> "list[np.ndarray]":
    """Map candidate ends in packed coordinates back to per-row absolute
    stream ends, dropping every halo/seam/short-history position — the
    "padding never leaks" guarantee."""
    out = [np.empty(0, dtype=np.int64) for _ in range(len(batch.starts))]
    if not len(packed_ends):
        return out
    pos = np.asarray(packed_ends, dtype=np.int64) - 1
    idx = np.searchsorted(batch.starts, pos, side="right") - 1
    idx = np.clip(idx, 0, len(batch.starts) - 1)
    rel = pos - batch.starts[idx]
    valid = (rel >= 0) & (rel < batch.lens[idx]) \
        & (rel + batch.hist_lens[idx] >= HALO)
    idx, rel = idx[valid], rel[valid]
    for i in range(len(batch.starts)):
        sel = idx == i
        if sel.any():
            out[i] = (batch.bases[i] + rel[sel] + 1).astype(np.int64)
    return out


def scan_rows_host(batch: RaggedBatch,
                   params: ChunkerParams) -> "list[np.ndarray]":
    """One flat host scan over the packed buffer (numpy twin; the
    native SIMD kernel rides underneath when available — bit-identical
    by the chunker parity gates)."""
    _bump("scan_dispatches")
    ends = _host_candidates(batch.buf, params)
    return _split_ends(batch, ends)


def scan_rows_device(batch: RaggedBatch,
                     params: ChunkerParams) -> "list[np.ndarray]":
    """One jitted device scan over the packed buffer (jax twin).  The
    buffer is pow2-padded so the jit cache stays bounded; pad positions
    fall outside every row and are dropped by ``_split_ends``.  (The
    jit pad is a compile-cache artifact, deliberately NOT counted as
    packing overhead — ``RaggedBatch.padding_bytes`` / the collector's
    occupancy gauge measure per-row packing waste only.)"""
    import jax.numpy as jnp

    from . import rolling_hash as rh
    _bump("scan_dispatches")
    buf = batch.buf
    n = len(buf)
    n_pad = max(1 << 12, 1 << int(n - 1).bit_length()) if n > 1 else 1 << 12
    if n_pad != n:
        buf = np.concatenate([buf, np.zeros(n_pad - n, dtype=np.uint8)])
    hits = np.asarray(rh.candidate_mask(
        jnp.asarray(buf), rh.device_tables(params),
        params.mask, params.magic))
    ends = np.flatnonzero(hits).astype(np.int64) + 1
    return _split_ends(batch, ends)


def digest_chunks_host(chunks: "list") -> "list[bytes]":
    """SHA-256 over a whole chunk batch in one host pass (hashlib)."""
    _bump("sha_dispatches")
    return [hashlib.sha256(c).digest() for c in chunks]


def digest_chunks_device(chunks: "list") -> "list[bytes]":
    """SHA-256 over a whole chunk batch through ``ops/sha256.py``'s
    entry, counted in its ``stats`` (hashlib there as well, since the
    device program loses to it: PERF.md section 6, PR 25)."""
    from . import sha256 as _sha
    _bump("sha_dispatches")
    return _sha.sha256_chunks([bytes(c) for c in chunks])


def scan_rows(batch: RaggedBatch,
              params: ChunkerParams) -> "list[np.ndarray]":
    return (scan_rows_device if jaxenv.pick_twin("ingest.scan")
            else scan_rows_host)(batch, params)


def digest_chunks(chunks: "list") -> "list[bytes]":
    return (digest_chunks_device if jaxenv.pick_twin("ingest.sha")
            else digest_chunks_host)(chunks)
