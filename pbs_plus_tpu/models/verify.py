"""VerifyPipeline — batched re-hash verification of stored chunks/files.

Reference capability: the verification job's server-side sha256 of sampled
files (minio sha256-simd, /root/reference/internal/server/verification/
job.go:765-1273) and the commit engine's xxh3 verify pool
(/root/reference/internal/pxarmount/commit_orchestrate.go:481-562).  Here
both become one batched device pass: re-hash chunk payloads and compare to
the index digests — thousands of chunks per dispatch instead of a
min(NumCPU,16) worker pool.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import numpy as np

from ..ops.sha256 import sha256_chunks, sha256_stream_chunks
from ..utils import jaxenv
from ..utils.log import L


@dataclass
class VerifyResult:
    checked: int = 0
    corrupt: list[int] = field(default_factory=list)   # indexes of failures
    # archive paths for the corrupt indexes — filled by verify_snapshot
    # (the sampled set is random, so bare indexes are unactionable in a
    # stored report; operators need the path)
    corrupt_paths: list[str] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.corrupt


class VerifyPipeline:
    """Batch verifier: compare recomputed digests against expected."""

    def verify_chunks(self, chunks: list[bytes],
                      expected: list[bytes]) -> VerifyResult:
        if len(chunks) != len(expected):
            raise ValueError("chunks/expected length mismatch")
        res = VerifyResult(checked=len(chunks))
        got = sha256_chunks(chunks)
        for i, (g, w) in enumerate(zip(got, expected)):
            if g != w:
                res.corrupt.append(i)
        return res

    def verify_stream(self, stream: bytes | np.ndarray,
                      bounds: list[tuple[int, int]],
                      expected: list[bytes]) -> VerifyResult:
        """Verify chunks of a device-resident stream without extraction."""
        if len(bounds) != len(expected):
            raise ValueError("bounds/expected length mismatch")
        res = VerifyResult(checked=len(bounds))
        got = sha256_stream_chunks(stream, bounds)
        for i, (g, w) in enumerate(zip(got, expected)):
            if g != w:
                res.corrupt.append(i)
        return res

    def verify_snapshot(self, reader, *, sample_rate: float = 1.0,
                        rng: np.random.Generator | None = None,
                        workers: int = 0) -> VerifyResult:
        """Spot-check a snapshot (SplitReader): systematic sampling of file
        entries, batched re-hash vs stored entry digests (reference:
        systematic/stratified file sampling, verification/job.go:41-130).

        ``workers > 1`` fetches file content / chunks on a thread pool
        (the reference's min(NumCPU,16) verify workers); verdicts are
        bit-identical to the sequential run — parallelism only reorders
        the IO, never the per-item check or the reported order.  All
        chunk reads go through the reader's chunk cache (verify-once:
        corruption surfaces as a load failure on the digest's FIRST
        read; resident chunks were verified when loaded)."""
        rng = rng or np.random.default_rng(0)
        files = [e for e in reader.entries()
                 if e.is_file and e.size and e.digest]
        if not files:
            # pxar2 archives carry no per-entry digest (the stock format
            # has none) — fall back to chunk-level verification against
            # the index digests, which is exactly what a stock PBS
            # verify job recomputes
            return self._verify_snapshot_chunks(reader, sample_rate, rng,
                                                workers=workers)
        if sample_rate < 1.0:
            k = max(1, int(len(files) * sample_rate))
            idx = np.sort(rng.choice(len(files), size=k, replace=False))
            files = [files[i] for i in idx]
        if workers and workers > 1 and len(files) > 1:
            with ThreadPoolExecutor(max_workers=workers,
                                    thread_name_prefix="verify") as pool:
                chunks = list(pool.map(reader.read_file, files))
        else:
            chunks = [reader.read_file(e) for e in files]
        res = self.verify_chunks(chunks, [e.digest for e in files])
        res.corrupt_paths = [files[i].path for i in res.corrupt]
        return res

    def _verify_snapshot_chunks(self, reader, sample_rate: float,
                                rng: np.random.Generator,
                                *, workers: int = 0) -> VerifyResult:
        digests: list[bytes] = []
        for index in (reader.meta_index, reader.payload_index):
            digests.extend(index.digest(i) for i in range(len(index.ends)))
        if sample_rate < 1.0 and digests:
            k = max(1, int(len(digests) * sample_rate))
            idx = np.sort(rng.choice(len(digests), size=k, replace=False))
            digests = [digests[i] for i in idx]
        digests = list(dict.fromkeys(digests))   # meta/payload may share
        res = VerifyResult(checked=len(digests))
        # batched device hashing only on an accelerator backend — the
        # jax SHA pipeline on the CPU backend is orders of magnitude
        # slower than hashlib (it exists for the TPU's batch geometry)
        use_device = jaxenv.pick_twin("verify.rehash")

        def fetch(d: bytes) -> bytes | None:
            # the cache path verifies sha256 on load (ChunkStore.get /
            # PBSReaderSource.get) and never admits a failed load, so a
            # successful fetch IS the verification verdict for d
            try:
                return reader.fetch_chunk(d)
            except Exception as e:
                L.debug("verify: chunk %s unreadable: %s", d.hex()[:16], e)
                return None

        pool = (ThreadPoolExecutor(max_workers=workers,
                                   thread_name_prefix="verify")
                if workers and workers > 1 and len(digests) > 1 else None)
        # waves bound in-flight decompressed memory (old code capped a
        # batch at 64 MiB of fetched bytes; 8 chunks ≤ 8×chunk_max keeps
        # the same order of magnitude with the pool).  Wave size is
        # FIXED — independent of the worker count — so device-flush
        # boundaries and therefore verdict order are bit-identical
        # between sequential and parallel runs.
        wave = 8
        pending: list[tuple[int, bytes, bytes]] = []    # device cross-check
        pending_bytes = 0
        batch_bytes = 64 << 20

        def flush_device() -> None:
            nonlocal pending, pending_bytes
            if not pending:
                return
            # device cross-check keeps the TPU batch-hash path
            # exercised; on CPU the load-time digest check above
            # already proved every fetched chunk
            sub = self.verify_chunks([g[2] for g in pending],
                                     [g[1] for g in pending])
            for j in sub.corrupt:
                res.corrupt.append(pending[j][0])
                res.corrupt_paths.append(f"chunk:{pending[j][1].hex()}")
            pending, pending_bytes = [], 0

        try:
            for base in range(0, len(digests), wave):
                batch = digests[base:base + wave]
                datas = list(pool.map(fetch, batch)) if pool is not None \
                    else [fetch(d) for d in batch]
                for j, (d, data) in enumerate(zip(batch, datas)):
                    if data is None:
                        res.corrupt.append(base + j)
                        res.corrupt_paths.append(f"chunk:{d.hex()}")
                    elif use_device:
                        pending.append((base + j, d, data))
                        pending_bytes += len(data)
                # non-device runs retain nothing: the fetch itself was
                # the verdict, and the bytes are released per wave
                if pending_bytes >= batch_bytes:
                    flush_device()
            flush_device()
        finally:
            if pool is not None:
                pool.shutdown(wait=False)
        return res
