"""TPU data-plane kernels (jnp).

The native-accelerated equivalent of the reference's chunker/hash hot loops
(SURVEY §2.10: "the hard kernel" — segment-parallel CDC; §3.4: the commit
pipeline's chunk+hash of new payload).  Everything here is batch-first and
jit-compatible: static shapes, masked variable-length work, no host syncs
inside the compiled step.

- rolling_hash: buzhash candidate masks via log2(W) doubling passes —
  the position-local closed form from chunker/spec.py makes per-position
  hashes embarrassingly parallel (no sequential rolling state).
- sha256: whole-chunk SHA-256 over batches of variable-length chunks,
  blocks gathered on device from the resident stream, SHA padding applied
  with masks, lax.scan over block index.
- cuckoo: on-device two-choice chunk-index probe (vmap'd gather+compare),
  host-authoritative insert mirror.
- similarity: simhash sketches (MXU projection matmul) + minhash
  signatures over chunk-digest sets (BASELINE.json config #5).
"""

from .rolling_hash import candidate_mask, candidate_ends_host
from .sha256 import sha256_chunks, sha256_stream_chunks
from .cuckoo import CuckooIndex, buckets_for_bytes, lookup_host
from .similarity import simhash_sketch, minhash_signature, pairwise_hamming

__all__ = [
    "candidate_mask", "candidate_ends_host",
    "sha256_chunks", "sha256_stream_chunks",
    "CuckooIndex", "buckets_for_bytes", "lookup_host",
    "simhash_sketch", "minhash_signature", "pairwise_hamming",
]
