"""The full multi-chip dedup step — every dense pass of the pipeline under
one jit over a (data × index) mesh.

This is the program the driver's ``dryrun_multichip`` compiles: agent
streams sharded over ``data``, the cuckoo table sharded over ``index``,
candidate masks + SHA-256 + probe (psum over ICI) + simhash sketches (MXU)
in a single shard_map'd step.  Variable-length cut selection stays on the
host (sparse, O(chunks)), so the in-jit digest pass here hashes the
fixed-length head segment of each stream — the full variable-length path
runs through models.DedupPipeline which calls the same kernels with
host-chosen bounds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..chunker.spec import ChunkerParams
from ..ops.cuckoo import CuckooIndex, _lookup_shard
from ..ops.rolling_hash import _candidate_mask_impl, device_tables
from ..ops.sha256 import _sha256_scan_impl
from ..ops.similarity import simhash_projection


def _words_to_bytes(words: jax.Array) -> jax.Array:
    """uint32[N,8] big-endian digest words → uint8[N,32]."""
    shifts = jnp.array([24, 16, 8, 0], dtype=jnp.uint32)
    b = (words[:, :, None] >> shifts[None, None, :]) & jnp.uint32(0xFF)
    return b.reshape(words.shape[0], 32).astype(jnp.uint8)


def _step_body(streams, table, index_table, proj, mask, magic,
               *, chunk_len: int, n_buckets: int,
               data_axis: str, index_axis: str):
    b_local, S = streams.shape
    # 1) candidate mask (dense pass 1)
    hit = _candidate_mask_impl(streams, table, mask, magic)
    cand_count = jnp.sum(hit, axis=1, dtype=jnp.int32)
    # 2) SHA-256 of each stream's head segment (dense pass 2)
    flat = streams.reshape(-1)
    starts = jnp.arange(b_local, dtype=jnp.int32) * S
    lens = jnp.full((b_local,), chunk_len, dtype=jnp.int32)
    words = _sha256_scan_impl(flat, starts, lens, (chunk_len + 8) // 64 + 1)
    digests = _words_to_bytes(words)
    # 3) distributed index probe: partial hits psum over the index axis
    part = _lookup_shard(index_table, digests, n_buckets, index_axis)
    hits = jax.lax.psum(part.astype(jnp.int32), index_axis) > 0
    # 4) simhash sketches (MXU matmul)
    bits = ((digests[:, :, None] >> jnp.arange(7, -1, -1, dtype=jnp.uint8)
             [None, None, :]) & jnp.uint8(1)).reshape(b_local, 256)
    scores = (bits.astype(jnp.float32) * 2.0 - 1.0) @ proj
    sk_bits = (scores >= 0).astype(jnp.uint32)
    k = proj.shape[1]
    shifts32 = jnp.arange(31, -1, -1, dtype=jnp.uint32)
    sketches = jnp.sum(sk_bits.reshape(b_local, k // 32, 32)
                       << shifts32[None, None, :], axis=-1, dtype=jnp.uint32)
    # 5) global stat rides the data axis
    total_candidates = jax.lax.psum(jnp.sum(cand_count), data_axis)
    return cand_count, hits, sketches, total_candidates


def multichip_dedup_step(mesh: Mesh, *, chunk_len: int, n_buckets: int,
                         data_axis: str = "data", index_axis: str = "index"):
    """Build the jitted sharded step.  Returns
    ``step(streams, table, index_table, proj, mask, magic) ->
    (cand_count[B], hits[B], sketches[B, k/32], total_candidates)``."""
    body = functools.partial(
        _step_body, chunk_len=chunk_len, n_buckets=n_buckets,
        data_axis=data_axis, index_axis=index_axis)
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(data_axis, None), P(None, None),
                  P(index_axis, None, None), P(), P(), P()),
        out_specs=(P(data_axis), P(data_axis), P(data_axis, None), P()),
    )
    return jax.jit(fn)


def build_step_inputs(mesh: Mesh, *, batch: int, seg_len: int,
                      params: ChunkerParams, index: CuckooIndex,
                      simhash_bits: int = 64, seed: int = 0,
                      data_axis: str = "data", index_axis: str = "index"):
    """Construct correctly-sharded inputs for multichip_dedup_step."""
    nd = mesh.shape[data_axis]
    if batch % nd:
        raise ValueError("batch must divide by data-axis size")
    rng = np.random.default_rng(seed)
    streams = rng.integers(0, 256, (batch, seg_len), dtype=np.uint8)
    s_sharded = jax.device_put(
        jnp.asarray(streams), NamedSharding(mesh, P(data_axis, None)))
    table = device_tables(params)
    idx_tab = jax.device_put(
        jnp.asarray(index._table),
        NamedSharding(mesh, P(index_axis, None, None)))
    proj = simhash_projection(simhash_bits)
    return s_sharded, table, idx_tab, proj, streams
