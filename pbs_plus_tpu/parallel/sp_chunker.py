"""Sequence-parallel CDC: one long stream sharded along its byte axis.

The long-context analog (SURVEY §5.7): buzhash's sliding window makes
per-position hashes local to 64 bytes, so sharding a stream across chips
needs only a 63-byte halo from the left neighbor — one ``ppermute`` over
ICI — after which every shard evaluates its candidates independently.
Bit-identical to the single-device kernel and the CPU chunker
(tests/test_parallel.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..chunker.spec import WINDOW, ChunkerParams, select_cuts
from ..ops.rolling_hash import _candidate_mask_impl, device_tables


def _sp_mask_local(local: jax.Array, table: jax.Array, mask: jax.Array,
                   magic: jax.Array, axis_name: str) -> jax.Array:
    """Per-shard body: halo exchange + local candidate mask."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    tail = local[-(WINDOW - 1):]
    # send my tail to my right neighbor; shard 0 receives zeros
    halo = jax.lax.ppermute(tail, axis_name,
                            [(i, i + 1) for i in range(n - 1)])
    hit = _candidate_mask_impl(local[None], table, mask, magic,
                               history=halo[None])[0]
    # shard 0's halo is synthetic zeros: its first W-1 stream positions
    # have no full window → invalid
    pos = jnp.arange(local.shape[0], dtype=jnp.int32)
    hit = hit & ((idx > 0) | (pos >= WINDOW - 1))
    return hit


def sp_candidate_mask(mesh: Mesh, data: jax.Array, params: ChunkerParams,
                      *, axis_name: str = "seq") -> jax.Array:
    """Candidate mask of a single stream uint8[S] sharded over ``axis_name``
    (S must divide evenly by the axis size; pad on host if needed).
    Returns bool[S] with the same sharding."""
    table = device_tables(params)
    fn = shard_map(
        functools.partial(_sp_mask_local, axis_name=axis_name),
        mesh=mesh,
        in_specs=(P(axis_name), P(), P(), P()),
        out_specs=P(axis_name),
    )
    return jax.jit(fn)(data, table, jnp.uint32(params.mask),
                       jnp.uint32(params.magic))


def sp_chunk_stream(mesh: Mesh, data: bytes | np.ndarray,
                    params: ChunkerParams, *,
                    axis_name: str = "seq") -> list[int]:
    """Sequence-parallel chunking of one long stream → absolute cut offsets
    (device-parallel candidates + the shared host greedy pass)."""
    arr = np.frombuffer(data, dtype=np.uint8) if not isinstance(data, np.ndarray) else data
    n = len(arr)
    n_dev = mesh.devices.size
    pad = (-n) % n_dev
    if pad:
        arr = np.concatenate([arr, np.zeros(pad, dtype=np.uint8)])
    sharding = NamedSharding(mesh, P(axis_name))
    d = jax.device_put(jnp.asarray(arr), sharding)
    hit = np.asarray(sp_candidate_mask(mesh, d, params, axis_name=axis_name))
    ends = np.nonzero(hit[:n])[0] + 1
    return select_cuts(ends.astype(np.int64), n, params)
