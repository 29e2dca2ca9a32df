"""Multi-chip tests on the virtual 8-device CPU mesh: sequence-parallel
chunker parity, the full sharded step (its probe is the index's own
sharded lookup: tests/test_sharded_table.py), and the graft entry
points."""

import hashlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from pbs_plus_tpu.chunker import ChunkerParams, chunk_bounds
from pbs_plus_tpu.ops.cuckoo import CuckooIndex
from pbs_plus_tpu.parallel import (
    build_step_inputs, make_mesh, make_seq_mesh, multichip_dedup_step,
    sp_chunk_stream,
)

P = ChunkerParams(avg_size=4 << 10)

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")


def _data(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8).tobytes()


def test_sp_chunker_matches_cpu():
    mesh = make_seq_mesh(8)
    data = _data(300_000, seed=1)        # not divisible by 8 → padded
    cuts = sp_chunk_stream(mesh, data, P)
    assert cuts == [e for _, e in chunk_bounds(data, P)]


def test_multichip_step():
    mesh = make_mesh(8)
    index = CuckooIndex(n_buckets=1 << 12)
    step = multichip_dedup_step(mesh, chunk_len=4096, n_buckets=index.n_buckets)
    streams, table, idx_tab, proj, host = build_step_inputs(
        mesh, batch=8, seg_len=1 << 14, params=P, index=index)
    cand, hits, sketches, total = step(
        streams, table, idx_tab, proj,
        jnp.uint32(P.mask), jnp.uint32(P.magic))
    cand = np.asarray(cand)
    assert int(total) == cand.sum()
    assert not np.asarray(hits).any()
    # insert stream 0's head digest → probe hits next step
    d0 = hashlib.sha256(host[0, :4096].tobytes()).digest()
    index.insert(d0)
    _, _, idx_tab2, _, _ = build_step_inputs(
        mesh, batch=8, seg_len=1 << 14, params=P, index=index)
    _, hits2, _, _ = step(streams, table, idx_tab2, proj,
                          jnp.uint32(P.mask), jnp.uint32(P.magic))
    hits2 = np.asarray(hits2)
    assert hits2[0] and not hits2[1:].any()
    # per-stream candidate counts match the CPU chunker's candidate sets
    from pbs_plus_tpu.chunker import candidates
    for i in range(8):
        want = len(candidates(host[i].tobytes(), P, force_numpy=True))
        assert cand[i] == want


def test_graft_entry_points():
    import __graft_entry__ as g
    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)
    cand_count, digests, hits, sketches = out
    # digest parity with hashlib on the example args
    streams = np.asarray(args[0])
    want = hashlib.sha256(streams[0, :4096].tobytes()).digest()
    assert np.asarray(digests)[0].tobytes() == want
    g.dryrun_multichip(8)
