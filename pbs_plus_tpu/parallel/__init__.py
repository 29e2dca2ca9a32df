"""Multi-chip parallelism: mesh construction, sequence-parallel CDC, and
the distributed chunk index.

Axes (SURVEY §2.10's TPU mapping):

- ``data``  — agent fan-in: independent agent streams batch across chips
              (the reference's N-agents × per-job-session concurrency).
- ``index`` — the chunk index's table split across chips by bucket range
              (``ops/cuckoo.py``: the server's own index, where one chip
              cannot hold it, and ``multichip_dedup_step``); probes resolve
              with a psum over partial hits (ICI collective, not DCN).
- ``seq``   — one very long stream sharded along its byte axis with a
              63-byte halo exchange (ppermute) — the long-context analog
              (SURVEY §5.7: segment-parallel CDC across devices).

Everything compiles under ``jax.sharding.Mesh`` + ``shard_map``; tested on
a virtual 8-device CPU mesh (tests/conftest.py) and dry-run by the driver
via __graft_entry__.dryrun_multichip.
"""

from .mesh import make_mesh, make_seq_mesh
from .sp_chunker import sp_candidate_mask, sp_chunk_stream
from .dist_index import (
    DistIndexClient, DistIndexError, IndexShardServer, ShardMap,
    parse_endpoints,
)
from .sharded_step import multichip_dedup_step, build_step_inputs

__all__ = [
    "make_mesh", "make_seq_mesh",
    "sp_candidate_mask", "sp_chunk_stream",
    "DistIndexClient", "DistIndexError", "IndexShardServer",
    "ShardMap", "parse_endpoints",
    "multichip_dedup_step", "build_step_inputs",
]
