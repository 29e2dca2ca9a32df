"""pbs-plus-tpu — a TPU-native re-design of PBS Plus (pbs-plus/pbs-plus).

PBS Plus is an agent-based, file-level backup/restore fabric for Proxmox
Backup Server (reference: /root/reference, ~86k LoC of Go).  This package
rebuilds its full capability surface TPU-first:

- **System plane** (agents, aRPC fabric, scheduler, server, archive formats)
  in Python/asyncio with C++ native hot paths — the reference's Go role.
- **Data plane** (content-defined chunking, SHA-256 fingerprinting, chunk
  index probing, similarity sketching) as batched JAX programs on TPU,
  sharded over `jax.sharding.Mesh` axes (agent fan-in = batch axis, sharded
  chunk index = index axis, long streams = sequence axis with halo exchange).

Layer map (mirrors SURVEY.md §1):

  utils/     L0 foundation (conf, log, crypto, calendar, safemap, validate)
  arpc/      L1 communication backend (mTLS + multiplexed streams, router)
  pxar/      L2 archive & dedup data plane (format, datastore, transfer,
             backupproxy LocalStore/PBSStore, chunker interface)
  ops/       TPU kernels: rolling-hash CDC, batched SHA-256, cuckoo probe,
             simhash — the native-accelerated equivalent of the reference's
             external chunker/hash libraries
  models/    flagship jittable pipelines (DedupPipeline, VerifyPipeline,
             SimilarityModel) — the TPU "model families"
  parallel/  mesh construction, shardings, sequence-parallel CDC,
             distributed chunk index (all_to_all routing)
  agent/     L3 agent (bootstrap, control session, agentfs, snapshots)
  server/    L4/L5 server core (store, jobs, scheduler, backup/restore/
             verification jobs, web API, metrics, notification)
  sidecar/   the gRPC shim between the system plane and the JAX data plane
"""

__version__ = "0.1.0"
