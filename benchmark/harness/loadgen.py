"""Load generator: seeded source trees, a real server with real agents,
and the closed loop that drains the backlog.

The server, PKI, aRPC listener and ``AgentLifecycle`` agents are the
program's own; ``spawn_agent``, ``Compiles`` and ``device_counters`` are
copies of ``chip_smoke.py``'s (proven on the chip in PR 21) — the
yardstick lives here so that later PRs may change ``chip_smoke.py`` but
not what the benchmark measures with.

Everything that belongs to one deployment or one traffic mix is data:
``configs/<name>.json`` and ``traffic/<name>.json``, read by
``load_cell``.  A key this file does not know is an error, so a typo in a
data file can never silently measure the default.
"""

from __future__ import annotations

import asyncio
import json
import os
import statistics
import time
from dataclasses import dataclass, field

import numpy as np

MIB = 1 << 20
KIB = 1 << 10
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CONFIG_KEYS = {
    "source", "deployment", "reduced", "assumed", "guarantees",   # prose
    "server", "meta_chunk_avg", "agents", "trees_per_agent", "tree",
    "warm_tree", "warm_shapes", "index_preload_digests",
}
SERVER_KEYS = {"chunker", "chunk_avg", "max_concurrent", "dedup_index_mb"}
TREE_KEYS = {
    "lognormal": {"kind", "mu", "sigma", "own_files", "common_files",
                  "dirs", "compressible_every"},
}
WARM_SHAPE_KEYS = {"scan_rows", "scan_seg_kib", "sha_classes"}
TRAFFIC_KEYS = {"why", "arrival", "agents", "jobs_per_agent"}
WORKLOAD_KEYS = {"config", "traffic", "chips", "why"}


class DataFileError(ValueError):
    """A configuration, traffic or workload file says something the
    generator does not understand."""


def _read_json(kind: str, name: str) -> dict:
    path = os.path.join(BENCH_DIR, kind, f"{name}.json")
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
    except FileNotFoundError:
        raise DataFileError(f"no {kind} file {path}") from None
    if not isinstance(data, dict):
        raise DataFileError(f"{path}: want one JSON object")
    return data


def _check_keys(where: str, data: dict, known: set) -> None:
    unknown = sorted(set(data) - known)
    if unknown:
        raise DataFileError(f"{where}: unknown key(s) {unknown}; "
                            f"known: {sorted(known)}")


def check_config(name: str, cfg: dict) -> dict:
    _check_keys(f"config {name}", cfg, CONFIG_KEYS)
    for need in ("server", "agents", "trees_per_agent", "tree",
                 "meta_chunk_avg"):
        if need not in cfg:
            raise DataFileError(f"config {name}: missing {need!r}")
    _check_keys(f"config {name}.server", cfg["server"], SERVER_KEYS)
    for key in ("tree", "warm_tree"):
        tree = cfg.get(key)
        if tree is None:
            continue
        kind = tree.get("kind")
        if kind not in TREE_KEYS:
            raise DataFileError(f"config {name}.{key}: unknown tree kind "
                                f"{kind!r}; known: {sorted(TREE_KEYS)}")
        _check_keys(f"config {name}.{key}", tree, TREE_KEYS[kind])
        own = tree["own_files"]
        if isinstance(own, list) and len(own) != cfg["agents"]:
            raise DataFileError(f"config {name}.{key}.own_files: "
                                f"{len(own)} counts for {cfg['agents']} "
                                "agents")
    _check_keys(f"config {name}.warm_shapes", cfg.get("warm_shapes", {}),
                WARM_SHAPE_KEYS)
    return cfg


def check_traffic(name: str, traffic: dict) -> dict:
    _check_keys(f"traffic {name}", traffic, TRAFFIC_KEYS)
    if traffic.get("arrival") != "burst":
        raise DataFileError(f"traffic {name}: arrival must be 'burst' (one "
                            "job per active agent at t0, the agent's next "
                            "on publish)")
    return traffic


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict


def load_cell(name: str) -> Cell:
    """Find a cell, its configuration and its traffic mix by file name."""
    wl = _read_json("workloads", name)
    _check_keys(f"workload {name}", wl, WORKLOAD_KEYS)
    return Cell(name=name, chips=int(wl["chips"]),
                config_name=wl["config"], traffic_name=wl["traffic"],
                config=check_config(wl["config"],
                                    _read_json("configs", wl["config"])),
                traffic=check_traffic(wl["traffic"],
                                      _read_json("traffic", wl["traffic"])))


# -- seeded trees ----------------------------------------------------------

@dataclass
class Tree:
    agent: str
    job_id: str
    path: str
    nbytes: int


def _write(path: str, data: bytes) -> int:
    with open(path, "wb") as f:
        f.write(data)
    return len(data)


def ladder(n: int, mu: float, sigma: float) -> np.ndarray:
    """``n`` file sizes at the quantiles (i + 1/2) / n of a lognormal with
    parameters ``mu``, ``sigma`` (of ln bytes), ascending.  A fixed set:
    every seed gets the same sizes, in another order, so the seed changes
    the bytes and the order and never the amount of work."""
    normal = statistics.NormalDist()
    z = np.array([normal.inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.maximum(1, np.exp(mu + sigma * z)).astype(np.int64)


def _write_files(top: str, rng, sizes: np.ndarray, dirs: int,
                 every: int) -> int:
    """One file per size under ``top/dNN/``: which name gets which size
    is the seed's; every ``every``-th size of the ladder (by rank, so the
    same sizes for every seed) holds compressible bytes: sixteen symbols,
    four bits of entropy a byte, and no period — a repeated pattern would
    give the rolling hash nothing to cut on and every chunk the maximum
    length."""
    for d in range(dirs):
        os.makedirs(os.path.join(top, f"d{d:02d}"))
    nbytes = 0
    for k, rank in enumerate(rng.permutation(len(sizes)).tolist()):
        size = int(sizes[rank])
        data = rng.integers(0, 16, size, dtype=np.uint8).tobytes() \
            if every and rank % every == 0 else rng.bytes(size)
        nbytes += _write(os.path.join(top, f"d{k % dirs:02d}",
                                      f"f{k:05d}.dat"), data)
    return nbytes


def _link_tree(src: str, dst: str) -> None:
    """``dst`` as a tree of hard links to the files of ``src``."""
    for dirpath, _, names in os.walk(src):
        out = os.path.join(dst, os.path.relpath(dirpath, src))
        os.makedirs(out, exist_ok=True)
        for n in names:
            os.link(os.path.join(dirpath, n), os.path.join(out, n))


def build_trees(root: str, seed: int, cfg: dict, *, warm: bool = False,
                ) -> dict[str, list[Tree]]:
    """The whole backlog, a function of ``seed`` and the configuration
    alone: ``trees_per_agent`` trees for each agent (``warm``: one small
    tree per agent from ``warm_tree``, on a seed stream of its own so it
    shares no chunk with the backlog).  A tree is ``home/``, the agent's
    own files, and ``usr/``, files common to every tree of every agent
    (written once, hard-linked): both are ladders of one lognormal;
    ``own_files`` may give each agent a count of its own."""
    recipe = cfg["warm_tree"] if warm else cfg["tree"]
    rng = np.random.default_rng([seed, 1 if warm else 0])
    tag = "warm" if warm else "src"
    per_agent = 1 if warm else cfg["trees_per_agent"]
    mu, sigma = recipe["mu"], recipe["sigma"]
    dirs, every = recipe["dirs"], recipe["compressible_every"]
    os.makedirs(root, exist_ok=True)
    common = os.path.join(root, f"{tag}-common")
    common_bytes = _write_files(
        common, rng, ladder(recipe["common_files"], mu, sigma), dirs, every)
    out: dict[str, list[Tree]] = {}
    for a in range(cfg["agents"]):
        agent = f"agent-{a:02d}"
        own = recipe["own_files"]
        sizes = ladder(own[a] if isinstance(own, list) else own, mu, sigma)
        out[agent] = []
        for k in range(per_agent):
            src = os.path.join(root, f"{tag}-{a:02d}-{k:02d}")
            nbytes = _write_files(os.path.join(src, "home"), rng, sizes,
                                  dirs, every)
            _link_tree(common, os.path.join(src, "usr"))
            out[agent].append(Tree(
                agent, f"{'warm' if warm else 'bench'}-{agent}-{k}", src,
                nbytes + common_bytes))
    return out


# -- counters ----------------------------------------------------------------

class Compiles:
    """Backend compilations seen by jax's own monitoring hooks: how many
    programs were asked for, how many of those the persistent cache
    answered, and the seconds they took."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.durations: list[float] = []
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += seconds
            self.durations.append(round(seconds, 2))

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compilations": self.count,
                "compile_cache_hits": self.cache_hits,
                "compile_seconds": round(self.seconds, 3)}


def device_counters() -> dict:
    """The program's own counts (PR 21): feeder rounds and rows, scan
    and hash dispatches with their real and padded bytes."""
    from pbs_plus_tpu.models.feeder import get_feeder
    from pbs_plus_tpu.ops import rolling_hash, sha256
    return {"feeder": dict(get_feeder().stats),
            "scan": dict(rolling_hash.stats), "sha": dict(sha256.stats)}


def counter_deltas(before: dict, after: dict) -> dict:
    return {layer: {k: after[layer][k] - before[layer][k]
                    for k in after[layer]
                    if isinstance(after[layer][k], (int, float))}
            for layer in after}


# -- server, agents ------------------------------------------------------------

async def spawn_agent(server, work: str, name: str):
    from pbs_plus_tpu.agent.lifecycle import AgentConfig, AgentLifecycle
    from pbs_plus_tpu.arpc import TlsClientConfig
    from pbs_plus_tpu.utils import mtls
    token_id, secret = server.issue_bootstrap_token()
    key = mtls.generate_private_key()
    cert_pem = server.bootstrap_agent(name, mtls.make_csr(key, name),
                                      token_id, secret)
    d = os.path.join(work, name)
    os.makedirs(d)
    with open(os.path.join(d, "c.pem"), "wb") as f:
        f.write(cert_pem)
    with open(os.path.join(d, "c.key"), "wb") as f:
        f.write(mtls.key_pem(key))
    agent = AgentLifecycle(AgentConfig(
        hostname=name, server_host="127.0.0.1",
        server_port=server.config.arpc_port,
        tls=TlsClientConfig(os.path.join(d, "c.pem"),
                            os.path.join(d, "c.key"),
                            server.certs.ca_cert_path)))
    task = asyncio.create_task(agent.run())
    await server.agents.wait_session(name, timeout=30)
    return agent, task


class Deployment:
    """The served entry: one ``Server`` with its PKI and aRPC listener
    and one ``AgentLifecycle`` per agent, all in this process."""

    def __init__(self, work: str, cfg: dict):
        from pbs_plus_tpu.server.store import Server, ServerConfig
        self.work = work
        self.cfg = cfg
        s = cfg["server"]
        self.server = Server(ServerConfig(
            state_dir=os.path.join(work, "state"),
            cert_dir=os.path.join(work, "certs"),
            datastore_dir=os.path.join(work, "ds"),
            chunker=s["chunker"], chunk_avg=s["chunk_avg"],
            max_concurrent=s["max_concurrent"],
            dedup_index_mb=s.get("dedup_index_mb", -1)))
        self.agents: dict = {}

    async def start(self, agent_names) -> None:
        await self.server.start()
        for name in agent_names:
            self.agents[name] = await spawn_agent(self.server, self.work,
                                                  name)

    def register(self, trees: dict[str, list[Tree]]) -> None:
        """One job row per tree, each with a backup id of its own: a
        second job of one agent must not find the first as its previous
        snapshot, so nothing is ever spliced."""
        from pbs_plus_tpu.server import database
        for jobs in trees.values():
            for t in jobs:
                self.server.db.upsert_backup_job(database.BackupJobRow(
                    id=t.job_id, target=t.agent, source_path=t.path,
                    backup_id=t.job_id,
                    chunker=self.cfg["server"]["chunker"]))

    @property
    def chunks(self):
        return self.server.datastore.datastore.chunks

    @property
    def index(self):
        return self.chunks.index

    def preload_index(self, seed: int, n: int) -> int:
        """Seeded digests of an existing datastore, through the index's
        own insert call, so probes and table copies work at a
        deployment's size."""
        if not n or self.index is None:
            return 0
        raw = np.random.default_rng([seed, 2]).bytes(32 * n)
        return self.index.insert_many(
            [raw[i:i + 32] for i in range(0, len(raw), 32)])

    async def stop(self) -> None:
        for agent, task in self.agents.values():
            await agent.stop()
            task.cancel()
        await self.server.stop()


_SHA_ROW_CLASSES = (8, 64, 512, 4096)     # the program's, ops/sha256.py
_SHA_SMALL_SLAB_MIB = 16


def _sha_warm_chunks(slab_mib: int, rows: int) -> list:
    """Chunks that land in one (staging class, row class) program: the
    staging class is chosen by the batch's bytes, the row class by how
    many chunks share a length bucket.  As few bytes per chunk as the
    class allows: SHA-256 runs one lane per chunk, at about 1 MiB/s."""
    below = [c for c in _SHA_ROW_CLASSES if c < rows]
    count = max(below[-1] + 1 if below else 1, 5)
    if slab_mib <= _SHA_SMALL_SLAB_MIB:
        size = KIB
    else:                                   # just over the small class
        size = (_SHA_SMALL_SLAB_MIB + 1) * MIB // count + 1
    return [np.zeros(size, dtype=np.uint8)] * count


def warm_shapes(cfg: dict) -> int:
    """Run every program the cell's traffic asks for, once, so none
    compiles inside the window.  Which: ``warm_shapes`` in the
    configuration file — scans as rows x segment lengths, hashes as
    ``sha_classes``, a list of (staging MiB, rows) pairs."""
    from pbs_plus_tpu.chunker import ChunkerParams
    from pbs_plus_tpu.ops import sha256 as sha
    from pbs_plus_tpu.ops.rolling_hash import (batched_candidate_hits,
                                               device_tables)
    shapes = cfg.get("warm_shapes", {})
    params = ChunkerParams(avg_size=cfg["server"]["chunk_avg"])
    tables = device_tables(params)
    n = 0
    for seg_kib in shapes.get("scan_seg_kib", []):
        row = np.zeros(seg_kib * KIB, dtype=np.uint8)
        for rows in shapes.get("scan_rows", []):
            batched_candidate_hits([row] * rows, [None] * rows, tables,
                                   params)
            n += 1
    for slab_mib, rows in shapes.get("sha_classes", []):
        sha.sha256_chunks(_sha_warm_chunks(slab_mib, rows))
        n += 1
    return n


# -- the closed loop -----------------------------------------------------------

@dataclass
class JobRecord:
    job_id: str
    agent: str
    tree_path: str
    nbytes: int
    enqueued: float
    done: float = 0.0
    status: str = ""
    error: str = ""
    snapshot: str = ""


@dataclass
class LoopResult:
    t0: float
    t_end: float                  # the interval's end
    t_drained: float              # when the last job in flight had ended
    drained: bool                 # backlog ran out before the window did
    jobs: list[JobRecord] = field(default_factory=list)
    enqueued_bytes: int = 0
    built_bytes: int = 0


class CommitLog:
    """When the chunk store took each chunk: (clock, digest) after every
    ``insert`` and every ``note_dedup_hit`` that found its chunk — the two
    calls by which a stream writer commits a chunk, new or known.  The
    rate's numerator is read from this log once the jobs have published
    and the chunks' lengths are known from their indexes, so it counts
    bytes that were scanned, cut, hashed and stored, and none that only
    sit in a read-ahead queue or a pending hash batch."""

    def __init__(self, clock=time.monotonic):
        self.clock = clock
        self.events: list[tuple[float, bytes]] = []

    def watch(self, store) -> None:
        """Wrap the two calls on this store object.  A store without
        them is an error: the rate would silently count nothing."""
        for name in ("insert", "note_dedup_hit"):
            if not callable(getattr(store, name, None)):
                raise RuntimeError(
                    f"{type(store).__name__} has no {name}(): the "
                    "benchmark counts committed bytes at that call")
        insert, hit = store.insert, store.note_dedup_hit
        events, clock = self.events, self.clock

        def logged_insert(digest, data, **kw):
            new = insert(digest, data, **kw)
            events.append((clock(), bytes(digest)))
            return new

        def logged_hit(digest):
            found = hit(digest)
            if found:               # not found: the writer inserts instead
                events.append((clock(), bytes(digest)))
            return found
        store.insert, store.note_dedup_hit = logged_insert, logged_hit

    def bytes_between(self, t0: float, t1: float,
                      sizes: dict) -> tuple[int, int]:
        """Bytes of the chunks committed in [t0, t1], and how many of
        those commits name a digest no published index holds."""
        inside = [d for t, d in list(self.events) if t0 <= t <= t1]
        return (sum(sizes.get(d, 0) for d in inside),
                sum(1 for d in inside if d not in sizes))


def chunk_sizes(streams) -> tuple[dict, int]:
    """From published streams, each (ends, digests, ...): the length of
    every chunk by its digest, and the streams' bytes in all."""
    sizes: dict = {}
    total = 0
    for ends, digests, *_ in streams:
        start = 0
        for end, digest in zip(ends, digests):
            sizes[bytes(digest)] = end - start
            start = end
        total += start
    return sizes, total


def plan_backlog(trees: dict[str, list[Tree]], traffic: dict,
                 ) -> dict[str, list[Tree]]:
    """Which agents are active and how deep each one's queue is."""
    agents = sorted(trees)
    n = traffic.get("agents", "all")
    if n != "all":
        agents = agents[:int(n)]
    depth = traffic.get("jobs_per_agent", "all")
    return {a: trees[a] if depth == "all" else trees[a][:int(depth)]
            for a in agents}


async def drain_backlog(server, backlog: dict[str, list[Tree]],
                        seconds: float, *, clock=time.monotonic,
                        job_timeout: float = 900.0,
                        on_end=None) -> LoopResult:
    """One job per active agent at ``t0``; an agent's next tree is
    enqueued the moment its job publishes; nothing is enqueued after
    ``t0 + seconds``.  Jobs in flight then drain outside the interval so
    that they too can be compared.  ``on_end`` runs at the interval's
    end, before that drain."""
    from pbs_plus_tpu.server import database
    jobs: list[JobRecord] = []
    t0 = clock()
    deadline = t0 + seconds
    last_publish = t0

    async def run_agent(agent: str, queue: list[Tree]) -> None:
        nonlocal last_publish
        for tree in queue:
            if clock() >= deadline:
                return
            rec = JobRecord(tree.job_id, agent, tree.path, tree.nbytes,
                            clock())
            jobs.append(rec)
            if not server.enqueue_backup(tree.job_id):
                rec.done, rec.status = clock(), "refused"
                rec.error = "enqueue_backup returned False"
                continue
            await server.jobs.wait(f"backup:{tree.job_id}",
                                   timeout=job_timeout)
            rec.done = clock()
            row = server.db.get_backup_job(tree.job_id)
            rec.status = row.last_status or ""
            rec.error = row.last_error or ""
            rec.snapshot = row.last_snapshot or ""
            if rec.status == database.STATUS_SUCCESS:
                last_publish = max(last_publish, rec.done)

    loops = [asyncio.ensure_future(run_agent(a, q))
             for a, q in backlog.items()]
    try:
        done, _ = await asyncio.wait(loops, timeout=max(0.0, seconds))
        drained = len(done) == len(loops)
        if drained:
            # the backlog ran out: the interval ends at the last publish
            t_end = last_publish
        else:
            t_end = clock()
        if on_end is not None:
            on_end()                # counters, at the interval's end
        await asyncio.gather(*loops)
    finally:
        for task in loops:
            task.cancel()
    built = sum(t.nbytes for q in backlog.values() for t in q)
    return LoopResult(t0=t0, t_end=t_end, t_drained=clock(), drained=drained,
                      jobs=jobs, enqueued_bytes=sum(j.nbytes for j in jobs),
                      built_bytes=built)
