#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once on a TPU, through the entry points a user
calls, at the widths of a deployment the repo supports (BASELINE.json
config #3 cut to one host, config #2 for the sidecar), and checks every
result against the plain host reference.  One process; no child that
imports jax; any phase that raises ends the run with a non-zero exit.

    python chip_smoke.py             # one chip: phases 1-3
    python chip_smoke.py --chips 4   # four chips: the mesh path only

Every line but the last carries counts and set-up times (seconds, bytes,
chunks, compilations, peak device memory) — they are not rates.  The
last line of stdout is the contract's:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Without a TPU (``JAX_PLATFORMS=cpu``, or no accelerator found) it says
why on stderr and exits 2 before any phase.  ``build/`` is never carried
between machines: the native chunker library is rebuilt here, for this
host's CPU, before anything loads it.
"""

from __future__ import annotations

import argparse
import asyncio
import filecmp
import hashlib
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

MIB = 1 << 20
CHUNK_AVG = 4 * MIB           # the reference's buzhash target: never cut

# phase 2: eight agents, one burst, >= 2 GiB in all
N_AGENTS = 8
SHARED_MIB = 128              # one file common to every agent
OWN_MIB = 96                  # one file of the agent's own
SMALL_FILES = 2048            # 1-64 KiB each, every other one compressible
VERIFY_SAMPLE = 0.03          # of each verified snapshot's files
# phase 3
SIDECAR_MIB = 256
PAGE = 4 * MIB

TWINS = ("index.probe", "similarity.sketch", "ingest.scan", "ingest.sha",
         "verify.rehash")


def say(**fields) -> None:
    print(json.dumps(fields), flush=True)


class Compiles:
    """Backend compilations seen by jax's own monitoring hooks: how many
    programs were asked for, how many of those the persistent cache
    answered, and the seconds they took."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += seconds

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compilations": self.count,
                "compile_cache_hits": self.cache_hits,
                "compile_seconds": round(self.seconds, 1)}

    def since(self, before: dict) -> dict:
        now = self.snapshot()
        return {k: round(now[k] - before[k], 1) for k in now}


def peak_bytes() -> int:
    import jax
    return int((jax.devices()[0].memory_stats() or {})
               .get("peak_bytes_in_use", 0))


def rebuild_native() -> None:
    """A library built on another machine (``-march=native``) must never
    be loaded here: drop whatever ``build/`` holds and build anew, before
    anything imports the chunker's native binding."""
    so = os.path.join(ROOT, "build", "libbuzhash_native.so")
    if os.path.exists(so):
        os.unlink(so)
    from pbs_plus_tpu.chunker import native
    if not native.available():
        raise RuntimeError("native chunker did not build on this host")


# -- phase 1: kernel parity on the chip --------------------------------------

def phase_kernels(seed: int, compiles: Compiles) -> None:
    import numpy as np

    from pbs_plus_tpu.chunker import ChunkerParams, candidates
    from pbs_plus_tpu.chunker.spec import WINDOW
    from pbs_plus_tpu.ops import sha256 as sha
    from pbs_plus_tpu.ops.cuckoo import CuckooIndex
    from pbs_plus_tpu.ops.rolling_hash import (batched_candidate_hits,
                                               device_tables)

    t0, c0 = time.monotonic(), compiles.snapshot()
    rng = np.random.default_rng(seed)

    def scan_parity(bufs, avg):
        params = ChunkerParams(avg_size=avg)
        hits = batched_candidate_hits(bufs, [None] * len(bufs),
                                      device_tables(params), params)
        for buf, h in zip(bufs, hits):
            if not np.array_equal(h[h >= WINDOW - 1] + 1,
                                  candidates(buf, params)):
                raise AssertionError(f"candidate scan diverges at "
                                     f"[{len(bufs)}, {len(buf)}], avg={avg}")
        return sum(len(h) for h in hits)

    # candidate scan: one 64 MiB row, every position compared.  4 MiB
    # average is the deployment's; 64 KiB on the same compiled program
    # (mask and magic are run-time arguments) gives a thousand hits
    row = np.frombuffer(rng.bytes(64 * MIB), dtype=np.uint8)
    n_cand = {avg: scan_parity([row], avg) for avg in (CHUNK_AVG, 64 << 10)}
    # ... and every batch shape four concurrent sessions feeding pages of
    # up to 4 MiB can form, so the main phase compiles none of them late
    for seg in (64 << 10, 256 << 10, MIB, PAGE):
        for rows in (1, 4):
            scan_parity([row[k * seg:(k + 1) * seg] for k in range(rows)],
                        64 << 10)

    # SHA-256, the device engine's TPU branch, against hashlib: the
    # padding edge cases and the deployment's chunk sizes — hashed
    # together, then the short ones alone (the smallest staging buffer),
    # then thirteen of one length (the 64-row class)
    d0 = sha.stats["dispatches"]
    chunks = [rng.bytes(n) for n in
              (0, 55, 56, 64, 1 * MIB, 4 * MIB, 16 * MIB)]
    many = [rng.bytes(3 * MIB // 2) for _ in range(13)]
    for batch in (chunks, chunks[:4], many):
        if sha.sha256_chunks_device(batch) != \
                [hashlib.sha256(c).digest() for c in batch]:
            raise AssertionError("device sha256 diverges from hashlib")
    # ... and the entry every caller of digests uses: the host's
    # SHA-256, no program runs
    d1, h0 = sha.stats["dispatches"], sha.stats["host_batches"]
    if sha.sha256_chunks(many) != [hashlib.sha256(c).digest() for c in many]:
        raise AssertionError("host sha256 diverges from hashlib")
    if (sha.stats["dispatches"], sha.stats["host_batches"]) != (d1, h0 + 1):
        raise AssertionError("sha256_chunks did not hash on the host")

    # cuckoo lookup against the host mirror: 64k digests, half of them
    # inserted, table of 1 << 20 buckets (DedupConfig's default)
    index = CuckooIndex(n_buckets=1 << 20)
    digs = rng.integers(0, 256, (1 << 16, 32), dtype=np.uint8)
    index.insert_many([d.tobytes() for d in digs[::2]])
    dev, host = index.probe(digs), index.probe_host(digs)
    if not np.array_equal(dev, host) or not dev[::2].all():
        raise AssertionError("device cuckoo lookup diverges from the host")

    say(phase="kernels", seconds=round(time.monotonic() - t0, 1),
        scan_bytes=int(row.size), scan_candidates=n_cand,
        sha_lengths=[len(c) for c in chunks],
        sha_dispatches=sha.stats["dispatches"] - d0,
        cuckoo_probes=int(len(digs)), cuckoo_hits=int(dev.sum()),
        peak_bytes_in_use=peak_bytes(), **compiles.since(c0))


# -- phase 2: the fan-in backup ----------------------------------------------

def make_trees(root: str, seed: int, *, n_agents: int, shared_mib: int,
               own_mib: int, small_files: int) -> dict[str, str]:
    """One seeded source tree per agent: a file common to all (cross-agent
    duplicates), a file of its own, and many small files in 32
    directories, every other one compressible."""
    import numpy as np
    rng = np.random.default_rng(seed)
    shared = rng.bytes(shared_mib * MIB)
    trees = {}
    for i in range(n_agents):
        name = f"agent-{i:02d}"
        src = os.path.join(root, f"src-{i:02d}")
        os.makedirs(src)
        with open(os.path.join(src, "shared.bin"), "wb") as f:
            f.write(shared)
        with open(os.path.join(src, "own.bin"), "wb") as f:
            f.write(rng.bytes(own_mib * MIB))
        for d in range(32):
            os.mkdir(os.path.join(src, f"d{d:02d}"))
        for k in range(small_files):
            size = int(rng.integers(1 << 10, (64 << 10) + 1))
            data = rng.bytes(size) if k % 2 else \
                (rng.bytes(64) * (size // 64 + 1))[:size]
            with open(os.path.join(src, f"d{k % 32:02d}", f"f{k:05d}.dat"),
                      "wb") as f:
                f.write(data)
        trees[name] = src
    return trees


def tree_bytes(src: str) -> int:
    return sum(os.path.getsize(os.path.join(dp, f))
               for dp, _, fs in os.walk(src) for f in fs)


def same_tree(a: str, b: str) -> bool:
    """Byte-for-byte: the same relative paths, each with equal content."""
    def files(root):
        return sorted(os.path.relpath(os.path.join(dp, f), root)
                      for dp, _, fs in os.walk(root) for f in fs)
    names = files(a)
    return names == files(b) and all(
        filecmp.cmp(os.path.join(a, n), os.path.join(b, n), shallow=False)
        for n in names)


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


async def fanin(work: str, chunker: str, trees: dict[str, str], *,
                exact_index: bool = False, at_half=None, after=None) -> dict:
    """An in-process server with its real PKI and aRPC listener, one
    AgentLifecycle per tree, every backup enqueued in one burst.  Returns
    per-agent snapshot records; ``at_half`` runs when half the jobs have
    ended, ``after(server, agents, snaps)`` before the server stops."""
    from pbs_plus_tpu.pxar.datastore import parse_snapshot_ref
    from pbs_plus_tpu.server import database
    from pbs_plus_tpu.server.store import Server, ServerConfig

    os.makedirs(work)
    server = Server(ServerConfig(
        state_dir=os.path.join(work, "state"),
        cert_dir=os.path.join(work, "certs"),
        datastore_dir=os.path.join(work, "ds"),
        chunker=chunker, chunk_avg=CHUNK_AVG, max_concurrent=4,
        # the plain reference confirms against an exact set, with no
        # cuckoo filter in front of it
        dedup_index_mb=0 if exact_index else -1))
    await server.start()
    agents = {}
    try:
        for name, src in trees.items():
            agents[name] = await spawn_agent(server, work, name)
            server.db.upsert_backup_job(database.BackupJobRow(
                id=f"fan-{name}", target=name, source_path=src,
                chunker=chunker))
        for name in trees:
            if not server.enqueue_backup(f"fan-{name}"):
                raise RuntimeError(f"backup of {name} was not accepted")
        waits = [server.jobs.wait(f"backup:fan-{name}", timeout=900)
                 for name in trees]
        for k, done in enumerate(asyncio.as_completed(waits), 1):
            await done
            if at_half is not None and k == len(waits) // 2:
                at_half()
        snaps = {}
        for name in trees:
            row = server.db.get_backup_job(f"fan-{name}")
            if row.last_status != database.STATUS_SUCCESS:
                raise RuntimeError(f"{row.id}: {row.last_status}: "
                                   f"{row.last_error}")
            ref = parse_snapshot_ref(row.last_snapshot)
            reader = server.datastore.open_snapshot(ref)
            man = server.datastore.datastore.load_manifest(ref)
            snaps[name] = {
                "snapshot": row.last_snapshot,
                "indexes": [
                    [(int(e), ix.digest(i)) for i, e in enumerate(ix.ends)]
                    for ix in (reader.payload_index, reader.meta_index)],
                "new_chunks": man["stats"]["new_chunks"],
                "known_chunks": man["stats"]["known_chunks"],
                "backend": man.get("chunker_backend", "")}
        if after is not None:
            await after(server, agents, snaps)
        return snaps
    finally:
        for agent, task in agents.values():
            await agent.stop()
            task.cancel()
        await server.stop()


def compare_with_reference(tpu: dict, ref: dict) -> dict:
    """Payload and meta index ends and digests equal, snapshot for
    snapshot; new/known equal when summed (which agent stores a shared
    chunk first is a race; the totals are not)."""
    for name in tpu:
        if tpu[name]["indexes"] != ref[name]["indexes"]:
            raise AssertionError(f"{name}: chunker=tpu cuts or digests "
                                 "differ from the scalar reference")
    sums = {k: (sum(s[k] for s in tpu.values()),
                sum(s[k] for s in ref.values()))
            for k in ("new_chunks", "known_chunks")}
    if any(a != b for a, b in sums.values()):
        raise AssertionError(f"new/known totals differ: {sums}")
    return {"chunks": sum(len(ix) for s in tpu.values()
                          for ix in s["indexes"]),
            "new_chunks": sums["new_chunks"][0],
            "known_chunks": sums["known_chunks"][0]}


def device_counters() -> dict:
    from pbs_plus_tpu.models.feeder import get_feeder
    from pbs_plus_tpu.ops import rolling_hash, sha256
    return {"feeder": dict(get_feeder().stats),
            "scan": dict(rolling_hash.stats), "sha": dict(sha256.stats)}


def assert_device_did_the_work(before: dict, now: dict,
                               hashed: int) -> None:
    """``hashed``: the bytes of the burst's payload streams, all of
    which the tpu batch hasher hashes — on the host (ops/sha256.py),
    while the scans have the device to themselves."""
    if now["scan"]["dispatches"] <= before["scan"]["dispatches"]:
        raise AssertionError("TpuChunker never dispatched")
    sha = {k: now["sha"][k] - before["sha"][k]
           for k in ("dispatches", "host_batches", "host_bytes")}
    if sha["host_batches"] < 1 or sha["host_bytes"] != hashed \
            or sha["dispatches"]:
        raise AssertionError(f"the tpu batch hasher did not hash the "
                             f"burst's {hashed} bytes on the host: {sha}")
    feeder = now["feeder"]
    if feeder["max_mask_batch"] <= 1:
        raise AssertionError(f"no cross-stream device batch formed: {feeder}")
    if feeder["mask_retried_alone"] or feeder["sha_retried_alone"]:
        raise AssertionError(f"the feeder retried requests alone: {feeder}")


def twin_report() -> dict:
    from pbs_plus_tpu.utils import jaxenv
    return {name: {"reached": name in jaxenv.twin_counts,
                   "on_device":
                       jaxenv.twin_counts.get(name, {}).get("device", 0) > 0}
            for name in TWINS}


async def restore_and_verify(server, agents, snaps, work: str,
                             trees: dict[str, str]) -> dict:
    from pbs_plus_tpu.server import database
    from pbs_plus_tpu.server.restore_job import run_restore_job
    from pbs_plus_tpu.server.verification_job import run_verification
    name = next(iter(trees))
    dest = os.path.join(work, "restored")
    server.db.create_restore("smoke-restore", name,
                             snaps[name]["snapshot"], dest)
    await run_restore_job(server, "smoke-restore", target=name,
                          snapshot=snaps[name]["snapshot"], destination=dest)
    agent = agents[name][0]
    for _ in range(600):              # the agent's restore task writes on
        if not agent.jobs:
            break
        await asyncio.sleep(0.1)
    status = server.db.get_restore("smoke-restore")["status"]
    if status != database.STATUS_SUCCESS or not same_tree(dest, trees[name]):
        raise AssertionError(f"restore of {name} ({status}) is not "
                             "byte-identical to its source tree")
    # a 3 % file sample (the job's own setting; its default is 10 %):
    # every sampled file is one "chunk" to the verifier, and a hundred
    # files of one length class would ask for a 512-row hash program no
    # backup uses — a minute of compilation this run can do without
    report = await run_verification(
        server, {"id": "smoke-verify", "sample_rate": VERIFY_SAMPLE})
    others = [s for s in report["snapshots"]
              if s != snaps[name]["snapshot"]]
    if report["corrupt"] or not report["checked"] or not others:
        raise AssertionError(f"verification: {report}")
    return {"restored": snaps[name]["snapshot"],
            "verified": report["snapshots"],
            "verified_files": report["checked"]}


def phase_fanin(work: str, seed: int, compiles: Compiles, *,
                sizes: dict, one_chip: bool) -> dict:
    """``one_chip`` adds what the four-chip call leaves out: restore,
    verification, and the demand that the burst's second half compiles
    nothing (phase 1 has compiled this deployment's shape classes)."""
    t0, c0 = time.monotonic(), compiles.snapshot()
    trees = make_trees(work, seed, **sizes)
    total = sum(tree_bytes(s) for s in trees.values())
    t_made = time.monotonic()
    before = device_counters()
    marks = {}
    extra = {}

    async def after(server, agents, snaps):
        marks["backed_up"] = (time.monotonic(), compiles.snapshot(),
                              device_counters())
        if one_chip:
            extra.update(await restore_and_verify(
                server, agents, snaps, os.path.join(work, "tpu"), trees))

    tpu = asyncio.run(fanin(
        os.path.join(work, "tpu"), "tpu", trees,
        at_half=lambda: marks.setdefault("half", compiles.snapshot()),
        after=after))
    t_tpu = time.monotonic()
    now = marks["backed_up"][2]
    assert_device_did_the_work(
        before, now, sum(s["indexes"][0][-1][0] for s in tpu.values()))
    second_half = {k: marks["backed_up"][1][k] - marks["half"][k]
                   for k in marks["half"]}
    if one_chip and second_half["compilations"]:
        raise AssertionError("programs were compiled in the second half of "
                             f"the backup burst: {second_half}")
    if {s["backend"] for s in tpu.values()} != {"tpu"}:
        raise AssertionError("a session was not bound to the tpu chunker")

    ref = asyncio.run(fanin(os.path.join(work, "scalar"), "scalar", trees,
                            exact_index=True))
    equal = compare_with_reference(tpu, ref)
    say(phase="fanin", agents=len(trees), bytes=total, **equal,
        seconds={"trees": round(t_made - t0, 1),
                 "tpu_backup": round(marks["backed_up"][0] - t_made, 1),
                 "restore_verify": round(t_tpu - marks["backed_up"][0], 1),
                 "scalar_reference": round(time.monotonic() - t_tpu, 1)},
        second_half_compilations=second_half["compilations"],
        twins=twin_report(), peak_bytes_in_use=peak_bytes(),
        **now, **extra, **compiles.since(c0))
    return now


# -- phase 3: the sidecar ----------------------------------------------------

def phase_sidecar(seed: int, compiles: Compiles, *, stream_mib: int) -> None:
    import numpy as np

    from pbs_plus_tpu.chunker import ChunkerParams, chunk_bounds
    from pbs_plus_tpu.sidecar import serve_sidecar
    from pbs_plus_tpu.sidecar.client import SidecarClient

    t0, c0 = time.monotonic(), compiles.snapshot()
    data = np.random.default_rng(seed + 3).bytes(stream_mib * MIB)
    params = ChunkerParams(avg_size=CHUNK_AVG)
    server, port, _svc = serve_sidecar("127.0.0.1:0", params=params,
                                       use_tpu=True)
    client = SidecarClient(f"127.0.0.1:{port}")
    try:
        cuts, digests = [], []
        for off in range(0, len(data), PAGE):
            r = client.chunk("smoke", data[off:off + PAGE],
                             eof=off + PAGE >= len(data))
            cuts += r["cuts"]
            digests += r["digests"]
        bounds = chunk_bounds(data, params)
        if cuts != [e for _, e in bounds] or digests != \
                [hashlib.sha256(data[s:e]).digest() for s, e in bounds]:
            raise AssertionError("sidecar cuts or digests differ from the "
                                 "scalar reference")
        absent = [hashlib.sha256(d).digest() for d in digests]
        inserted = client.insert_index(digests)
        present = client.probe_index(digests + absent)
        stats = client.stats()
        if inserted != len(set(digests)) or \
                present != [True] * len(digests) + [False] * len(absent):
            raise AssertionError("sidecar index answers are wrong")
        if stats["use_tpu"] is not True or stats["bytes"] != len(data) \
                or stats["index_size"] != inserted:
            raise AssertionError(f"sidecar stats: {stats}")
    finally:
        client.close()
        server.stop(grace=5).wait()
    say(phase="sidecar", seconds=round(time.monotonic() - t0, 1),
        bytes=len(data), chunks=len(cuts), index_size=inserted,
        use_tpu=stats["use_tpu"], peak_bytes_in_use=peak_bytes(),
        **compiles.since(c0))


# -- phase 4: four chips -----------------------------------------------------

def assert_mesh_did_the_work(now: dict, n: int) -> None:
    """Code that never saw two chips may put everything on the first.
    The scan is what the fan-in runs on the mesh; the SHA-256 program is
    off its path (ops/sha256.py) and its row sharding is checked on
    virtual devices only (tests/test_sha_engine.py)."""
    s = now["scan"]
    if s["mesh_devices"] != n or s["mesh_dispatches"] < 1 \
            or s["mesh_shard_devices"] != n:
        raise AssertionError(f"scan dispatches did not spread over "
                             f"{n} devices: {s}")


# -- main ----------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-device mesh path and what "
                         "it is compared with")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: jax found no TPU (platform "
              f"{devices[0].platform!r}, JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS', '')!r}); this script "
              "checks the program on the chip and does not fall back",
              file=sys.stderr)
        return 2
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 2

    from pbs_plus_tpu.utils import jaxenv
    cache_dir = jaxenv.configure_compile_cache()
    rebuild_native()
    compiles = Compiles()
    sizes = dict(n_agents=N_AGENTS, shared_mib=SHARED_MIB, own_mib=OWN_MIB,
                 small_files=SMALL_FILES)
    work = tempfile.mkdtemp(prefix="chip-smoke-")
    say(phase="start", device_kind=devices[0].device_kind,
        devices=len(devices), seed=args.seed, compile_cache=cache_dir,
        cache_entries=len(os.listdir(cache_dir))
        if os.path.isdir(cache_dir) else 0,
        disk_free_bytes=shutil.disk_usage(work).free)
    try:
        if args.chips == 1:
            phase_kernels(args.seed, compiles)
            phase_fanin(os.path.join(work, "fanin"), args.seed, compiles,
                        sizes=sizes, one_chip=True)
            phase_sidecar(args.seed, compiles, stream_mib=SIDECAR_MIB)
        else:
            now = phase_fanin(os.path.join(work, "fanin"), args.seed,
                              compiles, sizes=sizes, one_chip=False)
            assert_mesh_did_the_work(now, args.chips)
            import __graft_entry__
            __graft_entry__.dryrun_multichip(args.chips)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    say(phase="end", **compiles.snapshot())
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
