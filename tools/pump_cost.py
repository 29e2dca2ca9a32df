#!/usr/bin/env python3
"""What the backup pump costs per file and per MiB, with nothing behind
it: real PKI and TLS, the aRPC listener and a connected session, the
agent's ``AgentFSServer`` and the server's ``AgentFSClient`` on one
event loop as in the benchmark's cells, ``RemoteTreeBackup`` against a
writer that only reads (PERF.md section 5; ROADMAP S8, the pump).

    python3 tools/pump_cost.py          # on the bench host: no device used

Three trees — 1,024 files of 13 KB (the distribution's median) in one
directory, the same in 16 directories of 64 (single-tree's shape: a run
of small files ends with its directory) and 8 files of 32 MiB — each
pumped REPEATS times against the agent as it is, against one without
``agentfs.read_many`` (the answer of an agent from before PR 31) and
against one that also ignores ``read`` on ``agentfs.open`` (one from
before PR 29).  Per tree
and agent, the median run: wall and process CPU seconds, milliseconds a
file and a MiB, the pump's own counts — calls a file, the files that
came in a ``read_many`` answer and those answers — and what the bulk
bytes' way did on the session's two ends (``MuxConnection.stats``):
``drain_waits_per_frame_tx``, the share of the agent's frames that had
to wait for the peer under the write deadline's timer, and
``rx_direct_per_byte_rx``, the share of the bytes the server received
that went from their frames into the pump's buffers with one copy.  One JSON line on
stdout, the same in ``chiprun_out/pump_cost.json``.  It never imports
jax; the numbers are the host's and only mean something on the host they
were taken on.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from pbs_plus_tpu.agent.agentfs import (  # noqa: E402
    AgentFSClient, AgentFSServer,
)
from pbs_plus_tpu.arpc.call import (  # noqa: E402
    STATUS_NOT_FOUND, Response,
)

MIB = 1 << 20
REPEATS = 3
# name: (files, bytes a file, files a directory)
TREES = {"small": (1024, 13_000, 1024), "small_dirs64": (1024, 13_000, 64),
         "large": (8, 32 * MIB, 8)}


class NoReadMany(AgentFSServer):
    """An agent from before ``agentfs.read_many``: the router's answer
    to a method it does not know."""

    async def _read_many(self, req, ctx):
        return Response(STATUS_NOT_FOUND, f"unknown method {req.method!r}")


class IgnoresRead(NoReadMany):
    """An agent from before the ``read`` key as well: the unknown key is
    ignored and the answer is the bare handle."""

    async def _open(self, req, ctx):
        req.payload.pop("read", None)
        return await super()._open(req, ctx)


class _NullWriter:
    """The writer's surface the pump drives; every byte read and dropped."""

    def write_entry(self, entry) -> None:
        pass

    def write_entry_reader(self, entry, reader) -> None:
        while reader.read(4 * MIB):
            pass


class _NullSession:
    writer = _NullWriter()


def _make_tree(root: str, files: int, size: int, per_dir: int) -> None:
    body = os.urandom(size)
    for i in range(files):
        d = root if per_dir >= files \
            else os.path.join(root, f"d{i // per_dir:03d}")
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, f"f{i:05d}.bin"), "wb") as f:
            f.write(body)


async def _pump_once(pki: dict, root: str, agent_cls) -> dict:
    from pbs_plus_tpu.arpc import (
        Router, Session, TlsClientConfig, TlsServerConfig,
        connect_to_server, serve,
    )
    from pbs_plus_tpu.server.backup_job import RemoteTreeBackup
    fs = agent_cls(root)
    router = Router()
    fs.register(router)
    agent_ends = []

    async def on_conn(conn, peer, headers):
        agent_ends.append(conn)
        await router.serve_connection(conn)

    srv = await serve("127.0.0.1", 0,
                      TlsServerConfig(pki["server_cert"], pki["server_key"],
                                      pki["ca"]), on_connection=on_conn)
    conn = await connect_to_server(
        "127.0.0.1", srv.sockets[0].getsockname()[1],
        TlsClientConfig(*pki["client"], pki["ca"]))
    try:
        pump = RemoteTreeBackup(AgentFSClient(Session(conn)), _NullSession())
        wall0, cpu0 = time.perf_counter(), time.process_time()
        res = await pump.run()
        wall, cpu = time.perf_counter() - wall0, time.process_time() - cpu0
        if res.errors:
            raise RuntimeError(f"pump errors: {res.errors[:3]}")
        return {"wall_s": wall, "cpu_s": cpu, "files": res.files,
                "bytes": res.bytes_total, "pump": dict(pump.pump),
                "agent": dict(fs.stats),
                # the agent's end sends the bulk, the server's receives it
                "mux_agent": dict(agent_ends[0].stats),
                "mux_server": dict(conn.stats)}
    finally:
        await conn.close()
        srv.close()
        await srv.wait_closed()
        fs.close_all()


def _make_pki(d: str) -> dict:
    from pbs_plus_tpu.utils import mtls
    cm = mtls.CertManager(d)
    cm.load_or_create_ca()
    cm.ensure_server_identity("server.test")
    cert, key = cm.issue("pump-cost")
    cp, kp = os.path.join(d, "agent.pem"), os.path.join(d, "agent.key")
    for path, blob in ((cp, cert), (kp, key)):
        with open(path, "wb") as f:
            f.write(blob)
    return {"ca": cm.ca_cert_path, "server_cert": cm.server_cert_path,
            "server_key": cm.server_key_path, "client": (cp, kp)}


def main() -> int:
    rows = []
    with tempfile.TemporaryDirectory(prefix="pump_cost.") as work:
        pki = _make_pki(os.path.join(work, "pki"))
        for tree, (files, size, per_dir) in TREES.items():
            root = os.path.join(work, tree)
            _make_tree(root, files, size, per_dir)
            for agent, cls in (("honours_read", AgentFSServer),
                               ("no_read_many", NoReadMany),
                               ("ignores_read", IgnoresRead)):
                runs = [asyncio.run(_pump_once(pki, root, cls))
                        for _ in range(REPEATS)]
                mid = sorted(runs, key=lambda r: r["wall_s"])[REPEATS // 2]
                mib = mid["bytes"] / MIB
                rows.append({
                    "tree": tree, "files": files, "file_bytes": size,
                    "agent": agent,
                    "wall_s": mid["wall_s"], "cpu_s": mid["cpu_s"],
                    "wall_s_runs": [r["wall_s"] for r in runs],
                    "ms_per_file": 1e3 * mid["wall_s"] / files,
                    "ms_per_mib": 1e3 * mid["wall_s"] / mib,
                    "calls_per_file": mid["pump"]["calls"]
                    / mid["pump"]["files"],
                    "batched_files": mid["pump"]["batched_files"],
                    "batch_calls": mid["pump"]["batch_calls"],
                    "drain_waits_per_frame_tx":
                    mid["mux_agent"]["drain_waits"]
                    / mid["mux_agent"]["frames_tx"],
                    "rx_direct_per_byte_rx":
                    mid["mux_server"]["rx_direct_bytes"]
                    / mid["mux_server"]["bytes_rx"],
                    "pump": mid["pump"], "agent_stats": mid["agent"],
                    "mux_agent": mid["mux_agent"],
                    "mux_server": mid["mux_server"]})
    line = json.dumps({"host_cores": os.cpu_count(), "repeats": REPEATS,
                       "rows": rows})
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "pump_cost.json"), "w",
              encoding="utf-8") as f:
        f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
