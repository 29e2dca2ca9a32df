"""pbslint battery: one positive + one negative fixture per rule,
baseline ratchet semantics, inline/file suppression parsing, CLI exit
codes, and the acceptance gate (the live tree lints clean against the
committed baseline; a seeded violation fails)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from tools.lint import Baseline, lint_source
from tools.lint.baseline import Baseline as _B
from tools.lint.core import REPO_ROOT, Violation, lint_paths
from tools.lint.graph import build_program
from tools.lint.rules import (build_program_rules, build_rules,
                              program_rule_names, rule_names)


def run_lint(src, path="pbs_plus_tpu/fake.py", rules=None):
    only = set(rules) if rules else None
    return lint_source(textwrap.dedent(src), path,
                       build_rules(only), relativize=False)


def names(violations):
    return [v.rule for v in violations]


# ---------------------------------------------------------------- rules


def test_registry_has_expected_rules():
    assert set(rule_names()) == {
        "no-silent-swallow", "no-blocking-in-async",
        "locked-store-discipline", "jit-purity",
        "no-hostsync-in-hot-loop", "subprocess-timeout",
        "thread-hygiene", "resource-ctx", "mutable-default",
        "failpoint-discipline", "cache-discipline",
        "bounded-queue-discipline", "index-discipline",
        "delta-discipline", "sync-discipline", "span-discipline",
        "ingest-discipline", "service-discipline",
        "dist-index-discipline",
    }
    assert set(program_rule_names()) == {
        "guarded-by", "lock-order",
        "no-blocking-in-async-transitive", "registry-consistency",
        "durable-write-discipline", "ordering-discipline",
        "typed-error-discipline",
    }
    # a --rules subset may name rules from either registry
    assert build_rules({"guarded-by"}) == []
    assert [r.name for r in build_program_rules({"guarded-by"})] == \
        ["guarded-by"]
    with pytest.raises(ValueError):
        build_program_rules({"no-such-rule"})


# ---------------------------------------------------- cache-discipline


def test_cache_discipline_flags_direct_store_get_in_read_path():
    v = run_lint("""
        def serve(reader, digest):
            return reader.store.get(digest)
    """, path="pbs_plus_tpu/pxar/remote.py", rules=["cache-discipline"])
    assert names(v) == ["cache-discipline"]
    assert "chunk cache" in v[0].message


def test_cache_discipline_flags_chunks_get():
    v = run_lint("""
        def scan(ds, digest):
            return ds.chunks.get(digest)
    """, path="pbs_plus_tpu/server/verification_job.py",
        rules=["cache-discipline"])
    assert names(v) == ["cache-discipline"]


def test_cache_discipline_cache_path_and_dict_get_clean():
    v = run_lint("""
        def serve(reader, payload, digest):
            path = payload.get("path")       # dict .get: not a store
            return reader.fetch_chunk(digest), path
    """, path="pbs_plus_tpu/pxar/zipdl.py", rules=["cache-discipline"])
    assert v == []


def test_cache_discipline_scoped_to_read_path_modules():
    # the cache module itself (and writers) legitimately hit the source
    v = run_lint("""
        def load(store, digest):
            return store.get(digest)
    """, path="pbs_plus_tpu/pxar/chunkcache.py", rules=["cache-discipline"])
    assert v == []


# -------------------------------------------------- delta-discipline


def test_delta_discipline_flags_resolverless_call():
    v = run_lint("""
        def load(store, digest):
            return store.get_resolved(digest)
    """, path="pbs_plus_tpu/server/restore_job.py",
        rules=["delta-discipline"])
    assert names(v) == ["delta-discipline"]
    assert "chunk cache" in v[0].message


def test_delta_discipline_flags_none_resolver():
    v = run_lint("""
        def load(store, digest):
            return store.get_resolved(digest, None)
    """, path="pbs_plus_tpu/pxar/remote.py", rules=["delta-discipline"])
    assert names(v) == ["delta-discipline"]
    v = run_lint("""
        def load(store, digest):
            return store.get_resolved(digest, resolver=None)
    """, path="pbs_plus_tpu/pxar/remote.py", rules=["delta-discipline"])
    assert names(v) == ["delta-discipline"]


def test_delta_discipline_real_resolver_clean():
    v = run_lint("""
        def load(self, store, digest, chain):
            return store.get_resolved(
                digest, self._base_resolver(store, chain))
    """, path="pbs_plus_tpu/pxar/chunkcache.py", rules=["delta-discipline"])
    assert v == []


def test_delta_discipline_datastore_exempt():
    # the oracle's own plain `get` is the sanctioned recursive fallback
    v = run_lint("""
        def get(self, digest):
            return self.get_resolved(digest, None)
    """, path="pbs_plus_tpu/pxar/datastore.py", rules=["delta-discipline"])
    assert v == []


def test_delta_discipline_unrelated_calls_clean():
    v = run_lint("""
        def load(payload, digest):
            return payload.get(digest)
    """, path="pbs_plus_tpu/pxar/remote.py", rules=["delta-discipline"])
    assert v == []


# -------------------------------------------------- sync-discipline


def test_sync_discipline_flags_per_digest_has_loop():
    v = run_lint("""
        def negotiate(dest, digests):
            return [d for d in digests if not dest.chunks.has(d)]
    """, path="pbs_plus_tpu/pxar/syncwire.py", rules=["sync-discipline"])
    assert names(v) == ["sync-discipline"]
    assert "probe_batch" in v[0].message


def test_sync_discipline_flags_contains_and_on_disk():
    v = run_lint("""
        def check(index, store, d):
            return index.contains(d) or store.on_disk(d)
    """, path="pbs_plus_tpu/server/sync_job.py", rules=["sync-discipline"])
    assert names(v) == ["sync-discipline", "sync-discipline"]


def test_sync_discipline_flags_exists_on_chunk_path():
    v = run_lint("""
        import os
        def probe(store, digest):
            return os.path.exists(store._path(digest))
    """, path="pbs_plus_tpu/pxar/syncwire.py", rules=["sync-discipline"])
    assert names(v) == ["sync-discipline"]


def test_sync_discipline_batched_calls_clean():
    v = run_lint("""
        def negotiate(dest, digests):
            present = dest.chunks.probe_batch(digests)
            if present is None:
                present = dest.chunks.on_disk_many(digests)
            return [d for d, ok in zip(digests, present) if not ok]
    """, path="pbs_plus_tpu/pxar/syncwire.py", rules=["sync-discipline"])
    assert v == []


def test_sync_discipline_non_chunk_exists_clean():
    # snapshot-dir / state-file existence is not chunk membership
    v = run_lint("""
        import os
        def has_snapshot(ds, ref):
            return os.path.exists(os.path.join(ds.snapshot_dir(ref),
                                               "manifest.json"))
    """, path="pbs_plus_tpu/pxar/syncwire.py", rules=["sync-discipline"])
    assert v == []


def test_sync_discipline_out_of_scope_clean():
    # the membership surface itself lives outside the sync modules
    v = run_lint("""
        def has(self, digest):
            return self.index.contains(digest)
    """, path="pbs_plus_tpu/pxar/datastore.py", rules=["sync-discipline"])
    assert v == []


# ------------------------------------------------ service-discipline


def test_service_discipline_flags_construction_outside_roots():
    v = run_lint("""
        from .services import PruneService

        def make_sweeper(db, store):
            return PruneService(datastore=store, policy_factory=dict,
                                jobs_active=lambda: 0, db=db)
    """, path="pbs_plus_tpu/server/web.py", rules=["service-discipline"])
    assert names(v) == ["service-discipline"]
    assert "composition roots" in v[0].message


def test_service_discipline_roots_may_construct():
    src = """
        from .services import JobQueueService, PruneService

        class Server:
            def __init__(self, db):
                self.job_queue = JobQueueService(db=db)
                self.prune = PruneService(datastore=None,
                                          policy_factory=dict,
                                          jobs_active=lambda: 0, db=db)
    """
    for root in ("pbs_plus_tpu/server/store.py",
                 "pbs_plus_tpu/server/fleetproc.py"):
        assert run_lint(src, path=root,
                        rules=["service-discipline"]) == []


def test_service_discipline_flags_private_reach_through():
    v = run_lint("""
        async def snapshot_delete(server, ref):
            async with server.prune._lock:
                server.job_queue._admission_flushed.clear()
    """, path="pbs_plus_tpu/server/web.py", rules=["service-discipline"])
    assert names(v) == ["service-discipline", "service-discipline"]
    assert "reaches through" in v[0].message


def test_service_discipline_public_surface_clean():
    # the delegating-property pattern the composition root uses, plus
    # narrow public calls from anywhere, are the sanctioned surface
    v = run_lint("""
        async def route(server, ref):
            await server.prune.delete_snapshot(ref)
            return server.job_queue.live_progress, server.prune.gc_active
    """, path="pbs_plus_tpu/server/web.py", rules=["service-discipline"])
    assert v == []


def test_service_discipline_service_owns_its_privates():
    # inside server/services/ a service touches its own private state
    v = run_lint("""
        class PruneService:
            def poke(self, sibling):
                return sibling.prune._lock
    """, path="pbs_plus_tpu/server/services/prune_service.py",
        rules=["service-discipline"])
    assert v == []


# ------------------------------------------------- ingest-discipline


def test_ingest_discipline_flags_getattr_duck_typing():
    v = run_lint("""
        def probe_known(self, digests):
            probe = getattr(self.store, "probe_batch", None)
            if probe is None:
                return None
            return probe(digests)
    """, path="pbs_plus_tpu/pxar/transfer.py", rules=["ingest-discipline"])
    assert names(v) == ["ingest-discipline"]
    assert "DECLARED capability" in v[0].message


def test_ingest_discipline_flags_per_stage_store_call():
    v = run_lint("""
        def flush(self, digests, chunks):
            known = self.store.probe_batch(digests)
            self.store.presketch_batch(digests, chunks, known)
    """, path="pbs_plus_tpu/pxar/pipeline.py", rules=["ingest-discipline"])
    assert names(v) == ["ingest-discipline", "ingest-discipline"]
    assert "per-stage store call" in v[0].message


def test_ingest_discipline_flags_direct_fingerprint_kernel():
    v = run_lint("""
        from pbs_plus_tpu.ops.sha256 import sha256_chunks

        def flush(self, chunks):
            return sha256_chunks(chunks)
    """, path="pbs_plus_tpu/pxar/transfer.py", rules=["ingest-discipline"])
    assert names(v) == ["ingest-discipline"]
    assert "batch_hasher" in v[0].message


def test_ingest_discipline_declared_backend_clean():
    v = run_lint("""
        def flush(self, digests, chunks):
            backend = self._ingest
            known = None
            if backend.capabilities.probe:
                known = backend.probe_batch(digests)
            if backend.capabilities.presketch:
                backend.presketch_batch(digests, chunks, known)
            return known
    """, path="pbs_plus_tpu/pxar/transfer.py", rules=["ingest-discipline"])
    assert v == []


def test_ingest_discipline_flags_an_undeclared_store_fan_out():
    v = run_lint("""
        from .storepool import StoreFanOut

        def store(self, novel, helpers):
            if self.store.thread_safe:
                fan = StoreFanOut(self.store.insert, novel)
                fan.start(helpers)
                fan.join()
    """, path="pbs_plus_tpu/pxar/transfer.py", rules=["ingest-discipline"])
    assert names(v) == ["ingest-discipline"]
    assert "concurrent_insert" in v[0].message


def test_ingest_discipline_declared_store_fan_out_clean():
    v = run_lint("""
        from .storepool import StoreFanOut

        def width(self):
            return int(self._ingest.capabilities.concurrent_insert)

        def store(self, novel, helpers):
            fan = StoreFanOut(self.store.insert, novel)
            fan.start(helpers)
            fan.join()
    """, path="pbs_plus_tpu/pxar/transfer.py", rules=["ingest-discipline"])
    assert v == []


def test_ingest_discipline_scoped_to_stream_modules():
    # the sync plane legitimately calls probe_batch
    v = run_lint("""
        def negotiate(self, digests):
            return self.store.probe_batch(digests)
    """, path="pbs_plus_tpu/pxar/syncwire.py", rules=["ingest-discipline"])
    assert v == []


# -------------------------------------------------- index-discipline


def test_index_discipline_flags_exists_on_chunks_path():
    v = run_lint("""
        import os
        def probe(ds, digest):
            return os.path.exists(os.path.join(ds.base, ".chunks",
                                               digest.hex()))
    """, path="pbs_plus_tpu/server/verification_job.py",
        rules=["index-discipline"])
    assert names(v) == ["index-discipline"]
    assert "membership oracle" in v[0].message


def test_index_discipline_flags_stat_on_path_builder():
    v = run_lint("""
        import os
        def hot(store, digest):
            return os.stat(store._path(digest)).st_size > 0
    """, path="pbs_plus_tpu/pxar/remote.py", rules=["index-discipline"])
    assert names(v) == ["index-discipline"]


def test_index_discipline_clean_on_non_chunk_paths():
    v = run_lint("""
        import os
        def check(snapdir):
            return os.path.exists(os.path.join(snapdir, "manifest.json"))
    """, path="pbs_plus_tpu/server/restore_job.py",
        rules=["index-discipline"])
    assert v == []


def test_index_discipline_datastore_module_exempt():
    # the store implements the oracle: its own legacy fallback probe
    # (index disabled) is sanctioned
    v = run_lint("""
        import os
        def has(self, digest):
            return os.path.exists(self._path(digest))
    """, path="pbs_plus_tpu/pxar/datastore.py", rules=["index-discipline"])
    assert v == []


def test_index_discipline_out_of_scope_module_clean():
    v = run_lint("""
        import os
        def peek(base, digest):
            return os.path.exists(os.path.join(base, ".chunks", digest))
    """, path="pbs_plus_tpu/agent/client.py", rules=["index-discipline"])
    assert v == []


def test_index_discipline_flags_segment_open_outside_digestlog():
    v = run_lint("""
        import os
        def peek(store, name):
            with open(os.path.join(store, ".chunkindex", "segments",
                                   name), "rb") as f:
                return f.read(33)
    """, path="pbs_plus_tpu/server/verification_job.py",
        rules=["index-discipline"])
    assert names(v) == ["index-discipline"]
    assert "digestlog" in v[0].message


def test_index_discipline_flags_os_open_on_segments():
    v = run_lint("""
        import os
        def raw(seg_dir, name):
            return os.open(seg_dir + "/.chunkindex/segments/" + name,
                           os.O_RDONLY)
    """, path="pbs_plus_tpu/pxar/remote.py", rules=["index-discipline"])
    assert names(v) == ["index-discipline"]


def test_index_discipline_digestlog_owns_segment_files():
    v = run_lint("""
        import os
        def _open_segment(path):
            fd = os.open(path, os.O_RDONLY)
            with open(path + ".chunkindex/segments/x", "rb") as f:
                return fd, f.read()
    """, path="pbs_plus_tpu/pxar/digestlog.py",
        rules=["index-discipline"])
    assert v == []


def test_index_discipline_chunkindex_may_open_snapshot_manifest():
    v = run_lint("""
        def load(self, path):
            with open(path, "rb") as f:      # the .chunkindex snapshot
                return f.read()
    """, path="pbs_plus_tpu/pxar/chunkindex.py",
        rules=["index-discipline"])
    assert v == []


def test_index_discipline_non_segment_open_clean():
    v = run_lint("""
        def load_manifest(snapdir):
            with open(snapdir + "/manifest.json") as f:
                return f.read()
    """, path="pbs_plus_tpu/server/restore_job.py",
        rules=["index-discipline"])
    assert v == []


# --------------------------------------------- dist-index-discipline


def test_dist_index_discipline_flags_per_digest_contains():
    v = run_lint("""
        def check(self, d):
            return self.dist_index.contains(d)
    """, path="pbs_plus_tpu/pxar/datastore.py",
        rules=["dist-index-discipline"])
    assert names(v) == ["dist-index-discipline"]
    assert "probe_batch" in v[0].message


def test_dist_index_discipline_flags_per_digest_insert():
    v = run_lint("""
        def learn(dist_client, d):
            dist_client.insert(d)
    """, path="pbs_plus_tpu/server/sync_job.py",
        rules=["dist-index-discipline"])
    assert names(v) == ["dist-index-discipline"]


def test_dist_index_discipline_flags_per_digest_discard_and_has():
    v = run_lint("""
        def gc(dist_index_client, d):
            if dist_index_client.has(d):
                dist_index_client.discard(d)
    """, path="pbs_plus_tpu/server/gc.py",
        rules=["dist-index-discipline"])
    assert names(v) == ["dist-index-discipline", "dist-index-discipline"]


def test_dist_index_discipline_flags_handrolled_wire_call():
    v = run_lint("""
        def probe(conn, body):
            conn.request("POST", "/distidx/v1/probe", body)
            return conn.getresponse().read()
    """, path="pbs_plus_tpu/pxar/syncwire.py",
        rules=["dist-index-discipline"])
    assert names(v) == ["dist-index-discipline"]
    assert "DistIndexClient" in v[0].message


def test_dist_index_discipline_flags_datablob_flag_per_digest():
    v = run_lint("""
        def tag(index_client, d):
            index_client.mark_datablob(d)
    """, path="pbs_plus_tpu/pxar/remote.py",
        rules=["dist-index-discipline"])
    assert names(v) == ["dist-index-discipline"]


def test_dist_index_discipline_clean_on_batched_surface():
    v = run_lint("""
        def probe(dist_index, batch):
            hits = dist_index.probe_batch(batch)
            dist_index.insert_many([d for d, h in zip(batch, hits)
                                    if not h])
            return dist_index.discard_many_acked(batch)
    """, path="pbs_plus_tpu/pxar/datastore.py",
        rules=["dist-index-discipline"])
    assert v == []


def test_dist_index_discipline_module_itself_exempt():
    # the client implements the wire; its own endpoint strings and
    # per-digest convenience shims are sanctioned
    v = run_lint("""
        def request(self, conn, body):
            conn.request("POST", "/distidx/v1/insert", body)
        def contains(self, d):
            return self.dist_index.contains(d)
    """, path="pbs_plus_tpu/parallel/dist_index.py",
        rules=["dist-index-discipline"])
    assert v == []


def test_dist_index_discipline_local_index_receiver_clean():
    # per-digest calls on the LOCAL in-process index are index-discipline
    # territory, not this rule's
    v = run_lint("""
        def check(store, d):
            return store.index.contains(d)
    """, path="pbs_plus_tpu/pxar/datastore.py",
        rules=["dist-index-discipline"])
    assert v == []


def test_dist_index_discipline_out_of_scope_clean():
    v = run_lint("""
        def poke(dist_index, d):
            return dist_index.contains(d)
    """, path="tests/helpers.py", rules=["dist-index-discipline"])
    assert v == []


def test_index_discipline_unrelated_segments_file_clean():
    # a bare "segments" path with no .chunkindex component is NOT the
    # exact-confirm tier's — the rule must not annex the word
    v = run_lint("""
        def load(self):
            with open(self.log_segments_path, "rb") as f:
                return f.read()
    """, path="pbs_plus_tpu/server/sync_job.py",
        rules=["index-discipline"])
    assert v == []


# --------------------------------------------- bounded-queue-discipline


def test_bounded_queue_flags_unbounded_in_arpc():
    v = run_lint("""
        import asyncio
        q = asyncio.Queue()
    """, path="pbs_plus_tpu/arpc/mux.py",
        rules=["bounded-queue-discipline"])
    assert names(v) == ["bounded-queue-discipline"]
    assert "maxsize" in v[0].message


def test_bounded_queue_flags_bare_queue_import_in_server():
    v = run_lint("""
        from queue import Queue
        def pump():
            return Queue()
    """, path="pbs_plus_tpu/server/jobs.py",
        rules=["bounded-queue-discipline"])
    assert names(v) == ["bounded-queue-discipline"]


def test_bounded_queue_simplequeue_unboundable_by_type():
    v = run_lint("""
        import queue
        q = queue.SimpleQueue()
    """, path="pbs_plus_tpu/server/backup_job.py",
        rules=["bounded-queue-discipline"])
    assert names(v) == ["bounded-queue-discipline"]
    assert "cannot be bounded" in v[0].message


def test_bounded_queue_explicit_maxsize_clean():
    v = run_lint("""
        import asyncio, queue
        a = asyncio.Queue(maxsize=64)
        b = queue.Queue(16)
    """, path="pbs_plus_tpu/arpc/mux.py",
        rules=["bounded-queue-discipline"])
    assert v == []


def test_bounded_queue_scoped_to_fleet_facing_layers():
    # outside arpc/ and server/, unbounded queues are not this rule's
    # business (pipeline-internal queues are bounded by construction)
    v = run_lint("""
        import queue
        q = queue.Queue()
    """, path="pbs_plus_tpu/pxar/pipeline.py",
        rules=["bounded-queue-discipline"])
    assert v == []


def test_bounded_queue_inline_disable_with_rationale():
    v = run_lint("""
        import asyncio
        # deliberate: drained synchronously before every await point
        q = asyncio.Queue()  # pbslint: disable=bounded-queue-discipline
    """, path="pbs_plus_tpu/arpc/mux.py",
        rules=["bounded-queue-discipline"])
    assert v == []


# ------------------------------------------------- failpoint-discipline


def test_failpoint_literal_required():
    v = run_lint("""
        from pbs_plus_tpu.utils import failpoints
        name = "arpc.mux.read_frame"
        failpoints.hit(name)
    """, rules=["failpoint-discipline"])
    assert names(v) == ["failpoint-discipline"]
    assert "string literal" in v[0].message


def test_failpoint_duplicate_name_flagged():
    v = run_lint("""
        from pbs_plus_tpu.utils import failpoints
        failpoints.hit("arpc.mux.read_frame")
        failpoints.ahit("arpc.mux.read_frame")
    """, rules=["failpoint-discipline"])
    assert names(v) == ["failpoint-discipline"]
    assert "globally unique" in v[0].message
    assert v[0].line == 4


def test_failpoint_undocumented_name_flagged():
    v = run_lint("""
        from pbs_plus_tpu.utils import failpoints
        failpoints.hit("totally.bogus.site")
    """, rules=["failpoint-discipline"])
    assert names(v) == ["failpoint-discipline"]
    assert "fault-injection.md" in v[0].message


def test_failpoint_documented_literal_clean():
    # a catalogued name used once, via the plain and aliased receivers
    v = run_lint("""
        from pbs_plus_tpu.utils import failpoints
        from pbs_plus_tpu.utils import failpoints as _failpoints
        failpoints.hit("arpc.mux.read_frame")
        _failpoints.ahit("pipeline.hash", b"x")
        unrelated.hit("not a failpoint")
    """, rules=["failpoint-discipline"])
    assert v == []


def test_failpoint_sites_in_tree_match_catalog():
    """Acceptance: the live tree's instrumented sites lint clean with
    the rule active (literal + unique + catalogued)."""
    res = lint_paths([os.path.join(REPO_ROOT, "pbs_plus_tpu")],
                     build_rules({"failpoint-discipline"}))
    assert res.violations == [], [str(x) for x in res.violations]


def test_swallow_flags_broad_pass():
    v = run_lint("""
        try:
            x = 1
        except Exception:
            pass
    """)
    assert names(v) == ["no-silent-swallow"]
    assert v[0].line == 4


def test_swallow_flags_bare_except_and_tuple():
    v = run_lint("""
        try:
            x = 1
        except:
            cleanup()
        try:
            y = 2
        except (ValueError, Exception):
            ...
    """)
    assert names(v) == ["no-silent-swallow"] * 2


def test_swallow_negative_logging_or_raise_or_narrow():
    v = run_lint("""
        try:
            x = 1
        except Exception as e:
            L.warning("boom: %s", e)
        try:
            y = 2
        except Exception:
            raise
        except OSError:
            pass
        try:
            z = 3
        except:
            raise
    """)
    assert v == []


def test_async_blocking_positive():
    v = run_lint("""
        import time, subprocess

        async def handler():
            time.sleep(1)
            subprocess.run(["x"], timeout=5)
    """)
    assert names(v) == ["no-blocking-in-async"] * 2


def test_async_blocking_negative_sync_def_and_nested():
    v = run_lint("""
        import time

        def worker():
            time.sleep(1)              # sync context: fine

        async def outer():
            def inner():
                time.sleep(1)          # nested sync def: fine
            await asyncio.sleep(1)
    """, rules=["no-blocking-in-async"])
    assert v == []


def test_async_blocking_open_only_in_server():
    src = """
        async def handler():
            with open("/etc/x") as f:
                return f.read()
    """
    assert names(run_lint(src, path="pbs_plus_tpu/server/web.py",
                          rules=["no-blocking-in-async"])) == \
        ["no-blocking-in-async"]
    assert run_lint(src, path="pbs_plus_tpu/agent/x.py",
                    rules=["no-blocking-in-async"]) == []


def test_async_blocking_flags_sync_fsio():
    # the gap this suite itself could open: fsio's sync halves used in
    # an async handler bypass a lexical open() check
    v = run_lint("""
        from pbs_plus_tpu.utils import fsio

        async def handler(p):
            return fsio.read_bytes(p)
    """, rules=["no-blocking-in-async"])
    assert names(v) == ["no-blocking-in-async"]
    v = run_lint("""
        from pbs_plus_tpu.utils import fsio

        async def handler(p):
            return await fsio.aread_bytes(p)
    """, rules=["no-blocking-in-async"])
    assert v == []


def test_store_discipline_positive():
    v = run_lint("""
        from concurrent.futures import ThreadPoolExecutor

        class W:
            def go(self):
                self._pool = ThreadPoolExecutor(2)
                self.store.insert(b"d", b"c")
                self._store.touch(b"d")
    """, path="pbs_plus_tpu/pxar/x.py", rules=["locked-store-discipline"])
    assert names(v) == ["locked-store-discipline"] * 2


def test_store_discipline_negative():
    # unthreaded module, wrapped receiver, _LockedStore itself, non-pxar
    threaded = """
        import threading

        class _LockedStore:
            def insert(self, d, c):
                self._store.insert(d, c)

        def go(store):
            threading.Thread(target=None, daemon=True)
            locked_store(store).insert(b"d", b"c")
    """
    assert run_lint(threaded, path="pbs_plus_tpu/pxar/x.py",
                    rules=["locked-store-discipline"]) == []
    unthreaded = """
        def go(store):
            store.insert(b"d", b"c")
    """
    assert run_lint(unthreaded, path="pbs_plus_tpu/pxar/x.py",
                    rules=["locked-store-discipline"]) == []
    assert run_lint(threaded.replace("locked_store(store)", "store"),
                    path="pbs_plus_tpu/models/x.py",
                    rules=["locked-store-discipline"]) == []


def test_jit_purity_positive_decorated():
    v = run_lint("""
        import functools, time, jax

        @functools.partial(jax.jit, static_argnames=("k",))
        def kernel(x, k):
            t = time.time()
            print(x)
            return x * t
    """, rules=["jit-purity"])
    assert names(v) == ["jit-purity"] * 2


def test_jit_purity_positive_wrapped_and_mutation():
    v = run_lint("""
        import jax
        import numpy as np

        _count = 0

        def impl(x):
            global _count
            _count += 1
            return np.asarray(x).item()

        impl_jit = jax.jit(impl)
    """, rules=["jit-purity"])
    assert sorted(names(v)) == ["jit-purity"] * 3   # global, asarray, item


def test_jit_purity_negative():
    v = run_lint("""
        import time, jax
        import jax.numpy as jnp

        @jax.jit
        def kernel(x):
            return jnp.asarray(x) + 1

        def host_side():
            return time.time()      # not jitted: fine
    """, rules=["jit-purity"])
    assert v == []


def test_hostsync_positive():
    v = run_lint("""
        import jax

        def scan(xs):
            out = []
            for x in xs:
                out.append(x.item())
                jax.device_get(x)
            return out
    """, path="pbs_plus_tpu/ops/x.py", rules=["no-hostsync-in-hot-loop"])
    assert names(v) == ["no-hostsync-in-hot-loop"] * 2


def test_hostsync_negative_outside_loop_and_scope():
    src = """
        import jax

        def once(x):
            return x.item()         # not in a loop
    """
    assert run_lint(src, path="pbs_plus_tpu/ops/x.py",
                    rules=["no-hostsync-in-hot-loop"]) == []
    loop = """
        import jax

        def scan(xs):
            return [x.item() for x in xs]
    """
    # outside chunker/ops/parallel the rule is inert
    assert run_lint(loop.replace("import jax", "import jax\n"),
                    path="pbs_plus_tpu/server/x.py",
                    rules=["no-hostsync-in-hot-loop"]) == []
    # numpy-only module (no jax import): np.asarray in a loop is free
    numpy_only = """
        import numpy as np

        def scan(xs):
            for x in xs:
                np.asarray(x)
    """
    assert run_lint(numpy_only, path="pbs_plus_tpu/chunker/x.py",
                    rules=["no-hostsync-in-hot-loop"]) == []


def test_subprocess_timeout_positive():
    v = run_lint("""
        import subprocess
        from subprocess import check_output

        def go():
            subprocess.run(["x"], check=True)
            check_output(["y"])
            subprocess.Popen(["z"])
    """, rules=["subprocess-timeout"])
    assert names(v) == ["subprocess-timeout"] * 3


def test_subprocess_timeout_negative():
    v = run_lint("""
        import subprocess

        def go(run):
            subprocess.run(["x"], timeout=30)
            run(["y"])      # injected runner: the default carries timeout
    """, rules=["subprocess-timeout"])
    assert v == []


def test_thread_hygiene_positive():
    v = run_lint("""
        import threading

        def go(items):
            t = threading.Thread(target=None)
            for _ in items:
                lk = threading.Lock()
    """, rules=["thread-hygiene"])
    assert names(v) == ["thread-hygiene"] * 2


def test_thread_hygiene_negative():
    v = run_lint("""
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self._t = threading.Thread(target=None, daemon=True)
    """, rules=["thread-hygiene"])
    assert v == []


def test_resource_ctx_positive():
    v = run_lint("""
        def leak(p):
            data = open(p).read()
            f = open(p, "rb")
            return data
    """, rules=["resource-ctx"])
    assert names(v) == ["resource-ctx"] * 2


def test_resource_ctx_negative():
    v = run_lint("""
        def fine(p, q):
            with open(p) as f:
                data = f.read()
            g = open(q)
            try:
                g.read()
            finally:
                g.close()
            return data

        def handoff(p):
            return open(p)          # ownership transfers to the caller

        def stored(self, p):
            self.fh = open(p)       # owner object closes it
    """, rules=["resource-ctx"])
    assert v == []


def test_resource_ctx_flags_non_owning_consumers():
    v = run_lint("""
        import json

        def load_cfg(p):
            return json.load(open(p))
    """, rules=["resource-ctx"])
    assert names(v) == ["resource-ctx"]
    # genuine ownership transfer to an unknown callee stays exempt
    v = run_lint("""
        def hand_off(p, owner):
            owner.adopt(open(p))
    """, rules=["resource-ctx"])
    assert v == []


def test_mutable_default_positive_and_negative():
    v = run_lint("""
        def bad(xs=[]):
            return xs

        def also_bad(m=dict()):
            return m

        def fine(xs=None, n=3, s="x"):
            return xs or []
    """, rules=["mutable-default"])
    assert names(v) == ["mutable-default"] * 2


# ------------------------------------------------------- suppressions


def test_inline_disable_same_line():
    v = run_lint("""
        try:
            x = 1
        except Exception:   # pbslint: disable=no-silent-swallow
            pass
    """)
    assert v == []


def test_inline_disable_comment_line_above():
    v = run_lint("""
        try:
            x = 1
        # pbslint: disable=no-silent-swallow
        except Exception:
            pass
    """)
    assert v == []


def test_inline_disable_wrong_rule_does_not_suppress():
    v = run_lint("""
        try:
            x = 1
        except Exception:   # pbslint: disable=resource-ctx
            pass
    """)
    assert names(v) == ["no-silent-swallow"]


def test_disable_inside_string_literal_does_not_suppress():
    # only real COMMENT tokens suppress; docs/help strings must not
    v = run_lint("""
        HELP = "suppress with # pbslint: disable=all"

        def f(xs=[]):
            return xs
    """)
    assert "mutable-default" in names(v)
    v = run_lint("""
        try:
            x = 1
        except Exception:   # pbslint: disable=all
            pass
    """)
    assert v == []      # but a REAL comment still works


def test_disable_all_and_disable_file():
    v = run_lint("""
        try:
            x = 1
        except Exception:   # pbslint: disable=all
            pass
    """)
    assert v == []
    v = run_lint("""
        # pbslint: disable-file=no-silent-swallow
        try:
            x = 1
        except Exception:
            pass

        def bad(xs=[]):
            return xs
    """)
    assert names(v) == ["mutable-default"]      # file-disable is per-rule


# ----------------------------------------------------------- baseline


def V(path, rule, line=1):
    return Violation(rule, path, line, "m")


def test_baseline_ratchet_new_violation_fails():
    bl = _B({"a.py::no-silent-swallow": 1})
    diff = bl.compare([V("a.py", "no-silent-swallow"),
                       V("a.py", "no-silent-swallow", 9)])
    # only the EXCESS beyond the bucket is new, and counting is stable
    # in file order: the first stays deferred, the line-9 one reports
    assert not diff.ok
    assert [v.line for v in diff.new] == [9]
    assert diff.baselined == 1


def test_baseline_ratchet_baselined_passes_and_stale_reported():
    bl = _B({"a.py::no-silent-swallow": 2})
    diff = bl.compare([V("a.py", "no-silent-swallow")])
    assert diff.ok and diff.baselined == 1
    assert diff.stale == {"a.py::no-silent-swallow": 1}


def test_baseline_other_file_not_borrowed():
    # counts are per (file, rule): headroom in a.py must not excuse b.py
    bl = _B({"a.py::no-silent-swallow": 5})
    diff = bl.compare([V("b.py", "no-silent-swallow")])
    assert not diff.ok


def test_baseline_roundtrip(tmp_path):
    p = str(tmp_path / "bl.json")
    _B({"a.py::r": 2, "b.py::q": 1}).save(p)
    assert Baseline.load(p).entries == {"a.py::r": 2, "b.py::q": 1}
    assert Baseline.load(str(tmp_path / "missing.json")).entries == {}


def test_baseline_rejects_bad_counts(tmp_path):
    p = tmp_path / "bl.json"
    p.write_text(json.dumps({"version": 1, "entries": {"a.py::r": 0}}))
    with pytest.raises(ValueError):
        Baseline.load(str(p))


# ---------------------------------------------------------- CLI / gate


def _cli(args, cwd=REPO_ROOT):
    return subprocess.run([sys.executable, "-m", "tools.lint", *args],
                          capture_output=True, text=True, cwd=cwd,
                          timeout=120)


def test_cli_live_tree_is_clean_against_committed_baseline():
    r = _cli(["pbs_plus_tpu"])
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_seeded_violation_fails(tmp_path):
    bad = tmp_path / "seeded.py"
    bad.write_text("try:\n    x = 1\nexcept Exception:\n    pass\n")
    r = _cli([str(bad)])
    assert r.returncode == 1
    assert "no-silent-swallow" in r.stdout


def test_cli_json_output(tmp_path):
    bad = tmp_path / "seeded.py"
    bad.write_text("def f(xs=[]):\n    return xs\n")
    r = _cli(["--json", str(bad)])
    data = json.loads(r.stdout)
    assert data["ok"] is False
    assert data["new"][0]["rule"] == "mutable-default"


def test_cli_write_baseline_refuses_growth(tmp_path):
    bad = tmp_path / "seeded.py"
    bad.write_text("try:\n    x = 1\nexcept Exception:\n    pass\n")
    bl = tmp_path / "bl.json"
    _B({}).save(str(bl))
    r = _cli(["--baseline", str(bl), "--write-baseline", str(bad)])
    assert r.returncode == 2 and "refusing to GROW" in r.stderr
    r = _cli(["--baseline", str(bl), "--write-baseline", "--force",
              str(bad)])
    assert r.returncode == 0
    entries = json.loads(bl.read_text())["entries"]
    assert list(entries.values()) == [1]
    # with the forced baseline the same tree now passes
    r = _cli(["--baseline", str(bl), str(bad)])
    assert r.returncode == 0


def test_cli_parse_error_fails(tmp_path):
    bad = tmp_path / "broken.py"
    bad.write_text("def f(:\n")
    r = _cli([str(bad)])
    assert r.returncode == 1 and "PARSE ERROR" in r.stdout


def test_committed_baseline_is_small():
    """Acceptance: the committed ratchet defers at most 10 violations."""
    bl = Baseline.load(os.path.join(REPO_ROOT, "tools",
                                    "lint_baseline.json"))
    assert bl.total() <= 10


def test_lint_paths_walks_and_sorts(tmp_path):
    (tmp_path / "b.py").write_text("def f(xs=[]):\n    return xs\n")
    (tmp_path / "a.py").write_text("def g(m={}):\n    return m\n")
    (tmp_path / "__pycache__").mkdir()
    (tmp_path / "__pycache__" / "c.py").write_text("def h(s=set()): pass\n")
    res = lint_paths([str(tmp_path)], build_rules({"mutable-default"}))
    assert res.files == 2                       # __pycache__ skipped
    assert [os.path.basename(v.path) for v in res.violations] == \
        ["a.py", "b.py"]


# ------------------------------------------------- utils.fsio helpers
# fsio exists because of two rules (resource-ctx funnels small-file IO
# here; no-blocking-in-async funnels server handlers to the a* forms),
# so its contract is pinned alongside them.


def test_fsio_roundtrip_and_private_mode(tmp_path):
    from pbs_plus_tpu.utils import fsio
    p = str(tmp_path / "f.txt")
    fsio.write_text(p, "hi")
    assert fsio.read_text(p) == "hi"
    b = str(tmp_path / "f.bin")
    fsio.write_bytes(b, b"\x00\x01")
    assert fsio.read_bytes(b) == b"\x00\x01"
    k = str(tmp_path / "key.pem")
    fsio.write_private_bytes(k, b"secret")
    assert fsio.read_bytes(k) == b"secret"
    assert os.stat(k).st_mode & 0o777 == 0o600


def test_fsio_async_forms(tmp_path):
    import asyncio

    from pbs_plus_tpu.utils import fsio

    async def go():
        p = str(tmp_path / "a.txt")
        await fsio.awrite_text(p, "x")
        assert await fsio.aread_text(p) == "x"
        await fsio.awrite_bytes(p, b"y")
        assert await fsio.aread_bytes(p) == b"y"

    asyncio.run(go())


def test_cli_write_baseline_refuses_parse_errors(tmp_path):
    (tmp_path / "broken.py").write_text("def f(:\n")
    bl = tmp_path / "bl.json"
    r = _cli(["--baseline", str(bl), "--write-baseline", "--force",
              str(tmp_path)])
    assert r.returncode == 1 and "refusing" in r.stderr
    assert not bl.exists()


def test_cli_write_baseline_bad_existing_baseline_exits_2(tmp_path):
    (tmp_path / "ok.py").write_text("x = 1\n")
    bl = tmp_path / "bl.json"
    bl.write_text("{not json")
    r = _cli(["--baseline", str(bl), "--write-baseline", str(tmp_path)])
    assert r.returncode == 2 and "bad baseline" in r.stderr


def test_fsio_private_mode_reasserted_on_existing_file(tmp_path):
    from pbs_plus_tpu.utils import fsio
    p = str(tmp_path / "key.pem")
    with open(p, "w") as f:         # pre-existing world-readable file
        f.write("old")
    os.chmod(p, 0o644)
    fsio.write_private_bytes(p, b"new-secret")
    assert os.stat(p).st_mode & 0o777 == 0o600
    assert fsio.read_bytes(p) == b"new-secret"


def test_locked_store_slots_fallback_still_locks(tmp_path):
    """A store that rejects attribute memoization still gets a working
    per-call proxy (with a warning) — never an unwrapped store."""
    from pbs_plus_tpu.pxar.pipeline import _LockedStore, locked_store

    class SlotsStore:
        __slots__ = ()
        def insert(self, d, c, *, verify=True): return True
        def touch(self, d): pass

    st = SlotsStore()
    p = locked_store(st)
    assert isinstance(p, _LockedStore)
    assert p.insert(b"d", b"c") is True


def test_cli_write_baseline_subset_preserves_out_of_scope_buckets(tmp_path):
    """Reproduces the round-6 finding: ratcheting down on a path subset
    must not delete deferral state for files it never linted."""
    sub = tmp_path / "pkg"
    sub.mkdir()
    (sub / "clean.py").write_text("x = 1\n")
    bl = tmp_path / "bl.json"
    _B({"elsewhere/web.py::no-silent-swallow": 3}).save(str(bl))
    r = _cli(["--baseline", str(bl), "--write-baseline", str(sub)])
    assert r.returncode == 0, r.stdout + r.stderr
    entries = json.loads(bl.read_text())["entries"]
    assert entries == {"elsewhere/web.py::no-silent-swallow": 3}
    # but a bucket FOR a linted file does ratchet away when fixed
    rel = os.path.relpath(str(sub / "clean.py"), REPO_ROOT).replace(
        os.sep, "/")
    _B({f"{rel}::mutable-default": 2,
        "elsewhere/web.py::no-silent-swallow": 3}).save(str(bl))
    r = _cli(["--baseline", str(bl), "--write-baseline", str(sub)])
    assert r.returncode == 0, r.stdout + r.stderr
    entries = json.loads(bl.read_text())["entries"]
    assert entries == {"elsewhere/web.py::no-silent-swallow": 3}


def test_cli_write_baseline_rules_subset_preserves_other_rules(tmp_path):
    """--rules subset writes must leave other rules' buckets alone."""
    bad = tmp_path / "bad.py"
    bad.write_text("def f(xs=[]):\n    return xs\n")
    rel = os.path.relpath(str(bad), REPO_ROOT).replace(os.sep, "/")
    bl = tmp_path / "bl.json"
    _B({f"{rel}::no-silent-swallow": 1}).save(str(bl))
    r = _cli(["--baseline", str(bl), "--write-baseline", "--force",
              "--rules", "mutable-default", str(bad)])
    assert r.returncode == 0, r.stdout + r.stderr
    entries = json.loads(bl.read_text())["entries"]
    assert entries == {f"{rel}::no-silent-swallow": 1,
                       f"{rel}::mutable-default": 1}


# =================================================================
# v2 whole-program engine (tools/lint/graph.py) + interprocedural
# rules: guarded-by, lock-order, no-blocking-in-async-transitive,
# registry-consistency — docs/static-analysis.md is the reference.
# =================================================================


def _program(tmp_path, files):
    """Write `files` (relpath -> source) under tmp_path and link them
    into a Program rooted there (no cache)."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    prog, errors = build_program([str(tmp_path)], root=str(tmp_path),
                                 use_cache=False)
    assert errors == [], errors
    return prog


def _analyze(tmp_path, files, rule_name):
    prog = _program(tmp_path, files)
    [rule] = build_program_rules({rule_name})
    return rule.analyze(prog)


# ------------------------------------------------------- guarded-by


GUARDED_CLASS = """
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._d = dict()         # guarded-by: self._lock

        def good(self, k):
            with self._lock:
                return self._d.get(k)

        def {name}(self, k, v):
            {body}
"""


def test_guarded_by_flags_unguarded_write(tmp_path):
    v = _analyze(tmp_path, {"m.py": GUARDED_CLASS.format(
        name="bad", body="self._d[k] = v")}, "guarded-by")
    assert [x.rule for x in v] == ["guarded-by"]
    assert "self._d" in v[0].message and "bad" in v[0].message


def test_guarded_by_lexical_guard_clean(tmp_path):
    v = _analyze(tmp_path, {"m.py": GUARDED_CLASS.format(
        name="fine", body="with self._lock:\n                self._d[k] = v"
    )}, "guarded-by")
    assert v == []


def test_guarded_by_init_exempt_and_suppression(tmp_path):
    # __init__ populates before publication: exempt by design
    v = _analyze(tmp_path, {"m.py": """
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._d = {}         # guarded-by: self._lock
                self._d["seed"] = 1

            def bad(self):
                return self._d   # pbslint: disable=guarded-by
    """}, "guarded-by")
    assert v == []


def test_guarded_by_interprocedural_helper_clean(tmp_path):
    # helper touches _d unguarded but is ONLY called under the lock
    v = _analyze(tmp_path, {"m.py": """
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._d = {}         # guarded-by: self._lock

            def put(self, k, v):
                with self._lock:
                    self._put_locked(k, v)

            def _put_locked(self, k, v):
                self._d[k] = v
    """}, "guarded-by")
    assert v == []


def test_guarded_by_interprocedural_leak_flagged(tmp_path):
    # same helper, but ALSO reachable from an unguarded entry point
    v = _analyze(tmp_path, {"m.py": """
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._d = {}         # guarded-by: self._lock

            def put(self, k, v):
                with self._lock:
                    self._put_locked(k, v)

            def put_fast(self, k, v):
                self._put_locked(k, v)

            def _put_locked(self, k, v):
                self._d[k] = v
    """}, "guarded-by")
    assert [x.rule for x in v] == ["guarded-by"]
    assert "_put_locked" in v[0].message


def test_guarded_by_subscripted_lock_list(tmp_path):
    # `# guarded-by: self._locks` satisfied by `with self._locks[i]`
    v = _analyze(tmp_path, {"m.py": """
        import threading

        class Sharded:
            def __init__(self, n):
                self._locks = [threading.Lock() for _ in range(n)]
                self._slots = {}     # guarded-by: self._locks

            def put(self, i, k, v):
                with self._locks[i]:
                    self._slots[k] = v

            def bad(self, k):
                return self._slots.get(k)
    """}, "guarded-by")
    assert [x.rule for x in v] == ["guarded-by"]
    assert v[0].message.startswith("read of `self._slots`")


def test_guarded_by_module_global(tmp_path):
    v = _analyze(tmp_path, {"m.py": """
        import threading

        _lock = threading.Lock()
        _armed = {}                  # guarded-by: _lock

        def arm(site, fp):
            with _lock:
                _armed[site] = fp

        def peek(site):
            return _armed.get(site)
    """}, "guarded-by")
    assert [x.rule for x in v] == ["guarded-by"]
    assert "_armed" in v[0].message and "peek" in v[0].message


def test_guarded_by_annotation_does_not_bleed_to_next_line(tmp_path):
    # the trailing annotation on _d must not attach to _other
    v = _analyze(tmp_path, {"m.py": """
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._d = {}         # guarded-by: self._lock
                self._other = []

            def fine(self):
                return len(self._other)
    """}, "guarded-by")
    assert v == []


# ------------------------------------------------------- lock-order


def test_lock_order_lexical_cycle(tmp_path):
    v = _analyze(tmp_path, {"m.py": """
        import threading

        class AB:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def one(self):
                with self._a:
                    with self._b:
                        pass

            def two(self):
                with self._b:
                    with self._a:
                        pass
    """}, "lock-order")
    assert [x.rule for x in v] == ["lock-order"]
    assert "cycle" in v[0].message
    assert "AB._a" in v[0].message and "AB._b" in v[0].message


def test_lock_order_cycle_through_call_graph(tmp_path):
    # A held across a call whose callee acquires B, and vice versa
    v = _analyze(tmp_path, {"m.py": """
        import threading

        class AB:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def one(self):
                with self._a:
                    self._take_b()

            def _take_b(self):
                with self._b:
                    pass

            def two(self):
                with self._b:
                    self._take_a()

            def _take_a(self):
                with self._a:
                    pass
    """}, "lock-order")
    assert [x.rule for x in v] == ["lock-order"]
    assert "cycle" in v[0].message


def test_lock_order_consistent_order_clean(tmp_path):
    v = _analyze(tmp_path, {"m.py": """
        import threading

        class AB:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def one(self):
                with self._a:
                    with self._b:
                        pass

            def two(self):
                with self._a:
                    self._take_b()

            def _take_b(self):
                with self._b:
                    pass
    """}, "lock-order")
    assert v == []


def test_lock_order_self_nesting(tmp_path):
    # a plain Lock acquired while held is a self-deadlock; RLock is fine
    v = _analyze(tmp_path, {"m.py": """
        import threading

        class Bad:
            def __init__(self):
                self._lk = threading.Lock()

            def go(self):
                with self._lk:
                    with self._lk:
                        pass
    """}, "lock-order")
    assert [x.rule for x in v] == ["lock-order"]
    assert "self-deadlock" in v[0].message
    v = _analyze(tmp_path / "r", {"m.py": """
        import threading

        class Fine:
            def __init__(self):
                self._lk = threading.RLock()

            def go(self):
                with self._lk:
                    with self._lk:
                        pass
    """}, "lock-order")
    assert v == []


def test_lock_order_vocabulary_names_opaque_lock(tmp_path):
    # the resolver can't see `peer.lock`; the vocab comment names it,
    # closing the cycle against the class lock
    v = _analyze(tmp_path, {"m.py": """
        import threading

        class Conn:
            def __init__(self, peer):
                self._mine = threading.Lock()
                self.peer = peer

            def send(self):
                with self._mine:
                    with self.peer.lock:   # pbslint: lock-order peer-lock
                        pass

            def recv(self):
                with self.peer.lock:       # pbslint: lock-order peer-lock
                    with self._mine:
                        pass
    """}, "lock-order")
    assert [x.rule for x in v] == ["lock-order"]
    assert "peer-lock" in v[0].message


def test_lock_order_declaration_vocab_unifies(tmp_path):
    # declaration-site rename: acquisitions of the attr use the name
    v = _analyze(tmp_path, {"m.py": """
        import threading

        class J:
            def __init__(self):
                self._mu = threading.Lock()   # pbslint: lock-order the-mu

            def go(self):
                with self._mu:
                    pass
    """}, "lock-order")
    assert v == []      # no cycle; just exercises the rename path


# ---------------------------------- no-blocking-in-async-transitive


def test_transitive_blocking_three_frames_down(tmp_path):
    v = _analyze(tmp_path, {"m.py": """
        import time

        def inner():
            time.sleep(1)

        def middle():
            inner()

        async def handler():
            middle()
    """}, "no-blocking-in-async-transitive")
    assert [x.rule for x in v] == ["no-blocking-in-async-transitive"]
    assert "handler" in v[0].message
    assert "middle -> inner -> time.sleep" in v[0].message


def test_transitive_blocking_through_module_alias(tmp_path):
    # cross-module resolution through an import alias
    v = _analyze(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/helpers.py": """
            import time

            def slow():
                time.sleep(1)
        """,
        "pkg/web.py": """
            from pkg import helpers

            async def handler():
                helpers.slow()
        """}, "no-blocking-in-async-transitive")
    assert [x.rule for x in v] == ["no-blocking-in-async-transitive"]
    assert "slow -> time.sleep" in v[0].message


def test_transitive_blocking_to_thread_reference_clean(tmp_path):
    # a function REFERENCE handed to to_thread is not a call edge
    v = _analyze(tmp_path, {"m.py": """
        import asyncio
        import time

        def slow():
            time.sleep(1)

        async def handler():
            await asyncio.to_thread(slow)
    """}, "no-blocking-in-async-transitive")
    assert v == []


def test_transitive_blocking_depth0_left_to_per_file_rule(tmp_path):
    # direct calls are the per-file rule's finding, not this one's
    src = {"m.py": """
        import time

        async def handler():
            time.sleep(1)
    """}
    assert _analyze(tmp_path, src, "no-blocking-in-async-transitive") == []
    v = run_lint("""
        import time

        async def handler():
            time.sleep(1)
    """, rules=["no-blocking-in-async"])
    assert names(v) == ["no-blocking-in-async"]


def test_transitive_blocking_async_callee_not_propagated(tmp_path):
    # an async callee owns its own body; no double report at the caller
    v = _analyze(tmp_path, {"m.py": """
        import time

        async def inner():
            time.sleep(1)

        async def outer():
            await inner()
    """}, "no-blocking-in-async-transitive")
    assert v == []


# ------------------------------------------------ registry-consistency


_REG_CONF = """
    ENV_VARS = {{
        {entries}
    }}
"""
_REG_DOC = """# config

| Variable | Meaning |
|---|---|
{rows}
"""


def _registry_tree(declared, documented, reader_src):
    entries = "\n        ".join(
        f'"{n}": "doc",' for n in declared)
    rows = "\n".join(f"| `{n}` | x |" for n in documented)
    return {
        "pbs_plus_tpu/utils/conf.py": _REG_CONF.format(entries=entries),
        "docs/configuration.md": _REG_DOC.format(rows=rows),
        "docs/metrics.md": "| `pbs_plus_x` | x |",
        "pbs_plus_tpu/reader.py": reader_src,
    }


def test_registry_undeclared_env_string_flagged(tmp_path):
    v = _analyze(tmp_path, _registry_tree(
        ["PBS_PLUS_KNOWN"], ["PBS_PLUS_KNOWN"], """
        import os
        A = os.environ.get("PBS_PLUS_KNOWN", "")
        B = os.environ.get("PBS_PLUS_MYSTERY", "")
    """), "registry-consistency")
    assert [x.rule for x in v] == ["registry-consistency"]
    assert "PBS_PLUS_MYSTERY" in v[0].message
    assert v[0].path == "pbs_plus_tpu/reader.py"


def test_registry_orphan_declaration_flagged(tmp_path):
    v = _analyze(tmp_path, _registry_tree(
        ["PBS_PLUS_KNOWN", "PBS_PLUS_DEAD"],
        ["PBS_PLUS_KNOWN", "PBS_PLUS_DEAD"], """
        import os
        A = os.environ.get("PBS_PLUS_KNOWN", "")
    """), "registry-consistency")
    assert [x.rule for x in v] == ["registry-consistency"]
    assert "PBS_PLUS_DEAD" in v[0].message
    assert "nothing in the product tree references" in v[0].message


def test_registry_undocumented_env_flagged(tmp_path):
    v = _analyze(tmp_path, _registry_tree(
        ["PBS_PLUS_KNOWN"], [], """
        import os
        A = os.environ.get("PBS_PLUS_KNOWN", "")
    """), "registry-consistency")
    assert len(v) >= 1
    assert all("configuration.md" in x.message for x in v)


def test_registry_docstrings_and_prefixes_exempt(tmp_path):
    v = _analyze(tmp_path, _registry_tree(
        ["PBS_PLUS_KNOWN"], ["PBS_PLUS_KNOWN"], '''
        """Module doc naming PBS_PLUS_UNDECLARED is fine."""
        import os
        PREFIX = "PBS_PLUS_INIT_"          # trailing _: a prefix
        HOOK = "PBS_PLUS__STATUS"          # double underscore: hooks ns
        A = os.environ.get("PBS_PLUS_KNOWN", "")
    '''), "registry-consistency")
    assert v == []


def test_registry_metrics_doc_sync(tmp_path):
    files = _registry_tree(["PBS_PLUS_K"], ["PBS_PLUS_K"], """
        import os
        A = os.environ.get("PBS_PLUS_K", "")
    """)
    files["pbs_plus_tpu/server/metrics.py"] = """
        def render(gauge):
            gauge("pbs_plus_documented", "h", [({}, 1.0)])
            gauge("pbs_plus_missing_doc", "h", [({}, 1.0)])
            gauge("pbs_plus_documented", "h", [({}, 2.0)])
            gauge("pbs_plus_dead", "h", [])
    """
    files["docs/metrics.md"] = (
        "| `pbs_plus_documented` | x |\n"
        "| `pbs_plus_dead` | x |\n"
        "| `pbs_plus_ghost` | x |\n")
    v = _analyze(tmp_path, files, "registry-consistency")
    msgs = sorted(x.message for x in v)
    assert any("pbs_plus_missing_doc" in m and "metrics.md" in m
               for m in msgs)
    assert any("registered twice" in m for m in msgs)
    assert any("pbs_plus_dead" in m and "empty sample" in m for m in msgs)
    assert any("pbs_plus_ghost" in m and "no such gauge" in m for m in msgs)
    assert len(v) == 4


def test_registry_live_tree_is_closed():
    """Acceptance: the real tree's env/metrics registries are closed in
    both directions (ENV_VARS <-> code <-> docs tables)."""
    prog, errors = build_program(
        [os.path.join(REPO_ROOT, "pbs_plus_tpu")], use_cache=False)
    assert errors == []
    [rule] = build_program_rules({"registry-consistency"})
    assert rule.analyze(prog) == []


# ------------------------------------------- durable-write-discipline


def test_durable_write_flags_raw_replace(tmp_path):
    v = _analyze(tmp_path, {"pbs_plus_tpu/pxar/datastore.py": """
        import os
        def publish(tmp, final):
            os.replace(tmp, final)
    """}, "durable-write-discipline")
    assert len(v) == 1 and "atomicio" in v[0].message
    assert "os.replace" in v[0].message


def test_durable_write_flags_write_open_and_shutil_move(tmp_path):
    v = _analyze(tmp_path, {"pbs_plus_tpu/pxar/chunkindex.py": """
        import shutil
        def snap(path):
            with open(path, "wb") as f:
                f.write(b"x")
        def mv(a, b):
            shutil.move(a, b)
    """}, "durable-write-discipline")
    assert len(v) == 2
    assert any("write-mode open" in x.message for x in v)
    assert any("shutil.move" in x.message for x in v)


def test_durable_write_flags_helper_publishing_on_behalf(tmp_path):
    # the interprocedural leg: the raw op hides one (and two) calls away
    v = _analyze(tmp_path, {
        "pbs_plus_tpu/pxar/digestlog.py": """
            from pbs_plus_tpu.helpers import swap
            def flush(tmp, final):
                swap(tmp, final)
        """,
        "pbs_plus_tpu/helpers.py": """
            import os
            def swap(a, b):
                _inner(a, b)
            def _inner(a, b):
                os.rename(a, b)
        """}, "durable-write-discipline")
    assert len(v) == 1
    assert v[0].path.endswith("digestlog.py")
    assert "on behalf" in v[0].message


def test_durable_write_atomicio_calls_and_deletes_clean(tmp_path):
    # atomicio IS the sanctioned raw-fs user: calling it never taints,
    # and deletions/read-opens are not publishes
    v = _analyze(tmp_path, {
        "pbs_plus_tpu/pxar/datastore.py": """
            import os
            from pbs_plus_tpu.utils import atomicio
            def publish(path, data):
                atomicio.replace_bytes(path, data)
            def reap(p):
                os.unlink(p)
            def read(p):
                with open(p, "rb") as f:
                    return f.read()
        """,
        "pbs_plus_tpu/utils/atomicio.py": """
            import os
            def replace_bytes(path, data):
                tmp = path + ".tmp.x"
                with open(tmp, "wb") as f:
                    f.write(data)
                os.replace(tmp, path)
        """}, "durable-write-discipline")
    assert v == []


def test_durable_write_scoped_to_durable_modules(tmp_path):
    # a raw publish in a module outside DURABLE_MODULES (with no durable
    # caller) is out of scope for this rule
    v = _analyze(tmp_path, {"pbs_plus_tpu/server/web.py": """
        import os
        def rotate(a, b):
            os.replace(a, b)
    """}, "durable-write-discipline")
    assert v == []


# ----------------------------------------------- ordering-discipline


def test_ordering_flags_unlink_without_discard(tmp_path):
    v = _analyze(tmp_path, {"pbs_plus_tpu/pxar/datastore.py": """
        import os
        def sweep(paths):
            for p in paths:
                os.unlink(p)
    """}, "ordering-discipline")
    assert len(v) == 1
    assert "discard-before-unlink" in v[0].message


def test_ordering_flags_inverted_lexical_order(tmp_path):
    v = _analyze(tmp_path, {"pbs_plus_tpu/pxar/datastore.py": """
        import os
        def sweep(self, p, digests):
            os.unlink(p)
            self.index.discard_many_acked(digests)
    """}, "ordering-discipline")
    assert len(v) == 1 and "discard-before-unlink" in v[0].message


def test_ordering_flags_sweep_without_mark_and_retire_without_install(
        tmp_path):
    v = _analyze(tmp_path, {
        "pbs_plus_tpu/server/prune.py": """
            def gc(self, ds):
                ds.chunks.sweep(before=0)
        """,
        "pbs_plus_tpu/parallel/dist_index.py": """
            def rebalance(self):
                self._retire_from_old()
                self._install_map_on_all()
            def _retire_from_old(self):
                pass
            def _install_map_on_all(self):
                pass
        """}, "ordering-discipline")
    msgs = sorted(x.message for x in v)
    assert any("mark-before-sweep" in m for m in msgs)
    assert any("map-install-before-retire" in m for m in msgs)
    assert len(v) == 2


def test_ordering_in_function_order_satisfies(tmp_path):
    v = _analyze(tmp_path, {"pbs_plus_tpu/pxar/chunkindex.py": """
        def discard(self, d, fp):
            self._log.discard(d)
            self._cuckoo.discard_fp(fp)
    """}, "ordering-discipline")
    assert v == []


def test_ordering_caller_domination_satisfies(tmp_path):
    # the after-site lives in a helper; EVERY caller performs the
    # before-event ahead of the call site, so the helper is dominated
    v = _analyze(tmp_path, {"pbs_plus_tpu/pxar/datastore.py": """
        import os
        class Store:
            def sweep(self, digests, paths):
                self.index.discard_many_acked(digests)
                self._reap(paths)
            def _reap(self, paths):
                for p in paths:
                    os.unlink(p)
    """}, "ordering-discipline")
    assert v == []


def test_ordering_undominated_second_caller_flags(tmp_path):
    # same helper, but a second caller reaches it WITHOUT the discard:
    # domination fails and the after-site is flagged
    v = _analyze(tmp_path, {"pbs_plus_tpu/pxar/datastore.py": """
        import os
        class Store:
            def sweep(self, digests, paths):
                self.index.discard_many_acked(digests)
                self._reap(paths)
            def wipe(self, paths):
                self._reap(paths)
            def _reap(self, paths):
                for p in paths:
                    os.unlink(p)
    """}, "ordering-discipline")
    assert len(v) == 1 and "discard-before-unlink" in v[0].message


def test_ordering_inline_disable_honored(tmp_path):
    v = _analyze(tmp_path, {"pbs_plus_tpu/pxar/datastore.py": """
        import os
        def reap_debris(p):
            # consume-once debris, no index entry pairs with this
            # pbslint: disable=ordering-discipline
            os.unlink(p)
    """}, "ordering-discipline")
    assert v == []


# --------------------------------------------- typed-error-discipline


def test_typed_error_flags_runtime_error_at_boundary(tmp_path):
    v = _analyze(tmp_path, {"pbs_plus_tpu/pxar/syncwire.py": """
        class SyncError(Exception): pass
        class SyncWireError(SyncError): pass
        def pull(ok):
            if not ok:
                raise RuntimeError("peer rejected")
    """}, "typed-error-discipline")
    assert len(v) == 1
    assert "raise RuntimeError" in v[0].message
    assert "SyncError" in v[0].message        # taxonomy named in the fix


def test_typed_error_flags_bare_exception_and_dotted(tmp_path):
    v = _analyze(tmp_path, {"pbs_plus_tpu/server/web.py": """
        import builtins
        def handler(req):
            raise Exception("bad request")
        def other(req):
            raise builtins.RuntimeError("oops")
    """}, "typed-error-discipline")
    assert len(v) == 2
    assert all("web" in x.message for x in v)


def test_typed_error_missing_declared_class_flags(tmp_path):
    # TYPED_ERRORS declares SyncError at syncwire.py; renaming it away
    # must fail the build
    v = _analyze(tmp_path, {"pbs_plus_tpu/pxar/syncwire.py": """
        class SyncWireError(Exception): pass
    """}, "typed-error-discipline")
    assert any("SyncError" in x.message and "no such class" in x.message
               for x in v)


def test_typed_error_taxonomy_and_reraise_clean(tmp_path):
    # raising FROM the taxonomy, other typed errors, and bare re-raise
    # are all legal; RuntimeError outside a boundary is out of scope
    v = _analyze(tmp_path, {
        "pbs_plus_tpu/pxar/syncwire.py": """
            class SyncError(Exception): pass
            class SyncWireError(SyncError): pass
            def pull(ok):
                if not ok:
                    raise SyncWireError("peer rejected")
                try:
                    return 1
                except OSError:
                    raise
            def check(v):
                if v < 0:
                    raise ValueError(v)
        """,
        "pbs_plus_tpu/pxar/other.py": """
            def internal():
                raise RuntimeError("not a boundary")
        """}, "typed-error-discipline")
    assert v == []


# ------------------------------------------------ engine: graph + cache


def test_call_resolution_self_and_alias_and_from_import(tmp_path):
    prog = _program(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/a.py": """
            def af():
                pass

            class C:
                def m(self):
                    self.helper()

                def helper(self):
                    pass
        """,
        "pkg/b.py": """
            from pkg import a
            from pkg.a import af

            def direct():
                af()

            def aliased():
                a.af()
        """})
    s = prog.by_module["pkg.b"]
    assert prog.resolve_call(s, "direct", "af") == "pkg/a.py::af"
    assert prog.resolve_call(s, "aliased", "a.af") == "pkg/a.py::af"
    sa = prog.by_module["pkg.a"]
    assert prog.resolve_call(sa, "C.m", "self.helper") == "pkg/a.py::C.helper"
    # reverse edges link back
    assert any(c[0] == "pkg/b.py::direct"
               for c in prog.callers["pkg/a.py::af"])


def test_method_resolution_through_project_base_class(tmp_path):
    prog = _program(tmp_path, {
        "m.py": """
            class Base:
                def helper(self):
                    pass

            class Child(Base):
                def go(self):
                    self.helper()
        """})
    s = prog.by_module["m"]
    assert prog.resolve_call(s, "Child.go", "self.helper") == \
        "m.py::Base.helper"


def test_graph_cache_roundtrip_and_invalidation(tmp_path):
    src_v1 = "import os\nA = os.environ.get('X', '')\n"
    src_v2 = "import time\n\ndef f():\n    time.sleep(1)\n"
    mod = tmp_path / "m.py"
    mod.write_text(src_v1)
    cache = tmp_path / "cache.json"
    p1, _ = build_program([str(tmp_path)], root=str(tmp_path),
                          use_cache=True, cache_path=str(cache))
    assert cache.exists()
    assert "f" not in p1.by_module["m"].functions
    # unchanged file: the cached summary round-trips identically
    p2, _ = build_program([str(tmp_path)], root=str(tmp_path),
                          use_cache=True, cache_path=str(cache))
    assert p2.by_module["m"].functions == p1.by_module["m"].functions
    # edited file: sha mismatch forces re-summarize through the cache
    mod.write_text(src_v2)
    p3, _ = build_program([str(tmp_path)], root=str(tmp_path),
                          use_cache=True, cache_path=str(cache))
    assert "f" in p3.by_module["m"].functions
    assert [c[0] for c in p3.by_module["m"].functions["f"]["calls"]] == \
        ["time.sleep"]


def test_graph_cache_corrupt_or_stale_version_ignored(tmp_path):
    (tmp_path / "m.py").write_text("x = 1\n")
    cache = tmp_path / "cache.json"
    cache.write_text("{not json")
    p, errors = build_program([str(tmp_path)], root=str(tmp_path),
                              use_cache=True, cache_path=str(cache))
    assert errors == [] and "m" in p.by_module
    cache.write_text(json.dumps({"version": -1, "files": {}}))
    p, errors = build_program([str(tmp_path)], root=str(tmp_path),
                              use_cache=True, cache_path=str(cache))
    assert errors == [] and "m" in p.by_module


def test_graph_cache_keyed_on_rule_set_hash(tmp_path):
    """An edited rule (or protocols.py declaration) must force
    re-analysis even though the ANALYZED files' hashes are unchanged:
    the cache is keyed on ``rules_fingerprint()`` over the engine's own
    sources.  Simulated by poisoning a cached summary and flipping the
    stored fingerprint — a stale fingerprint must drop the whole cache
    (the poison vanishes), a current one must honor it."""
    from tools.lint.graph import rules_fingerprint
    (tmp_path / "m.py").write_text("import time\n\ndef f():\n"
                                   "    time.sleep(1)\n")
    cache = tmp_path / "cache.json"
    build_program([str(tmp_path)], root=str(tmp_path),
                  use_cache=True, cache_path=str(cache))
    data = json.loads(cache.read_text())
    fp = rules_fingerprint()
    assert data["rules"] == fp == rules_fingerprint()   # stable key
    # poison the cached summary; same fingerprint → cache honored, the
    # poisoned record round-trips (proving the cache really was read)
    data["files"]["m.py"]["summary"]["functions"]["f"]["calls"] = []
    cache.write_text(json.dumps(data))
    p, _ = build_program([str(tmp_path)], root=str(tmp_path),
                         use_cache=True, cache_path=str(cache))
    assert p.by_module["m"].functions["f"]["calls"] == []
    # stale fingerprint (an edited rule file) → full re-extract: the
    # poison is gone and the rewritten cache carries the current key
    data["rules"] = "stale" + fp[:8]
    cache.write_text(json.dumps(data))
    p, _ = build_program([str(tmp_path)], root=str(tmp_path),
                         use_cache=True, cache_path=str(cache))
    assert [c[0] for c in p.by_module["m"].functions["f"]["calls"]] == \
        ["time.sleep"]
    assert json.loads(cache.read_text())["rules"] == fp


def test_graph_subset_run_does_not_evict_cache(tmp_path):
    (tmp_path / "a.py").write_text("x = 1\n")
    (tmp_path / "b.py").write_text("y = 2\n")
    cache = tmp_path / "cache.json"
    build_program([str(tmp_path)], root=str(tmp_path),
                  use_cache=True, cache_path=str(cache))
    build_program([str(tmp_path / "a.py")], root=str(tmp_path),
                  use_cache=True, cache_path=str(cache))
    data = json.loads(cache.read_text())
    assert set(data["files"]) == {"a.py", "b.py"}


def test_program_rules_all_clean_on_live_tree():
    """Acceptance: all four interprocedural passes are clean over the
    real tree (the committed baseline stays EMPTY — any true positive
    they surface gets fixed or carries a justified inline disable)."""
    prog, errors = build_program(
        [os.path.join(REPO_ROOT, "pbs_plus_tpu")], use_cache=False)
    assert errors == []
    found = []
    for rule in build_program_rules():
        found.extend(rule.analyze(prog))
    assert found == [], [str(x) for x in found]


def test_static_lock_graph_matches_runtime_witness(tmp_path):
    """Static/dynamic cross-check at unit scale: drive a real ChunkStore
    insert + sweep under lockwatch; the observed edges must be acyclic
    (the property the static pass proves for the same code)."""
    import hashlib as _hl

    from pbs_plus_tpu.utils import lockwatch

    with lockwatch.watching() as watch:
        from pbs_plus_tpu.pxar.datastore import ChunkStore
        store = ChunkStore(str(tmp_path), n_shards=4, index_budget_mb=1)
        for i in range(8):
            data = bytes([i]) * 64
            store.insert(_hl.sha256(data).digest(), data)
        store.sweep(before=0.0)     # nothing old enough; exercises locks
    watch.assert_acyclic()
    assert any("datastore.py" in a or "datastore.py" in b
               for a, b in watch.edges()), watch.edges()


def test_lint_the_linter():
    """tools/lint holds itself to its own rules (wired into
    tools/verify_lint.sh as the second gate)."""
    res = lint_paths([os.path.join(REPO_ROOT, "tools", "lint")],
                     build_rules())
    assert res.errors == []
    assert res.violations == [], [str(x) for x in res.violations]
    prog, errors = build_program(
        [os.path.join(REPO_ROOT, "tools", "lint")], use_cache=False)
    assert errors == []
    found = []
    for rule in build_program_rules():
        found.extend(rule.analyze(prog))
    assert found == [], [str(x) for x in found]


def test_whole_program_run_wall_clock_bound():
    """Perf gate: the full v2 run (per-file + graph build with a cold
    cache + all four program rules) stays comfortably interactive on
    this 1-core host.  Measured ~3s cold / ~1.5s warm; the bound leaves
    CI-noise headroom without ever letting the pass become a minutes-
    long chore nobody runs."""
    import time as _t
    t0 = _t.monotonic()
    r = _cli(["--no-cache", "pbs_plus_tpu"])
    elapsed = _t.monotonic() - t0
    assert r.returncode == 0, r.stdout + r.stderr
    assert elapsed < 60.0, f"whole-program lint took {elapsed:.1f}s"


# ------------------------------------------------- CLI: sarif / changed


def test_cli_sarif_output(tmp_path):
    bad = tmp_path / "seeded.py"
    bad.write_text("def f(xs=[]):\n    return xs\n")
    r = _cli(["--format", "sarif", str(bad)])
    assert r.returncode == 1
    doc = json.loads(r.stdout)
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "pbslint"
    results = run["results"]
    assert results[0]["ruleId"] == "mutable-default"
    loc = results[0]["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("seeded.py")
    assert loc["region"]["startLine"] == 1
    [rr] = [rr for rr in run["tool"]["driver"]["rules"]
            if rr["id"] == "mutable-default"]
    # per-rule metadata round-trips: the invariant as shortDescription
    # and a helpUri anchored into the rule's docs section
    assert rr["helpUri"] == "docs/static-analysis.md#mutable-default"
    assert "default" in rr["shortDescription"]["text"]


def test_sarif_program_rule_metadata_roundtrip(tmp_path):
    # program-rule findings carry the same metadata shape: invariant as
    # shortDescription, per-rule docs anchor as helpUri
    from tools.lint.cli import _sarif
    vs = _analyze(tmp_path, {"pbs_plus_tpu/pxar/datastore.py": """
        import os
        def sweep(p):
            os.unlink(p)
    """}, "ordering-discipline")
    assert vs
    [rule] = build_program_rules({"ordering-discipline"})
    doc = _sarif(vs, [], rule_index={rule.name: rule})
    run = doc["runs"][0]
    assert run["results"][0]["ruleId"] == "ordering-discipline"
    [rr] = run["tool"]["driver"]["rules"]
    assert rr["helpUri"] == \
        "docs/static-analysis.md#ordering-discipline"
    assert rr["shortDescription"]["text"] == rule.invariant
    assert "happens-before" in rr["shortDescription"]["text"]
    json.loads(json.dumps(doc))                # serializable round-trip


def test_cli_sarif_clean_tree_empty_results(tmp_path):
    ok = tmp_path / "ok.py"
    ok.write_text("x = 1\n")
    r = _cli(["--format", "sarif", str(ok)])
    assert r.returncode == 0
    assert json.loads(r.stdout)["runs"][0]["results"] == []


def test_cli_changed_only_filters_outside_files(tmp_path):
    # a violation in a file OUTSIDE the repo's changed set is filtered
    bad = tmp_path / "seeded.py"
    bad.write_text("def f(xs=[]):\n    return xs\n")
    r = _cli([str(bad)])
    assert r.returncode == 1
    r = _cli(["--changed-only", str(bad)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "changed files only" in r.stdout


def test_cli_changed_only_keeps_changed_files():
    # an untracked bad file INSIDE the repo is in the changed set
    p = os.path.join(REPO_ROOT, "_pbslint_changed_probe.py")
    with open(p, "w") as f:
        f.write("def f(xs=[]):\n    return xs\n")
    try:
        r = _cli(["--changed-only", p])
        assert r.returncode == 1, r.stdout + r.stderr
        assert "mutable-default" in r.stdout
    finally:
        os.unlink(p)


# ------------------------------------- baseline rename gap (+ prune)


def test_baseline_orphaned_entry_fails(tmp_path):
    """Regression for the long-standing ratchet gap: a renamed file's
    baseline buckets used to linger silently forever."""
    ok = tmp_path / "ok.py"
    ok.write_text("x = 1\n")
    bl = tmp_path / "bl.json"
    _B({"no/longer/exists.py::no-silent-swallow": 2}).save(str(bl))
    r = _cli(["--baseline", str(bl), str(ok)])
    assert r.returncode == 1
    assert "no longer exist" in r.stdout
    assert "no/longer/exists.py::no-silent-swallow" in r.stdout


def test_baseline_prune_escape_hatch(tmp_path):
    ok = tmp_path / "ok.py"
    ok.write_text("x = 1\n")
    bl = tmp_path / "bl.json"
    rel = os.path.relpath(str(ok), REPO_ROOT).replace(os.sep, "/")
    _B({"no/longer/exists.py::no-silent-swallow": 2,
        f"{rel}::mutable-default": 1}).save(str(bl))
    r = _cli(["--baseline", str(bl), "--prune-baseline", str(ok)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "pruned 1" in r.stdout
    entries = json.loads(bl.read_text())["entries"]
    # the live file's bucket survives; only the orphan went
    assert entries == {f"{rel}::mutable-default": 1}


def test_baseline_orphan_check_respects_existing_files(tmp_path):
    # entries for files that DO exist never trip the orphan check
    ok = tmp_path / "ok.py"
    ok.write_text("x = 1\n")
    rel = os.path.relpath(str(ok), REPO_ROOT).replace(os.sep, "/")
    bl = tmp_path / "bl.json"
    _B({f"{rel}::mutable-default": 1}).save(str(bl))
    r = _cli(["--baseline", str(bl), str(ok)])
    assert r.returncode == 0, r.stdout + r.stderr


# --------------------------------------- review-hardening regressions


def test_guarded_by_vocab_named_with_still_satisfies(tmp_path):
    """A `# pbslint: lock-order` name on the `with` must not stop the
    same acquisition from satisfying guarded-by (held entries carry
    both the raw expression and the vocabulary name)."""
    v = _analyze(tmp_path, {"m.py": """
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._d = dict()     # guarded-by: self._lock

            def put(self, k, x):
                with self._lock:     # pbslint: lock-order box-lock
                    self._d[k] = x
    """}, "guarded-by")
    assert v == []


def test_guarded_by_other_classes_same_named_lock_not_sufficient(tmp_path):
    """Lock identity is canonical: another class holding ITS OWN
    `self._lock` does not guard this class's annotated state."""
    v = _analyze(tmp_path, {"m.py": """
        import threading

        class A:
            def __init__(self):
                self._lock = threading.Lock()
                self._d = dict()     # guarded-by: self._lock

            def unsafe(self):
                self._d["x"] = 1

        class B:
            def __init__(self, a):
                self._lock = threading.Lock()
                self.a = a

            def go(self):
                with self._lock:         # B's lock, not A's
                    A.unsafe(self.a)
    """}, "guarded-by")
    assert [x.rule for x in v] == ["guarded-by"]
    assert "unsafe" in v[0].message


def test_registry_env_doc_prefix_name_not_sufficient(tmp_path):
    """`PBS_PLUS_CHUNKER` must not count as documented just because
    `PBS_PLUS_CHUNKER_BACKEND` appears in the table (exact backticked
    names only)."""
    v = _analyze(tmp_path, _registry_tree(
        ["PBS_PLUS_CHUNKER"], ["PBS_PLUS_CHUNKER_BACKEND"], """
        import os
        A = os.environ.get("PBS_PLUS_CHUNKER", "")
    """), "registry-consistency")
    msgs = [x.message for x in v]
    assert any("PBS_PLUS_CHUNKER" in m and "configuration.md" in m
               for m in msgs), msgs


def test_lock_order_startup_mu_vocab_site_enters_graph():
    """The property-reached jobs.startup_mu acquisition joins the
    static graph via its vocabulary name — the site moved with the
    enqueue path into the JobQueueService (ISSUE 15), and the fleet
    worker's mirror site carries the same annotation."""
    prog, errors = build_program(
        [os.path.join(REPO_ROOT, "pbs_plus_tpu")], use_cache=False)
    assert errors == []
    for path in ("server/services/jobqueue.py", "server/fleetproc.py"):
        s = next(x for x in prog.files.values()
                 if x.path.endswith(path))
        vocabs = [a[3] for fn in s.functions.values()
                  for a in fn["acquires"]]
        assert "jobs.startup-mu" in vocabs, path


# ------------------------------------------------- span-discipline


def test_span_discipline_bare_span_call_flagged():
    v = run_lint("""
        from pbs_plus_tpu.utils import trace

        def f():
            sp = trace.span("job")
            sp.__enter__()
    """, rules={"span-discipline"})
    assert names(v) == ["span-discipline"]
    assert "with" in v[0].message


def test_span_discipline_nonliteral_names_flagged():
    v = run_lint("""
        from pbs_plus_tpu.utils import trace

        def f(name):
            with trace.span(name):
                pass
            trace.record("mux." + "write_frame", 1e-6)
    """, rules={"span-discipline"})
    assert names(v) == ["span-discipline", "span-discipline"]
    assert all("literal" in x.message for x in v)


def test_span_discipline_with_and_oneshot_usage_clean():
    # names come from the real docs/observability.md catalog
    v = run_lint("""
        from pbs_plus_tpu.utils import trace

        def f(ctx):
            with trace.span("job", kind="backup"):
                with trace.attached(ctx), trace.span("ingest.sha",
                                                     chunks=3):
                    pass
            trace.emit("ingest.cdc", 0.25, aggregated=True)
            trace.record("mux.write_frame", 1e-6)
    """, rules={"span-discipline"})
    assert v == []


def test_span_discipline_round_trip_is_a_span_api_too():
    """``trace.round_trip`` (the span of one trip to the device) is held
    to the same contract: a ``with`` item, a literal documented name."""
    v = run_lint("""
        from pbs_plus_tpu.utils import trace

        def f(stats, name):
            with trace.round_trip("device.scan", stats, seg_pad=4) as rt:
                with rt.phase("pack"):
                    pass
            rt = trace.round_trip("device.sha", stats)
            with trace.round_trip(name, stats):
                pass
            with trace.round_trip("device.nothing", stats):
                pass
    """, rules={"span-discipline"})
    assert names(v) == ["span-discipline"] * 3
    assert "with" in v[0].message and "round_trip" in v[0].message
    assert "literal" in v[1].message
    assert "observability.md" in v[2].message


def test_span_discipline_undocumented_name_flagged():
    v = run_lint("""
        from pbs_plus_tpu.utils import trace

        def f():
            with trace.span("no.such.span"):
                pass
    """, rules={"span-discipline"})
    assert names(v) == ["span-discipline"]
    assert "observability.md" in v[0].message


def test_span_discipline_trace_module_itself_exempt():
    v = run_lint("""
        import trace

        def helper(name):
            return trace.span(name)
    """, path="pbs_plus_tpu/utils/trace.py", rules={"span-discipline"})
    assert v == []


# ----------------------------------- registry-consistency: spans/hists


def _span_tree(registry, documented, user_src):
    trace_src = ("SPANS = {\n"
                 + "".join(f'    "{n}": None,\n' for n in registry)
                 + "}\n")
    rows = "\n".join(f"| `{n}` | x |" for n in documented)
    return {
        "pbs_plus_tpu/utils/trace.py": trace_src,
        "docs/observability.md": f"# spans\n\n| Span | Meaning |\n"
                                 f"|---|---|\n{rows}\n",
        "pbs_plus_tpu/user.py": user_src,
    }


def test_registry_span_literal_not_declared_flagged(tmp_path):
    v = _analyze(tmp_path, _span_tree(
        ["known.span"], ["known.span"], """
        from pbs_plus_tpu.utils import trace

        def f():
            with trace.span("known.span"):
                trace.record("mystery.span", 1.0)
    """), "registry-consistency")
    assert [x.rule for x in v] == ["registry-consistency"]
    assert "mystery.span" in v[0].message
    assert v[0].path == "pbs_plus_tpu/user.py"


def test_registry_round_trip_site_uses_its_span_name(tmp_path):
    v = _analyze(tmp_path, _span_tree(
        ["known.span", "device.trip"], ["known.span", "device.trip"], """
        from pbs_plus_tpu.utils import trace

        def f(stats):
            with trace.span("known.span"):
                with trace.round_trip("device.trip", stats):
                    pass
                with trace.round_trip("device.other", stats):
                    pass
    """), "registry-consistency")
    assert len(v) == 1 and "device.other" in v[0].message


def test_registry_span_orphan_declaration_flagged(tmp_path):
    v = _analyze(tmp_path, _span_tree(
        ["known.span", "dead.span"], ["known.span", "dead.span"], """
        from pbs_plus_tpu.utils import trace

        def f():
            with trace.span("known.span"):
                pass
    """), "registry-consistency")
    assert [x.rule for x in v] == ["registry-consistency"]
    assert "dead.span" in v[0].message and "no trace.span" in v[0].message


def test_registry_span_doc_sync_both_directions(tmp_path):
    v = _analyze(tmp_path, _span_tree(
        ["known.span", "undoc.span"], ["known.span", "ghost.span"], """
        from pbs_plus_tpu.utils import trace

        def f():
            with trace.span("known.span"):
                pass
            trace.emit("undoc.span", 0.1)
    """), "registry-consistency")
    msgs = sorted(x.message for x in v)
    assert len(v) == 2
    assert any("undoc.span" in m and "missing from" in m for m in msgs)
    assert any("ghost.span" in m and "does not declare" in m for m in msgs)


def test_registry_histograms_join_the_metric_check(tmp_path):
    files = {
        "pbs_plus_tpu/server/metrics.py": """
            def render(gauge, histogram):
                gauge("pbs_plus_g", "h", [({}, 1.0)])
                histogram("pbs_plus_h_doc", "h")
                histogram("pbs_plus_h_nodoc", "h")
                histogram("pbs_plus_g", "h")
        """,
        "docs/metrics.md": ("| `pbs_plus_g` | x |\n"
                            "| `pbs_plus_h_doc` | x |\n"),
    }
    v = _analyze(tmp_path, files, "registry-consistency")
    msgs = sorted(x.message for x in v)
    assert len(v) == 2, msgs
    assert any("pbs_plus_h_nodoc" in m and "metrics.md" in m for m in msgs)
    assert any("pbs_plus_g" in m and "registered twice" in m for m in msgs)
