"""The load generator without a chip: seeded trees, the closed loop on a
fake server, the rate's arithmetic, and data files that say something
unknown."""

import asyncio
import hashlib
import math
import os
import types

import pytest

from benchmark.harness import loadgen, window

TREE = {"kind": "lognormal", "mu": 9.48, "sigma": 2.46, "own_files": 6,
        "common_files": 4, "dirs": 2, "compressible_every": 2}


def config(tree, agents=2, per_agent=2):
    return loadgen.check_config("t", {
        "server": {"chunker": "tpu", "chunk_avg": 65536,
                   "max_concurrent": 16},
        "meta_chunk_avg": 65536, "agents": agents,
        "trees_per_agent": per_agent, "tree": tree, "warm_tree": tree})


def fingerprint(trees) -> dict:
    out = {}
    for jobs in trees.values():
        for t in jobs:
            for dp, _, names in os.walk(t.path):
                for n in names:
                    with open(os.path.join(dp, n), "rb") as f:
                        rel = os.path.relpath(os.path.join(dp, n), t.path)
                        out[t.job_id, rel] = hashlib.sha256(
                            f.read()).hexdigest()
    return out


@pytest.mark.parametrize("own", [6, [3, 9]], ids=["alike", "per-agent"])
def test_trees_are_a_function_of_the_seed_alone(tmp_path, own):
    cfg = config(dict(TREE, own_files=own))
    big = 2**31 + 12345                     # the driver's seeds are large
    a = loadgen.build_trees(str(tmp_path / "a"), big, cfg)
    b = loadgen.build_trees(str(tmp_path / "b"), big, cfg)
    c = loadgen.build_trees(str(tmp_path / "c"), big + 1, cfg)
    assert fingerprint(a) == fingerprint(b) != fingerprint(c)
    # another seed changes the bytes and the order, never the amount of work
    assert [t.nbytes for q in a.values() for t in q] == \
        [t.nbytes for q in c.values() for t in q]
    warm = loadgen.build_trees(str(tmp_path / "a"), big, cfg, warm=True)
    assert not set(fingerprint(warm).values()) & set(fingerprint(a).values())


def test_a_tree_is_two_ladders_of_one_lognormal(tmp_path):
    sizes = loadgen.ladder(256, 9.48, 2.46)
    assert list(sizes) == sorted(sizes) and len(sizes) == 256
    assert int(sizes[127]) < math.exp(9.48) < int(sizes[128]) + 1  # median
    assert 15 << 20 < int(sizes[-1]) < 16 << 20       # the 99.8th percentile
    trees = loadgen.build_trees(str(tmp_path), 5, config(
        dict(TREE, own_files=[3, 9])))
    first, last = trees["agent-00"][0], trees["agent-01"][1]

    def lengths(tree, part):
        return sorted(os.path.getsize(os.path.join(dp, n))
                      for dp, _, ns in os.walk(os.path.join(tree.path, part))
                      for n in ns)
    assert lengths(first, "home") == list(loadgen.ladder(3, 9.48, 2.46))
    assert lengths(last, "home") == list(loadgen.ladder(9, 9.48, 2.46))
    common = list(loadgen.ladder(4, 9.48, 2.46))
    assert lengths(first, "usr") == lengths(last, "usr") == common
    assert first.nbytes == sum(lengths(first, "home")) + sum(common)
    # common to every tree of every agent: the same bytes under the same names
    usr = {k[1]: v for k, v in fingerprint({"a": [first]}).items()
           if k[1].startswith("usr")}
    assert usr == {k[1]: v for k, v in fingerprint({"a": [last]}).items()
                   if k[1].startswith("usr")}
    with pytest.raises(loadgen.DataFileError, match="own_files"):
        config(dict(TREE, own_files=[1, 2, 3]))


class FakeServer:
    """Jobs that take ``job_s`` seconds each, and count what was asked."""

    def __init__(self, job_s: float, fail: str = ""):
        self.job_s, self.fail = job_s, fail
        self.enqueued: list[tuple[float, str]] = []
        self.done: dict[str, float] = {}
        self._tasks: dict = {}
        self.jobs = types.SimpleNamespace(wait=self._wait)
        self.db = types.SimpleNamespace(get_backup_job=self._row)

    def enqueue_backup(self, job_id: str) -> bool:
        loop = asyncio.get_running_loop()
        self.enqueued.append((loop.time(), job_id))
        self._tasks[job_id] = loop.create_task(asyncio.sleep(self.job_s))
        return True

    async def _wait(self, key: str, timeout=None) -> None:
        job_id = key.removeprefix("backup:")
        await self._tasks[job_id]
        self.done[job_id] = asyncio.get_running_loop().time()

    def _row(self, job_id: str):
        status = "error" if job_id == self.fail else "success"
        return types.SimpleNamespace(last_status=status, last_error="",
                                     last_snapshot=f"host/{job_id}/x")


def backlog(agents=2, depth=3, nbytes=100):
    return {f"agent-{a:02d}": [
        loadgen.Tree(f"agent-{a:02d}", f"bench-agent-{a:02d}-{k}", "/nowhere",
                     nbytes) for k in range(depth)] for a in range(agents)}


def test_closed_loop_enqueues_next_on_publish_and_nothing_after_window():
    server = FakeServer(job_s=0.1, fail="bench-agent-01-0")
    trees = backlog(agents=2, depth=5)

    async def go():
        return await loadgen.drain_backlog(server, trees, 0.25)
    run = asyncio.run(go())
    by_agent = {}
    for t, job_id in server.enqueued:
        by_agent.setdefault(job_id[6:14], []).append((t, job_id))
    for agent, seq in by_agent.items():
        # one job in flight per agent: each enqueue follows the publish of
        # the one before it, in backlog order
        assert [j for _, j in seq] == [t.job_id for t in trees[agent]][:len(seq)]
        for (_, prev), (t_next, _) in zip(seq, seq[1:]):
            assert t_next >= server.done[prev]
    # a burst at t0, then 2 more rounds fit into 0.25 s of 0.1 s jobs
    assert len(run.jobs) == 6 and not run.drained
    assert all(t <= run.t0 + 0.25 for t, _ in server.enqueued)
    # jobs in flight at the end drained outside the interval
    assert all(j.done >= j.enqueued > 0 for j in run.jobs)
    assert run.t_end == pytest.approx(run.t0 + 0.25, abs=0.05)
    assert [j.status for j in run.jobs].count("error") == 1
    assert run.enqueued_bytes == 600 and run.built_bytes == 1000
    assert window.published_inside(run.jobs, run.t0, run.t_end) == 3
    assert len(window.publish_seconds(run.jobs)) == 5


def test_drained_backlog_ends_the_interval_at_the_last_publish():
    server = FakeServer(job_s=0.05)

    async def go():
        return await loadgen.drain_backlog(
            server, backlog(agents=2, depth=1), 5.0)
    run = asyncio.run(go())
    assert run.drained and len(run.jobs) == 2
    assert run.t_end == max(j.done for j in run.jobs) < run.t0 + 1.0
    assert window.backlog_used_pct(run.enqueued_bytes,
                                   run.built_bytes) == 100.0
    assert run.t_drained >= run.t_end


class FakeStore:
    def __init__(self, present=()):
        self.present = set(present)

    def insert(self, digest, data, *, verify=True):
        new = digest not in self.present
        self.present.add(digest)
        return new

    def note_dedup_hit(self, digest):
        return digest in self.present


def test_rate_counts_committed_bytes_over_all_seconds():
    """The numerator is what the store took inside the interval: a chunk
    read ahead or waiting to be hashed at its end counts for nothing."""
    now = [0.0]
    log = loadgen.CommitLog(clock=lambda: now[0])
    store = FakeStore(present={b"k"})
    log.watch(store)
    a, b, k, gone = b"a", b"b", b"k", b"gone"
    for t, call in ((99.0, lambda: store.insert(a, b"x")),      # before t0
                    (101.0, lambda: store.insert(b, b"x")),
                    (104.0, lambda: store.note_dedup_hit(k)),   # known chunk
                    (105.0, lambda: store.note_dedup_hit(gone)),  # not found:
                    (105.5, lambda: store.insert(gone, b"x")),  # ... inserted
                    (111.0, lambda: store.insert(b, b"x"))):    # after the end
        now[0] = t
        call()
    sizes, total = loadgen.chunk_sizes([
        ([10, 30], [a, b]), ([5], [k])])
    assert sizes == {a: 10, b: 20, k: 5} and total == 35
    # b, k and a digest no index holds: 25 bytes, one unknown commit
    assert log.bytes_between(100.0, 110.0, sizes) == (25, 1)
    assert log.bytes_between(100.0, 120.0, sizes) == (45, 1)
    mib = 1 << 20
    assert window.ingest_mib_s(30 * mib, 100.0, 110.0) == 3.0
    assert window.backlog_used_pct(3, 12) == 25.0
    with pytest.raises(ValueError):
        window.ingest_mib_s(mib, 10.0, 10.0)
    assert window.median_or_none([]) is None
    assert window.seconds_inside([(0, 2), (9, 12), (20, 21)], 1, 10) == 2.0
    with pytest.raises(RuntimeError, match="note_dedup_hit"):
        loadgen.CommitLog().watch(types.SimpleNamespace(insert=print))


def test_traffic_plan_picks_agents_and_depth():
    trees = backlog(agents=3, depth=4)
    plan = loadgen.plan_backlog(trees, {"agents": 1, "jobs_per_agent": 2})
    assert list(plan) == ["agent-00"] and len(plan["agent-00"]) == 2
    assert loadgen.plan_backlog(trees, {"agents": "all"}) == trees


@pytest.mark.parametrize("where", ["config", "server", "tree", "traffic",
                                   "workload"])
def test_unknown_key_in_a_data_file_raises(where):
    cfg = {"server": {"chunker": "tpu", "chunk_avg": 65536,
                      "max_concurrent": 16},
           "meta_chunk_avg": 65536, "agents": 1, "trees_per_agent": 1,
           "tree": dict(TREE)}
    with pytest.raises(loadgen.DataFileError, match="unknown"):
        if where == "config":
            loadgen.check_config("t", {**cfg, "linger_ms": 2})
        elif where == "server":
            loadgen.check_config("t", {**cfg, "server": {
                **cfg["server"], "turbo": True}})
        elif where == "tree":
            loadgen.check_config("t", {**cfg, "tree": {**TREE, "sparse": 1}})
        elif where == "traffic":
            loadgen.check_traffic("t", {"arrival": "burst", "rate": 3})
        else:
            loadgen._check_keys("workload t", {"config": "a", "rate": 1},
                                loadgen.WORKLOAD_KEYS)
