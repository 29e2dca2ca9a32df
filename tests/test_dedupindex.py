"""Dedup-index subsystem battery (ISSUE 8): the cuckoo filter itself
(growth, eviction fallback, discard, device/numpy parity, empirical FP
rate), the DedupIndex front (batched probe exactness, snapshot
journal), the sharded index-fronted ChunkStore (disk-free negative
probes — structurally asserted, single-utime dedup hits, boot rebuild,
sweep coherence under failpoints), the writer batch-probe entry points,
and GC integration."""

import hashlib
import io
import os
import threading
import time

import numpy as np
import pytest

from pbs_plus_tpu.ops.cuckoo import (
    SLOTS, CuckooIndex, buckets_for_bytes, lookup_host)
from pbs_plus_tpu.pxar import chunkindex
from pbs_plus_tpu.pxar.chunkindex import DedupIndex
from pbs_plus_tpu.pxar.datastore import ChunkStore
from pbs_plus_tpu.utils import failpoints


def _digests(n: int, seed: int = 0) -> list[bytes]:
    rng = np.random.default_rng(seed)
    arr = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    return [arr[i].tobytes() for i in range(n)]


def _chunk(i: int, size: int = 512) -> tuple[bytes, bytes]:
    data = (b"%08d" % i) * (size // 8)
    return hashlib.sha256(data).digest(), data


# ---------------------------------------------------------- cuckoo filter


def test_buckets_for_bytes_power_of_two_budget():
    nb = buckets_for_bytes(1 << 20)
    assert nb & (nb - 1) == 0
    assert nb * SLOTS * 8 <= 1 << 20 < nb * 2 * SLOTS * 8
    assert buckets_for_bytes(0) == 1 << 10          # floor


def test_filter_growth_under_load_factor_pressure():
    idx = CuckooIndex(n_buckets=8)                  # 32 slots
    digs = _digests(500, seed=1)
    for d in digs:
        idx.insert(d)
    assert idx.n_buckets > 8                        # grew under pressure
    assert all(idx.probe_confirmed(digs))
    # the table never overcommits its slots
    assert len(idx) <= idx.n_buckets * SLOTS


def test_eviction_loop_fallback_tiny_table():
    # 2 buckets x 4 slots: the 9th insert can only land via the
    # eviction chain, and chain exhaustion forces a growth rebuild —
    # every digest must remain findable through both
    idx = CuckooIndex(n_buckets=2)
    digs = _digests(64, seed=2)
    for d in digs:
        idx.insert(d)
    assert all(idx.probe_confirmed(digs))
    assert all(lookup_host(idx._table, np.frombuffer(
        b"".join(digs), dtype=np.uint8).reshape(-1, 32)))


def test_discard_removes_membership_and_fingerprint():
    idx = CuckooIndex(n_buckets=1 << 8)
    digs = _digests(100, seed=3)
    for d in digs:
        idx.insert(d)
    victim = digs[17]
    assert idx.discard(victim)
    assert not idx.discard(victim)                  # second time: absent
    assert not idx.contains_exact(victim)
    arr = np.frombuffer(victim, dtype=np.uint8).reshape(1, 32)
    assert not lookup_host(idx._table, arr)[0]      # slot really zeroed
    keep = [d for d in digs if d != victim]
    assert all(idx.probe_confirmed(keep))           # nobody else harmed


def test_device_numpy_lookup_parity():
    idx = CuckooIndex(n_buckets=1 << 10)
    members = _digests(400, seed=4)
    for d in members:
        idx.insert(d)
    probe = members[:200] + _digests(200, seed=5)
    arr = np.frombuffer(b"".join(probe), dtype=np.uint8).reshape(-1, 32)
    dev = np.asarray(idx.probe(arr))                # jit'd gather+compare
    host = lookup_host(idx._table, arr)             # numpy twin
    assert np.array_equal(dev, host)
    assert host[:200].all()                         # members all hit


def _fp_sweep(n_members: int, n_probes: int, seed: int) -> int:
    """Insert n_members, probe n_probes NON-members in array batches;
    returns observed filter false positives (maybe-present that fail
    the exact confirm)."""
    idx = CuckooIndex(n_buckets=buckets_for_bytes(
        n_members * SLOTS * 8 * 2))
    idx.insert_many(_digests(n_members, seed=seed))
    fps = 0
    step = 1 << 20
    rng = np.random.default_rng(seed + 1)
    remaining = n_probes
    while remaining > 0:
        k = min(step, remaining)
        arr = rng.integers(0, 256, (k, 32), dtype=np.uint8)
        maybe = idx.probe_host(arr)
        for i in np.flatnonzero(maybe):
            if not idx.contains_exact(arr[int(i)].tobytes()):
                fps += 1
        remaining -= k
    return fps


def test_false_positive_rate_reduced_profile():
    # 64-bit fingerprints: analytic per-probe bound 2*SLOTS/2^64 = 2^-61
    # <= the 2^-40 acceptance bar; empirically 1e5 non-member probes
    # must observe zero
    assert 2 * SLOTS / 2.0 ** 64 <= 2.0 ** -40
    assert _fp_sweep(100_000, 100_000, seed=6) == 0


@pytest.mark.slow
def test_false_positive_rate_at_1e7_probes():
    """ISSUE 8 satellite scale: 10^7 synthetic digests probed against a
    1M-member filter — zero observed false positives, consistent with
    the <= 2^-40 analytic rate."""
    assert _fp_sweep(1_000_000, 10_000_000, seed=7) == 0


# ------------------------------------------------------------- DedupIndex


def test_probe_batch_exact_and_fp_counting():
    idx = DedupIndex(budget_mb=1)
    members = _digests(1000, seed=8)
    assert idx.insert_many(members) == 1000
    out = idx.probe_batch(members[:500] + _digests(500, seed=9))
    assert out[:500] == [True] * 500
    assert out[500:] == [False] * 500
    assert len(idx) == 1000
    assert idx.resident_bytes > idx.table_bytes


@pytest.fixture
def device_host(monkeypatch):
    """A host whose jax backend passes for an accelerator: the index
    takes its device twin and builds its lookup programs, on the CPU
    backend."""
    from pbs_plus_tpu.utils import jaxenv
    monkeypatch.setattr(jaxenv, "on_accelerator", lambda: True)


@pytest.mark.parametrize("spill", [False, True], ids=["ram", "spill"])
@pytest.mark.parametrize("n", [1, 63, 64, 65, 256, 257, 512])
def test_device_probe_across_classes_answers_as_a_set(
        device_host, tmp_path, n, spill):
    """ISSUE 36: batches that fill a probe class, fall one short and run
    one over, between scalar inserts and discards: the device twin
    answers as a Python set does, and the thread's own counts add up —
    an update of the device's table at every probe that follows a change
    and at no other (whole the first time, the changed buckets after;
    in spill mode the preload's own probe made the first, and a preload
    of more than 64 goes whole again), bytes = whole uploads × the table's
    bytes + the deltas' bytes, the padding by class."""
    from pbs_plus_tpu.ops import cuckoo
    from pbs_plus_tpu.utils import trace
    idx = DedupIndex(budget_mb=2,
                     spill_dir=str(tmp_path) if spill else None)
    idx.mark_booted()
    pool = _digests(2 * n + 8, seed=36)
    known = set(pool[:n:2])                 # every other one of a batch
    idx.insert_many(sorted(known))
    clock = trace.ThreadClock(label="test")
    stats0 = dict(cuckoo.stats)
    rounds = [pool[:n], pool[:n], pool[n:2 * n], pool[1:n + 1]]
    dirty = [True, False, True, True]       # preloaded; clean; then both
    hits = 0
    with trace.clocked(clock):
        for k, batch in enumerate(rounds):
            assert idx.probe_batch(batch) == [d in known for d in batch]
            hits += sum(d in known for d in batch)
            if k == 1:                      # a scalar insert, new
                assert idx.insert(pool[2 * n]) is True
                known.add(pool[2 * n])
            elif k == 2:                    # a discard, then one again
                assert idx.discard(pool[0]) is (pool[0] in known)
                assert idx.insert(pool[0]) is True
                assert idx.insert(pool[0]) is False
                known.add(pool[0])
    c = dict.fromkeys(("index_table_uploads", "index_table_delta_uploads",
                       "index_table_delta_buckets"), 0)
    c.update(clock.counts)
    klass = next(k for k in (64, 256, 1024) if k >= n)
    assert c["index_probe_trips"] == len(rounds)
    assert c["index_probe_digests"] == len(rounds) * n
    assert c["index_probe_padded"] == len(rounds) * klass
    spent = {k: cuckoo.stats[k] - stats0[k] for k in stats0}
    assert c["index_table_uploads"] == spent["table_uploads"]
    assert c["index_table_delta_uploads"] == spent["table_delta_uploads"] \
        == sum(dirty) - c["index_table_uploads"] >= 2
    assert c["index_table_delta_buckets"] == spent["table_delta_buckets"] \
        >= c["index_table_delta_uploads"]
    assert c["index_table_upload_bytes"] == spent["table_upload_bytes"] \
        == c["index_table_uploads"] * idx.table_bytes \
        + spent["table_delta_bytes"]
    assert c["index_upload_s"] > 0 and c["index_device_s"] > 0
    assert c["index_inserts"] == 2 and c["index_false_positives"] == 0
    assert c["index_hits"] == hits


def test_lookup_programs_are_built_before_a_writer_needs_them(device_host):
    """After boot on a device host a probe of each class a flush can
    produce compiles nothing on its own thread, nor does the update of
    the device's table by a change of each class; neither after the
    table has grown.  On a CPU host nothing is built."""
    from pbs_plus_tpu.ops import cuckoo
    from pbs_plus_tpu.ops.cuckoo import probe_classes_upto
    from pbs_plus_tpu.pxar.transfer import _HASH_BATCH_COUNT
    from pbs_plus_tpu.utils import jaxenv
    classes = probe_classes_upto(_HASH_BATCH_COUNT)
    assert classes == (64, 256, 1024)
    idx = DedupIndex(budget_mb=2)
    nb = idx.n_buckets
    for k in classes:                       # another test's, perhaps
        for programs in (cuckoo._programs, cuckoo._scatters):
            programs.pop((nb, k), None)
            programs.pop((2 * nb, k), None)
    probes = [_digests(k, seed=k) for k in (1, 64, 65, 256, 257, 512)]
    idx.probe_batch(_digests(2, seed=1))    # before boot: nothing built
    assert not any((nb, k) in cuckoo._programs for k in classes)
    idx.mark_booted()
    idx.wait_warm(60)
    assert all((nb, k) in cuckoo._programs for k in classes)
    assert [k for k in classes if (nb, k) in cuckoo._scatters] \
        == [k for k in classes if not cuckoo._goes_whole(nb, k)] == [64]
    before = jaxenv.thread_compiles()
    for batch in probes:
        assert idx.probe_batch(batch) == [False] * len(batch)
    # a flush's inserts, 1 to 512 digests: each update in place where
    # its class takes it so, else whole
    stats0 = dict(cuckoo.stats)
    for k in (1, 65, 512):
        fresh = _digests(k, seed=1000 + k)
        assert idx.insert_many(fresh) == k
        assert idx.probe_batch(fresh) == [True] * k
    assert cuckoo.stats["table_delta_uploads"] \
        - stats0["table_delta_uploads"] == 1
    assert cuckoo.stats["table_uploads"] - stats0["table_uploads"] == 2
    assert jaxenv.thread_compiles() == before
    # growth: past load 0.85 the table doubles, and the index asks for
    # the new shape's programs itself
    idx.insert_many(_digests(int(nb * SLOTS * 0.85) + 1, seed=99))
    assert idx.n_buckets == 2 * nb
    idx.wait_warm(60)
    assert all((2 * nb, k) in cuckoo._programs for k in classes)
    assert [k for k in classes if (2 * nb, k) in cuckoo._scatters] == [64]
    before = jaxenv.thread_compiles()
    for batch in probes:
        assert idx.probe_batch(batch) == [False] * len(batch)
    assert idx.insert(probes[0][0]) is True
    assert idx.probe_batch(probes[0]) == [True]
    assert jaxenv.thread_compiles() == before


# ------------------------------------- the device's table kept in step


def _digest_in(nb: int, b1: int, b2: int, rng) -> bytes:
    """A digest whose two candidate buckets in a table of ``nb`` are
    ``b1`` and ``b2``: word 2 picks b1, and fp0 · mix picks b1 ^ b2 in
    the low bits whatever the bits above them."""
    from pbs_plus_tpu.ops.cuckoo import _MIX
    inv = pow(int(_MIX), -1, 1 << 32)
    fp0 = ((b1 ^ b2) * inv + int(rng.integers(0, 1 << 16)) * nb) % (1 << 32)
    words = np.array([fp0, int(rng.integers(1, 1 << 32)),
                      (b1 + int(rng.integers(0, 1 << 16)) * nb) % (1 << 32)],
                     dtype=">u4")
    return words.tobytes() + rng.bytes(20)


def _step(idx: DedupIndex, op: str, known: set, rng) -> None:
    cu = idx._cuckoo
    nb = cu.n_buckets
    if op == "insert":
        for d in _digests(7, seed=int(rng.integers(1 << 30))):
            assert idx.insert(d) is True
            known.add(d)
    elif op == "insert_many":
        batch = _digests(300, seed=int(rng.integers(1 << 30)))
        assert idx.insert_many(batch) == 300
        known.update(batch)
    elif op == "discard":
        for d in sorted(known)[:3]:
            assert idx.discard(d) is True
            known.discard(d)
    elif op == "chain":
        # both buckets of a digest full: its insert kicks a fingerprint
        # on to its other bucket, and more if that one is full too
        x, y = (int(b) for b in rng.choice(nb, 2, replace=False))
        for b in (x, y):
            while not (cu._table[b] != 0).any(axis=1).all():
                d = _digest_in(nb, b, int(rng.integers(nb)), rng)
                assert idx.insert(d) is True
                known.add(d)
        before = cu._table.copy()
        d = _digest_in(nb, x, y, rng)
        assert idx.insert(d) is True
        known.add(d)
        assert len(np.flatnonzero((before != cu._table).any(axis=(1, 2)))) >= 2
    else:                                   # past load 0.85: it doubles
        batch = _digests(int(nb * SLOTS * 0.85) + 1 - len(idx),
                         seed=int(rng.integers(1 << 30)))
        idx.insert_many(batch)
        known.update(batch)
        assert idx._cuckoo.n_buckets == 2 * nb


@pytest.mark.parametrize("ops", [
    ("insert", "discard", "insert_many", "chain", "discard"),
    ("chain", "grow", "insert", "chain", "insert_many")],
    ids=["changes", "growth"])
@pytest.mark.parametrize("spill", [False, True], ids=["ram", "spill"])
def test_device_table_equals_the_mirror_after_every_probe(
        device_host, tmp_path, ops, spill):
    """Inserts, discards, eviction chains and a growth between probes:
    after each probe the device's table is the host mirror bit for bit,
    the index answers as a set does, and the device twin as the host
    twin."""
    from pbs_plus_tpu.ops import cuckoo
    rng = np.random.default_rng(37)
    idx = DedupIndex(budget_mb=2, spill_dir=str(tmp_path) if spill else None)
    idx.mark_booted()
    known = set(_digests(500, seed=370))
    idx.insert_many(sorted(known))
    stats0 = dict(cuckoo.stats)
    for op in ("probe",) + ops:
        if op != "probe":
            _step(idx, op, known, rng)
        asked = sorted(known)[:200] + _digests(56, seed=371)
        assert idx.probe_batch(asked) == [d in known for d in asked]
        cu = idx._cuckoo
        assert np.array_equal(np.asarray(cu._device_table), cu._table), op
        arr = np.frombuffer(b"".join(asked), np.uint8).reshape(-1, 32)
        assert np.array_equal(cu.probe(arr), lookup_host(cu._table, arr))
    spent = {k: cuckoo.stats[k] - stats0[k] for k in stats0}
    # a few buckets go in place; the first probe (500 preloaded), 300 at
    # once and a growth go whole
    assert spent["table_delta_uploads"] >= sum(
        op in ("insert", "discard", "chain") for op in ops)
    assert spent["table_uploads"] >= 1 + sum(
        op in ("insert_many", "grow") for op in ops)


def test_probes_on_threads_beside_an_inserter_agree_with_the_host_twin():
    """Six threads probe one ``CuckooIndex`` while a seventh inserts into
    it, with the interpreter switching threads every 10 µs: no thread
    meets an array the update gave away, every answer is the host twin's
    for the digests that were there before, and at the end the device's
    table is the mirror."""
    import sys

    from pbs_plus_tpu.ops import cuckoo
    idx = CuckooIndex(n_buckets=1 << 16)
    members = _digests(400, seed=380)
    idx.insert_many(members)
    fresh = _digests(400, seed=381)
    arr = np.frombuffer(b"".join(members[:200] + _digests(200, seed=382)),
                        np.uint8).reshape(-1, 32)
    want = lookup_host(idx._table, arr)
    assert want[:200].all() and not want[200:].any()
    idx.probe(arr)
    deltas0 = cuckoo.stats["table_delta_uploads"]
    errors, wrong = [], []

    def prober():
        try:
            for _ in range(20):
                got = idx.probe(arr)
                if not np.array_equal(got, want):
                    wrong.append(got)
        except Exception as e:          # noqa: BLE001 — reported below
            errors.append(e)

    def inserter():
        try:
            for d in fresh:
                idx.insert(d)
        except Exception as e:          # noqa: BLE001 — reported below
            errors.append(e)
    threads = [threading.Thread(target=prober) for _ in range(6)] \
        + [threading.Thread(target=inserter)]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in threads)
    assert not errors and not wrong
    assert cuckoo.stats["table_delta_uploads"] > deltas0
    everyone = np.frombuffer(b"".join(members + fresh),
                             np.uint8).reshape(-1, 32)
    assert idx.probe(everyone).all()
    assert np.array_equal(np.asarray(idx._device_table), idx._table)


@pytest.mark.parametrize("k", [1, 9, 40])
def test_a_probe_after_k_inserts_sends_their_buckets_and_no_more(
        k, monkeypatch):
    """After k scalar inserts (no eviction) a probe writes at most 2k
    buckets into the device's table, and the job's bytes are what went;
    a rebuild and a growth send the table whole."""
    from pbs_plus_tpu.ops import cuckoo
    from pbs_plus_tpu.utils import trace
    sent = []
    scatter = cuckoo._scatter

    def spy(table, idx, rows):
        sent.append(idx.nbytes + rows.nbytes)
        return scatter(table, idx, rows)
    monkeypatch.setattr(cuckoo, "_scatters", {})
    monkeypatch.setattr(cuckoo, "_scatter", spy)
    idx = CuckooIndex(n_buckets=1 << 16)
    idx.insert_many(_digests(100, seed=390))
    asked = np.frombuffer(b"".join(_digests(5, seed=391)),
                          np.uint8).reshape(-1, 32)
    idx.probe(asked)                        # the first copy, whole

    def counted(change) -> dict:
        clock = trace.ThreadClock()
        with trace.clocked(clock):
            change()
            idx.probe(asked)
        assert np.array_equal(np.asarray(idx._device_table), idx._table)
        return clock.counts
    c = counted(lambda: [idx.insert(d) for d in _digests(k, seed=392)])
    assert c["index_table_delta_uploads"] == 1
    assert "index_table_uploads" not in c
    assert 1 <= c["index_table_delta_buckets"] <= 2 * k
    assert c["index_table_upload_bytes"] == sum(sent) \
        == 64 * cuckoo.DELTA_BUCKET_BYTES
    for change in (idx._rebuild_bulk,       # the same shape, rebuilt
                   lambda: idx.insert_many(_digests(
                       int(idx.n_buckets * SLOTS * 0.85), seed=393))):
        del sent[:]
        nb = idx.n_buckets
        c = counted(change)
        assert c["index_table_uploads"] == 1 and not sent
        assert c["index_table_upload_bytes"] == idx._table.nbytes
        assert "index_table_delta_uploads" not in c
    assert idx.n_buckets == 2 * nb


def test_a_change_that_costs_more_than_a_copy_goes_whole():
    """A change of more than a 1,024th of the table's buckets goes whole:
    300 fresh buckets of a 65,536-bucket table, and from then on nothing
    more is noted.  A preload batch of 65,536 goes in place at
    ``index-at-size``'s 2 GiB table and whole at the 64 MiB one."""
    from pbs_plus_tpu.ops import cuckoo
    idx = CuckooIndex(n_buckets=1 << 16)
    asked = np.frombuffer(b"".join(_digests(5, seed=394)),
                          np.uint8).reshape(-1, 32)
    idx.probe(asked)
    before = dict(cuckoo.stats)
    idx.insert_many(_digests(300, seed=395))
    assert idx._whole and idx._n_marks == 0
    idx.probe(asked)
    assert cuckoo.stats["table_uploads"] == before["table_uploads"] + 1
    assert cuckoo.stats["table_delta_uploads"] \
        == before["table_delta_uploads"]
    assert np.array_equal(np.asarray(idx._device_table), idx._table)
    assert cuckoo._goes_whole(1 << 16, 256)
    assert not cuckoo._goes_whole(1 << 16, 64)
    assert not cuckoo._goes_whole(1 << 26, 1 << 16)
    assert cuckoo._goes_whole(1 << 21, 1 << 16)


def test_cpu_host_builds_no_lookup_program():
    from pbs_plus_tpu.ops import cuckoo
    idx = DedupIndex(budget_mb=2)
    for k in (64, 256, 1024):
        cuckoo._programs.pop((idx.n_buckets, k), None)
        cuckoo._scatters.pop((idx.n_buckets, k), None)
    idx.mark_booted()
    idx.wait_warm(60)
    idx.insert_many(_digests(100, seed=3))
    assert idx.probe_batch(_digests(100, seed=3)) == [True] * 100
    assert not any(key[0] == idx.n_buckets for key in cuckoo._programs)
    assert not any(key[0] == idx.n_buckets for key in cuckoo._scatters)


def test_dedupindex_discard_and_reinsert():
    idx = DedupIndex(budget_mb=1)
    d = _digests(1, seed=10)[0]
    assert idx.insert(d)
    assert not idx.insert(d)
    idx.mark_datablob(d)
    assert idx.discard(d)
    assert not idx.contains(d)
    assert not idx.is_datablob(d)                   # discard drops both
    assert idx.insert(d)                            # safe re-learn


def test_snapshot_roundtrip_and_corrupt_rejection(tmp_path):
    idx = DedupIndex(budget_mb=1)
    members = _digests(300, seed=11)
    idx.insert_many(members)
    idx.mark_datablob(members[0])
    snap = str(tmp_path / "snap")
    idx.save_snapshot(snap)

    fresh = DedupIndex(budget_mb=1)
    assert fresh.load_snapshot(snap)
    assert len(fresh) == 300
    assert fresh.probe_batch(members) == [True] * 300
    assert fresh.is_datablob(members[0])
    assert not fresh.is_datablob(members[1])

    # corrupt: flip one payload byte -> checksum rejects, index unchanged
    raw = bytearray(open(snap, "rb").read())
    raw[40] ^= 0xFF
    bad = str(tmp_path / "bad")
    open(bad, "wb").write(bytes(raw))
    before = len(fresh)
    assert not fresh.load_snapshot(bad)
    assert len(fresh) == before
    assert not fresh.load_snapshot(str(tmp_path / "missing"))


def test_rebuild_resets_to_exact_set():
    idx = DedupIndex(budget_mb=1)
    idx.insert_many(_digests(50, seed=12))
    target = _digests(20, seed=13)
    assert idx.rebuild(target) == 20
    assert len(idx) == 20
    assert idx.probe_batch(target) == [True] * 20


# ---------------------------------------------- sharded, index-fronted store


def _chunk_path_probes(monkeypatch):
    """Wrap the existence probes + utime so calls on chunk-file paths
    (64-hex basenames) are counted — the structural disk-free witness."""
    counts = {"exists": 0, "stat": 0, "utime": 0}
    real_exists, real_stat, real_utime = os.path.exists, os.stat, os.utime

    def is_chunk(p) -> bool:
        try:
            name = os.path.basename(os.fspath(p))
        except TypeError:
            return False
        return len(name) == 64

    def exists(p):
        if is_chunk(p):
            counts["exists"] += 1
        return real_exists(p)

    def stat(p, *a, **kw):
        if is_chunk(p):
            counts["stat"] += 1
        return real_stat(p, *a, **kw)

    def utime(p, *a, **kw):
        if is_chunk(p):
            counts["utime"] += 1
        return real_utime(p, *a, **kw)

    monkeypatch.setattr(os.path, "exists", exists)
    monkeypatch.setattr(os, "stat", stat)
    monkeypatch.setattr(os, "utime", utime)
    return counts


def test_filter_negative_insert_zero_prewrite_probes(tmp_path, monkeypatch):
    """ISSUE 8 acceptance: with the index enabled, inserting all-novel
    data performs ZERO existence stats (and zero utimes) on chunk
    paths; the dedup-hit path costs exactly one utime per hit."""
    store = ChunkStore(str(tmp_path), n_shards=4, index_budget_mb=4)
    pairs = [_chunk(i) for i in range(50)]
    counts = _chunk_path_probes(monkeypatch)
    for d, data in pairs:
        assert store.insert(d, data, verify=False)
    assert counts == {"exists": 0, "stat": 0, "utime": 0}
    # dedup hits: one utime each (the GC mark doubles as confirmation),
    # still zero existence probes
    for d, data in pairs:
        assert not store.insert(d, data, verify=False)
    assert counts["exists"] == 0 and counts["stat"] == 0
    assert counts["utime"] == len(pairs)
    # membership answers come from the index, not the disk
    assert store.has(pairs[0][0])
    assert counts["exists"] == 0 and counts["stat"] == 0


def test_all_novel_backup_is_stat_free(tmp_path, monkeypatch):
    """End-to-end: a whole backup session of novel data through the
    DedupWriter does zero existence probes on chunk paths."""
    from pbs_plus_tpu.chunker import ChunkerParams
    from pbs_plus_tpu.pxar.backupproxy import LocalStore
    from pbs_plus_tpu.pxar.walker import backup_tree

    src = tmp_path / "src"
    src.mkdir()
    rng = np.random.default_rng(14)
    for i in range(6):
        (src / f"f{i}.bin").write_bytes(
            rng.integers(0, 256, 40_000, dtype=np.uint8).tobytes())
    store = LocalStore(str(tmp_path / "ds"),
                       ChunkerParams(avg_size=8 << 10),
                       store_shards=4, dedup_index_mb=4)
    counts = _chunk_path_probes(monkeypatch)
    sess = store.start_session(backup_type="host", backup_id="novel")
    backup_tree(sess, str(src))
    man = sess.finish()
    assert counts["exists"] == 0 and counts["stat"] == 0
    assert counts["utime"] == 0                     # nothing deduped
    assert man["stats"]["new_chunks"] > 0
    assert man["stats"]["known_chunks"] == 0


def test_note_dedup_hit_stale_index_falls_back(tmp_path):
    store = ChunkStore(str(tmp_path), n_shards=2, index_budget_mb=2)
    d, data = _chunk(1)
    store.insert(d, data, verify=False)
    os.unlink(store._path(d))                       # external delete
    assert store.index.contains(d)                  # index now stale
    assert store.note_dedup_hit(d) is False         # refuses the skip
    assert store.insert(d, data, verify=False) is False or True
    # whichever count, the chunk is BACK on disk — no false skip
    assert os.path.exists(store._path(d))


def test_boot_rebuild_and_snapshot_consume_once(tmp_path):
    a = ChunkStore(str(tmp_path), n_shards=4, index_budget_mb=2)
    pairs = [_chunk(i) for i in range(20)]
    for d, data in pairs:
        a.insert(d, data, verify=False)
    # scan rebuild
    b = ChunkStore(str(tmp_path), n_shards=4, index_budget_mb=2)
    assert all(b.index.contains(d) for d, _ in pairs)
    # snapshot path, consumed on load
    b.save_index_snapshot()
    assert os.path.exists(b._index_snap)
    before = chunkindex.metrics_snapshot()["snapshot_loads"]
    c = ChunkStore(str(tmp_path), n_shards=4, index_budget_mb=2)
    # boot is lazy: nothing loaded until the first membership use
    assert not c._index.booted
    assert chunkindex.metrics_snapshot()["snapshot_loads"] == before
    assert all(c.index.contains(d) for d, _ in pairs)
    assert chunkindex.metrics_snapshot()["snapshot_loads"] == before + 1
    assert not os.path.exists(c._index_snap)        # consume-once


def test_read_only_open_never_scans(tmp_path):
    """A store opened for reads only (restore/verify/CLI) must not pay
    the index boot scan — it runs on the first membership probe."""
    a = ChunkStore(str(tmp_path), n_shards=4, index_budget_mb=2)
    d, data = _chunk(7)
    a.insert(d, data, verify=False)

    b = ChunkStore(str(tmp_path), n_shards=4, index_budget_mb=2)
    assert not b._index.booted
    assert b.get(d) == data                         # read path: no boot
    assert b.chunk_size(d) > 0
    assert not b._index.booted
    assert b.has(d)                                 # first probe boots
    assert b._index.booted


def test_sweep_coherence_under_failpoint(tmp_path):
    """Failpoint at pbsstore.chunk.sweep: a sweep that dies before any
    unlink has discarded NOTHING from the filter; a completed sweep
    leaves no swept digest in it — and a swept digest never yields a
    false dedup skip (the re-insert writes the file back)."""
    store = ChunkStore(str(tmp_path), n_shards=4, index_budget_mb=2)
    pairs = [_chunk(i) for i in range(12)]
    for d, data in pairs:
        store.insert(d, data, verify=False)
    with failpoints.armed("pbsstore.chunk.sweep", "raise"):
        with pytest.raises(failpoints.FailpointError):
            store.sweep(before=time.time() + 60)
    # filter untouched, files untouched
    assert all(store.index.contains(d) for d, _ in pairs)
    assert all(os.path.exists(store._path(d)) for d, _ in pairs)

    removed, _freed = store.sweep(before=time.time() + 60)
    assert removed == len(pairs)
    for d, data in pairs:
        assert not store.index.contains(d)          # left the filter
        assert store.insert(d, data, verify=False)  # TRUE: re-stored,
        assert os.path.exists(store._path(d))       # never skipped


def test_sweep_spares_marked_and_saves_snapshot(tmp_path):
    store = ChunkStore(str(tmp_path), n_shards=4, index_budget_mb=2)
    pairs = [_chunk(i) for i in range(10)]
    for d, data in pairs:
        store.insert(d, data, verify=False)
    cutoff = time.time() + 60
    live = [d for d, _ in pairs[:5]]
    time.sleep(0.02)
    store.touch_many(live)                          # mark after cutoff?
    # mark with fresh utimes, then sweep everything older than "now
    # minus nothing": only unmarked chunks go
    for d, _ in pairs[:5]:
        os.utime(store._path(d), (cutoff + 10, cutoff + 10))
    removed, _ = store.sweep(before=cutoff)
    assert removed == 5
    assert all(store.index.contains(d) for d in live)
    assert not any(store.index.contains(d) for d, _ in pairs[5:])
    assert os.path.exists(store._index_snap)        # post-sweep snapshot
    # index <-> disk coherence both ways
    disk = set(store.iter_digests())
    known = set(store.index.digests())
    assert disk == known == set(live)


def test_concurrent_shard_inserts_thread_safe(tmp_path):
    store = ChunkStore(str(tmp_path), n_shards=8, index_budget_mb=2)
    assert store.thread_safe
    pairs = [_chunk(i) for i in range(120)]
    new_counts = []

    def worker(sub):
        n = 0
        for d, data in sub:
            if store.insert(d, data, verify=False):
                n += 1
        new_counts.append(n)

    threads = [threading.Thread(target=worker, args=(pairs,))
               for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # every digest stored exactly once across all racing writers
    assert sum(new_counts) == len(pairs)
    assert sorted(store.iter_digests()) == sorted(d for d, _ in pairs)
    assert all(store.index.contains(d) for d, _ in pairs)


def test_sweep_racing_dedup_hits_never_false_skips(tmp_path):
    """Sweep holds the shard lock around its stat/discard/unlink
    triple, so a dedup hit's GC-mark utime can never land between the
    sweep's staleness check and the unlink: after hammering inserts
    against concurrent sweeps, a digest the writer saw as KNOWN is on
    disk, and the filter agrees with the disk digest-for-digest."""
    store = ChunkStore(str(tmp_path), n_shards=4, index_budget_mb=2)
    pairs = [_chunk(i) for i in range(40)]
    for d, data in pairs:
        store.insert(d, data, verify=False)
        os.utime(store._path(d), (1, 1))            # all sweep-eligible
    cutoff = time.time() - 30                       # past cutoff: a
    #                                                 fresh hit-utime
    #                                                 always spares
    stop = threading.Event()
    errors: list[str] = []

    def writer():
        while not stop.is_set():
            for d, data in pairs:
                known = not store.insert(d, data, verify=False)
                if known and not os.path.exists(store._path(d)):
                    errors.append(d.hex())          # recorded hit, no file

    def sweeper():
        while not stop.is_set():
            store.sweep(before=cutoff)

    threads = [threading.Thread(target=writer) for _ in range(2)] + \
              [threading.Thread(target=sweeper)]
    for t in threads:
        t.start()
    time.sleep(0.8)
    stop.set()
    for t in threads:
        t.join()
    assert not errors, errors[:5]
    # final coherence: filter <-> disk agree exactly
    assert set(store.iter_digests()) == set(store.index.digests())


def test_index_disabled_legacy_probe_still_works(tmp_path):
    store = ChunkStore(str(tmp_path), n_shards=2, index_budget_mb=0)
    assert store.index is None
    assert store.probe_batch([b"\0" * 32]) is None
    d, data = _chunk(2)
    assert store.insert(d, data, verify=False)
    assert not store.insert(d, data, verify=False)
    assert store.has(d)


def test_legacy_datablob_cap_evicts_half_not_all(tmp_path):
    store = ChunkStore(str(tmp_path), n_shards=1, index_budget_mb=0)
    store._datablob_seen_cap = 8
    digs = _digests(9, seed=15)
    for d in digs[:8]:
        store._remember_datablob(d)
    assert len(store._datablob_seen) == 8
    store._remember_datablob(digs[8])
    # at the cap: HALF evicted plus the newcomer kept — never a full
    # forget (the old clear-everything bug)
    assert len(store._datablob_seen) == 5
    assert digs[8] in store._datablob_seen


# -------------------------------------------------- writer batch probes


def test_writer_batch_hasher_probes_once_per_batch(tmp_path):
    from pbs_plus_tpu.chunker import ChunkerParams
    from pbs_plus_tpu.pxar.transfer import _ChunkedStream

    store = ChunkStore(str(tmp_path), n_shards=2, index_budget_mb=2)
    calls = []
    real = store.probe_batch
    store.probe_batch = lambda ds: calls.append(len(ds)) or real(ds)

    def hasher(chunks):
        return [hashlib.sha256(c).digest() for c in chunks]

    params = ChunkerParams(avg_size=4 << 10)
    rng = np.random.default_rng(16)
    data = rng.integers(0, 256, 256 << 10, dtype=np.uint8).tobytes()
    s = _ChunkedStream(store, params, batch_hasher=hasher)
    s.write(data)
    rec = s.finish()
    assert len(rec) > 4
    # one batched probe per hash flush, each covering the whole batch —
    # not one probe per digest
    assert calls and sum(calls) == len(rec)

    # identical re-run: every chunk known, zero new files written
    s2 = _ChunkedStream(store, params, batch_hasher=hasher)
    s2.write(data)
    rec2 = s2.finish()
    assert rec2 == rec
    assert s2.stats.known_chunks == len(rec) and s2.stats.new_chunks == 0


def test_pipelined_vs_sequential_parity_with_index(tmp_path):
    from pbs_plus_tpu.chunker import ChunkerParams
    from pbs_plus_tpu.pxar.pipeline import PipelinedStream
    from pbs_plus_tpu.pxar.transfer import _ChunkedStream

    def hasher(chunks):
        return [hashlib.sha256(c).digest() for c in chunks]

    params = ChunkerParams(avg_size=4 << 10)
    rng = np.random.default_rng(17)
    data = rng.integers(0, 256, 512 << 10, dtype=np.uint8).tobytes()
    # half the stream repeats -> a mix of novel and dedup-hit batches
    data = data + data[: 256 << 10]

    def run(make_stream, store):
        s = make_stream(store)
        for i in range(0, len(data), 64 << 10):
            s.write(data[i:i + 64 << 10])
        rec = s.finish()
        return rec, (s.stats.new_chunks, s.stats.known_chunks)

    st_a = ChunkStore(str(tmp_path / "a"), n_shards=2, index_budget_mb=2)
    st_b = ChunkStore(str(tmp_path / "b"), n_shards=2, index_budget_mb=2)
    rec_seq, stats_seq = run(
        lambda st: _ChunkedStream(st, params, batch_hasher=hasher), st_a)
    rec_pipe, stats_pipe = run(
        lambda st: PipelinedStream(st, params, batch_hasher=hasher,
                                   workers=2), st_b)
    assert rec_seq == rec_pipe
    assert stats_seq == stats_pipe
    assert sorted(st_a.iter_digests()) == sorted(st_b.iter_digests())


# ------------------------------------------------------- GC integration


def test_prune_gc_keeps_index_coherent(tmp_path):
    from pbs_plus_tpu.chunker import ChunkerParams
    from pbs_plus_tpu.pxar.backupproxy import LocalStore
    from pbs_plus_tpu.pxar.format import KIND_DIR, KIND_FILE, Entry
    from pbs_plus_tpu.server.prune import PrunePolicy, run_prune

    store = LocalStore(str(tmp_path / "ds"), ChunkerParams(avg_size=4 << 10),
                       store_shards=4, dedup_index_mb=2)
    rng = np.random.default_rng(18)

    def backup(name: str, t: float):
        sess = store.start_session(backup_type="host", backup_id="g",
                                   backup_time=t, auto_previous=False)
        sess.writer.write_entry(Entry(path="", kind=KIND_DIR))
        sess.writer.write_entry_reader(
            Entry(path=name, kind=KIND_FILE),
            io.BytesIO(rng.integers(0, 256, 64 << 10,
                                    dtype=np.uint8).tobytes()))
        return sess.finish()

    backup("old.bin", t=1_600_000_000.0)
    backup("new.bin", t=1_600_100_000.0)
    ds = store.datastore
    n_before = len(set(ds.chunks.iter_digests()))
    report = run_prune(ds, PrunePolicy(keep_last=1), gc=True, gc_grace_s=0)
    assert len(report.removed) == 1
    assert report.chunks_removed > 0
    # coherence both ways after mark (touch_many) + shard-parallel sweep
    disk = set(ds.chunks.iter_digests())
    known = set(ds.chunks.index.digests())
    assert disk == known
    assert len(disk) < n_before
    # the kept snapshot still reads end-to-end
    ref = ds.list_snapshots("host", "g")[0]
    reader = store.open_snapshot(ref)
    e = reader.lookup("new.bin")
    assert len(reader.read_file(e)) == e.size
