"""The dedup index's table split by bucket range over several devices
(``ops/cuckoo.py``: ``table_devices``, ``_lookup_sharded``,
``_scatter_sharded``), on the suite's virtual CPU devices: the lookup
answers as the host twin over the unsharded mirror, a change written
shard by shard and a whole copy leave every shard equal to the mirror's
rows, ``DedupIndex`` on four devices answers as the exact set through
inserts, discards, a growth and a rebuild, the placement rule keeps the
benchmark's tables on one device and splits 16 GiB over four, and a
table no set of devices holds is refused when the server is built."""

import jax
import numpy as np
import pytest

from pbs_plus_tpu.ops import cuckoo
from pbs_plus_tpu.ops.cuckoo import (
    DELTA_BUCKET_BYTES, SLOTS, CuckooIndex, TableTooLarge, lookup_host,
    shards_for)
from pbs_plus_tpu.pxar.chunkindex import DedupIndex

pytestmark = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs 4 virtual devices")

GIB = 1 << 30
V5E = int(15.75 * GIB)          # a v5e chip's bytes_limit


def _digests(n: int, seed: int) -> list[bytes]:
    raw = np.random.default_rng(seed).bytes(32 * n)
    return [raw[i:i + 32] for i in range(0, len(raw), 32)]


def _arr(digests) -> np.ndarray:
    return np.frombuffer(b"".join(digests), np.uint8).reshape(-1, 32)


@pytest.fixture
def on_devices(monkeypatch):
    """``on_devices(n)``: every table lies on the first n devices."""
    def place(n):
        devices = tuple(jax.devices()[:n])
        monkeypatch.setattr(cuckoo, "table_devices", lambda nb: devices)
    return place


def _shards_equal_the_mirror(index: CuckooIndex, shards: int) -> None:
    table = index._device_table
    assert index.table_shards == len(table.addressable_shards) == shards
    for shard in table.addressable_shards:
        assert np.array_equal(np.asarray(shard.data),
                              index._table[shard.index])
    assert np.array_equal(np.asarray(table), index._table)


@pytest.mark.parametrize("n", [1, 64, 65, 256, 257, 1024])
@pytest.mark.parametrize("shards", [2, 4])
def test_sharded_lookup_answers_as_the_host_twin(on_devices, shards, n):
    """Every probe class, half the digests present: the lookup over the
    split table equals ``lookup_host`` over the mirror."""
    on_devices(shards)
    index = CuckooIndex(n_buckets=1 << 12)
    asked = _digests(n, seed=shards)
    index.insert_many(asked[::2])
    got = index.probe(_arr(asked))
    assert np.array_equal(got, lookup_host(index._table, _arr(asked)))
    assert got[::2].all()
    _shards_equal_the_mirror(index, shards)


def _digest_in(nb: int, bucket: int, rng) -> bytes:
    """A digest whose first bucket in a table of ``nb`` is ``bucket``."""
    words = np.array([int(rng.integers(1, 1 << 32)),
                      int(rng.integers(1, 1 << 32)),
                      (bucket + int(rng.integers(0, 1 << 12)) * nb)
                      % (1 << 32)], dtype=">u4")
    return words.tobytes() + rng.bytes(20)


@pytest.mark.parametrize("shards", [2, 4])
def test_a_change_across_shard_boundaries_goes_to_the_shards_that_own_it(
        on_devices, shards):
    """Buckets on both sides of every boundary between shards, and the
    table's first and last: one probe writes each into its own shard in
    place and sends a class of rows a shard; then a rebuild sends the
    table whole, in one copy split over the devices."""
    on_devices(shards)
    nb = 1 << 18
    per = nb // shards
    rng = np.random.default_rng(38)
    index = CuckooIndex(n_buckets=nb)
    index.insert_many(_digests(100, seed=380))
    index.probe(_arr(_digests(3, seed=381)))        # the first copy
    buckets = [0, nb - 1] + [b for s in range(1, shards)
                             for b in (s * per - 1, s * per)]
    fresh = [_digest_in(nb, b, rng) for b in buckets]
    before = dict(cuckoo.stats)
    for d in fresh:
        assert index.insert(d) is True
    got = index.probe(_arr(fresh + _digests(5, seed=382)))
    assert got[:len(fresh)].all()
    spent = {k: cuckoo.stats[k] - before[k] for k in before}
    assert spent["table_uploads"] == 0 and spent["table_delta_uploads"] == 1
    assert spent["table_delta_buckets"] == len(buckets)
    assert spent["table_delta_bytes"] == shards * 64 * DELTA_BUCKET_BYTES
    _shards_equal_the_mirror(index, shards)
    index._rebuild_bulk()
    index.probe(_arr(fresh))
    assert cuckoo.stats["table_uploads"] == before["table_uploads"] + 1
    assert cuckoo.stats["table_shards"] == shards
    _shards_equal_the_mirror(index, shards)


def _step(index: DedupIndex, op: str, known: set, rng) -> None:
    if op == "insert":
        for d in _digests(9, seed=int(rng.integers(1 << 30))):
            assert index.insert(d) is True
            known.add(d)
    elif op == "discard":
        for d in sorted(known)[:4]:
            assert index.discard(d) is True
            known.discard(d)
    elif op == "grow":                  # past load 0.85: it doubles
        nb = index.n_buckets
        batch = _digests(int(nb * SLOTS * 0.85) + 1 - len(index),
                         seed=int(rng.integers(1 << 30)))
        index.insert_many(batch)
        known.update(batch)
        assert index.n_buckets == 2 * nb
    else:                               # the boot's rebuild
        assert index.rebuild(sorted(known)) == len(known)


@pytest.mark.parametrize("spill", [False, True], ids=["ram", "spill"])
def test_dedupindex_on_four_devices_answers_as_the_exact_set(
        on_devices, monkeypatch, tmp_path, spill):
    """The device twin forced, the table on four devices: after inserts,
    discards, a growth and a rebuild every batched probe answers as the
    exact set, and the device's table is the mirror, shard by shard;
    the changes between go in place, the growth and the rebuild whole."""
    from pbs_plus_tpu.utils import jaxenv
    monkeypatch.setattr(jaxenv, "on_accelerator", lambda: True)
    on_devices(4)
    rng = np.random.default_rng(39)
    # 2^18 buckets: a shard's 65,536 take a change of 64 in place
    index = DedupIndex(budget_mb=8, spill_dir=str(tmp_path) if spill
                       else None)
    index.mark_booted()
    known = set(_digests(500, seed=390))
    index.insert_many(sorted(known))
    before = dict(cuckoo.stats)
    for op in ("probe", "insert", "discard", "insert", "grow", "discard",
               "rebuild", "insert"):
        if op != "probe":
            _step(index, op, known, rng)
        asked = sorted(known)[:300] + _digests(60, seed=int(
            rng.integers(1 << 30)))
        assert index.probe_batch(asked) == [d in known for d in asked], op
        assert index.table_shards == 4
        _shards_equal_the_mirror(index._cuckoo, 4)
    spent = {k: cuckoo.stats[k] - before[k] for k in before}
    assert spent["table_delta_uploads"] >= 4
    assert spent["table_uploads"] >= 3     # the first, the growth, the rebuild


@pytest.mark.parametrize("table_gib, chips, shards", [
    (1 / 16, 1, 1), (1 / 16, 4, 1),     # the 64 MiB cells, -x4 among them
    (2, 1, 1), (2, 4, 1),               # index-at-size
    (8, 1, 1), (16, 4, 4)])             # 8 GiB stays whole; 16 GiB splits
def test_the_placement_rule_by_the_devices_memory(table_gib, chips, shards):
    nb = int(table_gib * GIB) // cuckoo.BUCKET_BYTES
    assert shards_for(nb, [V5E] * chips) == shards
    assert shards_for(nb, [None] * chips) == 1      # a backend with no limit


def test_a_table_no_set_of_devices_holds_is_refused_at_the_servers_start(
        monkeypatch, tmp_path):
    """16 GiB on one v5e, and on four the 64 MiB table beside four
    devices of 1 GiB: refused with the table's bytes and the devices'
    limits, when the server is built and not in a backup's probe."""
    from pbs_plus_tpu.server.store import Server, ServerConfig
    from pbs_plus_tpu.utils import jaxenv
    with pytest.raises(TableTooLarge, match="17,179,869,184 bytes"):
        shards_for((16 * GIB) // cuckoo.BUCKET_BYTES, [V5E])

    class Small:
        def memory_stats(self):
            return {"bytes_limit": GIB}
    monkeypatch.setattr(jaxenv, "on_accelerator", lambda: True)
    monkeypatch.setattr(cuckoo.jax, "devices", lambda: [Small()] * 4)
    monkeypatch.setattr(cuckoo, "_placements", {})
    with pytest.raises(TableTooLarge) as e:
        Server(ServerConfig(state_dir=str(tmp_path / "state"),
                            cert_dir=str(tmp_path / "certs"),
                            datastore_dir=str(tmp_path / "ds"),
                            dedup_index_mb=64))
    assert "67,108,864 bytes" in str(e.value)
    assert "1,073,741,824, 1,073,741,824" in str(e.value)
