"""The device kernels, compiled for a described TPU v5e at the widths
``chip_smoke.py`` runs them at (on-chip-measurement guide, section 2,
rehearsal 3).  Nothing runs: these tests ask the chip's own compiler,
which is installed here, whether it takes each program and how much
device memory it plans — what it refuses here costs no chip time.

The tests' backend stays the CPU, so code that picks by
``jax.default_backend()`` would take its CPU branch: the TPU branch of
SHA-256 is steered in by monkeypatch, in the test, never by an option of
the program.

Everything that touches the topology is inside fixtures and tests: only
one process at a time may load the TPU's library, and every xdist worker
imports this file.  For the same reason these tests live in ONE file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from pbs_plus_tpu.ops import cuckoo, fingerprint, rolling_hash, sha256
from pbs_plus_tpu.ops import similarity

MIB = 1 << 20
# what the chip's compiler reports for one v5e ("Used 16.00G of 15.75G hbm")
V5E_HBM_BYTES = int(15.75 * (1 << 30))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """``shape((rows, cols), dtype)`` → a ShapeDtypeStruct placed on the
    described topology's first chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                    sharding=one_chip)


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A program compiled for a described chip is written to the
    persistent cache but cannot be read back without the chip: the next
    run would warn and compile again.  Off around this file."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


def compile_scan(shape, rows: int, seg: int):
    """The batched scan's program: the mask, packed 32 positions a word."""
    return rolling_hash._candidate_words_jit.lower(
        shape((rows, seg), jnp.uint8), shape((2, 16), jnp.uint32),
        shape((), jnp.uint32), shape((), jnp.uint32),
        shape((rows, 63), jnp.uint8)).compile()


@pytest.fixture
def v5e_budget(monkeypatch):
    """The scan's memory budget as the program derives it on one v5e
    (here ``memory_stats()`` is the CPU's, which reports no limit)."""
    budget = V5E_HBM_BYTES // rolling_hash._SCAN_MEMORY_SHARE
    monkeypatch.setattr(rolling_hash, "scan_budget_bytes", lambda: budget)
    return budget


def test_scan_compiles_at_the_feeders_largest_batch(shape, v5e_budget):
    """64 concurrent 4 MiB pages: the widest dispatch the feeder forms."""
    rows = rolling_hash.dispatch_rows(4 * MIB)
    assert rows == rolling_hash._ROW_CLASSES[-1] == 64
    assert device_bytes(compile_scan(shape, rows, 4 * MIB)) <= v5e_budget


def test_scan_budget_splits_what_the_chip_refuses(shape, v5e_budget):
    """DedupPipeline's old default, 32 rows of 64 MiB in one dispatch,
    is refused by the chip's compiler; the budget splits the same request
    into dispatches that each compile and fit."""
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
        compile_scan(shape, 32, 64 * MIB)
    rows = rolling_hash.dispatch_rows(64 * MIB)
    assert 1 <= rows < 32 and rows in rolling_hash._ROW_CLASSES
    assert device_bytes(compile_scan(shape, rows, 64 * MIB)) <= v5e_budget
    # the longest segment class the budget admits at all, one row
    assert rolling_hash.dispatch_rows(256 * MIB) == 1
    assert device_bytes(compile_scan(shape, 1, 256 * MIB)) <= v5e_budget
    with pytest.raises(ValueError, match="does not fit"):
        rolling_hash.dispatch_rows(1 << 30)


def test_packed_scan_keeps_its_rows_where_they_are_on_four_chips(topo):
    """The fan-in's sharded dispatch on a v5e 2x2: packing the answer is
    row-local, so the words come out sharded as the rows went in and the
    program holds no collective (ISSUE 33)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
    by_rows, whole = NamedSharding(mesh, P("data", None)), \
        NamedSharding(mesh, P())

    def arg(dims, dtype, sharding):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)
    compiled = rolling_hash._candidate_words_jit.lower(
        arg((4, 4 * MIB), jnp.uint8, by_rows),
        arg((2, 16), jnp.uint32, whole), arg((), jnp.uint32, whole),
        arg((), jnp.uint32, whole),
        arg((4, 63), jnp.uint8, by_rows)).compile()
    assert compiled.output_shardings.is_equivalent_to(by_rows, 2)
    text = compiled.as_text()
    assert not any(op in text for op in (
        "all-gather", "all-reduce", "all-to-all", "collective-permute",
        "reduce-scatter"))


def test_sha256_tpu_branch_compiles_once_for_every_length(shape, monkeypatch):
    """The branch no CPU test runs: all 64 rounds unrolled, 16 blocks a
    step.  Compiled ONCE, as the program does since the trip count became
    a run-time argument: one staging-buffer class and one row class serve
    every chunk length (this is the program chip_smoke's 4 MiB chunks
    use most).  About 70 s here."""
    monkeypatch.setattr(sha256, "_compress", sha256._compress_unrolled)
    slab = sha256._class_for(sha256.SLAB_BYTES, sha256._SLAB_CLASSES)
    compiled = sha256._sha256_scan.lower(
        shape((slab,), jnp.uint8), shape((8,), jnp.int32),
        shape((8,), jnp.int32), shape((), jnp.int32), unroll=16).compile()
    assert "while" in compiled.as_text()       # run-time trip count
    assert device_bytes(compiled) < 2 * slab


def test_cuckoo_lookup_compiles_at_the_default_table(shape):
    """1 << 20 buckets (DedupConfig's default), 64k digests a probe."""
    compiled = cuckoo._lookup.lower(
        shape((1 << 20, cuckoo.SLOTS, 2), jnp.uint32),
        shape((1 << 16, 32), jnp.uint8)).compile()
    assert device_bytes(compiled) < 64 * MIB


@pytest.mark.parametrize("k", [256, 1 << 16])
def test_cuckoo_table_update_is_written_in_place(shape, k):
    """The update of a changed table at ``index-at-size``'s 2 GiB table
    (2^26 buckets), for a flush's class and a preload batch's: the table
    it is given comes back as its output, aliased, with KiB of scratch —
    no second table on the device (a ``scatter`` of whole rows has the
    compiler relay the table out at 32 times its size first)."""
    nb = 1 << 26
    compiled = cuckoo._scatter.lower(
        shape((nb, cuckoo.SLOTS, 2), jnp.uint32), shape((k,), jnp.int32),
        shape((k, cuckoo.SLOTS, 2), jnp.uint32)).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes == m.output_size_in_bytes \
        == nb * cuckoo.BUCKET_BYTES
    assert m.temp_size_in_bytes < MIB


@pytest.fixture(scope="module")
def by_buckets(topo):
    """``by_buckets(dims, dtype, spec)`` → a ShapeDtypeStruct on a 1-D
    mesh of the described v5e 2x2's four chips, the mesh beside it."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    mesh = Mesh(np.array(topo.devices).reshape(4), (cuckoo._AXIS,))
    return mesh, lambda dims, dtype, spec: jax.ShapeDtypeStruct(
        dims, dtype, sharding=NamedSharding(mesh, spec))


COLLECTIVES = ("all-gather", "all-reduce", "all-to-all",
               "collective-permute", "reduce-scatter")


@pytest.mark.parametrize("k", [256, 1 << 14])
def test_sharded_table_update_is_written_in_place_on_four_chips(
        by_buckets, k):
    """``index-100tib``'s 16 GiB table (2^29 buckets) over four chips, a
    flush's class and a preload batch's a shard: each chip's 4 GiB shard
    comes back as its output, aliased, with KiB of scratch, and no
    collective — no shard is gathered or relaid."""
    from jax.sharding import PartitionSpec as P
    mesh, arg = by_buckets
    nb, rows = 1 << 29, cuckoo._SHARDED
    compiled = cuckoo._scatter_sharded.lower(
        arg((nb, cuckoo.SLOTS, 2), jnp.uint32, rows),
        arg((4 * k,), jnp.int32, P(cuckoo._AXIS)),
        arg((4 * k, cuckoo.SLOTS, 2), jnp.uint32, rows), mesh=mesh).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes == m.output_size_in_bytes \
        == nb // 4 * cuckoo.BUCKET_BYTES
    assert m.temp_size_in_bytes < MIB
    assert not any(op in compiled.as_text() for op in COLLECTIVES)


def test_sharded_lookup_sums_the_shards_hits_on_four_chips(by_buckets):
    """The lookup over the same table: a 4 GiB shard a chip is all it
    holds of it, and the partial hits meet in one all-reduce."""
    from jax.sharding import PartitionSpec as P
    mesh, arg = by_buckets
    nb = 1 << 29
    compiled = cuckoo._lookup_sharded.lower(
        arg((nb, cuckoo.SLOTS, 2), jnp.uint32, cuckoo._SHARDED),
        arg((1024, 32), jnp.uint8, P()), mesh=mesh).compile()
    assert device_bytes(compiled) < nb // 4 * cuckoo.BUCKET_BYTES + MIB
    assert "all-reduce" in compiled.as_text()


def test_simhash_projection_compiles(shape):
    compiled = similarity._simhash.lower(
        shape((1 << 16, 32), jnp.uint8), shape((256, 64), jnp.float32),
        k=64).compile()
    assert device_bytes(compiled) < 64 * MIB


# -- kernels chip_smoke does not reach (off by default or test-only):
#    compiled at modest widths so a lowering the chip refuses shows here ----

def test_minhash_compiles(shape):
    similarity._minhash.lower(
        shape((1 << 16, 32), jnp.uint8), shape((128,), jnp.uint32),
        shape((128,), jnp.uint32), k=128).compile()


def test_pairwise_hamming_compiles(shape):
    similarity.pairwise_hamming.lower(
        shape((4096, 2), jnp.uint32), shape((4096, 2), jnp.uint32)).compile()


def test_content_sketch_compiles(shape):
    """The similarity tier's device twin (delta tier, off by default).
    Its jit key is (chunks, longest chunk) — unbounded, and about 14 s a
    compile even at 64 KiB: ROADMAP has it to settle before that tier is
    switched on where there is a chip."""
    similarity._content_sketch_words.lower(
        shape((8, 64 << 10), jnp.uint8), shape((8,), jnp.int32)).compile()


def test_fold_fingerprint_compiles(shape):
    jax.jit(fingerprint.fold_fingerprint, static_argnames=("t_max",)).lower(
        shape((16 * MIB,), jnp.uint8), shape((64,), jnp.int32),
        shape((64,), jnp.int32), t_max=1024).compile()
