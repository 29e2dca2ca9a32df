"""The deployment ``big-stream-fanin`` (ISSUE 32) without a chip, at a
small size: four agents at once, a volume of one or two large files
each, nothing known to the datastore.  Through ``run.run_cell`` a sound
run reads correct and PR 23's control does not; every job publishes and
the reference reckons no chunk known; the feeder's ``mask_rows_shared``
is the rows of the window's dispatches of two or more; the two readers
this cell brings read that program and are silent on one without the
counters; and the cell's files state upstream's widths.  Equalities
only: whether rows met is timing, and a CPU's timing at that."""

import asyncio
import json
import math
import os

import pytest

from benchmark.harness import loadgen
from benchmark.harness.window import Window, read_metric

# one file of 7.0 MiB, or two of 5.0 and 9.8: every one is more than a
# SCAN_SEGMENT (4 MiB), so rows are full segments
OWN_FILES = [1, 2, 1, 2]
TREE = {"kind": "lognormal", "mu": math.log(7 << 20), "sigma": 0.5,
        "own_files": OWN_FILES, "common_files": 0, "dirs": 2,
        "compressible_every": 2}
CELL = "big-stream-fanin.burst"
MIB = 1 << 20


def small_cell():
    """The cell's own traffic file over the configuration cut to the
    CPU: four agents, 64 KiB chunks; no 16-row program, which four
    sessions cannot fill and a CPU is slow to compile at 4 MiB a row."""
    cfg = loadgen.check_config("big-stream-fanin-small", {
        "server": {"chunker": "tpu", "chunk_avg": 65536,
                   "max_concurrent": 16, "dedup_index_mb": -1},
        "meta_chunk_avg": 65536, "agents": len(OWN_FILES),
        "trees_per_agent": 1, "tree": TREE,
        "warm_tree": dict(TREE, mu=math.log(1 << 20), own_files=2, dirs=1),
        "warm_shapes": {"scan_rows": [1, 4],
                        "scan_seg_kib": [64, 256, 1024, 4096]},
        "index_preload_digests": 500})
    traffic = loadgen.load_cell(CELL).traffic
    return loadgen.Cell(CELL, 1, "big-stream-fanin-small", "burst", cfg,
                        traffic)


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """One run of the small cell with the control beside the reference:
    the result object, the lines it said on the way by phase, and the
    attrs of every scan's ``feeder.dispatch`` span, in order."""
    import contextlib
    import io

    import jax

    from benchmark import run as bench_run
    from benchmark.harness import reference
    from pbs_plus_tpu.utils import trace
    scans: list[dict] = []

    def on_span(rec: dict) -> None:
        if rec["name"] == "feeder.dispatch" and \
                rec["attrs"]["kind"] == "scan":
            scans.append(rec["attrs"])
    said = io.StringIO()
    trace.subscribe(on_span)
    try:
        with contextlib.redirect_stdout(said):
            result = asyncio.run(bench_run.run_cell(
                small_cell(), seed=2**31 + 32, seconds=120.0, trace=False,
                work=str(tmp_path_factory.mktemp("big-stream-fanin")),
                devices=jax.devices()[:1],
                controls={"window32": reference.control_cuts}))
    finally:
        trace.unsubscribe(on_span)
    assert result is not None, "a program compiled inside the window"
    lines = [json.loads(ln) for ln in said.getvalue().splitlines()
             if ln.startswith("{")]
    return result, {ln["phase"]: ln for ln in lines if "phase" in ln}, scans


def test_sound_run_is_correct_and_every_job_publishes(run):
    result, said, _ = run
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] == len(OWN_FILES) and result["failed"] == 0
    assert len(result["compared"]) == 8
    assert all(c["value"] == c["limit"] == 0
               for c in result["compared"].values())
    assert said["window"]["jobs_published_in_window"] == len(OWN_FILES)
    assert said["window"]["drained"] is True
    assert set(result["metrics"]) == {"setup_s", "ingest_mib_s"}


def test_the_control_reads_not_correct(run):
    control = run[0]["controls"]["window32"]
    assert control["correct"] is False
    assert control["compared"]["cut_mismatches"]["value"] >= 1
    assert control["compared"]["digest_mismatches"]["value"] == 0


def test_no_chunk_is_known_as_the_reference_reckons(run):
    """First backups of different volumes share no file: the index
    answers only misses, in the program's manifests and the
    reference's reckoning alike."""
    seen = run[1]["compare"]
    assert seen["reference_known"] == 0 and seen["known"] == 0
    assert seen["new"] == seen["reference_new"] == seen["chunks"] > 0


def test_shared_rows_are_the_rows_of_the_windows_wider_dispatches(run):
    """``mask_rows_shared`` (``DeviceFeeder._mask_hits``) against the
    ``feeder.dispatch`` spans: the backlog drains inside the window and
    nothing scans after it, so the window's dispatches are the run's
    last ``mask_dispatches``; their ``reqs`` sum to ``mask_rows``, and
    those of two or more to ``mask_rows_shared``."""
    feeder = run[1]["window"]["counters"]["feeder"]
    assert 0 <= feeder["mask_rows_shared"] <= feeder["mask_rows"]
    assert feeder["mask_retried_alone"] == 0
    ours = run[2][-feeder["mask_dispatches"]:]
    assert sum(a["reqs"] for a in ours) == feeder["mask_rows"]
    assert sum(a["reqs"] for a in ours if a["reqs"] >= 2) \
        == feeder["mask_rows_shared"]
    # every file is scanned in full segments and one rest
    sizes = [int(s) for n in OWN_FILES
             for s in loadgen.ladder(n, TREE["mu"], TREE["sigma"])]
    assert feeder["mask_rows"] >= sum(-(-s // (4 * MIB)) for s in sizes)


def test_the_two_readers_read_this_program(run):
    counters = run[1]["window"]["counters"]
    whole = Window(seconds=run[1]["window"]["interval_s"], loop=None,
                   counters=counters)
    feeder, scan = counters["feeder"], counters["scan"]
    assert read_metric("feeder_shared_rows_pct", whole) == pytest.approx(
        100.0 * feeder["mask_rows_shared"] / feeder["mask_rows"])
    trip_s = sum(scan[k] for k in ("pack_s", "h2d_s", "device_s", "d2h_s",
                                   "unpack_s"))
    assert trip_s > 0 and scan["bytes"] > 0
    assert read_metric("scan_ms_per_mib", whole) == pytest.approx(
        1000.0 * trip_s / (scan["bytes"] / MIB))


@pytest.mark.parametrize("name, counters, want", [
    ("feeder_shared_rows_pct",
     {"feeder": {"mask_rows": 40, "mask_rows_shared": 10}}, 25.0),
    ("feeder_shared_rows_pct",
     {"feeder": {"mask_rows": 40, "mask_rows_shared": 0}}, 0.0),
    ("feeder_shared_rows_pct", {"feeder": {"mask_rows": 40}}, None),
    ("feeder_shared_rows_pct",
     {"feeder": {"mask_rows": 0, "mask_rows_shared": 0}}, None),
    ("feeder_shared_rows_pct", {}, None),
    ("scan_ms_per_mib",
     {"scan": {"pack_s": 0.1, "h2d_s": 0.2, "device_s": 0.3, "d2h_s": 0.3,
               "unpack_s": 0.1, "bytes": 250 * MIB,
               "padded_bytes": 500 * MIB}}, 4.0),
    ("scan_ms_per_mib",
     {"scan": {"pack_s": 0.1, "h2d_s": 0.2, "device_s": 0.3, "d2h_s": 0.3,
               "unpack_s": 0.1, "bytes": 0}}, None),
    ("scan_ms_per_mib", {"scan": {"bytes": 250 * MIB}}, None),
    ("scan_ms_per_mib", {}, None),
], ids=["a-quarter", "none-met", "no-counter", "no-rows", "no-feeder",
        "four-ms", "no-bytes", "no-clocks", "no-scan"])
def test_readers_by_hand_and_on_a_program_without_the_counters(
        name, counters, want):
    got = read_metric(name, Window(seconds=50.0, loop=None,
                                   counters=counters))
    assert got == (pytest.approx(want) if want is not None else None)


def test_cell_files_load_and_state_upstreams_widths():
    cell = loadgen.load_cell(CELL)
    cfg = cell.config
    assert (cell.chips, cell.config_name, cell.traffic_name) == \
        (1, "big-stream-fanin", "burst")
    assert cfg["agents"] == 8 and cfg["trees_per_agent"] == 1
    assert cfg["server"] == {"chunker": "tpu", "chunk_avg": 4 << 20,
                             "max_concurrent": 16, "dedup_index_mb": -1}
    assert cfg["meta_chunk_avg"] == 128 << 10
    big = loadgen.load_cell("big-stream.serial").config
    tree = cfg["tree"]
    assert (tree["mu"], tree["sigma"]) == \
        (big["tree"]["mu"], big["tree"]["sigma"]) == (19.41, 0.5)
    assert tree["common_files"] == 0 and len(tree["own_files"]) == 8
    assert cfg["warm_tree"] == big["warm_tree"]
    assert cfg["guarantees"] == big["guarantees"]
    assert cfg["index_preload_digests"] == big["index_preload_digests"]
    # up to eight rows meet: the class above four, at every segment class
    assert 16 in cfg["warm_shapes"]["scan_rows"]
    assert 4096 in cfg["warm_shapes"]["scan_seg_kib"]
    assert "sha_classes" not in cfg["warm_shapes"]
    assert set(cfg["reduced"]) == {"agents", "total_bytes",
                                   "index_preload_digests"}
    assert "configs[3]" in cfg["source"] and "buffer.go:33-38" in \
        cfg["source"]
    assert cell.traffic == loadgen.load_cell("fanin8-mixed.burst").traffic
    backlog = loadgen.plan_backlog(
        {f"agent-{a:02d}": [a] for a in range(cfg["agents"])}, cell.traffic)
    assert len(backlog) == 8 and all(len(q) == 1 for q in backlog.values())


def test_the_ladder_gives_the_sizes_the_config_states():
    """A volume of one dump of 256.5 MiB or of two, 183.1 and 359.4:
    3,195.6 MiB over the eight, every file more than one READ_BLOCK and
    dozens of full scan segments."""
    from pbs_plus_tpu.models.dedup import SCAN_SEGMENT
    from pbs_plus_tpu.server.backup_job import READ_BLOCK
    cfg = loadgen.load_cell(CELL).config
    tree = cfg["tree"]
    volumes = [loadgen.ladder(n, tree["mu"], tree["sigma"])
               for n in tree["own_files"]]
    assert [[round(s / MIB, 1) for s in v] for v in volumes] == \
        [[256.5], [183.1, 359.4]] * 4
    total = sum(int(v.sum()) for v in volumes)
    assert round(total / MIB, 1) == 3195.6
    assert round(total / (1 << 30), 2) == 3.12
    sizes = sorted({int(s) for v in volumes for s in v})
    assert [-(-s // READ_BLOCK) for s in sizes] == [23, 33, 45]
    assert [s // SCAN_SEGMENT for s in sizes] == [45, 64, 89]
    for text in ("256.5 MiB", "183.1 and 359.4 MiB", "3,195.6 MiB"):
        assert text in cfg["deployment"]
    assert "3.12 GiB" in cfg["reduced"]["total_bytes"]
