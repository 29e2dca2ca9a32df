"""The deployment ``big-stream`` (ISSUE 30) without a chip, at a small
size: one agent, one job at a time, volumes of a few large files, half
of every volume files the datastore already holds.  Through
``run.run_cell`` a sound run reads correct and PR 23's control does
not; the second volume finds the common files' chunks in the index, as
the reference reckons; most files go by the pump's ``read_at`` path;
the feeder's clocks hold the time the writer stood at the device, and
their reader is silent on a program without them; and the cell's
files state upstream's widths."""

import asyncio
import json
import math
import os

import pytest

from benchmark.harness import loadgen
from benchmark.harness.window import Window, read_metric

# 5.0 and 9.8 MiB: the larger is more than one READ_BLOCK (8 MiB), both
# are more than one SCAN_SEGMENT (4 MiB)
TREE = {"kind": "lognormal", "mu": math.log(7 << 20), "sigma": 0.5,
        "own_files": 2, "common_files": 2, "dirs": 2,
        "compressible_every": 2}
VOLUMES = 2
CELL = "big-stream.serial"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def small_cell():
    """The cell's own traffic file over the configuration cut to the
    CPU: 64 KiB chunks, two volumes of two own and two common files."""
    cfg = loadgen.check_config("big-stream-small", {
        "server": {"chunker": "tpu", "chunk_avg": 65536,
                   "max_concurrent": 16, "dedup_index_mb": -1},
        "meta_chunk_avg": 65536, "agents": 1, "trees_per_agent": VOLUMES,
        "tree": TREE,
        "warm_tree": dict(TREE, mu=math.log(1 << 20), common_files=0,
                          dirs=1),
        "warm_shapes": {"scan_rows": [1, 4],
                        "scan_seg_kib": [64, 256, 1024, 4096]},
        "index_preload_digests": 500})
    traffic = loadgen.load_cell(CELL).traffic
    return loadgen.Cell(CELL, 1, "big-stream-small", "serial", cfg,
                        traffic)


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """One run of the small cell with the control beside the reference:
    the result object, the lines it said on the way, and the attrs of
    every ``backup.pump`` span the window's jobs closed."""
    import contextlib
    import io

    import jax

    from benchmark import run as bench_run
    from benchmark.harness import reference
    from pbs_plus_tpu.utils import trace
    pumps: list[dict] = []

    def on_span(rec: dict) -> None:
        if rec["name"] == "backup.pump":
            pumps.append(rec["attrs"])
    said = io.StringIO()
    trace.subscribe(on_span)
    try:
        with contextlib.redirect_stdout(said):
            result = asyncio.run(bench_run.run_cell(
                small_cell(), seed=2**31 + 30, seconds=120.0, trace=False,
                work=str(tmp_path_factory.mktemp("big-stream")),
                devices=jax.devices()[:1],
                controls={"window32": reference.control_cuts}))
    finally:
        trace.unsubscribe(on_span)
    assert result is not None, "a program compiled inside the window"
    lines = [json.loads(ln) for ln in said.getvalue().splitlines()
             if ln.startswith("{")]
    return result, {ln["phase"]: ln for ln in lines if "phase" in ln}, pumps


def test_sound_run_is_correct_on_all_eight_comparisons(run):
    result = run[0]
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] == VOLUMES and result["failed"] == 0
    assert len(result["compared"]) == 8
    assert all(c["value"] == c["limit"] == 0
               for c in result["compared"].values())
    assert set(result["metrics"]) == {"setup_s", "ingest_mib_s"}
    assert result["metrics"]["ingest_mib_s"]["value"] > 0


def test_the_control_reads_not_correct(run):
    control = run[0]["controls"]["window32"]
    assert control["correct"] is False
    assert control["compared"]["cut_mismatches"]["value"] >= VOLUMES
    assert control["compared"]["digest_mismatches"]["value"] == 0


def test_the_second_volume_finds_the_common_files_chunks(run):
    """The reference reckons chunks known when they arrive, and the
    program's manifests count as many: the common files' chunks after
    each one's first, from the second volume on — near a quarter of the
    window's chunks (half of the second volume of two)."""
    seen = run[1]["compare"]
    assert seen["reference_known"] > 0
    assert seen["known"] == seen["reference_known"]
    assert seen["new"] == seen["reference_new"]
    assert 0.15 < seen["known"] / seen["chunks"] < 0.25


def test_most_files_go_by_read_at(run):
    """A file of more than one READ_BLOCK is not one call: the pump
    counted fewer ``one_call_files`` than ``files`` in every job (the
    warm-up's two small files are one call each)."""
    pumps = run[2]
    assert len(pumps) == VOLUMES + 1
    volumes = [p for p in pumps if p["files"] == 4]
    assert len(volumes) == VOLUMES
    for p in volumes:
        assert p["one_call_files"] < p["files"]
        assert p["calls"] > p["files"]


def test_the_feeders_clocks_hold_the_time_writers_stood(run):
    """No counter times a writer's stay at the device: it is the
    request's queue wait and then its dispatch, and the feeder keeps
    both (``tests/test_feeder.py`` ties their sum to the writers' own
    clocks); deltas over the window."""
    feeder = run[1]["window"]["counters"]["feeder"]
    assert feeder["mask_wait_s"] > 0 and feeder["mask_busy_s"] > 0
    assert feeder["mask_rows"] >= feeder["mask_dispatches"] > 0
    assert feeder["mask_wait_s"] + feeder["mask_busy_s"] \
        < run[1]["window"]["interval_s"]     # one writer: under 100 %


def test_scan_turnaround_pct_reads_wait_and_busy_or_nothing(run):
    counters = run[1]["window"]["counters"]
    seconds = run[1]["window"]["interval_s"]
    whole = Window(seconds=seconds, loop=None, counters=counters)
    assert read_metric("scan_turnaround_pct", whole) == pytest.approx(
        100.0 * (counters["feeder"]["mask_wait_s"]
                 + counters["feeder"]["mask_busy_s"]) / seconds)
    hand = Window(seconds=50.0, loop=None, counters={
        "feeder": {"mask_wait_s": 2.5, "mask_busy_s": 10.0}})
    assert read_metric("scan_turnaround_pct", hand) == pytest.approx(25.0)
    # a program that keeps only one of the clocks; a window of no seconds
    for old in (Window(seconds=50.0, loop=None,
                       counters={"feeder": {"mask_wait_s": 0.6,
                                            "mask_rows": 300}}),
                Window(seconds=50.0, loop=None, counters={}),
                Window(seconds=0.0, loop=None, counters={
                    "feeder": {"mask_wait_s": 1.0, "mask_busy_s": 1.0}})):
        assert read_metric("scan_turnaround_pct", old) is None


def test_cell_files_load_and_state_upstreams_widths():
    cell = loadgen.load_cell(CELL)
    cfg = cell.config
    assert (cell.chips, cell.config_name, cell.traffic_name) == \
        (1, "big-stream", "serial")
    assert cfg["agents"] == 1 and cfg["server"]["chunker"] == "tpu"
    assert cfg["server"]["chunk_avg"] == 4 << 20
    assert cfg["server"]["max_concurrent"] == 16
    tree = cfg["tree"]
    assert tree["common_files"] == tree["own_files"] == 2   # 50 % common
    assert cfg["warm_tree"]["common_files"] == 0
    backlog = loadgen.plan_backlog(
        {"agent-00": list(range(cfg["trees_per_agent"]))}, cell.traffic)
    assert list(backlog) == ["agent-00"]
    assert len(backlog["agent-00"]) == cfg["trees_per_agent"] >= 3
    assert "rewritten" in cfg["assumed"]["common_share"]
    single = loadgen.load_cell("single-tree.serial")
    assert cfg["guarantees"] == single.config["guarantees"]
    assert cell.traffic == single.traffic


def test_the_ladder_gives_the_sizes_the_config_states():
    """183.1 and 359.4 MiB, twice: a volume of 1,084.8 MiB, every file
    more than one READ_BLOCK; the warm-up's 5.7 and 11.2 MiB."""
    from pbs_plus_tpu.server.backup_job import READ_BLOCK
    cfg = loadgen.load_cell(CELL).config
    mib = 1 << 20
    sizes = loadgen.ladder(2, 19.41, 0.5)
    assert [round(s / mib, 1) for s in sizes] == [183.1, 359.4]
    tree = cfg["tree"]
    own = loadgen.ladder(tree["own_files"], tree["mu"], tree["sigma"])
    common = loadgen.ladder(tree["common_files"], tree["mu"],
                            tree["sigma"])
    assert list(own) == list(common) == list(sizes)
    assert round((own.sum() + common.sum()) / mib, 1) == 1084.8
    assert common.sum() / (own.sum() + common.sum()) == 0.5
    assert [-(-int(s) // READ_BLOCK) for s in sizes] == [23, 45]
    assert "183.1 and 359.4 MiB" in cfg["deployment"]
    warm = cfg["warm_tree"]
    assert [round(s / mib, 1) for s in loadgen.ladder(
        warm["own_files"], warm["mu"], warm["sigma"])] == [5.7, 11.2]
