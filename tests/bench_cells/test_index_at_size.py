"""The deployment ``index-at-size`` (ISSUE 36) without a chip, at a small
size: ``big-stream``'s one agent and its volumes at upstream's smallest
chunk size, 64 KiB, so that a hash batch asks the dedup index dozens of
digests at once — through the index's device twin, forced onto the CPU
backend, so that every flush that inserts copies the filter table as it
does on the chip.  Through ``run.run_cell`` a sound run reads correct
and PR 23's control does not; known and new totals equal the
reference's on the second volume; the index's work is on each job's
record and the four readers of it agree with a count made by hand from
the spans, and are silent on a record without the keys; and the cell's
files state upstream's widths and the table's arithmetic."""

import asyncio
import json
import math
import os
from types import SimpleNamespace

import pytest

from benchmark.harness import loadgen
from benchmark.harness.window import MIB, Window, read_metric

# 2.1 and 4.2 MiB, twice a volume: ~200 chunks of 64 KiB
TREE = {"kind": "lognormal", "mu": math.log(3 << 20), "sigma": 0.5,
        "own_files": 2, "common_files": 2, "dirs": 2,
        "compressible_every": 2}
VOLUMES = 2
CELL = "index-at-size.serial"
INDEX_MB = 8
READERS = ("index_upload_pct", "index_upload_mib_per_trip",
           "index_probe_rows", "index_hit_pct")


def small_cell():
    """The cell's own traffic file over the configuration cut to the
    CPU: 64 KiB chunks, an 8 MiB table, two volumes of two own and two
    common files."""
    cfg = loadgen.check_config("index-at-size-small", {
        "server": {"chunker": "tpu", "chunk_avg": 65536,
                   "max_concurrent": 16, "dedup_index_mb": INDEX_MB},
        "meta_chunk_avg": 65536, "agents": 1, "trees_per_agent": VOLUMES,
        "tree": TREE,
        "warm_tree": dict(TREE, mu=math.log(1 << 20), common_files=0,
                          dirs=1),
        "warm_shapes": {"scan_rows": [1, 4],
                        "scan_seg_kib": [64, 256, 1024, 4096]},
        "index_preload_digests": 300})
    traffic = loadgen.load_cell(CELL).traffic
    return loadgen.Cell(CELL, 1, "index-at-size-small", "serial", cfg,
                        traffic)


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """One run of the small cell on a (forced) device host, with the
    control beside the reference: the result object, the lines it said,
    and every closed span of the four names the hand count needs.  A
    hash batch is cut to 2 MiB so that a few MiB flush as often as a few
    hundred do at the cell's own size."""
    import contextlib
    import io

    import jax

    from benchmark import run as bench_run
    from benchmark.harness import reference
    from pbs_plus_tpu.ops import cuckoo
    from pbs_plus_tpu.pxar import transfer
    from pbs_plus_tpu.utils import jaxenv, trace
    names = ("backup.pump", "ingest.probe", "ingest.store", "device.probe")
    spans = {n: [] for n in names}

    def on_span(rec: dict) -> None:
        if rec["name"] in spans:
            spans[rec["name"]].append(rec)
    said = io.StringIO()
    patch = pytest.MonkeyPatch()
    patch.setattr(jaxenv, "on_accelerator", lambda: True)
    patch.setattr(transfer, "_HASH_BATCH_BYTES", 2 << 20)
    probe0 = dict(cuckoo.stats)
    trace.subscribe(on_span)
    try:
        with contextlib.redirect_stdout(said):
            result = asyncio.run(bench_run.run_cell(
                small_cell(), seed=2**31 + 36, seconds=120.0, trace=False,
                work=str(tmp_path_factory.mktemp("index-at-size")),
                devices=jax.devices()[:1],
                controls={"window32": reference.control_cuts}))
    finally:
        trace.unsubscribe(on_span)
        patch.undo()
    assert result is not None, "a program compiled inside the window"
    lines = [json.loads(ln) for ln in said.getvalue().splitlines()
             if ln.startswith("{")]
    probe = {k: cuckoo.stats[k] - probe0[k] for k in probe0}
    return (result, {ln["phase"]: ln for ln in lines if "phase" in ln},
            spans, probe)


def window_jobs(run):
    """The window's jobs' records (the warm-up's is not among them) and
    a window that names them, as the harness's own would."""
    ids = [j[0] for j in run[1]["compare"]["jobs_from_t0_s"]]
    pumps = [p for p in run[2]["backup.pump"] if p["attrs"]["job"] in ids]
    assert len(pumps) == len(ids) == VOLUMES
    jobs = [SimpleNamespace(job_id=i, status="success") for i in ids]
    return pumps, Window(seconds=run[1]["window"]["interval_s"],
                         loop=SimpleNamespace(jobs=jobs), counters={})


def retable(pumps, drop=()):
    """Make the program's table of job records hold exactly these."""
    from pbs_plus_tpu.utils import trace
    trace.clear()
    for p in pumps:
        trace.emit("backup.pump", p["dur_s"], **{
            k: v for k, v in p["attrs"].items() if not k.startswith(drop)})


def test_sound_run_is_correct_on_all_eight_comparisons(run):
    result = run[0]
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] == VOLUMES and result["failed"] == 0
    assert len(result["compared"]) == 8
    assert all(c["value"] == c["limit"] == 0
               for c in result["compared"].values())
    assert set(result["metrics"]) == {"setup_s", "ingest_mib_s"}
    assert result["metrics"]["ingest_mib_s"]["value"] > 0
    assert run[1]["window"]["compiles_in_window"] == 0


def test_the_control_reads_not_correct(run):
    control = run[0]["controls"]["window32"]
    assert control["correct"] is False
    assert control["compared"]["cut_mismatches"]["value"] >= VOLUMES
    assert control["compared"]["digest_mismatches"]["value"] == 0


def test_the_second_volume_finds_the_common_files_chunks(run):
    """Known and new as the reference reckons them: the common files'
    chunks from the second volume on, a quarter of the window's."""
    seen = run[1]["compare"]
    assert seen["reference_known"] > 0
    assert seen["known"] == seen["reference_known"]
    assert seen["new"] == seen["reference_new"]
    assert 0.15 < seen["known"] / seen["chunks"] < 0.30
    assert seen["chunks"] > 300                 # 64 KiB chunks, not 4 MiB


def test_the_jobs_records_hold_the_indexes_work(run):
    """Counted by hand from the spans the writer's thread closed under
    its job's ``backup.pump`` span: a trip an ``ingest.probe``, its
    ``chunks`` the digests asked; the table's copies and their seconds
    from the ``device.probe`` spans under those; what the store found
    known from ``ingest.store``.  (A stream's last hash batch is flushed
    by ``session.finish`` on a pool thread, after the record closed: in
    neither count.)"""
    from pbs_plus_tpu.server import backup_job
    pumps, _ = window_jobs(run)
    spans = run[2]
    for pump in pumps:
        at = pump["attrs"]
        assert {"index_" + k for k in backup_job.INDEX_COUNTS} \
            | {"index_table_bytes"} <= set(at)
        probes = [s for s in spans["ingest.probe"]
                  if s["parent"] == pump["span"]]
        trips = {s["span"] for s in probes}
        device = [s["attrs"] for s in spans["device.probe"]
                  if s["parent"] in trips]
        stores = [s["attrs"] for s in spans["ingest.store"]
                  if s["parent"] == pump["span"]]
        assert at["index_probe_trips"] == len(probes) == len(device) > 3
        assert at["index_probe_digests"] \
            == sum(s["attrs"]["chunks"] for s in probes) \
            == sum(d["probes"] for d in device)
        assert at["index_probe_padded"] \
            == sum(d["padded_bytes"] for d in device) // 32
        assert at["index_table_uploads"] \
            == sum(d.get("table_uploads", 0) for d in device)
        assert at["index_table_upload_bytes"] \
            == sum(d.get("table_upload_bytes", 0) for d in device) \
            == at["index_table_uploads"] * at["index_table_bytes"]
        assert at["index_upload_s"] == pytest.approx(
            sum(d.get("upload_s", 0.0) for d in device))
        assert at["index_device_s"] == pytest.approx(
            sum(d["device_s"] for d in device))
        assert at["index_hits"] \
            == sum(s["chunks"] - s["new"] for s in stores)
        assert at["index_inserts"] == sum(s["new"] for s in stores)
        assert at["index_contains"] == at["index_inserts"]
        assert at["index_false_positives"] == 0
        assert at["index_table_bytes"] == INDEX_MB * MIB
        # every flush that stored a new chunk dirtied the table: the
        # next probe copied it
        assert 0 < at["index_table_uploads"] <= at["index_probe_trips"]
    first, second = sorted(pumps, key=lambda p: p["start"])
    assert first["attrs"]["index_hits"] == 0 < second["attrs"]["index_hits"]


def test_the_four_readers_agree_with_the_hand_count(run):
    pumps, win = window_jobs(run)
    retable(pumps)
    total = {k: sum(p["attrs"][k] for p in pumps) for k in (
        "index_probe_trips", "index_probe_digests", "index_hits",
        "index_table_upload_bytes", "index_upload_s", "writer_life_s")}
    assert read_metric("index_probe_rows", win) == pytest.approx(
        total["index_probe_digests"] / total["index_probe_trips"])
    assert read_metric("index_upload_mib_per_trip", win) == pytest.approx(
        total["index_table_upload_bytes"] / total["index_probe_trips"]
        / MIB)
    assert read_metric("index_hit_pct", win) == pytest.approx(
        100.0 * total["index_hits"] / total["index_probe_digests"])
    assert read_metric("index_upload_pct", win) == pytest.approx(
        100.0 * total["index_upload_s"] / total["writer_life_s"])
    assert 0 < read_metric("index_upload_mib_per_trip", win) <= INDEX_MB
    assert 10 < read_metric("index_probe_rows", win) < 64
    # what the cell's acceptance asks of the chip, here: the readers'
    # bytes are the op's own counter's, less the copies made outside the
    # writers' threads (the preload's, the pool thread's last flushes)
    assert total["index_table_upload_bytes"] <= run[3]["table_upload_bytes"]


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_on_a_record_without_the_keys(run, name):
    """The parent's records: PR 35's attrs and none of the index's."""
    pumps, win = window_jobs(run)
    retable(pumps, drop=("index_",))
    assert read_metric(name, win) is None
    retable([])
    assert read_metric(name, win) is None
    assert read_metric(name, Window(seconds=50.0, loop=None,
                                    counters={})) is None


def test_cell_files_load_and_state_upstreams_widths():
    from pbs_plus_tpu.ops.cuckoo import BUCKET_BYTES, SLOTS, \
        buckets_for_bytes
    cell = loadgen.load_cell(CELL)
    cfg = cell.config
    assert (cell.chips, cell.config_name, cell.traffic_name) == \
        (1, "index-at-size", "serial")
    assert cfg["server"]["chunk_avg"] == cfg["meta_chunk_avg"] == 64 << 10
    assert cfg["server"]["max_concurrent"] == 16
    assert cfg["server"]["chunker"] == "tpu" and cfg["agents"] == 1
    # a 10 TiB store at 64 KiB: 167,772,160 chunks -> 2^26 buckets by
    # the program's own growth rule -> a 2,048 MiB table
    chunks = (10 << 40) // cfg["server"]["chunk_avg"]
    assert chunks == 167_772_160
    buckets = 1 << 10
    while chunks > buckets * SLOTS * 0.85:
        buckets *= 2
    assert buckets == 1 << 26 == buckets_for_bytes(
        cfg["server"]["dedup_index_mb"] << 20)
    assert buckets * BUCKET_BYTES == 2048 * MIB
    assert round(chunks / (buckets * SLOTS), 2) == 0.62
    assert cfg["index_preload_digests"] == 2_621_440
    for said in ("167,772,160", "2,048 MiB", "0.62"):
        assert said in cfg["deployment"]
    assert "load 0.01" in cfg["reduced"]["index_preload_digests"]
    # everything else is big-stream's, byte for byte
    big = loadgen.load_cell("big-stream.serial")
    for key in ("agents", "trees_per_agent", "tree", "warm_shapes"):
        assert cfg[key] == big.config[key]
    assert cfg["warm_tree"] == dict(big.config["warm_tree"], mu=18.0)
    assert cfg["server"] == dict(big.config["server"], chunk_avg=65536,
                                 dedup_index_mb=2048)
    assert cfg["guarantees"][:4] == big.config["guarantees"]
    assert "exact tier" in cfg["guarantees"][4]
    assert cell.traffic == big.traffic
    assert [round(s / MIB, 1) for s in loadgen.ladder(2, 18.0, 0.5)] \
        == [44.7, 87.7]


def test_the_index_builds_the_classes_a_flush_can_ask():
    """64, 256 and 1024: what ``_HASH_BATCH_COUNT`` digests at most are
    padded to."""
    from pbs_plus_tpu.ops.cuckoo import probe_classes_upto
    from pbs_plus_tpu.pxar.transfer import _HASH_BATCH_COUNT
    assert _HASH_BATCH_COUNT == 512
    assert probe_classes_upto(_HASH_BATCH_COUNT) == (64, 256, 1024)
    assert probe_classes_upto(1) == probe_classes_upto(64) == (64,)
    assert probe_classes_upto(65) == (64, 256)
