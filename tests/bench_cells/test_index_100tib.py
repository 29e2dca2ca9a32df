"""The deployment ``index-100tib`` without a chip, at a small size:
``index-at-size``'s agent, volumes and 64 KiB chunks, with the index's
device twin forced onto the CPU backend and its table split by bucket
range over four of the suite's virtual devices, as a 16 GiB table lies
over a four-chip host.  Through ``run.run_cell`` a sound run reads
correct; each job's record says the table lay on four devices and that
every flush wrote its changed buckets into them in place; the three
readers this cell brought agree with a count made by hand and are silent
where there is nothing to read; and the cell's files state the table's
arithmetic and are ``index-at-size``'s otherwise."""

import asyncio
import json
import math
from types import SimpleNamespace

import jax
import pytest

from benchmark.harness import loadgen
from benchmark.harness.window import MIB, Window, read_metric

TREE = {"kind": "lognormal", "mu": math.log(3 << 20), "sigma": 0.5,
        "own_files": 2, "common_files": 2, "dirs": 2,
        "compressible_every": 2}
VOLUMES = 2
CELL = "index-100tib.serial-x4"
SHARDS = 4
INDEX_MB = 8          # 2^18 buckets: a shard's 65,536 take a flush in place
READERS = ("index_trip_ms", "index_lookup_roofline", "index_update_roofline")

pytestmark = pytest.mark.skipif(len(jax.devices()) < SHARDS,
                                reason="needs 4 virtual devices")


def small_cell():
    """The cell's own traffic over the configuration cut to the CPU."""
    cfg = loadgen.check_config("index-100tib-small", {
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
    return loadgen.Cell(CELL, SHARDS, "index-100tib-small", "serial", cfg,
                        traffic)


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """One run of the small cell with the table on four devices: the
    result, the lines it said, and the spans the hand count needs."""
    import contextlib
    import io

    from benchmark import run as bench_run
    from pbs_plus_tpu.ops import cuckoo
    from pbs_plus_tpu.pxar import transfer
    from pbs_plus_tpu.utils import jaxenv, trace
    names = ("backup.pump", "ingest.probe", "device.probe")
    spans = {n: [] for n in names}

    def on_span(rec: dict) -> None:
        if rec["name"] in spans:
            spans[rec["name"]].append(rec)
    said = io.StringIO()
    devices = tuple(jax.devices()[:SHARDS])
    patch = pytest.MonkeyPatch()
    patch.setattr(jaxenv, "on_accelerator", lambda: True)
    patch.setattr(cuckoo, "table_devices", lambda nb: devices)
    patch.setattr(transfer, "_HASH_BATCH_BYTES", 2 << 20)
    trace.subscribe(on_span)
    try:
        with contextlib.redirect_stdout(said):
            result = asyncio.run(bench_run.run_cell(
                small_cell(), seed=2**31 + 38, seconds=120.0, trace=False,
                work=str(tmp_path_factory.mktemp("index-100tib")),
                devices=list(devices)))
    finally:
        trace.unsubscribe(on_span)
        patch.undo()
    assert result is not None, "a program compiled inside the window"
    lines = [json.loads(ln) for ln in said.getvalue().splitlines()
             if ln.startswith("{")]
    return result, {ln["phase"]: ln for ln in lines if "phase" in ln}, spans


def window_jobs(run):
    ids = [j[0] for j in run[1]["compare"]["jobs_from_t0_s"]]
    pumps = [p for p in run[2]["backup.pump"] if p["attrs"]["job"] in ids]
    assert len(pumps) == len(ids) == VOLUMES
    jobs = [SimpleNamespace(job_id=i, status="success") for i in ids]
    return pumps, Window(seconds=run[1]["window"]["interval_s"],
                         loop=SimpleNamespace(jobs=jobs), counters={},
                         device_kind="TPU v5 lite")


def retable(pumps, drop=()):
    from pbs_plus_tpu.utils import trace
    trace.clear()
    for p in pumps:
        trace.emit("backup.pump", p["dur_s"], **{
            k: v for k, v in p["attrs"].items() if k not in drop})


def test_sound_run_on_four_devices_is_correct(run):
    result = run[0]
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] == VOLUMES and result["failed"] == 0
    assert len(result["compared"]) == 8
    assert all(c["value"] == c["limit"] == 0
               for c in result["compared"].values())
    assert run[1]["window"]["compiles_in_window"] == 0


def test_every_flush_writes_its_buckets_into_the_four_shards(run):
    """The records: the table on four devices, never copied whole in
    the window, every update a class of rows a shard (36 B each); each
    device trip's span says the same."""
    from pbs_plus_tpu.ops.cuckoo import DELTA_BUCKET_BYTES
    pumps, _ = window_jobs(run)
    for pump in pumps:
        at = pump["attrs"]
        assert at["index_table_shards"] == SHARDS
        assert at["index_table_bytes"] == INDEX_MB * MIB
        assert at["index_table_uploads"] == 0
        assert 0 < at["index_table_delta_uploads"] <= at["index_probe_trips"]
        assert at["index_table_upload_bytes"] \
            % (SHARDS * 64 * DELTA_BUCKET_BYTES) == 0
        trips = {s["span"] for s in run[2]["ingest.probe"]
                 if s["parent"] == pump["span"]}
        device = [s["attrs"] for s in run[2]["device.probe"]
                  if s["parent"] in trips]
        assert len(device) == at["index_probe_trips"] > 3
        assert {d["shards"] for d in device} == {SHARDS}


def test_index_trip_ms_agrees_with_the_hand_count(run):
    pumps, win = window_jobs(run)
    retable(pumps)
    total = {k: sum(p["attrs"][k] for p in pumps) for k in (
        "index_device_s", "index_upload_s", "index_probe_trips")}
    assert read_metric("index_trip_ms", win) == pytest.approx(
        1000.0 * (total["index_device_s"] + total["index_upload_s"])
        / total["index_probe_trips"])


def test_the_rooflines_reckon_the_programs_bytes_by_hand(run):
    """On a trace the reduction would give (the programs' busy seconds
    summed over the planes): the slice's bytes at the window's mean rate
    over one chip's peak, 819 GB/s, over the program's busy time."""
    pumps, win = window_jobs(run)
    retable(pumps)
    at = [p["attrs"] for p in pumps]
    life = sum(a["writer_life_s"] for a in at)
    win.trace = {"window_s": 5.0, "device_ops": [
        ["jit__lookup_sharded", 0.02], ["jit__scatter_sharded", 0.04],
        ["jit__lookup", 9.0]]}
    lookup = sum(96 * a["index_probe_padded"] * SHARDS for a in at)
    update = sum((a["index_table_upload_bytes"] - a["index_table_uploads"]
                  * a["index_table_bytes"]) * 68 / 36 for a in at)
    for name, nbytes, busy in (("index_lookup_roofline", lookup, 0.02),
                               ("index_update_roofline", update, 0.04)):
        assert read_metric(name, win) == pytest.approx(
            100.0 * nbytes / life * 5.0 / 819e9 / busy)
        assert 0 < read_metric(name, win) < 100


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_where_there_is_nothing_to_read(run, name):
    """The parent's records (no ``index_table_shards``), a slice where
    the sharded programs did not run, no records at all."""
    pumps, win = window_jobs(run)
    win.trace = {"window_s": 5.0, "device_ops": [["jit__lookup", 0.1]]}
    retable(pumps)
    if name != "index_trip_ms":         # one device: nothing to read
        assert read_metric(name, win) is None
    retable(pumps, drop=("index_table_shards", "index_device_s"))
    win.trace["device_ops"] += [["jit__lookup_sharded", 0.1],
                                ["jit__scatter_sharded", 0.1]]
    assert read_metric(name, win) is None
    retable([])
    assert read_metric(name, win) is None


def test_cell_files_state_the_tables_arithmetic():
    """100 TiB at 64 KiB: 1,677,721,600 chunks -> 2^29 buckets by the
    program's own growth rule -> 16,384 MiB, over four v5e chips 4 GiB
    each; everything else is ``index-at-size``'s."""
    from pbs_plus_tpu.ops.cuckoo import BUCKET_BYTES, SLOTS, \
        buckets_for_bytes, shards_for
    cell = loadgen.load_cell(CELL)
    cfg = cell.config
    assert (cell.chips, cell.config_name, cell.traffic_name) == \
        (4, "index-100tib", "serial")
    chunks = (100 << 40) // cfg["server"]["chunk_avg"]
    assert chunks == 1_677_721_600
    buckets = 1 << 10
    while chunks > buckets * SLOTS * 0.85:
        buckets *= 2
    assert buckets == 1 << 29 == buckets_for_bytes(
        cfg["server"]["dedup_index_mb"] << 20)
    assert buckets * BUCKET_BYTES == 16384 * MIB
    assert round(chunks / (buckets * SLOTS), 2) == 0.78
    # the range of datastores this table is for: 2^28 buckets hold up to
    # 912,680,550 fingerprints, 2^29 up to 1,825,361,100
    low, high = (int(b * SLOTS * 0.85) for b in (1 << 28, 1 << 29))
    assert (low, high) == (912_680_550, 1_825_361_100)
    assert [round(n * (64 << 10) / (1 << 40), 1) for n in (low, high)] \
        == [54.4, 108.8]
    v5e = int(15.75 * (1 << 30))
    assert shards_for(buckets, [v5e] * 4) == 4
    for said in ("1,677,721,600", "16,384 MiB", "0.78", "4 GiB each"):
        assert said in cfg["deployment"]
    assert "54.4 TiB" in cfg["assumed"]["datastore_size"]
    assert "load 0.0012" in cfg["reduced"]["index_preload_digests"]
    at_size = loadgen.load_cell("index-at-size.serial")
    for key in ("agents", "trees_per_agent", "tree", "warm_tree",
                "warm_shapes", "guarantees", "meta_chunk_avg",
                "index_preload_digests"):
        assert cfg[key] == at_size.config[key]
    assert cfg["server"] == dict(at_size.config["server"],
                                 dedup_index_mb=16384)
    assert cell.traffic == at_size.traffic
    with open("BENCHMARK.json", encoding="utf-8") as f:
        manifest = json.load(f)
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 4 and entry["config"] == "index-100tib"
    assert CELL not in next(m for m in manifest["per_layer"] if m["name"]
                            == "scan_mesh_dispatch_pct")["workloads"]
