"""The index's work on a job's record once the device table is kept up
to date in place: the small ``index-at-size`` cell of
``tests/bench_cells/test_index_at_size.py`` (64 KiB chunks, an 8 MiB
table, the device twin forced onto the CPU backend) run once more, and
its records counted by hand from the spans.  A flush that stores new
chunks changes a few dozen of the table's 2^18 buckets, so the next
probe sends those buckets and the table never goes whole inside the
window: bytes = whole uploads × the table's bytes + the deltas' bytes."""

import pytest

from benchmark.harness.window import MIB, read_metric
from tests.bench_cells.test_index_at_size import (  # noqa: F401
    INDEX_MB, retable, run, window_jobs)


def test_the_jobs_records_count_the_tables_delta_updates(run):  # noqa: F811
    """A trip an ``ingest.probe``; the table's updates, their bytes and
    seconds from the ``device.probe`` spans under those.  (A stream's
    last hash batch is flushed by ``session.finish`` on a pool thread,
    after the record closed: in neither count.)"""
    from pbs_plus_tpu.server import backup_job
    pumps, _ = window_jobs(run)
    spans = run[2]
    for pump in pumps:
        at = pump["attrs"]
        assert {"index_" + k for k in backup_job.INDEX_COUNTS} \
            | {"index_table_bytes"} <= set(at)
        trips = {s["span"] for s in spans["ingest.probe"]
                 if s["parent"] == pump["span"]}
        device = [s["attrs"] for s in spans["device.probe"]
                  if s["parent"] in trips]
        assert at["index_probe_trips"] == len(trips) == len(device) > 3
        for key in ("table_uploads", "table_delta_uploads",
                    "table_delta_buckets"):
            assert at["index_" + key] == sum(d.get(key, 0) for d in device)
        assert at["index_table_upload_bytes"] \
            == sum(d.get("table_upload_bytes", 0) for d in device) \
            == at["index_table_uploads"] * at["index_table_bytes"] \
            + sum(d.get("table_delta_bytes", 0) for d in device)
        assert at["index_upload_s"] == pytest.approx(
            sum(d.get("upload_s", 0.0) for d in device))
        assert at["index_table_bytes"] == INDEX_MB * MIB
        # every flush that stored a new chunk changed the table: the next
        # probe sent the buckets it changed; the table went whole before
        # the window (the preload's first probe) and never in it
        assert at["index_table_uploads"] == 0
        assert 0 < at["index_table_delta_uploads"] <= at["index_probe_trips"]
        assert at["index_table_delta_uploads"] \
            <= at["index_table_delta_buckets"]


def test_a_trip_sends_the_changed_buckets_not_the_table(run):  # noqa: F811
    """``index_upload_mib_per_trip`` reads the deltas: under 1 MiB a
    trip against the 8 MiB the table would cost whole."""
    pumps, win = window_jobs(run)
    retable(pumps)
    total = {k: sum(p["attrs"][k] for p in pumps) for k in (
        "index_probe_trips", "index_table_upload_bytes")}
    per_trip = read_metric("index_upload_mib_per_trip", win)
    assert per_trip == pytest.approx(
        total["index_table_upload_bytes"] / total["index_probe_trips"]
        / MIB)
    assert 0 < per_trip < 1
