"""What a batched index probe costs the writer's thread at the device,
the table's update included: 1000 x sum (``index_device_s`` +
``index_upload_s``) / sum ``index_probe_trips`` over the job records of
the window's jobs — the lookup's ``device`` phase and the update's
wall clock inside the ``h2d`` phase (``CuckooIndex.probe``), tallied on
the writer's thread.  Beside a one-device table's reading it says what a
table split over four devices costs a trip.  A program whose records
lack the keys, or a window without a probe, gives nothing to read.
Layer: device ops.  Source: the job's ``backup.pump`` span."""

from benchmark.harness.jobclocks import records

KEYS = ("index_device_s", "index_upload_s", "index_probe_trips")


def read(window):
    recs = records(window)
    if not recs or any(k not in r for r in recs for k in KEYS):
        return None
    trips = sum(r["index_probe_trips"] for r in recs)
    if not trips:
        return None
    return 1000.0 * sum(r["index_device_s"] + r["index_upload_s"]
                        for r in recs) / trips
