"""MiB of the dedup index's filter table copied to the device per
batched probe: sum ``index_table_upload_bytes`` / sum
``index_probe_trips`` / 2^20 over the job records of the window's jobs.
A probe that follows an insert copies the whole table
(``CuckooIndex.probe``), so this reads the table's size when every probe
pays it (2,048 at a 2 GiB table) and 0 when none does.  On a host
without a device no table is copied and it reads 0.  A program whose
records lack the keys, or a window without a probe, gives nothing to
read.
Layer: device ops.  Source: the job's ``backup.pump`` span."""

from benchmark.harness.jobclocks import share_pct
from benchmark.harness.window import MIB


def read(window):
    # the harness's ratio of two sums over the records, less its percent
    pct = share_pct(window, "index_table_upload_bytes", "index_probe_trips")
    return None if pct is None else pct / 100.0 / MIB
