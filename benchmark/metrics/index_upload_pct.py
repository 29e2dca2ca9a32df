"""Share of the backup writer threads' lives spent copying the dedup
index's filter table to the device: 100 x sum ``index_upload_s`` / sum
``writer_life_s`` over the job records of the window's jobs.
``index_upload_s`` is the wall clock of the table's own copy inside a
probe's ``h2d`` phase (``CuckooIndex.probe``: ``device_table()`` until
the copy is on the device), apart from the digests' copy, tallied on the
writer's thread; the table goes whole, at every probe that follows an
insert.  Part of ``writer_probe_pct``.  A program whose records lack the
key gives nothing to read.
Layer: device ops.  Source: the job's ``backup.pump`` span."""

from benchmark.harness.jobclocks import share_pct


def read(window):
    return share_pct(window, "index_upload_s", "writer_life_s")
