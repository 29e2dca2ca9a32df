"""Share of the backup writer threads' lives spent at the chunker:
100 x sum ``writer_cdc_s`` / sum ``writer_life_s`` over the job records
of the window's jobs.  The state brackets ``chunker.feed`` and
``finalize`` in ``pxar/transfer.py`` ``_ChunkedStream`` (the two clock
reads ``write`` already paid): the gather of a write into its scan
segment and, when the segment is full, the stand at the device — the
request's wait for its dispatch and the dispatch.  It is the writer's
own clock where ``scan_turnaround_pct`` is derived from the batcher's,
so it is not under it; what it holds above it is the gather and the
wake-up.
Layer: stream writer.  Source: the job's ``backup.pump`` span."""

from benchmark.harness.jobclocks import share_pct


def read(window):
    return share_pct(window, "writer_cdc_s", "writer_life_s")
