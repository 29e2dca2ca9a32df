"""Share of the backup writer threads' lives spent probing the dedup
index: 100 x sum ``writer_probe_s`` / sum ``writer_life_s`` over the
job records of the window's jobs.  The state brackets the batched
membership probe of a hash batch's digests, the block of the
``ingest.probe`` span in ``_ChunkedStream._probe_known``.  Read in a
traced run it carries the profiler's weight: the probe's device round
trip is several times dearer while the profiler runs (PERF.md, PR 34).
Layer: stream writer.  Source: the job's ``backup.pump`` span."""

from benchmark.harness.jobclocks import share_pct


def read(window):
    return share_pct(window, "writer_probe_s", "writer_life_s")
