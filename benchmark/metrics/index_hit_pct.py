"""Of the digests the backup writers' batched index probes asked, the
share confirmed present: 100 x sum ``index_hits`` / sum
``index_probe_digests`` over the job records of the window's jobs
(``DedupIndex.probe_batch``: a filter positive counts once the exact
tier has confirmed it).  It says what traffic was measured — 0 in a
volume the datastore has never seen, about 50 in one half of whose files
it holds — and has no honest "better": a known chunk is cheaper than a
new one, but which arrive is the traffic's, not the program's.  Declared
"higher" because the manifest must say one.  A program whose records
lack the keys gives nothing to read.
Layer: device ops.  Source: the job's ``backup.pump`` span."""

from benchmark.harness.jobclocks import share_pct


def read(window):
    return share_pct(window, "index_hits", "index_probe_digests")
