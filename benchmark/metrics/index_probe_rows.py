"""Digests a batched index probe asks at once: sum
``index_probe_digests`` / sum ``index_probe_trips`` over the job records
of the window's jobs — one trip a hash batch's flush
(``DedupIndex._probe_arr``, tallied on the writer's thread).  4-5 at
4 MiB chunks, hundreds at 64 KiB: the width a device lookup has to be
worth its round trip at.  A program whose records lack the keys, or a
window without a probe, gives nothing to read.
Layer: device ops.  Source: the job's ``backup.pump`` span."""

from benchmark.harness.jobclocks import share_pct


def read(window):
    # the harness's ratio of two sums over the records, less its percent
    pct = share_pct(window, "index_probe_digests", "index_probe_trips")
    return None if pct is None else pct / 100.0
