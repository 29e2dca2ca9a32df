"""Share of the backup writer threads' lives in no named state: 100 x
sum ``writer_other_s`` / sum ``writer_life_s`` over the job records of
the window's jobs.  The residue of the partition — entry encoding, the
whole-file digest, the chunk buffer's copies, the meta stream, per-row
Python — reported so that the partition can be checked: with
``writer_pump_wait_pct``, ``writer_cdc_pct``, ``writer_store_pct``,
``writer_probe_pct`` and the hash and sketch states on the record
(``writer_sha_s``, ``writer_presketch_s``) it sums to 100.
Layer: stream writer.  Source: the job's ``backup.pump`` span."""

from benchmark.harness.jobclocks import share_pct


def read(window):
    return share_pct(window, "writer_other_s", "writer_life_s")
