"""The sharded lookup's share of its roofline (``jit__lookup_sharded``,
``ops/cuckoo.py``: a table that lies by bucket range over several
devices, every shard asked for both of a digest's buckets).  Its bytes,
per shard and per trip: the padded digests in (32 B each) and two bucket
rows read a digest (2 x 32 B) — 96 x ``index_probe_padded`` x
``index_table_shards`` over a job's record.  The slice's bytes at the
window's mean rate, over the program's busy seconds:
``harness/indexroof.py``.  Silent where the table lies on one device
(the program does not run) and on a program whose records lack
``index_table_shards``.
Layer: device ops.  Source: the device trace and the jobs' records."""

from benchmark.harness.indexroof import roofline_pct

PROGRAM = "jit__lookup_sharded"
KEYS = ("index_probe_padded", "index_table_shards", "writer_life_s")


def lookup_bytes(record: dict) -> int:
    return 96 * record["index_probe_padded"] * record["index_table_shards"]


def read(window):
    return roofline_pct(window, PROGRAM, lookup_bytes, KEYS)
