"""The sharded table update's share of its roofline
(``jit__scatter_sharded``, ``ops/cuckoo.py``: the changed buckets of a
table that lies by bucket range over several devices, each written into
its own shard in place).  Its bytes, per bucket the program writes (a
shard's change padded to its class): the row and its index in (36 B)
and the row written (32 B).  The record counts what went in as
``index_table_upload_bytes``, whole copies included; less those
(``index_table_uploads`` x ``index_table_bytes``) it is 36 B a bucket
written, so a record's bytes are 68/36 of its delta bytes.  The slice's
bytes at the window's mean rate, over the program's busy seconds:
``harness/indexroof.py``.  Silent where the table lies on one device
(the program does not run) and on a program whose records lack
``index_table_shards``.
Layer: device ops.  Source: the device trace and the jobs' records."""

from benchmark.harness.indexroof import roofline_pct

PROGRAM = "jit__scatter_sharded"
KEYS = ("index_table_upload_bytes", "index_table_uploads",
        "index_table_bytes", "index_table_shards", "writer_life_s")


def update_bytes(record: dict) -> float:
    sent = record["index_table_upload_bytes"] \
        - record["index_table_uploads"] * record["index_table_bytes"]
    return sent * 68 / 36


def read(window):
    return roofline_pct(window, PROGRAM, update_bytes, KEYS)
