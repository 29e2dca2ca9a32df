"""Share of the window the feeder's thread spent packing hash batches:
zeroing the 16 or 64 MiB staging buffer, copying the chunks in, the
buckets' ``starts``/``lens`` (``pack_s`` of ``sha256.stats``, timed
inside ``_hash_slab``).  Large: the zeroed staging buffer.
Layer: device ops.  Source: the program's own counters."""

from benchmark.harness.phases import share_pct


def read(window):
    return share_pct(window, ("sha", "pack_s"))
