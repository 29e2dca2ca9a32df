"""Writes of a stream carried by one row of a candidate scan: delta
``mask_feeds`` / delta ``mask_rows`` of ``get_feeder().stats``
(``DeviceFeeder._mask_hits``, where both are summed).  1: every write
is a device round trip of its own.  Large: the stream's chunker gathers
its writes into scan segments before it asks for a scan
(``models/dedup.py`` ``TpuChunker``).  A program without the counter
gives nothing to read.
Layer: stream writer.  Source: the program's own counters."""


def read(window):
    f = window.counters.get("feeder", {})
    if "mask_feeds" not in f or not f.get("mask_rows"):
        return None
    return f["mask_feeds"] / f["mask_rows"]
