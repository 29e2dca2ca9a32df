"""Share of the window the feeder's thread spent packing candidate-scan
rows: zeroing the ``[rows, segment]`` staging array and copying the
rows in (``pack_s`` of ``rolling_hash.stats``, timed inside
``_dispatch_hits``).  Large: the padding and the staging array (ROADMAP
S5).
Layer: device ops.  Source: the program's own counters."""

from benchmark.harness.phases import share_pct


def read(window):
    return share_pct(window, ("scan", "pack_s"))
