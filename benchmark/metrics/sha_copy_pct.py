"""Share of the window the feeder's thread spent copying for SHA-256: the
staging buffer and the buckets' rows in, to ``block_until_ready``, and
the digests home (``h2d_s`` + ``d2h_s`` of ``sha256.stats``).
Layer: device ops.  Source: the program's own counters."""

from benchmark.harness.phases import share_pct


def read(window):
    return share_pct(window, ("sha", "h2d_s"), ("sha", "d2h_s"))
