"""Share of the window the feeder's thread spent copying for the
candidate scan: the padded rows in, until ``jnp.asarray`` returns (the
host's part; no wait is taken, the transfer's tail is in
``scan_device_pct``), and the dense mask home (``h2d_s`` + ``d2h_s`` of
``rolling_hash.stats``).  Large, with ``scan_unpack_pct``: the dense
mask (ROADMAP S4).
Layer: device ops.  Source: the program's own counters."""

from benchmark.harness.phases import share_pct


def read(window):
    return share_pct(window, ("scan", "h2d_s"), ("scan", "d2h_s"))
