"""Share of the window the feeder's thread spent in ``np.nonzero`` over
the rows of the dense mask (``unpack_s`` of ``rolling_hash.stats``).
Layer: device ops.  Source: the program's own counters."""

from benchmark.harness.phases import share_pct


def read(window):
    return share_pct(window, ("scan", "unpack_s"))
