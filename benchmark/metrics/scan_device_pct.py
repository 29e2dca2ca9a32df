"""Share of the window between the candidate-scan program's launch and
``block_until_ready`` of its mask (``device_s`` of
``rolling_hash.stats``): the program's time on the device plus a launch,
what of the rows' transfer was still in flight, and a host wake-up per
dispatch, over the whole window.
Layer: device ops.  Source: the program's own counters."""

from benchmark.harness.phases import share_pct


def read(window):
    return share_pct(window, ("scan", "device_s"))
