"""Mean wait of a candidate-scan request from submit to the start of its
dispatch: delta ``mask_wait_s`` / delta ``mask_rows`` of
``get_feeder().stats``.  The wait is for the one feeder thread, which
scans and hashes share.
Layer: cross-session batcher.  Source: the program's own counters."""

from benchmark.harness.phases import mean_ms


def read(window):
    return mean_ms(window, "feeder", "mask_wait_s", "mask_rows")
