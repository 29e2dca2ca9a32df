"""Of the scan rows the cross-session batcher sent to the device, the
share that went beside another request's: 100 x delta
``mask_rows_shared`` / delta ``mask_rows`` of ``get_feeder().stats``,
both summed in ``DeviceFeeder._mask_hits`` (the rows of every scan group
of two or more; a group retried alone counts its rows as alone).  Rows
a round says how wide the mean dispatch was; a mean of 1.5 is half the
rows alone or none, and this says which: the share of the scan work
the batching mechanism touched.  A program without the counter gives
nothing to read.
Layer: cross-session batcher.  Source: the program's own counters."""


def read(window):
    f = window.counters.get("feeder", {})
    if "mask_rows_shared" not in f or not f.get("mask_rows"):
        return None
    return 100.0 * f["mask_rows_shared"] / f["mask_rows"]
