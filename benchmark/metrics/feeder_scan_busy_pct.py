"""Share of the window the feeder's thread spent inside a candidate-scan
dispatch (packing rows, copy in, the scan program, mask home), by the
benchmark's own clock around ``DeviceFeeder._dispatch_masks``, over the
whole window.  Layer: cross-session batcher.  Source: the host's clock."""


def read(window):
    took = window.dispatch_s.get("bench.feeder.dispatch_masks")
    return 100.0 * took / window.seconds if took else None
