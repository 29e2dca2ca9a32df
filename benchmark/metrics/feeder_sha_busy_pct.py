"""Share of the window the feeder's thread spent inside a hash dispatch
(packing the staging buffer, copy in, the SHA-256 program, digests
home), by the benchmark's own clock around ``DeviceFeeder._dispatch_sha``,
over the whole window: the hash path's time, which no trace can hold
(run.py), and an upper bound on the device's.
Layer: cross-session batcher.  Source: the host's clock."""


def read(window):
    took = window.dispatch_s.get("bench.feeder.dispatch_sha")
    return 100.0 * took / window.seconds if took else None
