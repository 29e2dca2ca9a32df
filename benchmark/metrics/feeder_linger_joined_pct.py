"""Of the feeder's rounds that waited out a linger, the share in which
a second request had arrived by the time the wait ended: 100 x delta
``linger_joined`` / delta ``linger_rounds`` of ``get_feeder().stats``
(``DeviceFeeder._run``).  Near 0: the linger widens nothing and only
delays the one request it held.  A program without the two counters
gives nothing to read.
Layer: cross-session batcher.  Source: the program's own counters."""


def read(window):
    f = window.counters.get("feeder", {})
    if "linger_joined" not in f or not f.get("linger_rounds"):
        return None
    return 100.0 * f["linger_joined"] / f["linger_rounds"]
