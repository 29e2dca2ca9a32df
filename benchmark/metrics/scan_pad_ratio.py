"""Bytes the candidate scan sent to the device over the bytes it was
asked to scan: delta ``padded_bytes`` / delta ``bytes`` of
``rolling_hash.stats``.  Layer: device ops."""


def read(window):
    s = window.counters["scan"]
    return s["padded_bytes"] / s["bytes"] if s["bytes"] else None
