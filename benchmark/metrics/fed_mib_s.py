"""Bytes the streams carried into ``TpuChunker.feed`` per second of
window, counted by the benchmark at the call: scanned, not yet hashed or
stored.  Steadier than the committed rate (chunks commit in batches of
16 MiB and more) and ahead of it by what waits to be hashed.
Layer: stream writer.  Source: the harness's own count."""

from benchmark.harness.window import MIB


def read(window):
    if not window.fed_bytes:
        return None
    return window.fed_bytes / window.seconds / MIB
