"""Least time the chip could take for the traced slice's bytes (each
read once, at the chip's peak HBM bandwidth) over the device's busy
time in that slice.  The slice is the window's start and ends before the
first hash dispatch (run.py), so the busy time is the candidate scan's;
the bytes are the benchmark's own count at ``TpuChunker.feed``.
Layer: device ops.  Source: the device trace."""


def read(window):
    return (window.trace or {}).get("scan_roofline")
