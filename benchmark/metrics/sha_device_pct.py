"""Share of the window between the SHA-256 program's launch and
``block_until_ready`` of its digests (``device_s`` of
``sha256.stats``): the hash program's time on the device, which no
profiler trace can hold (run.py), over the whole window.  Near
``feeder_sha_busy_pct``: the kernel is the cost (ROADMAP S6).
Layer: device ops.  Source: the program's own counters."""

from benchmark.harness.phases import share_pct


def read(window):
    return share_pct(window, ("sha", "device_s"))
