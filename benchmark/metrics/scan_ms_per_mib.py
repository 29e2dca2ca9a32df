"""What a candidate scan's round trip costs per real MiB, padding and
all: 1000 x delta (``pack_s`` + ``h2d_s`` + ``device_s`` + ``d2h_s`` +
``unpack_s``) / (delta ``bytes`` / 2^20) of ``rolling_hash.stats`` — the
five phase clocks of ``_dispatch_hits`` on the feeder's thread, over the
bytes the scans were asked for (``padded_bytes`` is what they sent).  A
round of several rows is packed to a row class (two rows as four, five
as sixteen), zeroed, copied in and brought home dense at the padded
size; beside a one-row cell's reading this says whether a joined round
at full width is cheaper or dearer than the rows alone.  A program
without the clocks, or a window without a scan, gives nothing to read.
Layer: device ops.  Source: the program's own counters."""

from benchmark.harness.window import MIB

PHASES = ("pack_s", "h2d_s", "device_s", "d2h_s", "unpack_s")


def read(window):
    s = window.counters.get("scan", {})
    if any(p not in s for p in PHASES) or not s.get("bytes"):
        return None
    return 1000.0 * sum(s[p] for p in PHASES) / (s["bytes"] / MIB)
