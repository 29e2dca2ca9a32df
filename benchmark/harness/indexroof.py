"""Arithmetic of the readers of the dedup index's programs' rooflines
(``index_lookup_roofline``, ``index_update_roofline``): the least time
one chip could take for a program's bytes in the traced slice over the
program's busy seconds in it.

The trace holds the slice's device time, and the job records hold the
window's bytes: each reader's own function reckons a program's bytes
from a record.  The slice's bytes are taken at the window's mean rate —
the records' bytes over their writers' lives, times the slice's seconds
— on the assumption that the slice's mix of probes is the window's.  In
``index-100tib.serial-x4`` the slice is the first volume's start, where
every flush inserts, so the update's bytes are under-counted there and
its share reads low.  Busy seconds are the program's events summed over
the device planes (``tracereduce.reduce``'s ``device_ops``) and bytes
are summed over the shards, so the share is a chip's.  A program whose
records lack the keys, a slice where the program did not run or an
untraced run gives nothing to read."""

from __future__ import annotations

from benchmark.harness import tracereduce
from benchmark.harness.jobclocks import records


def roofline_pct(window, program: str, bytes_of, keys) -> float | None:
    """100 x (slice bytes / one chip's peak) / ``program``'s busy seconds;
    ``bytes_of(record)`` a record's bytes, ``keys`` what it reads."""
    busy = sum(s for name, s in (window.trace or {}).get("device_ops", [])
               if name == program)
    recs = records(window)
    if busy <= 0 or not recs \
            or any(k not in r for r in recs for k in keys):
        return None
    life = sum(r["writer_life_s"] for r in recs)
    if life <= 0:
        return None
    slice_bytes = sum(bytes_of(r) for r in recs) / life \
        * window.trace["window_s"]
    peak = tracereduce.peak_bytes_per_s(tracereduce.load_peaks(),
                                        window.device_kind)
    return 100.0 * slice_bytes / peak / busy
