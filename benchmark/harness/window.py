"""What a measured window is, and the arithmetic of its rate.

``Window`` is everything a per-layer metric reader may look at: the
closed loop's record, the program's counters as deltas over the window,
the ``ingest.sha`` spans that closed inside it, and (traced runs) the
harness's own byte count at the chunker's feed, its clock around the
feeder's dispatches, and the reduction of the profiler's trace.  Readers live in
``benchmark/metrics/<name>.py`` and are found by file name.
"""

from __future__ import annotations

import importlib
import statistics
from dataclasses import dataclass, field

MIB = 1 << 20


def interval_seconds(t0: float, t_end: float) -> float:
    if t_end <= t0:
        raise ValueError(f"the interval is empty: t0={t0}, end={t_end}")
    return t_end - t0


def ingest_mib_s(committed_bytes: int, t0: float, t_end: float) -> float:
    """All bytes the chunk store took in the interval (scanned, cut,
    hashed, stored: ``loadgen.CommitLog``) over all of its seconds: a
    stall inside the window lowers the rate, and bytes that only wait in
    a queue at its end do not raise it."""
    return committed_bytes / interval_seconds(t0, t_end) / MIB


def backlog_used_pct(enqueued_bytes: int, built_bytes: int) -> float:
    if built_bytes <= 0:
        raise ValueError("no backlog was built")
    return 100.0 * enqueued_bytes / built_bytes


def publish_seconds(jobs) -> list[float]:
    """Enqueue-to-publish times, by the harness's own clock, of every job
    the window enqueued that published — those that published in the
    drain too: a closed loop's latencies are all due, and leaving out the
    jobs the window's end cut would keep only the short ones."""
    return [j.done - j.enqueued for j in jobs if j.status == "success"]


def published_inside(jobs, t0: float, t_end: float) -> int:
    return sum(1 for j in jobs
               if j.status == "success" and t0 <= j.done <= t_end)


def seconds_inside(spans, t0: float, t_end: float) -> float:
    """Of (start, end) spans on one thread, the seconds that lie in the
    interval."""
    return sum(max(0.0, min(e, t_end) - max(s, t0)) for s, e in spans)


def median_or_none(values: list[float]) -> float | None:
    return statistics.median(values) if values else None


@dataclass
class Window:
    seconds: float                      # the interval's length
    loop: object                        # loadgen.LoopResult
    counters: dict                      # deltas: feeder / scan / sha
    sha_spans: list = field(default_factory=list)   # dur_s of ingest.sha
    fed_bytes: int = 0                  # into TpuChunker.feed (traced runs)
    dispatch_s: dict = field(default_factory=dict)  # label -> feeder seconds
    trace: dict | None = None           # tracereduce.reduce(...) or None
    device_kind: str = ""


def read_metric(name: str, window: Window):
    """The metric's own reader: ``benchmark/metrics/<name>.py`` with a
    ``read(window)`` that returns a number, or None when it finds
    nothing to read (the harness then leaves the metric out)."""
    module = importlib.import_module(f"benchmark.metrics.{name}")
    return module.read(window)
