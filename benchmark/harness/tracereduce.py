"""From a profiler trace to numbers: device busy and idle, per-program
totals, who the host was when the device sat idle, and the share of the
chip's peak that the window's bytes amount to.

Two steps, so the second can be checked on a recorded input
(``tests/bench_cells/data/``): ``load_events`` turns an ``.xplane.pb``
into plain lists of intervals, ``reduce`` turns those into metrics.
Times are seconds; intervals are ``(start, end)``.
"""

from __future__ import annotations

import glob
import json
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
# the line of a device plane whose events are whole programs, named after
# the jitted function.  The line of single operations ("XLA Ops") is not
# read: SHA-256's loop writes 50 events a step, millions a second, and a
# program's interval differs from the union of its operations by under
# 2 % (my chip run, PR 23)
MODULES_LINE = "XLA Modules"
# host annotations the harness's wrappers write (run.py, traced runs),
# most specific first: a gap is charged to the first that covers it
HOST_LABELS = ("bench.feeder.dispatch_masks", "bench.feeder.dispatch_sha",
               "bench.chunker.feed")
NO_LABEL = "host:no_feed_in_flight"


class PeakUnknown(KeyError):
    """The device is not in the table of peaks: an error, not a default."""


def load_peaks() -> dict:
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def peak_bytes_per_s(peaks: dict, device_kind: str) -> float:
    try:
        return float(peaks["devices"][device_kind]["hbm_bytes_per_s"])
    except KeyError:
        raise PeakUnknown(f"no peak for device kind {device_kind!r} in "
                          "benchmark/harness/peaks.json") from None


# -- interval arithmetic -----------------------------------------------------

def union(intervals) -> list[tuple[float, float]]:
    """Merge overlapping, nested and touching intervals."""
    out: list[list[float]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a, b) -> list[tuple[float, float]]:
    """Of two merged, sorted lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a, b) -> list[tuple[float, float]]:
    """``a`` minus ``b``, both merged and sorted."""
    out = []
    j = 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, start: float, end: float) -> list[tuple[float, float]]:
    return subtract([(start, end)], busy)


# -- xplane -> events ----------------------------------------------------------

def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_events(xplane_path: str) -> dict:
    """Plain intervals from the profiler's file: per device plane the
    programs that ran (name, start, end), and the harness's own host
    annotations.  Seconds from the earliest event kept."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    devices: dict[str, dict[str, list]] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    lines.setdefault(line.name, []).extend(
                        [ev.name, ev.start_ns, ev.start_ns + ev.duration_ns]
                        for ev in line.events)
        else:
            for line in plane.lines:
                host.extend([ev.name, ev.start_ns,
                             ev.start_ns + ev.duration_ns]
                            for ev in line.events if ev.name in HOST_LABELS)
    stamps = [t for lines in devices.values() for evs in lines.values()
              for ev in evs for t in ev[1:]] + [t for ev in host
                                                for t in ev[1:]]
    base = min(stamps) if stamps else 0

    def rel(evs):
        return [[n, (s - base) / 1e9, (e - base) / 1e9] for n, s, e in evs]
    return {"devices": {p: {ln: rel(evs) for ln, evs in lines.items()}
                        for p, lines in devices.items()},
            "host": rel(host)}


# -- events -> metrics ---------------------------------------------------------

def program_name(event_name: str) -> str:
    """``jit__candidate_mask_impl(1234567)`` -> ``jit__candidate_mask_impl``:
    the trace appends a fingerprint that changes with every build."""
    return event_name.split("(", 1)[0]


def top(totals: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(totals.items(),
                                      key=lambda kv: -kv[1])[:n]]


def reduce(events: dict, *, window_s: float, fed_bytes: int,
           device_kind: str, peaks: dict) -> dict:
    """Busy seconds (the union of the intervals in which a program ran on
    the device, averaged over the device planes), idle share of the
    traced slice, per-program totals, idle gaps by what the host was
    doing, and the slice's bytes against the chip's peak bandwidth.  The
    names say what the slice holds (run.py): candidate scans, never the
    hash program."""
    peak = peak_bytes_per_s(peaks, device_kind)
    if window_s <= 0:
        raise ValueError("the traced window is empty")
    planes = events["devices"]
    busy_by_plane = {}
    for name, lines in planes.items():
        busy_by_plane[name] = union(
            (s, e) for _, s, e in lines.get(MODULES_LINE, []))
    if not busy_by_plane or not any(busy_by_plane.values()):
        return {"busy_s": 0.0, "window_s": window_s}
    busy_s = sum(total(b) for b in busy_by_plane.values()) \
        / len(busy_by_plane)

    programs: dict[str, float] = {}
    for lines in planes.values():
        for name, s, e in lines.get(MODULES_LINE, []):
            key = program_name(name)
            programs[key] = programs.get(key, 0.0) + (e - s)

    # idle gaps of the first device plane, charged to the host activity
    # that covers them, most specific first
    first = sorted(busy_by_plane)[0]
    busy = busy_by_plane[first]
    stamps = [t for _, s, e in events["host"] for t in (s, e)] + \
        [t for s, e in busy for t in (s, e)]
    idle = gaps(busy, min(stamps), max(stamps))
    charged: dict[str, float] = {}
    for label in HOST_LABELS:
        cover = union((s, e) for n, s, e in events["host"] if n == label)
        part = intersect(idle, cover)
        if part:
            charged[label] = total(part)
        idle = subtract(idle, cover)
    if idle:
        charged[NO_LABEL] = total(idle)

    out = {"busy_s": busy_s, "window_s": window_s,
           "scan_phase_idle_pct": 100.0 * (1.0 - busy_s / window_s),
           "device_ops": top(programs), "idle_gaps": top(charged),
           "fed_bytes": fed_bytes}
    if fed_bytes > 0 and busy_s > 0:
        out["scan_roofline"] = 100.0 * (fed_bytes / peak) / busy_s
    return out
