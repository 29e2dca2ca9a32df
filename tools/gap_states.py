#!/usr/bin/env python3
"""How the device's idle seconds in one traced slice divide among the
backup writers' states (PERF.md section 5; docs/observability.md "The
session's clocks").

    python3 tools/gap_states.py <file.xplane.pb>

The benchmark's reduction (``benchmark/harness/tracereduce.py``) charges
every idle gap of the first device plane to the harness's own host
annotations and calls what none of them covers
``host:no_feed_in_flight``: no scan was dispatching and no writer was
inside ``TpuChunker.feed``.  A writer's thread enters each state of its
session clock as a profiler annotation ``writer.<state>``
(``trace.state``), on the same clock as the device's line, so the same
gaps can be cut once more: by what the writers were doing.  This reads
the profiler's file with nothing but jax, computes the gaps as the
reduction does, and gives for every ``writer.*`` annotation the seconds
of ``host:no_feed_in_flight`` (and of all idle time) it covers, the rest
being the writers' residue (``other_s``: no annotation) or no writer
alive.  With several writers at once the states' covers overlap and may
sum past the gap.  One JSON object on stdout.  The benchmark's harness
removes its work directory with the trace in it: keep the file with a
wrapper of your own around ``benchmark/run.py`` (PERF.md says how).
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import tracereduce as tr  # noqa: E402

WRITER_PREFIX = "writer."


def load(xplane_path: str) -> tuple[list, dict, dict]:
    """The first device plane's busy intervals, the harness's host
    annotations and the writers' by name; seconds."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    planes: dict[str, list] = {}
    bench: dict[str, list] = {}
    writer: dict[str, list] = {}
    for plane in data.planes:
        for line in plane.lines:
            if plane.name.startswith(tr.DEVICE_PLANE_PREFIX):
                if line.name == tr.MODULES_LINE:
                    planes.setdefault(plane.name, []).extend(
                        (ev.start_ns / 1e9,
                         (ev.start_ns + ev.duration_ns) / 1e9)
                        for ev in line.events)
                continue
            for ev in line.events:
                into = bench if ev.name in tr.HOST_LABELS else \
                    writer if ev.name.startswith(WRITER_PREFIX) else None
                if into is not None:
                    into.setdefault(ev.name, []).append(
                        (ev.start_ns / 1e9,
                         (ev.start_ns + ev.duration_ns) / 1e9))
    if not planes:
        raise SystemExit(f"{xplane_path}: no device plane with programs")
    return tr.union(planes[sorted(planes)[0]]), bench, writer


def divide(xplane_path: str) -> dict:
    busy, bench, writer = load(xplane_path)
    stamps = [t for evs in bench.values() for iv in evs for t in iv] + \
        [t for iv in busy for t in iv]
    idle = tr.gaps(busy, min(stamps), max(stamps))
    unlabelled = idle
    for label in tr.HOST_LABELS:
        unlabelled = tr.subtract(unlabelled, tr.union(bench.get(label, [])))
    covers = {name: tr.union(evs) for name, evs in writer.items()}
    every = tr.union(iv for cover in covers.values() for iv in cover)

    def cut(gaps_: list) -> dict:
        out = {name: tr.total(tr.intersect(gaps_, cover))
               for name, cover in sorted(covers.items())}
        out["no_writer_state"] = tr.total(tr.subtract(gaps_, every))
        return out
    return {"slice_s": max(stamps) - min(stamps), "busy_s": tr.total(busy),
            "idle_s": tr.total(idle),
            tr.NO_LABEL: tr.total(unlabelled),
            "of_" + tr.NO_LABEL: cut(unlabelled), "of_idle": cut(idle),
            "writer_events": {n: len(evs) for n, evs in
                              sorted(writer.items())}}


if __name__ == "__main__":
    if len(sys.argv) != 2:
        raise SystemExit(__doc__)
    print(json.dumps(divide(sys.argv[1])))
