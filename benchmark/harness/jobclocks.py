"""Arithmetic of the readers that take a session's own clocks from the
program's job records: one closed ``backup.pump`` span a backup job
(``pbs_plus_tpu.utils.trace.job_records()``, a table the program keeps
apart from its span ring), carrying the job's row id, the writer
thread's life by state (``writer_pump_wait_s`` … ``writer_other_s``,
``writer_cpu_s``, ``writer_life_s``), the pump's waits
(``pump_rpc_wait_s``, ``pump_put_wait_s``, ``pump_life_s``) and the
event loop thread's CPU clock at the pump's two ends (``loop_cpu0``,
``loop_cpu1``); docs/observability.md "The session's clocks".  The
readers run in the benchmark's own process after the window, and find
the window's jobs by their ids in ``window.loop.jobs``, those that
published in the drain included, as ``job_publish_s`` does.  A program
that keeps no such table or no such key — a parent commit from before
them — gives nothing to read: None, never an error."""

from __future__ import annotations


def records(window) -> list[dict]:
    """The attrs, with ``start`` and ``end`` on the wall clock, of the
    job records of the window's jobs."""
    from pbs_plus_tpu.utils import trace
    table = getattr(trace, "job_records", None)
    jobs = getattr(window.loop, "jobs", None)
    if table is None or not jobs:
        return []
    ids = {j.job_id for j in jobs}
    out = []
    for r in table():
        attrs = r.get("attrs") or {}
        if attrs.get("job") in ids:
            out.append(dict(attrs, start=r["start"],
                            end=r["start"] + r["dur_s"]))
    return out


def share_pct(window, part: str, whole: str) -> float | None:
    """100 * (sum of ``part``) / (sum of ``whole``) over the window's
    jobs: a state's share of the threads' lives."""
    recs = records(window)
    if not recs or any(part not in r or whole not in r for r in recs):
        return None
    total = sum(r[whole] for r in recs)
    if total <= 0:
        return None
    return 100.0 * sum(r[part] for r in recs) / total


def loop_cpu_pct(window) -> float | None:
    """The loop thread's CPU over the union of the window's jobs: from
    the earliest pump's start to the latest one's end."""
    recs = [r for r in records(window)
            if "loop_cpu0" in r and "loop_cpu1" in r]
    if not recs:
        return None
    wall = max(r["end"] for r in recs) - min(r["start"] for r in recs)
    if wall <= 0:
        return None
    cpu = max(r["loop_cpu1"] for r in recs) - min(r["loop_cpu0"]
                                                   for r in recs)
    return 100.0 * cpu / wall
