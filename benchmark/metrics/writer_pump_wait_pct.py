"""Share of the backup writer threads' lives spent waiting for the
pump: 100 x sum ``writer_pump_wait_s`` / sum ``writer_life_s`` over the
job records of the window's jobs.  The state is entered in
``server/backup_job.py`` ``_get_abortable`` — the writer's get on its
job's queue and on a large file's own block queue: it has nothing to
write.  High beside a low ``pump_put_wait_pct``: the agent and the
event loop are the limit, not the writer.
Layer: job queue.  Source: the job's ``backup.pump`` span."""

from benchmark.harness.jobclocks import share_pct


def read(window):
    return share_pct(window, "writer_pump_wait_s", "writer_life_s")
