"""Share of the backup pumps' lives suspended on the writer: 100 x sum
``pump_put_wait_s`` / sum ``pump_life_s`` over the job records of the
window's jobs.  Wall time of every hand-over to the writer's queues
(``_put``, the hop of ``_put_many``, a large file's ``fq.put``): each is
an executor hop even when the queue has room.  High: the writer is the
limit; low beside a high ``writer_pump_wait_pct``: the agent and the
event loop are.
Layer: job queue.  Source: the job's ``backup.pump`` span."""

from benchmark.harness.jobclocks import share_pct


def read(window):
    return share_pct(window, "pump_put_wait_s", "pump_life_s")
