"""Share of the backup pumps' lives suspended on the agent: 100 x sum
``pump_rpc_wait_s`` / sum ``pump_life_s`` over the job records of the
window's jobs.  Wall time of every awaited agentfs call of
``RemoteTreeBackup`` (``attr``, ``read_dir``, ``open_read``,
``read_at``, ``read_many``, ``close``): the agent's work, the wire, and
the event loop's turn-around on both sides of it.
Layer: job queue.  Source: the job's ``backup.pump`` span."""

from benchmark.harness.jobclocks import share_pct


def read(window):
    return share_pct(window, "pump_rpc_wait_s", "pump_life_s")
