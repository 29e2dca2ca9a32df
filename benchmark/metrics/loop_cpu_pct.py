"""CPU the event loop's thread used, as a share of the wall time its
pumps ran: 100 x (max ``loop_cpu1`` - min ``loop_cpu0``) / (latest end
- earliest start) over the job records of the window's jobs —
``time.thread_time()`` read on the loop's thread at each pump's start
and after its writer's join.  In the cells the agents share the
server's loop, so it is the thread's CPU, agents and TLS included; near
100 the one loop is the ceiling.
Layer: job queue.  Source: the job's ``backup.pump`` span."""

from benchmark.harness.jobclocks import loop_cpu_pct


def read(window):
    return loop_cpu_pct(window)
