"""Share of the window the feeder's thread spent lingering: woken by
exactly one request, waiting ``PBS_PLUS_FEEDER_LINGER_S`` for a second to
join its batch (``linger_s`` of ``get_feeder().stats``, written in
``DeviceFeeder._run``).  With one session nobody can join, and every
second of it is the writer's wait.  Read beside
``feeder_linger_joined_pct``: what the lingering bought.
Layer: cross-session batcher.  Source: the program's own counters."""

from benchmark.harness.phases import share_pct


def read(window):
    return share_pct(window, ("feeder", "linger_s"))
