"""Share of the window the feeder's thread spent idle, both queues
empty (``idle_s`` of ``get_feeder().stats``, written in
``DeviceFeeder._run``): no session had a scan to ask for, so the host
under the device — the writers' own work between round trips — is what
paces the window.
Layer: cross-session batcher.  Source: the program's own counters."""

from benchmark.harness.phases import share_pct


def read(window):
    return share_pct(window, ("feeder", "idle_s"))
