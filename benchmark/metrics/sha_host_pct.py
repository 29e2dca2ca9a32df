"""Seconds the writers' threads spent inside the host SHA-256 engine
(``host_s`` of ``sha256.stats``: the hashlib calls of every hash batch
that ``ops/sha256.py`` hashed on the host), summed over threads, as a
share of the window's seconds; eight writers can read up to 800.
Large: the host's cores are the hash path's cost, and a device kernel
that beats them (ROADMAP S6) would pay.
Layer: device ops.  Source: the program's own counters."""

from benchmark.harness.phases import share_pct


def read(window):
    return share_pct(window, ("sha", "host_s"))
