"""Mean wait of a hash batch from submit to the start of its dispatch:
delta ``sha_wait_s`` / delta ``sha_streams`` of ``get_feeder().stats``:
the part of ``hash_wait_ms`` that is queueing behind the feeder
thread's other work, not the dispatch itself.
Layer: cross-session batcher.  Source: the program's own counters."""

from benchmark.harness.phases import mean_ms


def read(window):
    return mean_ms(window, "feeder", "sha_wait_s", "sha_streams")
