"""Seconds writers stood at the device batcher for their candidate
scans, as a share of the window: delta ``mask_wait_s`` (every scan
request's wait from submit to the start of its dispatch, the linger
included) plus delta ``mask_busy_s`` (the feeder thread's clock inside
``_dispatch_masks``) of ``get_feeder().stats``, over the window's
seconds.  A request's stay in ``candidate_hits`` is its wait and then
its dispatch, so with one row a round — every cell since the chunker
sends full segments — the sum is the stays' sum to within a wake-up
(0.8 % apart where both were counted); a round of n rows adds its busy
time once and not n times, so there the sum reads low.  With one
session it is the share of the window the one writer stood at the
device; with eight it can pass 100.
Layer: stream writer.  Source: the program's own counters."""

from benchmark.harness.phases import share_pct


def read(window):
    return share_pct(window, ("feeder", "mask_wait_s"),
                     ("feeder", "mask_busy_s"))
