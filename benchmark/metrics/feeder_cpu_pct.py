"""CPU the device batcher's thread used, as a share of the window: delta
``cpu_s`` of ``get_feeder().stats`` — ``time.thread_time()`` of the
``device-feeder`` thread, brought up to date at the end of every round
(``DeviceFeeder._run``) — over the window's seconds.  Beside
``feeder_scan_busy_pct`` it says how much of a dispatch the thread
computes (packing rows, decoding the answer) and how much of it the
thread is blocked (the device, a transfer, the interpreter lock).
Layer: cross-session batcher.  Source: the program's own counters."""

from benchmark.harness.phases import share_pct


def read(window):
    return share_pct(window, ("feeder", "cpu_s"))
