"""Median, over every job the window enqueued, of the harness's own
clock from ``enqueue_backup`` to ``jobs.wait`` returning (jobs in flight
at the window's end publish in the drain and count with their whole
time).  Layer: job queue.  Nothing published: nothing to read."""

from benchmark.harness.window import median_or_none, publish_seconds


def read(window):
    return median_or_none(publish_seconds(window.loop.jobs))
