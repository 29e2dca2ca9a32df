"""Bytes of trees enqueued by the window's end over bytes built: 100
means the backlog drained and the cell needs resizing.
Layer: load generator.  Source: the harness's own count."""

from benchmark.harness.window import backlog_used_pct


def read(window):
    return backlog_used_pct(window.loop.enqueued_bytes,
                            window.loop.built_bytes)
