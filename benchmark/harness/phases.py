"""Arithmetic of the readers that take the program's own phase clocks
(``pack_s`` … ``unpack_s`` of ``rolling_hash.stats`` / ``sha256.stats``,
the waits of ``DeviceFeeder.stats``; docs/observability.md "Device round
trips") from ``Window.counters``.  A program that keeps no such counter
— a parent commit from before them — gives nothing to read: None, never
an error."""

from __future__ import annotations


def share_pct(window, *keys: tuple[str, str]) -> float | None:
    """100 * (sum of the counters' deltas) / the window's seconds; each
    key is (layer, counter), as in ``("scan", "pack_s")``."""
    values = [window.counters.get(layer, {}).get(name)
              for layer, name in keys]
    if None in values or not window.seconds:
        return None
    return 100.0 * sum(values) / window.seconds


def mean_ms(window, layer: str, total: str, count: str) -> float | None:
    """1000 * delta ``total`` seconds / delta ``count`` events."""
    c = window.counters.get(layer, {})
    if total not in c or not c.get(count):
        return None
    return 1000.0 * c[total] / c[count]
