"""Rows per candidate-scan round of the cross-session batcher:
delta ``mask_rows`` / delta ``mask_dispatches`` of ``get_feeder().stats``.
Layer: cross-session batcher."""


def read(window):
    f = window.counters["feeder"]
    if not f["mask_dispatches"]:
        return None
    return f["mask_rows"] / f["mask_dispatches"]
