"""Of the candidate scan's dispatches, the share whose rows were
sharded over the data mesh: 100 x delta ``mesh_dispatches`` / delta
``dispatches`` of ``rolling_hash.stats`` (``_dispatch_hits``: every
dispatch of two or more rows on a host with more than one device).  The
rest ran on one device, the others idle.
Layer: device ops.  Source: the program's own counters."""


def read(window):
    s = window.counters.get("scan", {})
    if "mesh_dispatches" not in s or not s.get("dispatches"):
        return None
    return 100.0 * s["mesh_dispatches"] / s["dispatches"]
