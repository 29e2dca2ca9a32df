"""1 - (union of device-program intervals / traced slice): how idle the
device is while streams are scanned for cuts.  The slice never holds a
hash dispatch (run.py): this is not the window's idle share.
Layer: device.  Source: the device trace."""


def read(window):
    return (window.trace or {}).get("scan_phase_idle_pct")
