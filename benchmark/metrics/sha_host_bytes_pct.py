"""Of the bytes hashed in the window, the share the host engine took:
delta ``host_bytes`` / (delta ``host_bytes`` + delta ``bytes``) of
``sha256.stats`` (``bytes`` is what went to the device program).  100:
no hash batch went to the device, whose program loses to the host's
SHA-256 on every batch shape so far; a kernel PR that wins batches back
lowers it.
Layer: device ops.  Source: the program's own counters."""


def read(window):
    sha = window.counters.get("sha", {})
    if "host_bytes" not in sha or "bytes" not in sha:
        return None
    hashed = sha["host_bytes"] + sha["bytes"]
    return 100.0 * sha["host_bytes"] / hashed if hashed else None
