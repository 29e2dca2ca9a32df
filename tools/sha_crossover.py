#!/usr/bin/env python3
"""The two rates behind ``ops/sha256.py``'s choice of the host's
SHA-256 over the device program — the bar a kernel PR has to clear
before digests go back to the device (PERF.md section 6, PR 25).

    python3 tools/sha_crossover.py [rows ...]     # through the chip tool

Host: ``hashlib.sha256`` over 4 MiB buffers on 1 and on 8 threads, bytes
per second of one thread and of all.  Device: the ``device`` phase of
``device.sha`` spans over one length bucket of 8, 64, 512 and 4096
equal chunks (or the row counts given), at two chunk lengths each in the
64 MiB staging class — seconds per block step as the plain quotient
``device_s / trip blocks`` and as the slope between the two lengths (the
quotient less the launch).  One JSON line on stdout, the same in
``chiprun_out/sha_crossover.json``, with ``device_wins_from_lanes``: per
row class, the busy lanes from which the device program would draw
level with one host thread measured in this same process
(``host rate x seconds per step / 64``) — it wins where that is fewer
than the class has rows.  Without a TPU it says why and exits 2: a CPU
run gives no rate.
"""

from __future__ import annotations

import hashlib
import json
import os
import statistics
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

MIB = 1 << 20
HOST_BUFFERS = 64               # of 4 MiB, per thread and repeat
REPEATS = 5
ROWS = (8, 64, 512, 4096)
BATCH_MIB = (20, 60)            # both in the 64 MiB staging class


def host_rates(threads: int) -> dict:
    """Bytes per second of ``threads`` threads hashing at once: the
    median over REPEATS of one thread's own rate and of all together."""
    buf = os.urandom(4 * MIB)
    own, together = [], []
    for _ in range(REPEATS):
        took = [0.0] * threads
        start = threading.Barrier(threads + 1)

        def work(k: int) -> None:
            start.wait()
            t0 = time.perf_counter()
            for _ in range(HOST_BUFFERS):
                hashlib.sha256(buf).digest()
            took[k] = time.perf_counter() - t0
        pool = [threading.Thread(target=work, args=(k,))
                for k in range(threads)]
        for t in pool:
            t.start()
        start.wait()
        t0 = time.perf_counter()
        for t in pool:
            t.join()
        wall = time.perf_counter() - t0
        nbytes = HOST_BUFFERS * len(buf)
        own.append(statistics.median(nbytes / s for s in took))
        together.append(threads * nbytes / wall)
    return {"threads": threads,
            "one_thread_bytes_per_s": statistics.median(own),
            "all_threads_bytes_per_s": statistics.median(together)}


def device_steps(rows: int) -> dict:
    """Seconds per block step of one program of ``rows`` equal chunks."""
    import numpy as np

    from pbs_plus_tpu.ops import sha256 as sha
    from pbs_plus_tpu.utils import trace
    spans: list = []

    def keep(rec: dict) -> None:
        if rec["name"] == "device.sha":
            spans.append(rec["attrs"])
    points = []
    trace.subscribe(keep)
    try:
        for mib in BATCH_MIB:
            length = mib * MIB // rows
            chunks = [np.full(length, 7, dtype=np.uint8)] * rows
            sha.sha256_chunks_device(chunks)            # compiles, if it must
            del spans[:]
            for _ in range(REPEATS):
                sha.sha256_chunks_device(chunks)
            assert all(a["dispatches"] == 1 and a["rows"] == rows
                       for a in spans), spans
            points.append({"chunk_bytes": length,
                           "trip_blocks": (length + 8) // 64 + 1,
                           "lanes_busy": spans[0]["lanes_busy"],
                           "device_s": statistics.median(
                               a["device_s"] for a in spans),
                           "device_s_all": [a["device_s"] for a in spans]})
    finally:
        trace.unsubscribe(keep)
    a, b = points
    return {"rows": rows, "points": points,
            "s_per_block_step": b["device_s"] / b["trip_blocks"],
            "s_per_block_step_slope": (b["device_s"] - a["device_s"])
            / (b["trip_blocks"] - a["trip_blocks"])}


def main() -> int:
    rows = [int(a) for a in sys.argv[1:]] or ROWS
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"sha_crossover: jax found no TPU (platform "
              f"{devices[0].platform!r}); the crossover is measured on "
              "the chip and its host", file=sys.stderr)
        return 2
    from pbs_plus_tpu.utils import jaxenv
    jaxenv.configure_compile_cache()
    host = [host_rates(1), host_rates(8)]
    device = [device_steps(n) for n in rows]
    result = {"device_kind": devices[0].device_kind,
              "host_cores": os.cpu_count(), "host": host, "device": device,
              "device_wins_from_lanes": {
                  d["rows"]: host[1]["one_thread_bytes_per_s"]
                  * d["s_per_block_step"] / 64 for d in device}}
    line = json.dumps(result)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "sha_crossover.json"), "w",
              encoding="utf-8") as f:
        f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
