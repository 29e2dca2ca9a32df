#!/usr/bin/env python3
"""What it costs to bring the dedup index's device table up to date, at
a deployment's table (ROADMAP S7): the whole copy against the update of
a change of 64 … 65,536 buckets written into the resident table, and a
probe's trip with a clean table.

    python3 tools/table_update_cost.py [log2 buckets]    # on a TPU host

A ``CuckooIndex`` of 2^26 buckets (a 2 GiB table, ``index-at-size``'s)
with a million digests in it.  Whole: the first probe after a rebuild.
Delta: ``k`` fresh digests inserted, then a probe of 211 of them (a
64 KiB-chunk flush), repeated; a change that weighs over a quarter of a
smaller table goes whole, and says so.  For each, from the
``device.probe`` span the probe closes: ``upload_s`` (the table's
update, to the table on the device), the whole trip, and its ``h2d`` and
``device`` phases, medians in ms; and the programs' build time from
shapes.  Then the device's peak
memory (``memory_stats()["peak_bytes_in_use"]``): the table once where
every update is written in place.  Every probe's answer is compared with
``lookup_host`` over the mirror.  One JSON line on stdout.  It runs in
no cell.  Without a TPU it says why and exits 2: a CPU run gives no
time.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

PRELOAD = 1 << 20
ASKED = 211
REPEATS = 12
CHANGES = (64, 256, 1024, 4096, 16384, 65536)


def _digests(rng, n: int) -> list:
    raw = rng.bytes(32 * n)
    return [raw[i:i + 32] for i in range(0, len(raw), 32)]


def _ms(values: list) -> float:
    return 1e3 * statistics.median(values)


def measure(log2_buckets: int) -> dict:
    from pbs_plus_tpu.ops import cuckoo
    from pbs_plus_tpu.utils import trace
    rng = np.random.default_rng(37)
    index = cuckoo.CuckooIndex(n_buckets=1 << log2_buckets)
    index.insert_many(_digests(rng, PRELOAD))
    t0 = time.perf_counter()
    cuckoo.warm_lookups(index.n_buckets, (256,) + CHANGES).join()
    built_s = time.perf_counter() - t0
    spans: list = []
    trace.subscribe(spans.append)
    same = True

    def probe(digests: list) -> dict:
        nonlocal same
        arr = np.frombuffer(b"".join(digests), np.uint8).reshape(-1, 32)
        got = index.probe(arr)
        same &= bool(np.array_equal(got, cuckoo.lookup_host(index._table,
                                                            arr)))
        rec = [s for s in spans if s["name"] == "device.probe"][-1]
        return dict(rec["attrs"], dur_s=rec["dur_s"])

    asked = _digests(rng, ASKED)
    whole = []
    for _ in range(3):
        index._mark_whole()
        whole.append(probe(asked))
    clean = [probe(asked) for _ in range(REPEATS)]
    delta = {}
    for k in CHANGES:
        runs = []
        for _ in range(REPEATS):
            fresh = _digests(rng, k)
            index.insert_many(fresh)
            runs.append(probe(fresh[:ASKED]))
        delta[k] = {
            "went_whole": sum(r.get("table_uploads", 0) for r in runs),
            "buckets": statistics.median(r.get("table_delta_buckets", 0)
                                         for r in runs),
            "sent_bytes": statistics.median(r["table_upload_bytes"]
                                            for r in runs),
            "upload_ms": _ms([r["upload_s"] for r in runs]),
            "trip_ms": _ms([r["dur_s"] for r in runs]),
            "h2d_ms": _ms([r["h2d_s"] for r in runs]),
            "device_ms": _ms([r["device_s"] for r in runs]),
            "compiled": sum(r.get("compiled", 0) for r in runs)}
    trace.unsubscribe(spans.append)
    table = np.asarray(index.device_table())
    return {"buckets": index.n_buckets, "table_bytes": index._table.nbytes,
            "programs_built_s": built_s,
            "whole": {"upload_ms": _ms([r["upload_s"] for r in whole]),
                      "trip_ms": _ms([r["dur_s"] for r in whole])},
            "clean": {"trip_ms": _ms([r["dur_s"] for r in clean]),
                      "h2d_ms": _ms([r["h2d_s"] for r in clean]),
                      "device_ms": _ms([r["device_s"] for r in clean])},
            "delta": delta, "same_answers": same,
            "device_table_equals_mirror": bool(np.array_equal(
                table, index._table))}


def main() -> int:
    log2_buckets = int(sys.argv[1]) if len(sys.argv) > 1 else 26
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"table_update_cost: jax found no TPU (platform "
              f"{devices[0].platform!r}); the update is timed on the chip",
              file=sys.stderr)
        return 2
    from pbs_plus_tpu.utils import jaxenv
    jaxenv.configure_compile_cache()
    result = {"device_kind": devices[0].device_kind,
              "host_cores": os.cpu_count(), **measure(log2_buckets),
              "peak_bytes_in_use":
                  devices[0].memory_stats().get("peak_bytes_in_use")}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
