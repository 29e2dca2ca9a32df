#!/usr/bin/env python3
"""The host's bar for the candidate scan (ROADMAP S4, first bullet): the
native scalar and vector scanners on one thread against the device
round trip, over full 4 MiB rows at upstream's 4 MiB average.

    python3 tools/scan_crossover.py [rows ...]    # through the chip tool

Host: ``chunker.native.candidates(threads=1)`` (the sequential rolling
scan) and ``candidates_vec`` (the doubling passes on CPU vectors), one
row at a time with its 63 bytes of history, MiB a second of one thread.
Device: ``batched_candidate_hits`` over 1, 4 and 16 such rows (or the
row counts given) from host memory to host positions, as the feeder's
one thread pays it — MiB a second of that thread, and the five phases of
a trip in ms.  Every engine's positions are compared: they have to be
the same.  One JSON line on stdout, the same in
``chiprun_out/scan_crossover.json``.  It runs in no cell.  Without a TPU
it says why and exits 2: a CPU run gives no rate.
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

MIB = 1 << 20
ROW = 4 * MIB
REPEATS = 15
ROWS = (1, 4, 16)


def _rate(nbytes: int, seconds: list) -> float:
    return nbytes / MIB / statistics.median(seconds)


def host_rates(rows: list, tails: list, params) -> tuple[dict, list]:
    """MiB/s of one thread for each native scanner the library has, and
    the scalar scanner's 0-based positions per row."""
    from pbs_plus_tpu.chunker import native
    engines = {"scalar": lambda r, t: native.candidates(
        r, params, prefix=t.tobytes(), threads=1)}
    if native.vec_available():
        engines["vector"] = lambda r, t: native.candidates_vec(
            r, params, prefix=t.tobytes())
    out, want = {"vector_impl": native.vec_impl()}, None
    for name, scan in engines.items():
        took = []
        for _ in range(REPEATS):
            t0 = time.perf_counter()
            ends = [scan(r, t) for r, t in zip(rows, tails)]
            took.append(time.perf_counter() - t0)
        got = [e - 1 for e in ends]     # end offsets in the row -> positions
        if want is None:
            want = got
        out[name] = {"mib_per_s": _rate(len(rows) * ROW, took),
                     "same_positions": all(
                         np.array_equal(a, b) for a, b in zip(got, want))}
    return out, want


def device_rate(rows: list, tails: list, params, want: list) -> dict:
    from pbs_plus_tpu.ops import rolling_hash as rh
    from pbs_plus_tpu.utils import trace
    tables = rh.device_tables(params)
    rh.batched_candidate_hits(rows, tails, tables, params)   # compiles
    took = []
    before = dict(rh.stats)
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        got = rh.batched_candidate_hits(rows, tails, tables, params)
        took.append(time.perf_counter() - t0)
    spent = {k: rh.stats[k] - before[k] for k in rh.stats}
    return {"rows": len(rows), "padded_rows": spent["padded_rows"] // REPEATS,
            "mib_per_s": _rate(len(rows) * ROW, took),
            "trip_ms": 1e3 * statistics.median(took),
            "phase_ms": {p: 1e3 * spent[p + "_s"] / REPEATS
                         for p in trace.PHASES},
            "home_bytes_per_padded_byte":
                spent["home_bytes"] / spent["padded_bytes"],
            "same_positions": all(np.array_equal(a, b)
                                  for a, b in zip(got, want))}


def main() -> int:
    counts = [int(a) for a in sys.argv[1:]] or ROWS
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"scan_crossover: jax found no TPU (platform "
              f"{devices[0].platform!r}); the crossover is measured on "
              "the chip and its host", file=sys.stderr)
        return 2
    # a library built on another machine must not be loaded on this one
    from chip_smoke import rebuild_native
    rebuild_native()
    from pbs_plus_tpu.chunker import ChunkerParams
    from pbs_plus_tpu.utils import jaxenv
    jaxenv.configure_compile_cache()
    params = ChunkerParams(avg_size=4 * MIB)
    rng = np.random.default_rng(33)
    rows = [rng.integers(0, 256, ROW, dtype=np.uint8)
            for _ in range(max(counts))]
    tails = [rng.integers(0, 256, 63, dtype=np.uint8) for _ in rows]
    host, want = host_rates(rows, tails, params)
    device = [device_rate(rows[:n], tails[:n], params, want[:n])
              for n in counts]
    result = {"device_kind": devices[0].device_kind,
              "host_cores": os.cpu_count(), "row_bytes": ROW,
              "hits": sum(len(w) for w in want), "host": host,
              "device": device}
    line = json.dumps(result)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "scan_crossover.json"), "w",
              encoding="utf-8") as f:
        f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
