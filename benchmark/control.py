#!/usr/bin/env python3
"""The control, on the chip, at a cell's own size — never a measured run.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed one whole run of the cell (set-up, a short window at the
cell's own load, drain), compared twice over the same published
snapshots: with the plain reference, which has to read ``correct``, and
with the control in the reference's place — the reference with its last
doubling pass left out (a 32-byte window), the cheaper scan a later PR
might be tempted by — which has to read not correct.  Prints one line
per seed with both readings; exits 0 only if every sound reading is
correct and every control reading is not.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    from benchmark import run
    from benchmark.harness import loadgen, reference
    cell = loadgen.load_cell(args.workload)
    devices, why = run.look_for_chips(cell.chips)
    if devices is None:
        print(f"control: {why}", file=sys.stderr)
        return run.EXIT_NO_CHIP
    run.configure_cache()
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        work = tempfile.mkdtemp(prefix="bench-control-")
        try:
            result = asyncio.run(run.run_cell(
                cell, seed=seed, seconds=args.seconds, trace=False,
                work=work, devices=devices[:cell.chips],
                controls={"window32": reference.control_cuts}))
        finally:
            shutil.rmtree(work, ignore_errors=True)
        if result is None:
            print(json.dumps({"seed": seed, "void": True}), flush=True)
            ok = False
            continue
        control = result["controls"]["window32"]
        print(json.dumps({
            "seed": seed, "jobs": result["attempted"],
            "sound": {"correct": result["correct"],
                      **{k: v["value"] for k, v in
                         result["compared"].items()}},
            "control": {"correct": control["correct"],
                        **{k: v["value"] for k, v in
                           control["compared"].items()}}}), flush=True)
        ok = ok and result["correct"] and not control["correct"]
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
