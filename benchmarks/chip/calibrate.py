#!/usr/bin/env python3
"""Readings for a cell's ``logit_gap`` limit, in one process on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 1,2,... --seconds <s> [--controls fp8] [--out <file>]

For each seed it makes one run of the cell as ``run.py`` would (a short
window at the cell's own load: enough for the mix's sessions that a run's
check samples), and prints one JSON line: the program's widest gap and the
other compared numbers, and, for each control, the widest gap of the
reference computed in that precision (``fp8``: matmul inputs rounded to
float8_e4m3fn) in the program's place.  The lower reading of the limit is
the largest program gap over the seeds, the upper the smallest control gap.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402
import spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    controls = [c for c in args.controls.split(",") if c]
    run.use_compile_cache()
    out = open(args.out, "a") if args.out else None
    for seed in (int(s) for s in args.seeds.split(",")):
        run.T_START = time.perf_counter()
        r = run.run_cell(cell, seed, args.seconds, False, controls=controls)
        line = json.dumps({"workload": cell.name, "seed": seed,
                           "checks": r["checks"],
                           "controls": r.get("controls", {}),
                           "metrics": r["metrics"], "device": r["device"]})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
