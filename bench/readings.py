"""Read the numbers that decide ``correct`` over many seeds in one process,
with the program sound or with a fault of ``faults.py`` planted: the
readings the limits are set from.

    python bench/readings.py --workload <name> --seeds 1,2,3 --seconds 20 [--fault tail]

Each seed runs the cell as ``run.py`` does, without a trace, and prints one
JSON line: the seed, ``correct`` and the compared numbers.  The benchmark's
own runs never plant a fault.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import tempfile
import time

import run  # puts the benchmark's modules and the program on the path

import faults  # noqa: E402
import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    args = ap.parse_args(argv)
    try:
        cell = harness.load_cell(args.workload)
        devices = run.check_device(cell.chips)
        peaks = harness.device_peaks(devices[0].device_kind)
    except harness.Refused as e:
        print(f"readings: {e}", file=sys.stderr)
        return 2
    run.enable_compile_cache()
    compiles = harness.CompileCounter()
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        planted = (faults.FAULTS[args.fault]() if args.fault
                   else contextlib.nullcontext())
        with planted, tempfile.TemporaryDirectory(prefix="bench-") as work:
            result = harness.run_cell(cell, seed, args.seconds, False, t0,
                                      work, compiles, peaks,
                                      log=lambda s: None)
        print(json.dumps({"seed": seed, "fault": args.fault,
                          "correct": result["correct"],
                          "metrics": result["metrics"],
                          "checks": result["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
