"""Record the small trace that ``test_bench_tracefile.py`` reads.

    python bench/tests/record_trace.py [out.xplane.pb]    # on a TPU

Runs the ``file-backup.first`` cell at the tests' tiny size
(``tiny.py``), warm-up included, traced for 3 seconds; keeps the trace
(default ``bench/tests/data/tiny.xplane.pb``) and prints the trace's planes
and lines, and the reduction.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

T0 = time.perf_counter()
BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "tests"), str(BENCH.parent / "src")]

import harness  # noqa: E402
import tracefile  # noqa: E402
from tiny import tiny_cell  # noqa: E402


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    out = Path(args[0]) if args else BENCH / "tests" / "data" / "tiny.xplane.pb"
    import jax

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    cell = tiny_cell("file-backup.first")
    with tempfile.TemporaryDirectory() as work:
        result = harness.run_cell(cell, 12345, 3.0, True, T0, work,
                                  harness.CompileCounter(),
                                  harness.device_peaks(
                                      jax.devices()[0].device_kind))
        found = tracefile.find_trace(os.path.join(work, "trace"))
        out.parent.mkdir(parents=True, exist_ok=True)
        shutil.copyfile(found, out)
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(out))
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            evs = list(line.events)
            lines[line.name] = [len(evs)] + [
                [e.name[:120], e.start_ns, e.duration_ns] for e in evs[:3]]
        print(json.dumps({"plane": plane.name, "lines": lines}))
    print(json.dumps({"bytes": out.stat().st_size,
                      "reduction": tracefile.reduce(tracefile.read(str(out))),
                      "correct": result["correct"],
                      "metrics": result["metrics"]}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
