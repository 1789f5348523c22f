"""Run one benchmark cell once on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` at the checkout's root) names a configuration
and a traffic mix; the run builds its data from ``--seed``, ingests the
set-up version into a fresh file-backed depot in a temporary directory,
warms up the device shapes the mix uses, then drives whole backups of the
tree through ``DedupService`` on its default served path for ``--seconds``
(the backup in flight then finishes); with ``--trace 1`` it profiles one
whole backup instead.
After the window it compares every acknowledged object with the plain
reference (``reference.py``).

Earlier lines of standard output are JSON objects with an ``info`` key
(device, set-up, the window's counts and compiles, trace and reference
times).  The last line is the result: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit,
also printed as the last lines of standard error.

It refuses to run, exits non-zero and prints no result when JAX's first
device is not a TPU, when there are fewer chips than the cell asks for, or
when an environment variable that moves the served path is set.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_compile_cache():
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX
    reads it itself), else ``<checkout>/.jax_cache``.  Every program is kept,
    however fast it compiled and however many a cell has: a size cap smaller
    than a cell's programs evicts in the order the next run asks for them,
    so every run would compile them all again."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def check_device(chips: int, require_tpu: bool = True):
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise harness.Refused(f"needs a TPU, JAX found "
                              f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise harness.Refused(f"the cell needs {chips} chips, JAX found "
                              f"{len(devices)}")
    return devices


def main(argv=None, *, require_tpu: bool = True, t0: float = T0) -> int:
    args = parse(argv)
    try:
        bad = harness.refused_env()
        if bad:
            raise harness.Refused(f"unset {bad}: they change the served path")
        cell = harness.load_cell(args.workload)
        devices = check_device(cell.chips, require_tpu)
        peaks = (harness.device_peaks(devices[0].device_kind)
                 if require_tpu else None)
    except harness.Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    enable_compile_cache()
    compiles = harness.CompileCounter()
    d = devices[0]
    print(json.dumps({"info": "device", "platform": d.platform,
                      "device_kind": d.device_kind, "count": len(devices)}),
          flush=True)
    with tempfile.TemporaryDirectory(prefix="bench-") as work:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), t0, work, compiles,
                                  peaks, log=lambda s: print(s, flush=True))
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
