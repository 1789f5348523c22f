"""Benchmark harness entry point: one module per paper table/figure.

  python -m benchmarks.run                      # default (small) budget
  python -m benchmarks.run --full               # paper-scale corpora
  python -m benchmarks.run --only bench_chunking
  python -m benchmarks.run --json BENCH_pr2.json

Besides the stdout CSV, every run serializes all collected rows into one
JSON file (default ``BENCH_<budget>.json``) with a meta header recording
backend and the pipeline configuration defaults (``mask_impl`` /
``step_impl`` / ``shards``).  Rows that exercise a non-default
configuration carry their own ``mask_impl``/``step_impl``/``shards``
fields (the service benchmarks do); consumers should fall back to the
meta defaults for rows that don't.  This is what makes BENCH_*.json
trajectories comparable across PRs: a throughput delta can be attributed
to the code or to a config change, not guessed at.

The meta header also carries a ``provenance`` block (git SHA with a
-dirty marker, UTC timestamp, hostname, jax version, device kind) tying
each trajectory point to an exact code state and machine, and the report's
``metrics`` key embeds the service-internal telemetry snapshots the
service benchmarks capture via ``common.emit_metrics`` — dispatch
latencies, writer backpressure, RPC counts (render them with
``scripts/obs_report.py``).
"""
from __future__ import annotations

import argparse
import datetime
import json
import os
import socket
import subprocess
import sys
import time


def provenance() -> dict:
    """Where/when/what a BENCH_*.json came from: git SHA (with a -dirty
    suffix when the tree has uncommitted changes), UTC timestamp, host,
    jax version, and the device kind behind the backend — enough to tie a
    throughput trajectory point back to an exact code state and machine."""
    here = os.path.dirname(os.path.abspath(__file__))

    def _git(*argv):
        try:
            return subprocess.run(
                ["git", *argv], cwd=here, capture_output=True, text=True,
                timeout=10,
            ).stdout.strip()
        except (OSError, subprocess.SubprocessError):
            return ""

    sha = _git("rev-parse", "HEAD") or None
    if sha and _git("status", "--porcelain"):
        sha += "-dirty"

    import jax

    try:
        device_kind = jax.devices()[0].device_kind
    except Exception:  # pragma: no cover — backend with no devices
        device_kind = None
    return {
        "git_sha": sha,
        "timestamp_utc": datetime.datetime.now(
            datetime.timezone.utc
        ).isoformat(timespec="seconds"),
        "hostname": socket.gethostname(),
        "jax_version": jax.__version__,
        "device_kind": device_kind,
    }

MODULES = [
    "bench_calibrate",        # Table I / SSV
    "bench_chunking",         # Figs 1, 7, 8, 9, 12
    "bench_space_savings",    # Figs 5, 6 / Table III
    "bench_breakdown",        # Fig 10
    "bench_distribution",     # Fig 11
    "bench_shift",            # SSIV
    "bench_intrinsics",       # SSV microbench (VPU analogue)
    "bench_pipeline",         # framework-level (ingest + checkpoint)
    "bench_service",          # streaming dedup service (docs/SERVICE.md)
    "bench_sharded_service",  # sharded service (docs/SHARDING.md)
    "bench_scheduler_occupancy",  # adversarial length mixes (docs/SERVICE.md)
    "bench_scenarios",        # versioned-corpus workloads (docs/SCENARIOS.md)
]

#: the --quick subset: minutes-fast modules that understand the tiny
#: budget, covering the service/scheduler trajectory (what PR-over-PR
#: comparisons track) without the paper-figure sweeps; bench_intrinsics
#: rides along for its fingerprint-kernel speedup rows (fp_impl
#: "reference" vs "pallas") and the end-to-end fused-pipeline rows
#: (pipeline_impl "split" vs "fused")
QUICK_MODULES = [
    "bench_service",
    "bench_sharded_service",
    "bench_scheduler_occupancy",
    "bench_intrinsics",
    "bench_scenarios",
]

#: configuration every benchmark uses unless its rows say otherwise;
#: "scenario" tags rows from the workload catalog (repro.scenarios) —
#: synthetic-corpus benchmarks use the "none" default
DEFAULTS = {"mask_impl": "jnp", "step_impl": "wide", "fp_impl": "reference",
            "pipeline_impl": "split", "packing_impl": "off", "shards": 1,
            "transport": "local", "scenario": "none", "codec": "none"}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="minutes-fast trajectory profile: tiny corpora, "
                         "service/scheduler modules only (QUICK_MODULES)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="output JSON path (default BENCH_<budget>.json)")
    args = ap.parse_args()
    if args.full and args.quick:
        ap.error("--full and --quick are mutually exclusive")
    budget = "full" if args.full else ("quick" if args.quick else "small")
    # a --only run gets its own default file so iterating on one module
    # never clobbers the canonical full-run trajectory
    json_path = args.json or (
        f"BENCH_{budget}.json" if args.only is None
        else f"BENCH_{budget}_{args.only}.json"
    )

    from repro.runtime import enable_compile_cache

    from . import common

    enable_compile_cache()
    common.reset_results()
    base = QUICK_MODULES if args.quick else MODULES
    mods = [m for m in base if args.only is None or args.only in m]
    ok = True
    failures = []
    for name in mods:
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        t0 = time.time()
        try:
            mod.run(budget)
            print(f"## {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
        except Exception as e:  # pragma: no cover
            ok = False
            failures.append(name)
            print(f"## {name} FAILED: {type(e).__name__}: {e}", file=sys.stderr)

    import jax

    report = {
        "meta": {
            "budget": budget,
            "backend": jax.default_backend(),
            "modules": mods,
            "failed_modules": failures,
            "defaults": dict(DEFAULTS),
            "provenance": provenance(),
        },
        "results": common.RESULTS,
        # service-internal telemetry captured by the benchmarks that run a
        # full service (emit_metrics): the *why* behind the throughput rows
        "metrics": common.METRICS,
    }
    with open(json_path, "w") as f:
        json.dump(report, f, indent=1)
    print(f"## wrote {len(common.RESULTS)} rows to {json_path}", file=sys.stderr)
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
