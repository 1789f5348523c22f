"""Cells of BENCHMARK.json cut to sizes a CPU test run holds, and one run of
such a cell with the chip look skipped and, optionally, a fault planted."""
import contextlib
import math
import tempfile
import time

import faults
import harness

CELLS = ["file-backup.weekly", "file-backup.first"]


def tiny_cell(name: str) -> harness.Cell:
    cell = harness.load_cell(name)
    # the configuration's spread of sizes about a smaller median: 10 B to
    # 400 KB
    cell.config.update(files=32, dirs=4, size_log_mean=math.log(2000))
    cell.traffic.update(group_max_objects=8)
    if cell.traffic.get("gets_per_group"):
        cell.traffic["gets_per_group"] = 8
    return cell


def run_tiny(name: str, fault: str | None = None, seed: int = 2**31 + 11,
             seconds: float = 1.0) -> dict:
    """A CPU run skips the warm-up: compiling every row count of every
    bucket in interpret mode would take minutes."""
    cell = tiny_cell(name)
    planted = faults.FAULTS[fault]() if fault else contextlib.nullcontext()
    warm_up = harness.warm_up
    harness.warm_up = lambda params, sizes: 0
    try:
        with planted, tempfile.TemporaryDirectory() as work:
            return harness.run_cell(cell, seed, seconds, False,
                                    time.perf_counter(), work,
                                    harness.CompileCounter(), None,
                                    log=lambda s: None)
    finally:
        harness.warm_up = warm_up
