"""Cells of BENCHMARK.json cut to sizes a CPU test run holds, and one run of
such a cell with the chip look skipped and, optionally, a fault planted."""
import contextlib
import json
import tempfile
import time

import faults
import generator
import harness

with open(harness.ROOT / "BENCHMARK.json") as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]


def tiny_cell(name: str) -> harness.Cell:
    """The cell with its tree model's ``TINY`` configuration keys, flush
    groups of at most 8 objects and 8 gets after each."""
    cell = harness.load_cell(name)
    cell.config.update(generator.model(cell.config).TINY)
    cell.traffic.update(group_max_objects=8)
    if cell.traffic.get("gets_per_group"):
        cell.traffic["gets_per_group"] = 8
    return cell


def run_tiny(name: str, fault: str | None = None, seed: int = 2**31 + 11,
             seconds: float = 1.0, traffic: dict | None = None) -> dict:
    """A CPU run skips the warm-up: compiling every row count of every
    bucket in interpret mode would take minutes.  ``traffic`` overrides
    keys of the cell's traffic mix."""
    cell = tiny_cell(name)
    cell.traffic.update(traffic or {})
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
