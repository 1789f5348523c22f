"""The trace reduction, on handmade intervals and on a small trace recorded
on a TPU v5e: 3 s of the service putting one 1.2 MB object per flush
through the benchmark's harness, with the device operations shorter than
10 us then dropped from the ``XLA Ops`` line to keep the file small (they
lie inside the loops that remain, so busy time moved by 0.04%).
``record_trace.py`` records such a trace of a cell at the tests' size."""
import os

import pytest

import tracefile

DATA = os.path.join(os.path.dirname(__file__), "data", "vm-tiny.xplane.pb")


def test_merge_and_gaps():
    busy = tracefile.merge([(5, 7), (0, 2), (1, 3), (6, 9), (12, 13)])
    assert busy == [(0, 3), (5, 9), (12, 13)]
    assert tracefile.gaps(busy, (0, 15)) == [(3, 5), (9, 12), (13, 15)]
    assert tracefile.gaps([], (2, 4)) == [(2, 4)]


def test_reduce_handmade_trace():
    s = 1e9  # ns per second
    trace = {
        "spans": [("bench.window", 0, 10 * s), ("bench.flush", 1 * s, 4.5 * s),
                  ("bench.submit", 5 * s, 6 * s), ("bench.get", 7 * s, 9.5 * s)],
        "devices": {
            "/device:TPU:0": [("fusion.1", 1 * s, 2 * s),
                              ("fusion.2", 1.5 * s, 3.2 * s),
                              ("copy", 5 * s, 6 * s),
                              ("fusion.1", 9.5 * s, 11 * s)],
        },
    }
    r = tracefile.reduce(trace)
    assert r["busy_s"] == pytest.approx(3.7)
    assert r["window_s"] == pytest.approx(10.0)
    assert r["devices"] == 1
    assert r["device_ops"][0] == ["fusion.2", pytest.approx(1.7)]
    assert dict(map(tuple, r["device_ops"]))["fusion.1"] == pytest.approx(1.5)
    gaps = r["idle_gaps"]
    assert gaps[0] == ["get", pytest.approx(3.5)]  # 6 s .. 9.5 s
    assert ["between", pytest.approx(1.0)] in gaps  # 0 .. 1 s
    assert ["flush", pytest.approx(1.8)] in gaps  # 3.2 .. 5 s, mid in flush


def test_reduce_finds_nothing_without_device_work():
    assert tracefile.reduce({"spans": [("bench.window", 0, 10)],
                             "devices": {}}) is None


def test_recorded_chip_trace():
    r = tracefile.reduce(tracefile.read(DATA))
    assert r is not None and r["devices"] == 1
    assert r["busy_s"] == pytest.approx(1.931368608, abs=1e-9)
    assert r["window_s"] == pytest.approx(3.160015178, abs=1e-9)
    assert r["device_ops"][0] == ["while.30", pytest.approx(0.817015501)]
    assert r["idle_gaps"][0] == ["flush", pytest.approx(0.181630167)]
    assert 1 <= len(r["device_ops"]) <= tracefile.TOP
    assert 1 <= len(r["idle_gaps"]) <= tracefile.TOP
    assert {g[0] for g in r["idle_gaps"]} <= {
        "between", "generate", "submit", "flush", "get", "check"}
    assert all(t > 0 for _, t in r["device_ops"] + r["idle_gaps"])
