"""Each metric reader on handmade records, and BENCHMARK.json against the
files the harness finds by name."""
import json
import os
import re

import pytest

import harness

REC = {
    "setup_s": 12.5,
    "ingest_s": 4.0,
    "ingest_bytes": 20_000_000,
    "get_latencies_s": [0.001 * i for i in range(1, 101)],
    "sched": {"stream_bytes": 600, "device_bytes": 1000, "tail_bytes": 150,
              "payload_bytes": 819_000},
    "phases": {"flush": {"commit": 1.0, "fp": 0.2, "sync": 0.6,
                         "chunk-dispatch": 2.0},
               "get": {"rpc": 0.0505, "verify": 0.0101}},
    "trace": {"busy_s": 0.25, "window_s": 1.0},
    "peaks": {"hbm_bytes_per_s": 819e9},
}

WANT = {
    "setup_s": 12.5,
    "ingest_MBps": 5.0,
    "get_p50_ms": 50.5,
    "get_p95_ms": 95.0,
    "sched_occupancy_pct": 60.0,
    "sched_tail_pct": 25.0,
    "device_idle_pct": 75.0,
    "chunk_hbm_roofline_pct": 100.0 * 1e-6 / 0.25,
    "commit_pct": 30.0,
    "sync_pct": 15.0,
    "get_gather_pct": 100.0 * 0.0505 / 5.05,
    "get_verify_pct": 100.0 * 0.0101 / 5.05,
    "ingest_MBps.first": 5.0,
    "device_idle_pct.first": 75.0,
    "commit_pct.first": 30.0,
    "sync_pct.first": 15.0,
}

EMPTY = {
    "setup_s": 1.0, "ingest_s": 0.0, "ingest_bytes": 0,
    "get_latencies_s": [],
    "sched": {"stream_bytes": 0, "device_bytes": 0, "tail_bytes": 0,
              "payload_bytes": 0},
    "phases": {}, "trace": None, "peaks": None,
}

with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
METRICS = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_handmade_records(name):
    assert harness.reader(name)(REC) == pytest.approx(WANT[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(set(WANT) - {"setup_s"}))
def test_reader_finds_nothing_and_says_so(name):
    assert harness.reader(name)(EMPTY) is None


def test_every_metric_has_a_reader_and_every_reader_a_metric():
    files = {f[:-3] for f in os.listdir(harness.HERE / "metrics")
             if f.endswith(".py")}
    assert files == set(METRICS) == set(WANT)


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_file_keeps_the_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    cfgs = {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        with open(os.path.join(harness.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert set(c["reduced"]) == set(cfg["reduced"])
    used = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in cfgs
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert (harness.HERE / "traffic" / f"{w['traffic']}.json").exists()
        used.add(w["config"])
        cell = harness.load_cell(w["name"])
        assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert cell.metrics[m]["moves"] in cell.end_to_end
    assert used == cfgs
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        layers.setdefault(m["layer"], []).append(m["name"])


def test_peaks_name_their_source_and_refuse_unknown_devices():
    assert harness.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.Refused):
        harness.device_peaks("cpu")
