"""Each metric reader on its own handmade case and on an empty record,
BENCHMARK.json against the files the harness finds by name, and new files
found without an edit to any file there is."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import byname
import harness

#: the shared handmade record; each metric's ``CASE`` adds its own fields
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
    "counters": {},
    "trace": {"busy_s": 0.25, "window_s": 1.0},
    "peaks": {"hbm_bytes_per_s": 819e9},
}

#: a record with nothing in it, not even a set-up time
EMPTY = {
    "setup_s": None, "ingest_s": 0.0, "ingest_bytes": 0,
    "get_latencies_s": [],
    "sched": {"stream_bytes": 0, "device_bytes": 0, "tail_bytes": 0,
              "payload_bytes": 0},
    "phases": {}, "counters": {}, "trace": None, "peaks": None,
}

with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
METRICS = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
FILES = sorted(f[:-3] for f in os.listdir(harness.HERE / "metrics")
               if f.endswith(".py"))


def merged(base: dict, fields: dict) -> dict:
    """``base`` with ``fields`` laid over it, dict by dict."""
    out = dict(base)
    for k, v in fields.items():
        out[k] = (merged(base[k], v) if isinstance(v, dict)
                  and isinstance(base.get(k), dict) else v)
    return out


@pytest.mark.parametrize("name", FILES)
def test_reader_on_handmade_records(name):
    fields, want = byname.load("metrics", name).CASE
    assert harness.reader(name)(merged(REC, fields)) == pytest.approx(
        want, rel=1e-12)


@pytest.mark.parametrize("name", FILES)
def test_reader_finds_nothing_and_says_so(name):
    assert harness.reader(name)(EMPTY) is None


def test_every_metric_has_a_reader_and_every_reader_a_metric():
    assert FILES == sorted(METRICS)


def test_device_stage_shares_sum_to_the_busy_time():
    stages = {"fingerprint": 0.0075, "automaton": 0.24, "masks": 0.001,
              "other": 0.0015}
    rec = merged(REC, {"trace": {"stages": stages}})
    shares = sum(harness.reader(f"device_{s}_pct")(rec)
                 for s in ("fp", "automaton", "masks"))
    assert shares + 100.0 * stages["other"] / 0.25 == pytest.approx(100.0)
    # a stage the trace does not name is not read as 0
    rec = merged(REC, {"trace": {"stages": {"automaton": 0.25}}})
    assert harness.reader("device_fp_pct")(rec) is None


def test_counters_are_window_deltas_and_a_missing_one_stays_missing():
    from repro.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    reg.inc("store.block_write_s", 2.0)
    reg.inc("store.key_hash_s", 0.5)
    before = reg.snapshot()
    reg.inc("store.block_write_s", 1.0)
    reg.inc("sched.payload_bytes", 4096)
    counters = harness.counter_delta(before, reg.snapshot())
    assert counters == {"store.block_write_s": 1.0, "store.key_hash_s": 0.0,
                        "sched.payload_bytes": 4096}
    rec = dict(REC, counters=counters)
    assert harness.reader("commit_write_pct.first")(rec) == pytest.approx(
        100.0 * 1.0 / 4.0)
    # the program keeps no commit.digest_s: the metric has nothing to read
    assert harness.reader("commit_hash_pct.first")(rec) is None


PROBE_METRIC = '''
def read(rec):
    return 2.0 * rec["ingest_s"]


CASE = ({}, 8.0)
'''

PROBE_MODEL = '''
import numpy as np

from generator import CONTENT, rng


class Model:
    def __init__(self, config, seed):
        self.blocks, self.seed = int(config["blocks"]), seed

    def sizes(self):
        return np.array([4096 * self.blocks])

    def tree(self, version):
        data = rng(self.seed, CONTENT, version).bytes(4096 * self.blocks)
        return {"disk.img": np.frombuffer(data, dtype=np.uint8)}
'''

PROBE_RUN = '''
import json, sys
sys.path.insert(0, "bench")
import harness
from generator import Traffic
cell = harness.load_cell("probe.daily")
t = Traffic(cell.config, cell.traffic, 7)
group = next(t.groups(0, 1))
print(json.dumps({"here": str(harness.HERE),
                  "per_layer": cell.per_layer,
                  "read": harness.reader("probe_pct")({"ingest_s": 4.0}),
                  "puts": [[n, int(d.size)] for n, d in group.puts],
                  "sizes": t.model.sizes().tolist()}))
'''


def test_new_files_are_found_by_name(tmp_path):
    """A cell of a new configuration, tree model, traffic mix and metric
    needs new files and new entries of BENCHMARK.json, and no edit to any
    file under ``bench/``."""
    shutil.copytree(harness.HERE, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "probe", "source": "-",
                             "file": "bench/configs/probe.json",
                             "reduced": [], "why": "-"})
    bench["workloads"].append({"name": "probe.daily", "config": "probe",
                               "traffic": "daily", "chips": 1, "why": "-"})
    bench["per_layer"].append({"name": "probe_pct", "unit": "%",
                               "better": "lower", "source": "host_clock",
                               "layer": "-", "moves": "setup_s",
                               "workloads": ["probe.daily"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "bench/configs/probe.json").write_text(
        json.dumps({"model": "probe_disk", "blocks": 3}))
    (tmp_path / "bench/traffic/daily.json").write_text(json.dumps(
        {"name": "day-{version}/{path}", "next": "same",
         "gets_per_group": 0}))
    (tmp_path / "bench/metrics/probe_pct.py").write_text(PROBE_METRIC)
    (tmp_path / "bench/models/probe_disk.py").write_text(PROBE_MODEL)
    env = dict(os.environ, PYTHONPATH=os.path.join(harness.ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", PROBE_RUN], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    got = json.loads(p.stdout.splitlines()[-1])
    assert got == {"here": str(tmp_path / "bench"), "per_layer": ["probe_pct"],
                   "read": 8.0, "puts": [["day-0/disk.img", 12288]],
                   "sizes": [12288]}


NAME = byname.NAME
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
