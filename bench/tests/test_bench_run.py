"""The command refuses what it cannot measure: no TPU, too few chips, an
environment variable that moves the served path, a checkout without the
program.  It prints no result then and exits non-zero."""
import os
import shutil
import subprocess
import sys
import tempfile
import time

import pytest

import harness
import run

ARGS = ["--workload", "file-backup.first", "--seed", "5", "--seconds", "1"]


def test_refuses_a_cpu(capsys):
    assert run.main(ARGS) == 2
    out = capsys.readouterr()
    assert out.out == "" and "needs a TPU" in out.err


@pytest.mark.parametrize("var", harness.REFUSED_ENV)
def test_refuses_a_served_path_variable(monkeypatch, capsys, var):
    monkeypatch.setenv(var, "1")
    assert run.main(ARGS, require_tpu=False) == 2
    out = capsys.readouterr()
    assert out.out == "" and var in out.err


def test_refuses_too_few_chips(monkeypatch, capsys):
    load = harness.load_cell

    def four(name, *a, **k):
        cell = load(name, *a, **k)
        cell.chips = 4
        return cell

    monkeypatch.setattr(harness, "load_cell", four)
    assert run.main(ARGS, require_tpu=False) == 2
    assert "4 chips" in capsys.readouterr().err


def test_refuses_an_unknown_workload(capsys):
    assert run.main(["--workload", "nope", "--seed", "1", "--seconds", "1"],
                    require_tpu=False) == 2
    assert capsys.readouterr().out == ""


def test_fails_in_a_checkout_of_the_benchmark_alone(tmp_path):
    root = os.path.dirname(harness.HERE)
    shutil.copy(os.path.join(root, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    code = ("import sys; sys.path.insert(0, 'bench'); import run; "
            f"sys.exit(run.main({ARGS!r}, require_tpu=False))")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


@pytest.mark.parametrize("trace,seconds", [(False, 1.0), (True, 0.0)])
def test_a_traced_window_is_one_version(monkeypatch, trace, seconds):
    """The untraced window runs for the seconds asked; the traced one for
    one whole version, whose trace a run has time to export and read."""
    import tiny

    asked = []
    drive = harness.drive

    def record(svc, groups, secs, *a):
        asked.append(secs)
        return drive(svc, groups, secs, *a)

    monkeypatch.setattr(harness, "drive", record)
    monkeypatch.setattr(harness, "warm_up", lambda params, sizes: 0)
    cell = tiny.tiny_cell("file-backup.first")
    with tempfile.TemporaryDirectory() as work:
        r = harness.run_cell(cell, 9, 1.0, trace, time.perf_counter(), work,
                             harness.CompileCounter(), None,
                             log=lambda s: None)
    assert asked == [seconds] and r["correct"], r["checks"]
    if trace:
        assert r["attempted"] == cell.config["files"]
