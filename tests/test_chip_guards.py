"""Guards that keep chip runs honest, and a CPU rehearsal of chip_smoke.py.

* Pallas kernels interpret only on the CPU: a TPU compiles them, any other
  backend is refused, and the baseline kernels with no chip build refuse a
  TPU rather than interpret there.
* Shard-server processes never import jax: they are spawned by a process
  that may hold the chip, and a child that reached for it would fail.
* The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
  a fixed directory of the checkout that git ignores.
* ``chip_smoke.py`` runs its phases at a tiny size on the CPU (interpret
  mode) through its functions — the program itself refuses a non-TPU run
  and prints no result line then.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)  # chip_smoke.py lives at the checkout root

import chip_smoke  # noqa: E402

from repro.kernels import ops  # noqa: E402
from repro import runtime  # noqa: E402

TINY = chip_smoke.Sizes(snapshot_bytes=256 << 10, snapshots=2,
                        scenario_budget="tiny", small_objects=6)


# -- interpret mode only on the CPU ---------------------------------------------

@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False)])
def test_interpret_follows_backend(monkeypatch, backend, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ops._interpret() is want


@pytest.mark.parametrize("backend", ["gpu", "metal"])
def test_interpret_refuses_other_backends(monkeypatch, backend):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with pytest.raises(NotImplementedError, match=backend):
        ops._interpret()


@pytest.mark.parametrize("kernel", ["gear_hash", "block_max"])
def test_baseline_kernels_refuse_tpu(monkeypatch, kernel):
    import jax.numpy as jnp

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="does not compile"):
        getattr(ops, kernel)(jnp.zeros((4096,), jnp.uint8))


# -- shard servers stay off jax -----------------------------------------------------

def test_shard_server_import_leaves_jax_out():
    code = textwrap.dedent("""
        import sys
        import repro.service.transport.shard_server
        print("jax" in sys.modules)
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"


# -- compile cache placement ----------------------------------------------------------

@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_respects_env(monkeypatch, cache_config, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # left to JAX


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(ROOT, ".jax_cache")
    assert runtime.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# -- chip_smoke.py ------------------------------------------------------------------

def test_chip_smoke_refuses_cpu(capsys):
    assert chip_smoke.main([]) != 0
    assert capsys.readouterr().out == ""


def test_chip_smoke_alone_fails(tmp_path):
    """Copied into a directory with nothing else of the repo, the script
    exits non-zero and prints no result line."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.mark.timeout(600)
def test_chip_smoke_one_chip_phases_rehearsal(tmp_path, capsys):
    """Every one-chip phase at a tiny size: oracle-exact boundaries,
    dict-reference accounting, byte-exact gets, GC to zero, each kernel
    path bit-identical to the default, and the remote 2-shard service."""
    chip_smoke.run_one_chip(TINY, str(tmp_path), require_compiled=False)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["phase"] for x in lines] == [
        "default", *chip_smoke.OTHER_PATHS, "remote-2-shards"]
    by_phase = {x["phase"]: x for x in lines}
    assert by_phase["packed-fused"]["packed_streams"] > 0  # really packed
    assert len({x["dedup_ratio"] for x in lines[:-1]}) == 1


@pytest.mark.timeout(600)
def test_chip_smoke_mesh_phase_rehearsal():
    """The --chips 4 phase on four virtual CPU devices."""
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {ROOT!r})
        import jax, chip_smoke as cs
        sizes = cs.Sizes(snapshot_bytes=1 << 16, snapshots=1,
                         scenario_budget="tiny", small_objects=12)
        cs.run_mesh(sizes, jax.make_mesh((4,), ("data",)))
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.splitlines()[-1])
    assert line["phase"] == "mesh-routing"
    assert line["overflow_rerouted"] == 0
    # owner s's routed slab lives on device s
    assert sorted(line["table_placement"].values()) == [
        [[s, s + 1]] for s in range(4)]
