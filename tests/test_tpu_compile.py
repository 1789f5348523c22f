"""Compile the service's device programs for a TPU v5e, without the chip.

The TPU compiler is installed with JAX and compiles for a topology that is
described rather than attached: it refuses what the chip would refuse (block
shapes off the (8, 128) tiling, unlowerable primitives, programs that do not
fit HBM), which interpret-mode tests cannot show.  Nothing runs here, so
these tests say nothing about results or speed; the kernels' results are
pinned by the interpret-mode parity tests and by ``chip_smoke.py`` on a chip.

The topology is described inside a module-scoped fixture (never at import):
only one process may hold the TPU library, and under several test workers
only the worker given this file loads it.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core.automaton import max_chunks_for
from repro.core.params import derived_params

P = derived_params(8192)
ROWS, ROW_BYTES = 8, 1 << 20
#: usable HBM of one v5e chip as its compiler reports it (15.75 GiB)
V5E_HBM_BYTES = int(15.75 * (1 << 30))


@pytest.fixture(scope="module")
def topo():
    old_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the persistent
    # cache: keep it off so nothing is written or warned about
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    if old_log is None:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The kernel wrappers ask the (CPU) backend whether to interpret;
    steer them to the chip build, as a TPU backend would."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _bytes(one_chip, shape, dtype=jnp.uint8):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_default_device_chunk_fits_hbm(one_chip, monkeypatch):
    """The default served path on a TPU (jnp masks, wide scan, and the
    fingerprint kernel the scheduler resolves there) at 8 x 1 MiB: XLA
    around one Mosaic kernel, with its temporaries inside one chip's HBM."""
    from repro.service.scheduler import ChunkScheduler, _device_chunk

    # what the scheduler and the kernel wrappers see on a TPU backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sched = ChunkScheduler(P)
    assert sched.fp_impl == "pallas"
    mc = max_chunks_for(ROW_BYTES, P)
    compiled = _compile(
        lambda x: _device_chunk(x, p=P, mc=mc, mask_impl=sched.mask_impl,
                                step_impl=sched.step_impl, with_fp=True,
                                fp_impl=sched.fp_impl, pipeline_impl="split"),
        _bytes(one_chip, (ROWS, ROW_BYTES)),
    )
    mem = compiled.memory_analysis()
    assert 0 < mem.temp_size_in_bytes < V5E_HBM_BYTES
    assert compiled.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize("kernel_path", [
    dict(mask_impl="jnp", fp_impl="reference", pipeline_impl="fused"),
    dict(mask_impl="pallas", fp_impl="pallas", pipeline_impl="split"),
], ids=["fused", "pallas-masks-fps"])
def test_scheduler_kernel_paths_compile(one_chip, compiled_kernels,
                                        kernel_path):
    """The scheduler's kernel selectors at 8 x 1 MiB hold Mosaic kernels:
    one fused dispatch, or the mask and fingerprint kernels (vmapped over
    the batch) around the XLA boundary scan."""
    from repro.service.scheduler import _device_chunk

    mc = max_chunks_for(ROW_BYTES, P)
    compiled = _compile(
        lambda x: _device_chunk(x, p=P, mc=mc, step_impl="wide",
                                with_fp=True, **kernel_path),
        _bytes(one_chip, (ROWS, ROW_BYTES)),
    )
    kernels = 1 if kernel_path["pipeline_impl"] == "fused" else 2
    assert compiled.as_text().count("tpu_custom_call") == kernels


def test_fused_pipeline_compiles_64mib_row(one_chip):
    """One 64 MiB row (the snapshot bucket of a backup chain) in one
    dispatch."""
    from repro.kernels.fused_pipeline import fused_pipeline_batch

    n = 64 << 20
    compiled = _compile(
        lambda x: fused_pipeline_batch(x, P, max_chunks=max_chunks_for(n, P),
                                       interpret=False),
        _bytes(one_chip, (1, n)),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_packed_pipeline_compiles(one_chip):
    """Segment-packed rows at the scheduler's 16 KiB minimum bucket."""
    from repro.kernels.fused_pipeline import packed_pipeline_batch

    S, G = 1 << 14, 64
    mc = max_chunks_for(S, P) + G
    compiled = _compile(
        lambda x, sep, e: packed_pipeline_batch(x, sep, e, P, max_chunks=mc,
                                                interpret=False),
        _bytes(one_chip, (ROWS, S)), _bytes(one_chip, (ROWS, S), jnp.int32),
        _bytes(one_chip, (ROWS, G), jnp.int32),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_mesh_route_compiles_for_four_chips(topo):
    """The sharded service's fingerprint route: an all_to_all over a
    4-chip mesh, partitioned one owner slab per chip."""
    from repro.dedup.dist_index import routed_fp_tables

    mesh = Mesh(np.array(topo.devices), ("data",))
    spec = NamedSharding(mesh, PartitionSpec("data"))
    rows = 4 * 2048
    with mesh:
        compiled = routed_fp_tables(mesh, "data").lower(
            jax.ShapeDtypeStruct((rows, 2), jnp.uint32, sharding=spec),
            jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=spec),
        ).compile()
    assert "all-to-all" in compiled.as_text()
    assert compiled.output_shardings[0].spec == PartitionSpec("data")
