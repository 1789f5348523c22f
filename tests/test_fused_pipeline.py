"""Fused single-dispatch pipeline kernel: bit-identity with the split path.

The fused kernel (kernels/fused_pipeline.py) collapses the three-dispatch
chunk+fingerprint pipeline into one ``pallas_call``; its contract is
bit-identity with the composed split path (``kernels/ref.fused_pipeline``)
across bounds, counts, fingerprints and lengths — over random streams, the
documented edge regimes (max-size-forced cuts, the 64 KiB limb boundary,
skip overshoots that spill bounds past a tile, file-end cuts behind the
scan position, empty/1-byte streams), tile sweeps, the scheduler hot path,
and with the first-dispatch ``PipelineDivergenceError`` guard armed.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # no hypothesis in this env: deterministic fallback
    from _hyp_fallback import given, settings, strategies as st

import jax.numpy as jnp

from repro.core.automaton import max_chunks_for
from repro.core.params import SeqCDCParams, derived_params
from repro.kernels import ref
from repro.kernels.fused_pipeline import fused_pipeline, fused_pipeline_batch
from repro.service.scheduler import ChunkScheduler, PipelineDivergenceError

P = SeqCDCParams(avg_size=256, seq_length=3, skip_trigger=6, skip_size=32,
                 min_size=64, max_size=512)
P5 = SeqCDCParams(avg_size=256, seq_length=5, skip_trigger=6, skip_size=32,
                  min_size=64, max_size=512)
#: skip_size wider than the smallest tile: overshooting skips resolved as
#: cuts emit bounds several tiles ahead of the firing block
P_SKID = SeqCDCParams(avg_size=4096, seq_length=5, skip_trigger=3,
                      skip_size=3000, min_size=2048, max_size=8192)

_SENTINEL = 1 << 30


def _assert_parity(d2: np.ndarray, p: SeqCDCParams, tile: int = 32 * 1024):
    mc = max_chunks_for(d2.shape[-1], p)
    x = jnp.asarray(d2)
    want = ref.fused_pipeline(x, p, max_chunks=mc)
    got = fused_pipeline_batch(x, p, max_chunks=mc, tile=tile, interpret=True)
    for g, w, name in zip(got, want, ("bounds", "counts", "fps", "lengths")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"{name} diverged")


@pytest.mark.parametrize("n", [1, 2, 63, 100, 1000, 5000, 33000, 70000])
def test_fused_pipeline_random(n, rng):
    _assert_parity(rng.integers(0, 256, (2, n), dtype=np.uint8), P)


def test_fused_pipeline_forced_max_size_cuts():
    """Constant bytes never form a monotone run: every cut is a max-size
    cut, the automaton's scan position leapfrogs whole tiles."""
    _assert_parity(np.zeros((2, 20000), dtype=np.uint8), P)


def test_fused_pipeline_decreasing_mode(rng):
    pd = SeqCDCParams(avg_size=256, seq_length=3, skip_trigger=6,
                      skip_size=32, min_size=64, max_size=512,
                      mode="decreasing")
    _assert_parity(rng.integers(0, 256, (2, 20000), dtype=np.uint8), pd)


@pytest.mark.parametrize("tile", [1024, 4096, 32 * 1024])
def test_fused_pipeline_tile_sweep(tile, rng):
    _assert_parity(rng.integers(0, 256, (2, 20000), dtype=np.uint8), P5,
                   tile=tile)


def test_fused_pipeline_skip_overshoot_spill(rng):
    """skip_size 3000 against 1024-byte tiles: overshooting skips resolved
    as cuts (_resolve's trig_cuts) emit bounds far past the firing tile,
    exercising the wide halo and the behind-the-tile file-end factor."""
    _assert_parity(rng.integers(0, 256, (2, 30000), dtype=np.uint8), P_SKID,
                   tile=1024)
    _assert_parity(rng.integers(0, 4, (2, 30000), dtype=np.uint8), P_SKID,
                   tile=1024)


def test_fused_pipeline_limb_boundary():
    """All-0xFF bytes at max_size 64 KiB: maximal 16-bit limb sums and
    chunk lengths at the power-table bound, the exactness edge."""
    p64 = derived_params(32768)
    assert p64.max_size == 65536
    _assert_parity(np.full((1, 65536 + 65535), 0xFF, dtype=np.uint8), p64)


def test_fused_pipeline_empty_and_single_byte(rng):
    b, c, f, ln = fused_pipeline_batch(
        jnp.zeros((2, 0), jnp.uint8), P, max_chunks=3, interpret=True)
    assert np.asarray(c).tolist() == [0, 0]
    assert (np.asarray(b) == _SENTINEL).all()
    assert not np.asarray(f).any() and not np.asarray(ln).any()
    _assert_parity(rng.integers(0, 256, (1, 1), dtype=np.uint8), P)


def test_fused_pipeline_single_stream_wrapper(rng):
    d = rng.integers(0, 256, 5000, dtype=np.uint8)
    mc = max_chunks_for(d.size, P)
    b1, c1, f1, l1 = fused_pipeline(jnp.asarray(d), P, max_chunks=mc,
                                    interpret=True)
    b2, c2, f2, l2 = fused_pipeline_batch(jnp.asarray(d)[None], P,
                                          max_chunks=mc, interpret=True)
    np.testing.assert_array_equal(np.asarray(b1), np.asarray(b2)[0])
    assert int(c1) == int(np.asarray(c2)[0])
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2)[0])
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2)[0])


@settings(max_examples=15, deadline=None)
@given(data=st.binary(min_size=1, max_size=4000),
       rep=st.integers(1, 8))
def test_property_fused_pipeline(data, rep):
    arr = np.frombuffer(data, dtype=np.uint8)
    arr = np.tile(arr, rep)[:6000]
    _assert_parity(arr[None], P)


# -- the scheduler hot path -----------------------------------------------------

def test_scheduler_fused_bit_identity(rng):
    """pipeline_impl='fused' with the guard armed: results identical to the
    split scheduler, and the first-dispatch cross-check actually ran."""
    sched = ChunkScheduler(P, slots=2, min_bucket=1024,
                           pipeline_impl="fused", cross_check_pipeline=True)
    split = ChunkScheduler(P, slots=2, min_bucket=1024,
                           pipeline_impl="split")
    streams = [rng.integers(0, 256, n, dtype=np.uint8)
               for n in (0, 1, 100, 1000, 1024, 3000, 5000)]
    for i, s in enumerate(streams):
        sched.submit(s, tag=i)
        split.submit(s, tag=i)
    got = {r.tag: r for r in sched.drain()}
    for r in split.drain():
        assert got[r.tag].bounds.tolist() == r.bounds.tolist()
        np.testing.assert_array_equal(got[r.tag].fps, r.fps)
        np.testing.assert_array_equal(got[r.tag].lengths, r.lengths)
    assert sched._pipeline_checked_buckets  # the guard actually ran


def test_scheduler_env_default(monkeypatch):
    monkeypatch.setenv("REPRO_PIPELINE_IMPL", "fused")
    assert ChunkScheduler(P, min_bucket=1024).pipeline_impl == "fused"
    monkeypatch.delenv("REPRO_PIPELINE_IMPL")
    assert ChunkScheduler(P, min_bucket=1024).pipeline_impl == "split"


def test_unknown_pipeline_impl_rejected():
    with pytest.raises(ValueError):
        ChunkScheduler(P, min_bucket=1024, pipeline_impl="bogus")


# -- divergence injection: the guard names the stage that broke -----------------

def _corrupting_scheduler():
    """split dispatch + armed pipeline guard: the guard replays the fused
    path via scheduler._run_fused, which the tests below corrupt."""
    # packing off: these tests pin the *bucket* path's guard, which fires
    # at submit time (under REPRO_PACKING_IMPL=segments the 900-byte
    # stream would queue for a packed row instead)
    return ChunkScheduler(P, slots=1, min_bucket=1024, pipeline_impl="split",
                          cross_check_pipeline=True, packing_impl="off")


def test_pipeline_divergence_boundary_stage(rng, monkeypatch):
    """Corrupt the fused kernel's boundary lane: the error must say the
    boundary stage diverged."""
    import repro.service.scheduler as sched_mod

    real = sched_mod._run_fused

    def lying(x, p, mc):
        b, c, f, ln = real(x, p, mc)
        return b + (b < _SENTINEL), c, f, ln  # shift every real bound by 1

    monkeypatch.setattr(sched_mod, "_run_fused", lying)
    sched = _corrupting_scheduler()
    with pytest.raises(PipelineDivergenceError) as ei:
        sched.submit(rng.integers(0, 256, 900, dtype=np.uint8))
    assert ei.value.stage == "boundaries"
    assert "boundary" in str(ei.value)


def test_pipeline_divergence_fingerprint_stage(rng, monkeypatch):
    """Corrupt only the hash limb path (boundaries intact): the error must
    say the fingerprint stage diverged."""
    import repro.service.scheduler as sched_mod

    real = sched_mod._run_fused

    def lying(x, p, mc):
        b, c, f, ln = real(x, p, mc)
        return b, c, f ^ 1, ln  # flip one bit of every fingerprint

    monkeypatch.setattr(sched_mod, "_run_fused", lying)
    sched = _corrupting_scheduler()
    with pytest.raises(PipelineDivergenceError) as ei:
        sched.submit(rng.integers(0, 256, 900, dtype=np.uint8))
    assert ei.value.stage == "fingerprints"
    assert "fingerprint" in str(ei.value)


def test_pipeline_guard_passes_clean(rng):
    """No corruption: the armed guard replays the fused path and agrees."""
    sched = _corrupting_scheduler()
    sched.submit(rng.integers(0, 256, 900, dtype=np.uint8))
    sched.drain()
    assert sched._pipeline_checked_buckets
