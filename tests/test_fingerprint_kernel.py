"""Pallas fingerprint kernel: bit-identity with the reference paths.

The fused kernel (kernels/fingerprint.py) must match both the jnp
gather/segment_sum chain (``fp_impl="reference"``) and the host-side
``fingerprints_numpy`` ground truth bit-for-bit — over random chunkings,
the documented edge cases (empty stream, single max-size 64 KiB chunk, the
65535-byte limb-overflow boundary, count=0 padding rows), the vmapped
scheduler path, and with the first-dispatch divergence guard armed.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # no hypothesis in this env: deterministic fallback
    from _hyp_fallback import given, settings, strategies as st

import jax.numpy as jnp

from repro.core.automaton import max_chunks_for
from repro.core.params import SeqCDCParams
from repro.core.seqcdc import boundaries_two_phase
from repro.dedup.fingerprint import (
    MAX_CHUNK,
    chunk_fingerprints,
    fingerprints_numpy,
)
from repro.kernels.fingerprint import fingerprint_pallas
from repro.service.scheduler import ChunkScheduler, FingerprintDivergenceError

P = SeqCDCParams(avg_size=256, seq_length=3, skip_trigger=6, skip_size=32,
                 min_size=64, max_size=512)

_SENTINEL = 1 << 30  # the automaton's bounds padding past count


def _padded_bounds(cuts: np.ndarray, max_chunks: int) -> np.ndarray:
    out = np.full(max_chunks, _SENTINEL, dtype=np.int32)
    out[: len(cuts)] = cuts
    return out


def _assert_parity(data: np.ndarray, cuts: np.ndarray, max_chunks: int,
                   tile: int = 64 * 1024):
    bounds = jnp.asarray(_padded_bounds(cuts, max_chunks))
    count = jnp.asarray(len(cuts))
    fp_k, len_k = fingerprint_pallas(
        jnp.asarray(data), bounds, count, max_chunks=max_chunks, tile=tile,
        interpret=True,
    )
    fp_r, len_r = chunk_fingerprints(
        jnp.asarray(data), bounds, count, max_chunks=max_chunks,
        fp_impl="reference",
    )
    np.testing.assert_array_equal(np.asarray(fp_k), np.asarray(fp_r))
    np.testing.assert_array_equal(np.asarray(len_k), np.asarray(len_r))
    want = fingerprints_numpy(data, cuts)
    np.testing.assert_array_equal(np.asarray(fp_k)[: len(cuts)], want)


def _random_cuts(rng, n: int, max_len: int = MAX_CHUNK) -> np.ndarray:
    cuts = []
    s = 0
    while s < n:
        s = min(n, s + int(rng.integers(1, max_len + 1)))
        cuts.append(s)
    return np.asarray(cuts, dtype=np.int64)


@pytest.mark.parametrize("n", [1, 2, 100, 1023, 1024, 1025, 4096, 70000])
def test_fingerprint_kernel_random_chunkings(n, rng):
    data = rng.integers(0, 256, n, dtype=np.uint8)
    cuts = _random_cuts(rng, n, max_len=max(1, n // 3))
    _assert_parity(data, cuts, max_chunks=len(cuts) + 3)


@pytest.mark.parametrize("tile", [1024, 4096, 64 * 1024])
def test_fingerprint_kernel_tile_sweep(tile, rng):
    data = rng.integers(0, 256, 50_000, dtype=np.uint8)
    cuts = _random_cuts(rng, data.size, max_len=9000)
    _assert_parity(data, cuts, max_chunks=len(cuts) + 2, tile=tile)


@pytest.mark.parametrize("n", [65535, 65536])
def test_fingerprint_kernel_single_max_chunk(n, rng):
    """One chunk at/next to the 64 KiB power-table and limb bound."""
    data = rng.integers(0, 256, n, dtype=np.uint8)
    _assert_parity(data, np.array([n], dtype=np.int64), max_chunks=4)


def test_fingerprint_kernel_limb_boundary():
    """All-0xFF 65535/65536-byte chunks maximize the 16-bit limb sums —
    the exactness bound of the in-kernel cumsum reduction."""
    data = np.full(65536 + 65535, 0xFF, dtype=np.uint8)
    cuts = np.array([65536, 65536 + 65535], dtype=np.int64)
    _assert_parity(data, cuts, max_chunks=5)


def test_fingerprint_kernel_empty_stream():
    fp, lens = fingerprint_pallas(
        jnp.zeros((0,), jnp.uint8), jnp.full((4,), _SENTINEL, jnp.int32),
        jnp.asarray(0), max_chunks=4, interpret=True,
    )
    assert fp.shape == (4, 2) and not np.asarray(fp).any()
    assert lens.shape == (4,) and not np.asarray(lens).any()


def test_fingerprint_kernel_count_zero_padding_row(rng):
    """A scheduler zero-padding row: data present, count = 0 — every slot
    must come back zeroed exactly like the reference."""
    data = np.zeros(4096, dtype=np.uint8)
    bounds = jnp.asarray(np.array([4096, _SENTINEL, _SENTINEL, _SENTINEL],
                                  dtype=np.int32))
    for impl in ("reference", "pallas"):
        fp, lens = chunk_fingerprints(
            jnp.asarray(data), bounds, jnp.asarray(0), max_chunks=4,
            fp_impl=impl,
        )
        assert not np.asarray(fp).any() and not np.asarray(lens).any()


@settings(max_examples=25, deadline=None)
@given(data=st.binary(min_size=1, max_size=3000), avg=st.integers(5, 60))
def test_property_fingerprint_kernel(data, avg):
    arr = np.frombuffer(data, dtype=np.uint8)
    rng = np.random.default_rng(len(data) * 31 + avg)
    cuts = _random_cuts(rng, arr.size, max_len=max(1, avg))
    _assert_parity(arr, cuts, max_chunks=len(cuts) + 2)


def test_chunker_bounds_layout_parity(rng):
    """Parity on real SeqCDC output (sentinel padding, final cut at n)."""
    data = rng.integers(0, 256, 30_000, dtype=np.uint8)
    b, c = boundaries_two_phase(jnp.asarray(data), P)
    mc = max_chunks_for(data.size, P)
    fp_k, len_k = fingerprint_pallas(jnp.asarray(data), b, c, max_chunks=mc,
                                     interpret=True)
    fp_r, len_r = chunk_fingerprints(jnp.asarray(data), b, c, max_chunks=mc)
    np.testing.assert_array_equal(np.asarray(fp_k), np.asarray(fp_r))
    np.testing.assert_array_equal(np.asarray(len_k), np.asarray(len_r))


#: the benchmark's chunking: SeqCDC Table I at an 8 KiB average (4-16 KiB)
TABLE_I_8K = SeqCDCParams(avg_size=8192, seq_length=5, skip_trigger=50,
                          skip_size=256, min_size=4096, max_size=16384)


@pytest.mark.parametrize("width", [16 << 10, 96 << 10],
                         ids=["16KiB-one-tile", "96KiB-two-tiles"])
def test_vmapped_kernel_parity_at_table_i_8k(width, rng):
    """The scheduler's batched form at the benchmark's chunking: a full row
    (its last chunk ends at the row's width), a short stream zero-padded to
    the width, and a zero-count padding row, through the vmapped kernel and
    the vmapped reference chain."""
    import jax

    from repro.core.seqcdc import boundaries_batch

    short = width // 3 + 123
    x = np.zeros((3, width), dtype=np.uint8)
    x[0] = rng.integers(0, 256, width, dtype=np.uint8)
    x[1, :short] = rng.integers(0, 256, short, dtype=np.uint8)
    mc = max_chunks_for(width, TABLE_I_8K)
    b, c = boundaries_batch(jnp.asarray(x), TABLE_I_8K, max_chunks=mc)
    b = np.asarray(b).copy()
    c = np.asarray(c).copy()
    assert b[0, c[0] - 1] == width
    b[2], c[2] = _SENTINEL, 0  # a padding row: no chunks
    args = (jnp.asarray(x), jnp.asarray(b), jnp.asarray(c))
    fp_k, len_k = jax.vmap(lambda d, bb, cc: fingerprint_pallas(
        d, bb, cc, max_chunks=mc, interpret=True))(*args)
    fp_r, len_r = jax.vmap(lambda d, bb, cc: chunk_fingerprints(
        d, bb, cc, max_chunks=mc, fp_impl="reference"))(*args)
    np.testing.assert_array_equal(np.asarray(fp_k), np.asarray(fp_r))
    np.testing.assert_array_equal(np.asarray(len_k), np.asarray(len_r))
    assert not np.asarray(fp_k)[2].any() and not np.asarray(len_k)[2].any()
    for row in range(2):
        cuts = b[row, : c[row]]
        np.testing.assert_array_equal(np.asarray(fp_k)[row, : c[row]],
                                      fingerprints_numpy(x[row], cuts))


# -- the scheduler hot path -----------------------------------------------------

def test_scheduler_fp_pallas_bit_identity(rng):
    """fp_impl='pallas' with the cross-check armed: results identical to the
    reference scheduler, and the first-dispatch guard actually ran."""
    sched = ChunkScheduler(P, slots=2, min_bucket=1024, fp_impl="pallas",
                           cross_check_fps=True)
    ref = ChunkScheduler(P, slots=2, min_bucket=1024)
    streams = [rng.integers(0, 256, n, dtype=np.uint8)
               for n in (100, 1000, 1024, 3000, 5000)]
    for i, s in enumerate(streams):
        sched.submit(s, tag=i)
        ref.submit(s, tag=i)
    got = {r.tag: r for r in sched.drain()}
    for r in ref.drain():
        assert got[r.tag].bounds.tolist() == r.bounds.tolist()
        np.testing.assert_array_equal(got[r.tag].fps, r.fps)
    assert sched._fp_checked_buckets  # the guard actually ran


# -- the platform chooses the served path ----------------------------------


def _backend(monkeypatch, name: str):
    """Make ``jax.default_backend()`` report ``name`` (nothing dispatches)."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: name)


@pytest.mark.parametrize("backend,want", [("tpu", "pallas"),
                                          ("cpu", "reference")])
def test_fp_impl_follows_platform(backend, want, monkeypatch):
    """Unset, ``fp_impl`` resolves once from the backend: the kernel on a
    TPU, the reference chain elsewhere; the services pass None through."""
    from repro.service import DedupService, ShardedDedupService

    _backend(monkeypatch, backend)
    assert ChunkScheduler(P).fp_impl == want
    assert ChunkScheduler(P, fp_impl=None).fp_impl == want
    assert DedupService(params=P).scheduler.fp_impl == want
    with ShardedDedupService(2, params=P) as svc:
        assert svc.scheduler.fp_impl == want


def test_fp_impl_reference_under_cpu_backend():
    """The tier-1 backend itself (no monkeypatch) resolves to the
    reference chain."""
    import jax

    assert jax.default_backend() == "cpu"
    assert ChunkScheduler(P).fp_impl == "reference"


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
@pytest.mark.parametrize("explicit", ["reference", "pallas"])
def test_explicit_fp_impl_wins(backend, explicit, monkeypatch):
    from repro.service import DedupService, ShardedDedupService

    _backend(monkeypatch, backend)
    assert ChunkScheduler(P, fp_impl=explicit).fp_impl == explicit
    assert DedupService(params=P, fp_impl=explicit).scheduler.fp_impl \
        == explicit
    with ShardedDedupService(2, params=P, fp_impl=explicit) as svc:
        assert svc.scheduler.fp_impl == explicit


def test_fingerprint_divergence_raises(rng, monkeypatch):
    """The guard fires when a corrupted kernel result is injected: the
    cross-check's replay sees fingerprints that differ from the dispatch."""
    import repro.service.scheduler as sched_mod

    # packing off: this pins the *bucket* path's guard, which fires at
    # submit time (under REPRO_PACKING_IMPL=segments a 900-byte stream
    # would queue for a packed row instead)
    sched = ChunkScheduler(P, slots=1, min_bucket=1024, fp_impl="reference",
                           cross_check_fps=True, packing_impl="off")
    real = sched_mod.chunk_fingerprints

    def lying(data, b, c, **kw):
        fp, lens = real(data, b, c, **kw)
        if kw.get("fp_impl") == "pallas":  # corrupt only the kernel path
            return fp ^ 1, lens  # flip one bit of every fingerprint
        return fp, lens

    monkeypatch.setattr(sched_mod, "chunk_fingerprints", lying)
    with pytest.raises(FingerprintDivergenceError):
        sched.submit(rng.integers(0, 256, 900, dtype=np.uint8))


def test_unknown_fp_impl_rejected(rng):
    data = rng.integers(0, 256, 100, dtype=np.uint8)
    with pytest.raises(ValueError):
        chunk_fingerprints(jnp.asarray(data),
                           jnp.asarray(np.array([100], dtype=np.int32)),
                           jnp.asarray(1), max_chunks=1, fp_impl="bogus")
