"""Chunk fingerprinting in JAX (paper SSII "Chunk Hashing").

The deduplication pipeline needs a content-only fingerprint per chunk.  On
the accelerator we use a 62-bit fingerprint built from two independent
polynomial hashes mod p = 2^31 - 1:

    h_r(chunk) = sum_i  b_i * r^(len-1-i)   mod p

computed *fully in parallel* over all bytes of all chunks: each byte's
contribution is b * r^(offset-from-chunk-end), a per-byte table gather plus a
multiply realised as 8 conditional 31-bit rotations (x * 2^k mod 2^31-1 is a
k-rotation of the 31-bit word — no 64-bit arithmetic needed, DESIGN.md SS8),
followed by a segment sum in 16-bit limbs to avoid uint32 overflow.

Collision-resistant SHA-256 (host-side, hashlib) is used where the paper
requires it — the content-addressed block store — in dedup/store.py.

Constraint: chunk length < 65536 bytes (the power table and the limb-sum
overflow bound).  All chunking configs here have max_size <= 64 KiB.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import stages

#: backend for :func:`chunk_fingerprints`: the jnp ``searchsorted``/gather/
#: ``segment_sum`` chain ("reference") or the fused Pallas kernel
#: (kernels/fingerprint.py) — bit-identical, guarded by the scheduler's
#: first-dispatch cross-check (docs/KERNELS.md); the service scheduler runs
#: the kernel on a TPU and the reference chain elsewhere
FpImpl = Literal["reference", "pallas"]

P31 = np.uint32((1 << 31) - 1)
MAX_CHUNK = 1 << 16
#: two independent generators (fixed, arbitrary < p)
R1 = 1_103_515_245
R2 = 747_796_405


@functools.lru_cache(maxsize=None)
def _pow_table_np(r: int, size: int = MAX_CHUNK) -> np.ndarray:
    p = (1 << 31) - 1
    out = np.empty(size, dtype=np.uint32)
    acc = 1
    for e in range(size):
        out[e] = acc
        acc = (acc * r) % p
    return out


def _rot31(x, k: int):
    """x * 2^k mod (2^31 - 1) for x < p: a 31-bit rotation."""
    return ((x << k) | (x >> (31 - k))) & P31


def _mulmod(b, y, bits: int = 8):
    """b * y mod p for b < 2^bits, y < p — ``bits`` conditional rotations
    (x * 2^j mod p is a j-rotation of the 31-bit word).  bits=8 is the
    per-byte form; the Pallas kernel uses bits=31 for general factors."""
    acc = jnp.zeros_like(y)
    for j in range(bits):
        bit = (b >> j) & 1
        term = _rot31(y, j)
        acc = _addmod(acc, jnp.where(bit.astype(bool), term, 0))
    return acc


def _byte_mulmod(b, y):
    """b * y mod p for b in [0,256), y < p — 8 conditional rotations."""
    return _mulmod(b, y, 8)


def _addmod(a, b):
    s = a + b  # a,b < p  =>  s < 2p < 2^32: one conditional subtract
    return jnp.where(s >= P31, s - P31, s)


def _segment_fold(contrib, seg, num_segments: int):
    """Segment-sum of values < p with exact mod-p folding via 16-bit limbs."""
    lo = contrib & 0xFFFF
    hi = contrib >> 16
    lo_s = jax.ops.segment_sum(lo, seg, num_segments=num_segments)
    hi_s = jax.ops.segment_sum(hi, seg, num_segments=num_segments)
    # lo_s < 2^16 * 2^16 = 2^32 (max chunk 65536 bytes): fold mod p
    lo_m = _fold32(lo_s)
    hi_m = _fold32(hi_s)
    return _addmod(lo_m, _rotk(hi_m, 16))


def _fold32(x):
    """x (uint32) mod p via 2^31 === 1: x = (x & p) + (x >> 31), twice."""
    x = (x & P31) + (x >> 31)
    return jnp.where(x >= P31, x - P31, x)


def _rotk(x, k: int):
    return _rot31(x, k)


@functools.partial(jax.jit, static_argnames=("max_chunks", "fp_impl"))
def chunk_fingerprints(
    data: jax.Array,
    bounds: jax.Array,
    count: jax.Array,
    *,
    max_chunks: int,
    fp_impl: FpImpl = "reference",
) -> tuple[jax.Array, jax.Array]:
    """Per-chunk (fp (max_chunks, 2) uint32, lengths (max_chunks,) int32).

    ``bounds`` are exclusive chunk ends, sorted, sentinel-padded past
    ``count`` (the layout produced by core.seqcdc / core.chunker).
    Entries past ``count`` have fp = 0 and length = 0.

    ``fp_impl="pallas"`` dispatches to the fused kernel
    (kernels/fingerprint.py, interpreted on the CPU, compiled on a TPU) —
    bit-identical output, no per-byte gather/scatter.
    """
    with jax.named_scope(stages.FINGERPRINT):
        if fp_impl == "pallas":
            # lazy: no cycle (see the ops docstring)
            from repro.kernels import ops

            return ops.chunk_fingerprints(data, bounds, count,
                                          max_chunks=max_chunks)
        if fp_impl != "reference":
            raise ValueError(f"unknown fp_impl {fp_impl!r}")
        n = data.shape[-1]
        d = data.astype(jnp.uint32)
        idx = jnp.arange(n, dtype=jnp.int32)
        # chunk id per byte: first j with bounds[j] > idx (the sentinel
        # keeps it valid)
        seg = jnp.searchsorted(bounds, idx, side="right").astype(jnp.int32)
        seg = jnp.minimum(seg, max_chunks - 1)
        end = bounds[seg]
        e = jnp.clip(end - 1 - idx, 0, MAX_CHUNK - 1)  # offset from the end

        fps = []
        for r in (R1, R2):
            pow_r = jnp.asarray(_pow_table_np(r))
            contrib = _byte_mulmod(d, pow_r[e])
            fps.append(_segment_fold(contrib, seg, max_chunks))
        fp = jnp.stack(fps, axis=-1)

        starts = jnp.concatenate([jnp.zeros((1,), bounds.dtype),
                                  bounds[:-1]])
        lengths = (bounds - starts).astype(jnp.int32)
        valid = jnp.arange(max_chunks) < count
        fp = jnp.where(valid[:, None], fp, 0)
        lengths = jnp.where(valid, lengths, 0)
        return fp, lengths


def fingerprints_numpy(data: np.ndarray, bounds: np.ndarray) -> np.ndarray:
    """Host-side reference (tests): exact same 62-bit fingerprint."""
    p = (1 << 31) - 1
    out = np.zeros((len(bounds), 2), dtype=np.uint32)
    s = 0
    t1 = _pow_table_np(R1)
    t2 = _pow_table_np(R2)
    for j, e in enumerate(np.asarray(bounds, dtype=np.int64)):
        chunk = np.asarray(data[s:e], dtype=np.uint64)
        exp = np.arange(e - s - 1, -1, -1, dtype=np.int64)
        out[j, 0] = np.uint32((chunk * t1[exp].astype(np.uint64)).sum() % p)
        out[j, 1] = np.uint32((chunk * t2[exp].astype(np.uint64)).sum() % p)
        s = e
    return out
