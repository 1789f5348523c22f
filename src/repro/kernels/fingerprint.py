"""Pallas TPU kernel: fused 62-bit chunk fingerprints (chunk-hashing hot path).

The reference pipeline (``dedup/fingerprint.py``, ``fp_impl="reference"``)
is gather-bound: per byte it pays a ``searchsorted`` over the chunk bounds,
a random gather from the 64 Ki-entry power table, and two ``segment_sum``
scatter-adds.  This kernel removes every per-byte gather/scatter with an
algebraic refactor of the polynomial hash

    h_r(chunk) = sum_i b_i * r^(len-1-i)   mod p,   p = 2^31 - 1,
               = (P_r(e) - P_r(s)) * r^(e-1) mod p,
    P_r(i)     = sum_{j<i} b_j * r^-j            (negative exponents via the
                                                  Fermat inverse, p prime)

for a chunk ``[s, e)``.  The grid walks one stream's tiles in order
(``kernels/lanes.py`` layout: a tile is ``(TILE // 128, 128)`` byte lanes)
and per tile, for both generators in one pass:

1. ``w[q] = b[q] * r^-q`` — the 8-conditional-rotation byte mulmod against
   a *fixed per-lane weight table* (the same VMEM block every grid step: no
   per-byte table gather), summed per 1024-byte group into an SMEM prefix
   table;
2. for every chunk whose end falls in this tile (an index range computed
   by the wrapper with one ``searchsorted`` per tile), ``P(e)`` from the
   cross-tile carry ``P(t0)``, the group table and one masked in-group
   reduction, the factor ``r^(e-1) = r^t0 * r^(e-1-t0)`` from a resident
   power table, and the fingerprint against the latched ``P(s)`` of the
   previous chunk, written with a masked store.

The service scheduler runs this kernel on a TPU (its default ``fp_impl``
there) and the reference chain on the CPU, where the kernel would run in
the Pallas interpreter.

Output is bit-identical to ``chunk_fingerprints(..., fp_impl="reference")``
and to ``fingerprints_numpy`` — tests/test_fingerprint_kernel.py and the
scheduler's first-dispatch cross-check (docs/KERNELS.md) enforce it;
tests/test_tpu_compile.py compiles it for a TPU v5e.

Constraints: TILE a multiple of 4096 (whole uint8 ``(32, 128)`` tiles);
chunk lengths <= MAX_CHUNK = 65536 (the power-table bound, same as the
reference); streams < 2 GiB — int32 byte positions, the same cap as the
reference path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import lanes
from .lanes import GROUP, LANES, P31, addmod, mulmod

DEFAULT_TILE = 64 * 1024


def _fp_kernel(tsc_ref, x_ref, bounds_ref, wneg_ref, postab_ref, fps_ref,
               st_ref, gp_ref, w_ref, *, tile: int):
    t0 = tsc_ref[0, 0]  # tile start offset in the stream
    rneg = (tsc_ref[0, 1], tsc_ref[0, 2])  # r^-t0
    rpos = (tsc_ref[0, 3], tsc_ref[0, 4])  # r^t0
    c_lo, c_hi = tsc_ref[0, 5], tsc_ref[0, 6]  # chunks ending in this tile
    hl = lanes.HashLanes(w_ref, gp_ref, tile // LANES)

    @pl.when(t0 == 0)  # first tile: P(0) carry and P(s) latch are zero
    def _init():
        for i in range(4):
            st_ref[i] = jnp.int32(0)
        fps_ref[...] = jnp.zeros(fps_ref.shape, jnp.int32)

    hl.fill(x_ref[...].astype(jnp.int32), wneg_ref)
    carry = (st_ref[0], st_ref[1])

    def body(c, ps):
        e = lanes.read_slot(bounds_ref, c)  # t0 < e <= t0 + tile
        pe = []
        for g in range(2):
            pe.append(addmod(carry[g],
                             mulmod(rneg[g], hl.prefix(g, e - t0), 31)))
            rfac = mulmod(rpos[g],
                          lanes.read_slot(postab_ref, e - 1 - t0, g), 31)
            lanes.write_slot(fps_ref, c,
                             mulmod(addmod(pe[g], P31 - ps[g]), rfac, 31), g)
        return tuple(pe)

    ps = jax.lax.fori_loop(c_lo, c_hi, body, (st_ref[2], st_ref[3]))
    for g in range(2):
        st_ref[g] = addmod(
            carry[g], mulmod(rneg[g], hl.total(g, tile // GROUP), 31))
        st_ref[2 + g] = ps[g]


@functools.partial(
    jax.jit, static_argnames=("max_chunks", "tile", "interpret")
)
def fingerprint_pallas(
    data: jax.Array,
    bounds: jax.Array,
    count: jax.Array,
    *,
    max_chunks: int,
    tile: int = DEFAULT_TILE,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """Per-chunk (fp (max_chunks, 2) uint32, lengths (max_chunks,) int32).

    Drop-in for ``chunk_fingerprints`` (same bounds layout: exclusive ends,
    sorted, sentinel-padded past ``count``; entries past ``count`` zeroed).
    """
    assert data.ndim == 1, data.shape
    n = data.shape[-1]
    if n == 0:
        return (jnp.zeros((max_chunks, 2), jnp.uint32),
                jnp.zeros((max_chunks,), jnp.int32))
    quantum = lanes.BYTE_ROWS * LANES
    tile = max(quantum, min(lanes.round_up(tile, quantum),
                            lanes.round_up(n, quantum)))
    nt = (n + tile - 1) // tile
    R = tile // LANES
    x = lanes.as_rows(data.astype(jnp.uint8), nt * R)
    b32 = bounds.astype(jnp.int32)
    starts32 = jnp.concatenate([jnp.zeros((1,), jnp.int32), b32[:-1]])
    mcr = lanes.round_up(max_chunks, GROUP) // LANES
    wneg, postab = lanes.hash_tables(R)
    # per-tile scalars: t0, r^-t0, r^t0, and the range [c_lo, c_hi) of
    # valid chunks whose exclusive end falls in (t0, t0 + tile]
    valid = jnp.arange(max_chunks) < count
    key = jnp.where(valid, b32, jnp.int32(2**31 - 1))
    tile_ends = (np.arange(1, nt + 1) * tile).astype(np.int32)
    c_hi = jnp.searchsorted(key, tile_ends, side="right").astype(jnp.int32)
    c_lo = jnp.concatenate([jnp.zeros((1,), jnp.int32), c_hi[:-1]])
    tsc = (jnp.asarray(lanes.tile_scalars(nt, tile))
           .at[:, 0, 5].set(c_lo).at[:, 0, 6].set(c_hi))

    fps = pl.pallas_call(
        functools.partial(_fp_kernel, tile=tile),
        grid=(nt,),  # sequential: the prefix carry threads through tiles
        in_specs=[
            # per-tile scalars as operands, not program_id: stays correct
            # when the whole call is vmapped over a batch
            pl.BlockSpec((None, 1, 8), lambda i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((R, LANES), lambda i: (i, 0)),
            pl.BlockSpec((mcr, LANES), lambda i: (0, 0)),
            pl.BlockSpec((2, R, LANES), lambda i: (0, 0, 0)),
            pl.BlockSpec((2, R, LANES), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((2, mcr, LANES), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((2, mcr, LANES), jnp.int32),
        scratch_shapes=[
            pltpu.SMEM((4,), jnp.int32),  # P(t0) carry x2, P(s) latch x2
            pltpu.SMEM((2 * (R // 8 + 1),), jnp.int32),  # group prefixes
            pltpu.VMEM((2, R, LANES), jnp.int32),  # hash weights
        ],
        interpret=interpret,
        name="chunk_fingerprint",
    )(tsc, x, lanes.as_rows(b32, mcr), wneg, postab)

    fp = lanes.unrows(fps, max_chunks).T.astype(jnp.uint32)
    lengths = b32 - starts32  # same masked tail as the reference path
    fp = jnp.where(valid[:, None], fp, 0)
    lengths = jnp.where(valid, lengths, 0)
    return fp, lengths
