"""Pallas TPU kernel: SeqCDC candidate/opposing bitmaps (phase 1).

TPU adaptation of the paper's AVX-512 scan (SSIII-D, Fig. 3).  The AVX version
loads 64-byte registers at offsets 0..SeqLength-1 and combines pairwise
``cmpgt`` masks; here each grid step stages a TILE-byte VMEM block (plus a
halo of the next tile's first bytes, a second block view of the same array
so BlockSpecs stay non-overlapping) and performs the same shifted compares
on 8x128 VPU lanes.  Per byte of input the kernel does L-1 compares + L-2 ANDs + 1 compare
— arithmetic intensity ~L ops/byte, firmly HBM-bandwidth-bound, which is the
design point: phase 1 runs at memory speed and phase 2 (core/automaton.py)
touches only per-block summaries.

Layout (``kernels/lanes.py``): a TILE-byte block is a ``(TILE // 128,
128)`` uint8 array and the halo is the first ``(32, 128)`` block of the
next tile, so every block is tile-aligned for the chip compiler; the
shifted compares are lane rolls.  Outputs are int8 0/1 lanes (Mosaic
stores no bool arrays) turned into bool bitmaps by the wrapper.  VMEM per
grid step (TILE = 64 KiB): input 64 KiB + 4 KiB halo + 2 x 64 KiB outputs
+ int32 shifted temporaries ~ 1.5 MiB << 16 MiB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import lanes

DEFAULT_TILE = 64 * 1024


def _masks_kernel(x_ref, halo_ref, cand_ref, opp_ref, *, L: int, inc: bool):
    ext = jnp.concatenate([x_ref[...].astype(jnp.int32),
                           halo_ref[...].astype(jnp.int32)], axis=0)
    cand, opp = lanes.mask_lanes(ext, x_ref.shape[0], L, inc)
    cand_ref[...] = cand.astype(jnp.int8)
    opp_ref[...] = opp.astype(jnp.int8)


@functools.partial(
    jax.jit, static_argnames=("seq_length", "mode", "tile", "interpret")
)
def seqcdc_masks_pallas(
    data: jax.Array,
    seq_length: int,
    mode: str = "increasing",
    *,
    tile: int = DEFAULT_TILE,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """(candidate, opposing) bitmaps for a 1-D uint8 stream of any length.

    Pads to a tile multiple, runs the grid, then masks the tail so that
    cand[k] is False for k > n - L and opp[n-1:] is False — bit-identical to
    kernels/ref.py::seqcdc_masks.
    """
    assert data.ndim == 1, data.shape
    n = data.shape[0]
    L = int(seq_length)
    if L > lanes.LANES:
        raise ValueError(f"seq_length {L} exceeds {lanes.LANES}")
    inc = mode == "increasing"
    if n == 0:
        z = jnp.zeros((0,), dtype=bool)
        return z, z
    hrows = lanes.halo_rows(L - 1)
    quantum = hrows * lanes.LANES
    tile = max(quantum, min(lanes.round_up(tile, quantum),
                            lanes.round_up(n, quantum)))
    nt = (n + tile - 1) // tile
    R = tile // lanes.LANES
    x = lanes.as_rows(data.astype(jnp.uint8), nt * R + hrows)

    cand, opp = pl.pallas_call(
        functools.partial(_masks_kernel, L=L, inc=inc),
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((R, lanes.LANES), lambda i: (i, 0)),
            # the halo: the next tile's first block of the same array
            pl.BlockSpec((hrows, lanes.LANES),
                         lambda i: ((i + 1) * (R // hrows), 0)),
        ],
        out_specs=[
            pl.BlockSpec((R, lanes.LANES), lambda i: (i, 0)),
            pl.BlockSpec((R, lanes.LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nt * R, lanes.LANES), jnp.int8),
            jax.ShapeDtypeStruct((nt * R, lanes.LANES), jnp.int8),
        ],
        interpret=interpret,
        name="chunk_masks",
    )(x, x)

    idx = jnp.arange(n)
    cand = (idx <= n - L) & (lanes.unrows(cand, n) != 0)
    opp = (idx < n - 1) & (lanes.unrows(opp, n) != 0)
    return cand, opp
