"""Pallas TPU kernel: per-block byte maxima (VectorCDC range-scan substrate).

VectorCDC accelerates RAM/AE by vectorizing their two phases, *extreme byte
search* and *range scan*.  On TPU the range scan maps to per-block maxima
computed at HBM bandwidth; the hashless automatons (the AE/RAM chunkers in
core/baselines/hashless.py) then skip whole blocks whose max cannot beat
the running extreme and only descend into candidate blocks — the same
wide-compare/first-hit pattern as VectorCDC's movemask+ffs, expressed as
block max + masked argmin.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK = 128
DEFAULT_TILE_BLOCKS = 512  # 512 blocks x 128 B = 64 KiB per grid step


def _block_max_kernel(x_ref, out_ref, *, block: int):
    x = x_ref[...]  # (TB * block,)
    tb = x.shape[0] // block
    out_ref[...] = jnp.max(x.reshape(tb, block), axis=-1)


@functools.partial(
    jax.jit, static_argnames=("block", "tile_blocks", "interpret")
)
def block_max_pallas(
    data: jax.Array,
    *,
    block: int = DEFAULT_BLOCK,
    tile_blocks: int = DEFAULT_TILE_BLOCKS,
    interpret: bool,
) -> jax.Array:
    """Per-block maxima of a 1-D uint8 stream; pads tail with 0 (neutral)."""
    assert data.ndim == 1
    n = data.shape[0]
    if n == 0:
        return jnp.zeros((0,), dtype=jnp.uint8)
    nb = (n + block - 1) // block
    tb = min(tile_blocks, nb)
    nb_pad = (nb + tb - 1) // tb * tb
    x = jnp.pad(data.astype(jnp.uint8), (0, nb_pad * block - n))
    nt = nb_pad // tb

    out = pl.pallas_call(
        functools.partial(_block_max_kernel, block=block),
        grid=(nt,),
        in_specs=[pl.BlockSpec((tb * block,), lambda i: (i,))],
        out_specs=pl.BlockSpec((tb,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((nb_pad,), jnp.uint8),
        interpret=interpret,
    )(x)
    return out[:nb]
