"""Pallas TPU kernel: parallel Gear rolling hash (SS-CDC substrate).

The Gear recurrence h[i] = (h[i-1] << 1) + G[b[i]] (uint32) looks sequential,
but the 32-bit register forgets contributions older than 32 bytes, so the
hash admits the closed window form

    h[i] = sum_{j=0..31} G[b[i-j]] << j      (uint32 wraparound)

— 32 independent table lookups + shifted adds per position.  This is the TPU
answer to SS-CDC's "roll with multiple heads" AVX-512 trick: instead of
scatter/gather across stream regions (expensive on TPU), we trade 32x
redundant VMEM table lookups for full data parallelism.  See DESIGN.md SS2.

Each grid step stages a TILE block with a 31-byte *left* halo of real
predecessor bytes; the first 31 positions of the stream (no predecessors) are
fixed up exactly in the wrapper.  The 256 x uint32 Gear table rides along in
VMEM (1 KiB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import gear_table

DEFAULT_TILE = 32 * 1024
_WIN = 32


def _gear_kernel(x_ref, head_ref, table_ref, out_ref):
    x = x_ref[...]  # (TILE,) uint8
    head = head_ref[0]  # (31,) uint8 : last 31 bytes of previous tile
    table = table_ref[...]  # (256,) uint32
    ext = jnp.concatenate([head, x])  # (TILE + 31,)
    g = table[ext.astype(jnp.int32)]  # VMEM gather
    tile = x.shape[0]
    acc = jnp.zeros((tile,), dtype=jnp.uint32)
    for j in range(_WIN):  # h[i] = sum_j G[b[i-j]] << j
        acc = acc + (g[_WIN - 1 - j : _WIN - 1 - j + tile] << j)
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def gear_hash_pallas(
    data: jax.Array,
    table: jax.Array | None = None,
    *,
    tile: int = DEFAULT_TILE,
    interpret: bool,
) -> jax.Array:
    """Per-position uint32 Gear hash of a 1-D uint8 stream (any length)."""
    assert data.ndim == 1, data.shape
    n = data.shape[0]
    if table is None:
        table = gear_table()
    if n == 0:
        return jnp.zeros((0,), dtype=jnp.uint32)
    tile = min(tile, max(1024, ((n + 1023) // 1024) * 1024))
    n_pad = (n + tile - 1) // tile * tile
    x = jnp.pad(data.astype(jnp.uint8), (0, n_pad - n))
    nt = n_pad // tile
    # heads[i] = x[i*tile - 31 : i*tile]  (zeros for i == 0)
    heads = jnp.pad(x, (tile, 0)).reshape(nt + 1, tile)[:-1, -(_WIN - 1):]

    out = pl.pallas_call(
        _gear_kernel,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((tile,), lambda i: (i,)),
            pl.BlockSpec((1, _WIN - 1), lambda i: (i, 0)),
            pl.BlockSpec((256,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((tile,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), jnp.uint32),
        interpret=interpret,
    )(x, heads, table)

    out = out[:n]
    # exact fix-up for the first 31 positions (zero-halo contributions differ)
    k = min(_WIN - 1, n)
    g0 = table[data[:k].astype(jnp.int32)]
    fix = jnp.zeros((k,), dtype=jnp.uint32)
    idx = jnp.arange(k)
    for j in range(_WIN):
        if j >= k:
            break
        sh = jnp.where(idx >= j, jnp.roll(g0, j) << j, 0)
        fix = fix + sh.astype(jnp.uint32)
    return out.at[:k].set(fix) if k else out
