"""Lane-layout helpers shared by the chip-compiled Pallas kernels.

Mosaic (the TPU kernel compiler) works on ``(8, 128)`` 32-bit vector
registers.  The byte kernels of this package therefore view a tile of
``T`` stream bytes as a ``(T // 128, 128)`` array — flat position ``q``
sits at ``(q // 128, q % 128)`` — and never index a vector value at a
dynamic position.  The idioms, each of which lowers on the chip and runs
unchanged in interpret mode on the CPU:

* **shifted views** — byte ``q + j`` at position ``q`` is two rolls and a
  lane select (:func:`shift_flat`); the pairwise compares of the SeqCDC
  mask lanes are built from them;
* **prefix sums** — Hillis-Steele roll-and-add passes over lanes, then
  over rows (:func:`prefix_sum_flat`): Mosaic has no ``cumsum``;
* **dynamic element access** — a 1024-position *group* (one ``(8, 128)``
  register, 8 rows at an 8-aligned row offset) is loaded at a dynamic row
  offset, and the element is selected by a masked reduction
  (:func:`read_slot`) or replaced by a masked store (:func:`write_slot`);
* **mod-p arithmetic in int32** — every hash value is a canonical residue
  ``< p = 2^31 - 1``, so signed 32-bit words hold it exactly; the
  add/rotate/multiply helpers below give the same residues as the uint32
  helpers of ``dedup/fingerprint.py`` (unsigned reductions do not lower).

:class:`HashLanes` reads a tile's position-weighted prefix
``sum_{q<m} b_q * w_q mod p`` at any dynamic ``m`` from a per-group table
kept in SMEM plus one masked in-group reduction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.dedup.fingerprint import R1, R2, _pow_table_np

LANES = 128
GROUP_ROWS = 8
GROUP = GROUP_ROWS * LANES  # positions per (8, 128) int32 register
#: uint8 blocks tile as (32, 128): byte tiles are multiples of 4096
BYTE_ROWS = 32

P31 = np.int32((1 << 31) - 1)


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def halo_rows(halo: int) -> int:
    """Rows of the halo block that holds ``halo`` bytes past a tile: a power
    of two of at least one uint8 tile, so it divides any tile that is a
    multiple of it (the halo is the next tile's first block)."""
    rows = BYTE_ROWS
    while rows * LANES < halo:
        rows *= 2
    return rows


def as_rows(x: jax.Array, rows: int) -> jax.Array:
    """Zero-pad ``(..., n)`` to ``rows * 128`` and view it as ``(..., rows, 128)``."""
    n = x.shape[-1]
    pad = [(0, 0)] * (x.ndim - 1) + [(0, rows * LANES - n)]
    return jnp.pad(x, pad).reshape(*x.shape[:-1], rows, LANES)


def table_rows(vec: np.ndarray, rows: int) -> np.ndarray:
    """A host table as ``(rows, 128)`` int32 (values must be < 2^31)."""
    out = np.zeros(rows * LANES, dtype=np.int32)
    out[: min(vec.size, out.size)] = vec[: out.size]
    return out.reshape(rows, LANES)


# -- mod-p arithmetic on canonical int32 residues ------------------------------


def rot31(x, k: int):
    """x * 2^k mod p for 0 <= x < p: a 31-bit rotation."""
    if k == 0:
        return x
    return ((x << k) | (x >> (31 - k))) & P31


def addmod(a, b):
    """(a + b) mod p for a < p, b <= p, without leaving int32."""
    s = a - (P31 - b)
    return jnp.where(s < 0, s + P31, s)


def mulmod(b, y, bits: int):
    """b * y mod p for b < 2^bits, y < p: ``bits`` conditional rotations
    (unrolled for the per-byte form, a loop for full-width factors)."""
    if bits <= 8:
        acc = jnp.zeros_like(y)
        for j in range(bits):
            acc = addmod(acc, jnp.where(((b >> j) & 1) == 1, rot31(y, j), 0))
        return acc

    def body(j, st):
        acc, term = st  # term = y * 2^j mod p
        acc = addmod(acc, jnp.where(((b >> j) & 1) == 1, term, 0))
        return acc, rot31(term, 1)

    return jax.lax.fori_loop(0, bits, body, (jnp.zeros_like(y), y))[0]


# -- flat-position views --------------------------------------------------------


def flat_iota(rows: int) -> jax.Array:
    """``(rows, 128)`` flat positions ``row * 128 + lane``."""
    shape = (rows, LANES)
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def shift_flat(x: jax.Array, j: int) -> jax.Array:
    """``y[q] = x[q + j]`` in flat order for ``0 < j < 128``; the last row
    wraps (callers read only rows with ``j`` bytes to spare behind them)."""
    assert 0 < j < LANES, j
    rows = x.shape[0]
    a = pltpu.roll(x, LANES - j, 1)  # a[r, c] = x[r, (c + j) % 128]
    b = pltpu.roll(a, rows - 1, 0)  # b[r, c] = a[r + 1, c]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane < LANES - j, a, b)


def prefix_sum_flat(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum of an int32 ``(rows, 128)`` array in flat order."""
    rows = x.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    tot = jnp.broadcast_to(jnp.sum(x, axis=1, keepdims=True), x.shape)
    d = 1
    while d < LANES:
        x = x + jnp.where(lane >= d, pltpu.roll(x, d, 1), 0)
        d *= 2
    acc = tot
    d = 1
    while d < rows:
        acc = acc + jnp.where(row >= d, pltpu.roll(acc, d, 0), 0)
        d *= 2
    return x + acc - tot


def mask_lanes(ext: jax.Array, rows: int, seq_length: int, increasing: bool):
    """SeqCDC phase-1 bits for the first ``rows`` rows of ``ext``.

    ``ext``: ``(rows + halo_rows, 128)`` int32 bytes (tile then halo).
    Returns ``(cand, opp)`` bool ``(rows, 128)``: ``cand[q]`` is the AND of
    the ``L - 1`` forward pair compares starting at ``q``, ``opp[q]`` the
    opposite compare of pair ``(q, q + 1)`` — ``core/masks.py``'s decisions
    before the stream-end clipping, which callers apply.
    """
    views = [ext] + [shift_flat(ext, j) for j in range(1, seq_length)]
    views = [v[:rows] for v in views]
    fwd, opp = [], None
    for j in range(seq_length - 1):
        gt = views[j + 1] > views[j]
        lt = views[j + 1] < views[j]
        fwd.append(gt if increasing else lt)
        if j == 0:
            opp = lt if increasing else gt
    cand = fwd[0]
    for f in fwd[1:]:
        cand = jnp.logical_and(cand, f)
    return cand, opp


# -- dynamic element access through (8, 128) groups ---------------------------


def group_iota() -> jax.Array:
    return flat_iota(GROUP_ROWS)


def group_rows(idx):
    """Row slice of the 1024-position group holding flat position ``idx``."""
    start = pl.multiple_of((idx // GROUP) * GROUP_ROWS, GROUP_ROWS)
    return pl.ds(start, GROUP_ROWS)


def read_slot(ref, idx, *lead):
    """``ref[*lead]`` viewed flat, element ``idx`` (int32, idx >= 0)."""
    v = ref[(*lead, group_rows(idx), slice(None))]
    return jnp.sum(jnp.where(group_iota() == idx % GROUP, v, 0))


def write_slot(ref, idx, val, *lead):
    """Set element ``idx`` of ``ref[*lead]`` viewed flat (a masked store)."""
    rows = group_rows(idx)
    v = ref[(*lead, rows, slice(None))]
    ref[(*lead, rows, slice(None))] = jnp.where(
        group_iota() == idx % GROUP, val, v)


def unrows(x: jax.Array, n: int) -> jax.Array:
    """Inverse of :func:`as_rows`: ``(..., rows, 128)`` -> ``(..., n)``."""
    return x.reshape(*x.shape[:-2], -1)[..., :n]


# -- hash lanes -----------------------------------------------------------------


class HashLanes:
    """A tile's position-weighted byte sums for both generators.

    ``w_ref``: VMEM ``(2, rows, 128)`` int32, ``w[g, q] = b_q * wpow[g, q]
    mod p``; ``gp_ref``: SMEM, ``gp[base + g * (groups + 1) + i]`` the sum of
    generator ``g``'s first ``i`` groups mod p.  :meth:`prefix` then reads
    ``sum_{q<m} w[g, q] mod p`` at a dynamic ``m`` with one group load.
    A group sums at most 1024 weights < p: the 16-bit low limbs stay under
    2^26 and the high limbs under 2^25, both already canonical residues.
    """

    def __init__(self, w_ref, gp_ref, rows: int, base: int = 0):
        self.w_ref = w_ref
        self.gp_ref = gp_ref
        self.groups = rows // GROUP_ROWS
        self.base = base

    def _gp(self, g: int, i):
        return self.base + g * (self.groups + 1) + i

    def fill(self, ext: jax.Array, wpow_ref):
        """Weight the tile bytes ``ext`` and fill the group prefix table."""
        for g in range(2):
            self.w_ref[g] = mulmod(ext, wpow_ref[g], 8)
            self.gp_ref[self._gp(g, 0)] = jnp.int32(0)

            def body(i, acc, g=g):
                v = self.w_ref[g, pl.ds(
                    pl.multiple_of(i * GROUP_ROWS, GROUP_ROWS), GROUP_ROWS), :]
                part = addmod(jnp.sum(v & 0xFFFF),
                              rot31(jnp.sum(v >> 16), 16))
                acc = addmod(acc, part)
                self.gp_ref[self._gp(g, i + 1)] = acc
                return acc

            jax.lax.fori_loop(0, self.groups, body, jnp.int32(0))

    def total(self, g: int, groups: int):
        """Sum of the first ``groups`` whole groups (a static count)."""
        return self.gp_ref[self._gp(g, groups)]

    def prefix(self, g: int, m):
        """Sum of the first ``m`` weights, mod p (0 <= m <= rows * 128)."""
        i = jnp.maximum(m - 1, 0)
        v = self.w_ref[g, group_rows(i), :]
        sel = group_iota() <= i % GROUP
        lo = jnp.sum(jnp.where(sel, v & 0xFFFF, 0))
        hi = jnp.sum(jnp.where(sel, v >> 16, 0))
        part = addmod(addmod(self.gp_ref[self._gp(g, i // GROUP)], lo),
                      rot31(hi, 16))
        return jnp.where(m > 0, part, jnp.int32(0))


@functools.lru_cache(maxsize=None)
def negpow_table(r: int, size: int) -> np.ndarray:
    """w[q] = r^-q mod p — the fixed per-lane prefix weight vector."""
    pm = int(P31)
    inv = pow(r, pm - 2, pm)  # Fermat: p is prime
    out = np.empty(size, dtype=np.uint32)
    acc = 1
    for q in range(size):
        out[q] = acc
        acc = (acc * inv) % pm
    return out


def hash_tables(rows: int):
    """Resident ``(2, rows, 128)`` int32 tables ``r^-q`` and ``r^q`` for
    both generators, ``q < rows * 128``."""
    size = rows * LANES
    wneg = np.stack([table_rows(negpow_table(r, size), rows)
                     for r in (R1, R2)])
    wpos = np.stack([table_rows(_pow_table_np(r, size), rows)
                     for r in (R1, R2)])
    return jnp.asarray(wneg), jnp.asarray(wpos)


def tile_scalars(nt: int, tile: int, extra=()) -> np.ndarray:
    """(nt, 1, 8) int32 per-tile SMEM scalars:
    ``t0, r1^-t0, r2^-t0, r1^t0, r2^t0, *extra``."""
    pm = int(P31)
    out = np.zeros((nt, 1, 8), dtype=np.int64)
    for i in range(nt):
        t0 = i * tile
        row = [t0] + [pow(pow(r, pm - 2, pm), t0, pm) for r in (R1, R2)]
        row += [pow(r, t0, pm) for r in (R1, R2)] + list(extra)
        out[i, 0, : len(row)] = row
    return out.astype(np.int32)
