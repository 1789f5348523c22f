"""Pallas TPU kernel: fused single-dispatch SeqCDC chunk+fingerprint pipeline.

The split pipeline the scheduler composes (``pipeline_impl="split"``) runs
three dispatches per padded bucket: the phase-1 extremum-mask kernel, the
phase-2 boundary-selection scan, and the fingerprint kernel — the last of
which re-reads every byte the mask pass already touched.  SeqCDC's
throughput argument (and the follow-up AVX vector-chunking paper) is that
boundary detection and hashing should share one pass over the data; this
kernel is that fusion: per (row, tile) grid step the TILE-byte block (plus
a halo from the next tile) is read **once** as ``(rows, 128)`` lanes
(``kernels/lanes.py``) and feeds

1. the mask comparison lanes — shifted pairwise compares AND-reduced into
   the candidate bitmap, one opposite compare for the opposing bitmap
   (identical decisions to ``core/masks.py`` / ``kernels/seqcdc_masks.py``),
   stored with a running count of opposing bits for the automaton;
2. the hash lanes — per-byte weights against a *fixed* per-lane ``r^-q``
   table (8 conditional 31-bit rotations, no per-byte gather) and a
   per-1024-byte group prefix table in SMEM;
3. the boundary automaton — a scalar ``fori_loop`` over the tile's W-byte
   blocks running the exact ``_scan_wide`` step (it calls
   ``core/automaton._resolve`` itself): each block loads the one ``(8,
   128)`` register group that holds it and reduces the first candidate,
   the skip trigger and the opposing count with masked min/sum reductions.
   The scan state lives in SMEM scratch across tiles (the grid iterates
   row-major, tiles innermost).

Boundary decisions are consumed *in-kernel* to segment the hash reduction:
the moment a block emits a chunk end ``e``, the fingerprint of ``[s, e)``
is read off the running prefix state —

    h_r(chunk) = (P_r(e) - P_r(s)) * r^(e-1)  mod p,
    P_r(i)     = sum_{j<i} b_j * r^-j          (prefix of position-weighted
                                                bytes; negative exponents via
                                                the Fermat inverse, p prime)

— one group load and masked sum for ``P_r(e)``, one for the factor, three
31-rotation mulmods.  ``P_r(s)`` was latched when the previous boundary was
emitted, and the cross-tile carry ``P_r(t0)`` lives in SMEM, so chunks
spanning any number of tiles cost the same as local ones.  Outputs are
written with masked stores into the row's resident ``(rows, 128)`` output
blocks.  The final file-end boundary fixup of ``select_boundaries`` is
replicated in-kernel at the last tile (``r^(n-1)`` is a host operand).

Output is bit-identical to the composed split path — bounds/count from
``boundaries_batch(step_impl="wide")`` and fps/lengths from
``chunk_fingerprints`` — which tests/test_fused_pipeline.py, the
differential matrix harness (tests/test_pipeline_matrix.py), and the
scheduler's first-dispatch ``PipelineDivergenceError`` cross-check
(docs/KERNELS.md) all enforce; tests/test_tpu_compile.py compiles it for a
TPU v5e.

Constraints: TILE a multiple of the halo block (a power of two of at least
4096 bytes holding ``skip_size + seq_length - 1`` bytes: an overshooting
skip resolved as a cut can emit a bound that far past its block);
``seq_length <= 128``; chunk lengths <= ``MAX_CHUNK`` = 65536 (the
power-table bound, as everywhere); streams < 2 GiB (int32 positions).
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.automaton import _BIG, _resolve
from repro.core.params import SeqCDCParams
from repro.dedup.fingerprint import MAX_CHUNK, R1, R2, _pow_table_np

from . import lanes
from .lanes import GROUP, LANES, P31, addmod, mulmod

#: selects the scheduler's device pipeline: three dispatches ("split" —
#: masks, boundary scan, fingerprints) or this kernel ("fused")
PipelineImpl = Literal["split", "fused"]

DEFAULT_TILE = 32 * 1024


def _geometry(n: int, p: SeqCDCParams, tile: int):
    """(tile, nt, halo_rows, nb_split) for an ``n``-byte row.

    The split automaton pads its bitmaps so every event fires in-scan
    (``core/automaton._padded_blocks``): the grid covers exactly those
    ``nb_split`` blocks.  The halo block holds the bytes a mask compare or
    an emitted bound can reach past the tile (``skip_size + L - 1``).
    """
    if p.seq_length > LANES:
        raise ValueError(f"seq_length {p.seq_length} exceeds {LANES}")
    W = p.block_width
    hrows = lanes.halo_rows(p.skip_size + p.seq_length - 1)
    quantum = hrows * LANES
    nb_split = (n + p.skip_size + W + W - 1) // W
    cover = nb_split * W
    tile = max(quantum, min(lanes.round_up(tile, quantum),
                            lanes.round_up(cover, quantum)))
    assert tile % W == 0 and tile % GROUP == 0, (tile, W)
    nt = (cover + tile - 1) // tile
    return tile, nt, hrows, nb_split


def _empty(B: int, mc: int):
    return (jnp.full((B, mc), _BIG, jnp.int32), jnp.zeros((B,), jnp.int32),
            jnp.zeros((B, mc, 2), jnp.uint32), jnp.zeros((B, mc), jnp.int32))


def _out_specs_shapes(B: int, mc: int):
    mcr = lanes.round_up(mc, GROUP) // LANES
    specs = [
        pl.BlockSpec((None, mcr, LANES), lambda b, i: (b, 0, 0)),  # bounds
        pl.BlockSpec((None, mcr, LANES), lambda b, i: (b, 0, 0)),  # lengths
        pl.BlockSpec((None, 2, mcr, LANES), lambda b, i: (b, 0, 0, 0)),
        pl.BlockSpec((None, 8, LANES), lambda b, i: (b, 0, 0)),  # count
    ]
    shapes = [
        jax.ShapeDtypeStruct((B, mcr, LANES), jnp.int32),
        jax.ShapeDtypeStruct((B, mcr, LANES), jnp.int32),
        jax.ShapeDtypeStruct((B, 2, mcr, LANES), jnp.int32),
        jax.ShapeDtypeStruct((B, 8, LANES), jnp.int32),
    ]
    return specs, shapes


def _unpack_outputs(bounds, lens, fps, counts, mc: int):
    fps = jnp.swapaxes(lanes.unrows(fps, mc), 1, 2).astype(jnp.uint32)
    return (lanes.unrows(bounds, mc), counts[:, 0, 0], fps,
            lanes.unrows(lens, mc))


def _byte_specs(R: int, hrows: int):
    return [
        pl.BlockSpec((None, R, LANES), lambda b, i: (b, i, 0)),
        pl.BlockSpec((None, hrows, LANES),
                     lambda b, i: (b, (i + 1) * (R // hrows), 0)),
    ]


def _table_specs(ext_rows: int):
    return [pl.BlockSpec((2, ext_rows, LANES), lambda b, i: (0, 0, 0))] * 2


class _Outputs:
    """The row's resident output blocks, written one chunk at a time."""

    def __init__(self, bounds_ref, lens_ref, fps_ref, counts_ref, mc: int):
        self.bounds, self.lens, self.fps = bounds_ref, lens_ref, fps_ref
        self.counts = counts_ref
        self.mc = mc

    def init(self):
        self.bounds[...] = jnp.full(self.bounds.shape, _BIG, jnp.int32)
        self.lens[...] = jnp.zeros(self.lens.shape, jnp.int32)
        self.fps[...] = jnp.zeros(self.fps.shape, jnp.int32)
        self.counts[...] = jnp.zeros(self.counts.shape, jnp.int32)

    def put(self, cnt, bound, length, fp0, fp1):
        """Chunk ``cnt`` (dropped past ``mc``: the split path's
        ``mode="drop"`` scatter)."""
        @pl.when(cnt < self.mc)
        def _():
            lanes.write_slot(self.bounds, cnt, bound)
            lanes.write_slot(self.lens, cnt, length)
            lanes.write_slot(self.fps, cnt, fp0, 0)
            lanes.write_slot(self.fps, cnt, fp1, 1)

    def last_bound(self, cnt):
        return jnp.where(cnt > 0, lanes.read_slot(
            self.bounds, jnp.clip(cnt - 1, 0, self.mc - 1)), 0)


def _block_events(msk_ref, j: int, W: int, t0, k, c, T):
    """(kc, kt, carry) of W-block ``j`` from the packed mask lanes.

    ``msk = cand + 2 * opp + 4 * incl`` where ``incl`` is the tile's
    inclusive opposing-bit count; a W-block (W <= 1024, a power of two)
    never straddles a 1024-position group, so one register group holds it.
    ``kc``: first candidate at or after ``k``; ``kt``: first opposing pair
    whose running count (carry ``c`` plus active pairs so far) exceeds
    ``T``; ``carry``: ``c`` plus the block's active opposing pairs.
    """
    start = j * W
    v = msk_ref[lanes.group_rows(start), :]
    q = lanes.group_iota()
    off = start % GROUP
    pos = t0 + (start - off) + q
    act = (q >= off) & (q < off + W) & (pos >= k)
    cb = (v & 1) == 1
    ob = ((v >> 1) & 1) == 1
    incl = v >> 2
    kc = jnp.min(jnp.where(act & cb, pos, _BIG))
    # exclusive count at the first active position (incl is nondecreasing)
    base = jnp.min(jnp.where(act, incl - (v >> 1 & 1), _BIG))
    oa = act & ob
    kt = jnp.min(jnp.where(oa & (c + incl - base > T), pos, _BIG))
    carry = c + jnp.sum(jnp.where(oa, 1, 0))
    return kc, kt, carry


def _pipeline_kernel(
    tsc_ref, x_ref, halo_ref, wneg_ref, postab_ref,
    bounds_ref, lens_ref, fps_ref, counts_ref,
    st_ref, gp_ref, w_ref, msk_ref,
    *, p: SeqCDCParams, n: int, mc: int, tile: int, hrows: int,
    nb_split: int, last_t0: int,
):
    t0 = tsc_ref[0, 0]  # tile start offset in the (padded) stream
    rneg = (tsc_ref[0, 1], tsc_ref[0, 2])  # r^-t0
    rpos = (tsc_ref[0, 3], tsc_ref[0, 4])  # r^t0
    rnm1 = (tsc_ref[0, 5], tsc_ref[0, 6])  # r^(n-1)
    L = p.seq_length
    W = p.block_width
    R = tile // LANES
    ext_len = (R + hrows) * LANES
    T = jnp.int32(p.skip_trigger)
    out = _Outputs(bounds_ref, lens_ref, fps_ref, counts_ref, mc)
    hl = lanes.HashLanes(w_ref, gp_ref, R + hrows)

    @pl.when(t0 == 0)  # first tile of a row: reset state and outputs
    def _init():
        for i in range(8):  # k, c, s, cnt, P(t0) carry x2, P(s) latch x2
            st_ref[i] = jnp.int32(p.sub_min_skip if i == 0 else 0)
        out.init()

    # -- the one byte read: tile + halo from the next tile ------------------
    ext = jnp.concatenate([x_ref[...].astype(jnp.int32),
                           halo_ref[...].astype(jnp.int32)], axis=0)

    # -- mask lanes (phase 1, same decisions as core/masks.py) --------------
    cand, opp = lanes.mask_lanes(ext, R, L, p.mode == "increasing")
    pos = t0 + lanes.flat_iota(R)
    cand = cand & (pos <= n - L)  # the reference wrapper's tail masking
    opp = (opp & (pos < n - 1)).astype(jnp.int32)
    msk_ref[...] = (cand.astype(jnp.int32) + 2 * opp
                    + 4 * lanes.prefix_sum_flat(opp))

    # -- hash lanes: position-weighted bytes and their group prefixes ------
    hl.fill(ext, wneg_ref)
    carry = (st_ref[4], st_ref[5])  # P(t0) per generator

    def prefix_at(g, e):
        """P(e) for a stream position ``e`` inside [t0, t0 + ext_len]."""
        m = jnp.clip(e - t0, 0, ext_len)
        return addmod(carry[g], mulmod(rneg[g], hl.prefix(g, m), 31))

    def chunk_fp(g, ps_g, e):
        """(P(e) - P(s)) * r^(e-1): the fingerprint of the closing chunk."""
        pe = prefix_at(g, e)
        diff = addmod(pe, P31 - ps_g)
        fi = jnp.clip(e - 1 - t0, 0, ext_len - 1)
        rfac = mulmod(rpos[g], lanes.read_slot(postab_ref, fi, g), 31)
        # a bound behind this tile is only ever the file-end cut (the scan
        # position can overshoot cut_k = n - L + 1 when the tail is shorter
        # than a skip landing); its factor r^(n-1) is the host operand —
        # prefix_at is already exact there, P(t0) == P(n) past the data
        rfac = jnp.where(e - 1 - t0 < 0, rnm1[g], rfac)
        return pe, mulmod(diff, rfac, 31)

    # -- boundary automaton: the exact _scan_wide step per W-block ----------
    def body(j, st):
        k, c, s, cnt, ps0, ps1 = st
        bstart = t0 + j * W
        bend = bstart + W
        # blocks past the split path's padded bitmap simply don't exist
        # there; masking in_block reproduces that exactly
        in_block = (k < bend) & (s < n) & (t0 // W + j < nb_split)
        kc, kt, cum_last = jax.lax.cond(
            in_block,
            lambda: _block_events(msk_ref, j, W, t0, k, c, T),
            lambda: (jnp.int32(_BIG), jnp.int32(_BIG), c),
        )
        new_k, new_s, emit, bound, any_event = _resolve(
            k, c, s, kc, kt, bend, in_block, n, p
        )
        new_c = jnp.where(any_event, 0, jnp.where(in_block, cum_last, c))

        def emit_chunk():
            pe0, fp0 = chunk_fp(0, ps0, bound)
            pe1, fp1 = chunk_fp(1, ps1, bound)
            out.put(cnt, bound, bound - s, fp0, fp1)
            return cnt + 1, pe0, pe1

        cnt, ps0, ps1 = jax.lax.cond(
            emit, emit_chunk, lambda: (cnt, ps0, ps1))
        return new_k, new_c, new_s, cnt, ps0, ps1

    k, c, s, cnt, ps0, ps1 = jax.lax.fori_loop(
        0, tile // W, body,
        (st_ref[0], st_ref[1], st_ref[2], st_ref[3], st_ref[6], st_ref[7]),
    )

    # -- final-boundary fixup (select_boundaries' post-scan guarantee) ------
    need = (t0 == last_t0) & (out.last_bound(cnt) < n)

    @pl.when(need)
    def _fixup():
        fps = [mulmod(addmod(prefix_at(g, jnp.int32(n)), P31 - ps), rnm1[g],
                      31) for g, ps in ((0, ps0), (1, ps1))]
        out.put(cnt, jnp.int32(n), jnp.int32(n) - s, *fps)

    cnt = cnt + need.astype(jnp.int32)

    # -- persist state for the next tile ------------------------------------
    counts_ref[...] = jnp.full(counts_ref.shape, cnt, jnp.int32)
    for i, v in enumerate((k, c, s, cnt)):
        st_ref[i] = v
    for g in range(2):
        st_ref[4 + g] = addmod(
            carry[g], mulmod(rneg[g], hl.total(g, tile // GROUP), 31))
    st_ref[6] = ps0
    st_ref[7] = ps1


@functools.partial(
    jax.jit, static_argnames=("p", "max_chunks", "tile", "interpret")
)
def fused_pipeline_batch(
    data: jax.Array,
    p: SeqCDCParams,
    *,
    max_chunks: int,
    tile: int = DEFAULT_TILE,
    interpret: bool,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Chunk + fingerprint a ``(B, S)`` uint8 batch in one dispatch.

    Returns ``(bounds (B, mc) int32, counts (B,) int32, fps (B, mc, 2)
    uint32, lengths (B, mc) int32)`` — bit-identical to
    ``boundaries_batch(..., step_impl="wide")`` composed with the vmapped
    ``chunk_fingerprints`` (any ``mask_impl``/``fp_impl``: all are
    bit-identical to each other).

    Precondition: ``max_chunks`` must be a true upper bound on the chunk
    count (``core.automaton.max_chunks_for`` — what the scheduler always
    passes).  With an undersized ``max_chunks`` the reference path folds
    all overflow bytes into the clamped last fp slot while this kernel
    drops overflow chunks whole, so the two fp tails differ (bounds,
    counts and lengths still agree).
    """
    assert data.ndim == 2, data.shape
    B, n = data.shape
    mc = max_chunks
    if n == 0:  # static: no chunks, matching the split path's empty case
        return _empty(B, mc)
    if p.max_size > MAX_CHUNK:
        raise ValueError(
            f"max_size {p.max_size} exceeds the fingerprint power-table "
            f"bound {MAX_CHUNK}"
        )
    tile, nt, hrows, nb_split = _geometry(n, p, tile)
    R = tile // LANES
    x = lanes.as_rows(data.astype(jnp.uint8), nt * R + hrows)
    wneg, postab = lanes.hash_tables(R + hrows)
    tsc = jnp.asarray(lanes.tile_scalars(
        nt, tile, [pow(r, n - 1, int(P31)) for r in (R1, R2)]))
    out_specs, out_shape = _out_specs_shapes(B, mc)
    ext_rows = R + hrows

    outs = pl.pallas_call(
        functools.partial(
            _pipeline_kernel, p=p, n=n, mc=mc, tile=tile, hrows=hrows,
            nb_split=nb_split, last_t0=(nt - 1) * tile,
        ),
        grid=(B, nt),  # row-major: each row's tiles run in order, so the
        # SMEM scan/hash state threads through them (re-init at t0 == 0)
        in_specs=[
            # per-tile scalars as operands, not program_id: the index map
            # owns the grid->tile mapping
            pl.BlockSpec((None, 1, 8), lambda b, i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            *_byte_specs(R, hrows),
            *_table_specs(ext_rows),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.SMEM((8,), jnp.int32),  # automaton + hash registers
            pltpu.SMEM((2 * (ext_rows // 8 + 1),), jnp.int32),  # group P
            pltpu.VMEM((2, ext_rows, LANES), jnp.int32),  # hash weights
            pltpu.VMEM((R, LANES), jnp.int32),  # packed mask lanes
        ],
        interpret=interpret,
        name="chunk_fused",
    )(tsc, x, x, wneg, postab)
    return _unpack_outputs(*outs, mc)


def fused_pipeline(
    data: jax.Array,
    p: SeqCDCParams,
    *,
    max_chunks: int,
    tile: int = DEFAULT_TILE,
    interpret: bool,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Single-stream convenience: ``(n,)`` -> (bounds, count, fps, lengths)."""
    b, c, f, ln = fused_pipeline_batch(
        data[None], p, max_chunks=max_chunks, tile=tile, interpret=interpret
    )
    return b[0], c[0], f[0], ln[0]


# ---------------------------------------------------------------------------
# Segment-packed rows: many small streams share one device row.
# ---------------------------------------------------------------------------


def _packed_pipeline_kernel(
    tsc_ref, x_ref, halo_ref, sep_ref, ends_ref, pend_ref, rend_ref,
    wneg_ref, postab_ref,
    bounds_ref, lens_ref, fps_ref, counts_ref,
    st_ref, gp_ref, w_ref, wprev_ref, msk_ref,
    *, p: SeqCDCParams, mc: int, tile: int, hrows: int,
    nb_split: int, last_t0: int,
):
    """``_pipeline_kernel`` with per-segment resets (docs/KERNELS.md).

    Four deltas against the unpacked kernel:

    * the automaton's file end is the *current segment's* end ``se`` (a
      fifth scan register) instead of the static row width, and every
      emit landing on ``se`` advances it — the registers the emit leaves
      behind are exactly a fresh stream's init state, so the segment reset
      costs nothing beyond the extra register (the proof lives with
      ``automaton._scan_wide_packed``, which this mirrors block-for-block);
    * the mask lanes clip per *position* against the ``seg_end_pos``
      operand (cross-segment byte pairs must not form candidates), where
      the unpacked kernel clips against the static ``n``;
    * one W-block can emit several chunks: a segment-end cut resolving
      late resets the scan position *behind* or *inside* the block it
      fired in, so the per-block step is a ``while_loop`` that re-resolves
      until the position clears the block (mirroring
      ``_scan_wide_packed``'s inner loop), not the unpacked kernel's
      single ``_resolve``;
    * a bound behind the tile start needs its prefix from somewhere the
      running carry can't provide — the bytes between it and ``t0`` are
      *later* segments' real bytes, so ``P(t0) != P(bound)``, unlike the
      unpacked kernel's zero-pad argument.  Segment-end cuts (arbitrarily
      far behind) read host-shaped per-segment operands (``pend`` /
      ``rend``) looked up by end offset; max-size cuts land at most
      ``skip_size - L`` behind (a skip crossed the tile edge) and read the
      previous tile's hash lanes, kept one tile longer, with
      ``r^(bound-1)`` reconstructed as ``r^t0 * r^-(t0-bound+1)`` from the
      resident negpow table.
    """
    t0 = tsc_ref[0, 0]
    rneg = (tsc_ref[0, 1], tsc_ref[0, 2])
    rpos = (tsc_ref[0, 3], tsc_ref[0, 4])
    L = p.seq_length
    W = p.block_width
    R = tile // LANES
    ext_rows = R + hrows
    ext_len = ext_rows * LANES
    HL = p.skip_size  # deepest behind-t0 reach of a max-size cut
    T = jnp.int32(p.skip_trigger)
    out = _Outputs(bounds_ref, lens_ref, fps_ref, counts_ref, mc)
    groups = ext_rows // 8
    hl = lanes.HashLanes(w_ref, gp_ref, ext_rows)
    hl_prev = lanes.HashLanes(wprev_ref, gp_ref, ext_rows,
                              base=2 * (groups + 1))
    ends = ends_ref[...]  # segment ends, zero-padded
    n_row = jnp.max(ends)  # dynamic payload end (0 for an all-pad row)

    def next_end(x):
        return jnp.min(jnp.where(ends > x, ends, _BIG))

    def end_lookup(tab_ref, g, e):
        """The operand entry for the segment whose end == ``e``
        (duplicate ends from empty segments carry identical values)."""
        return jnp.max(jnp.where(ends == e, tab_ref[g], 0))

    # scan registers: k, c, s, cnt, se; hash: P(t0) carry x2, P(s) latch
    # x2; the previous tile's carry x2 and r^-t0 x2
    @pl.when(t0 == 0)
    def _init():
        first_end = next_end(jnp.int32(0))
        for i in range(13):
            st_ref[i] = jnp.int32(0)
        # same init clamp as _scan_wide_packed: the first segment may be
        # shorter than min_size
        st_ref[0] = jnp.minimum(jnp.int32(p.sub_min_skip),
                                first_end - (L - 1))
        st_ref[4] = first_end
        out.init()

    ext = jnp.concatenate([x_ref[...].astype(jnp.int32),
                           halo_ref[...].astype(jnp.int32)], axis=0)

    # -- mask lanes, clipped per segment -------------------------------------
    cand, opp = lanes.mask_lanes(ext, R, L, p.mode == "increasing")
    pos = t0 + lanes.flat_iota(R)
    sep = sep_ref[...]  # exclusive end of each position's segment
    cand = cand & (pos <= sep - L)
    opp = (opp & (pos < sep - 1)).astype(jnp.int32)
    msk_ref[...] = (cand.astype(jnp.int32) + 2 * opp
                    + 4 * lanes.prefix_sum_flat(opp))

    hl.fill(ext, wneg_ref)
    carry = (st_ref[5], st_ref[6])
    carry_prev = (st_ref[9], st_ref[10])
    rneg_prev = (st_ref[11], st_ref[12])

    def prefix_at(g, e):
        m = jnp.clip(e - t0, 0, ext_len)
        return addmod(carry[g], mulmod(rneg[g], hl.prefix(g, m), 31))

    def chunk_fp(g, ps_g, e):
        # a bound behind this tile is a cut: a segment end (pend/rend
        # operands, any depth) or a max-size cut a skip carried across the
        # tile edge (< skip_size behind: the previous tile's hash lanes,
        # with the factor r^(e-1) = r^t0 * r^-(t0-e+1) off the negpow table)
        behind = e - 1 - t0 < 0
        is_end = jnp.max(jnp.where(ends == e, 1, 0)) > 0
        m_prev = jnp.clip(e - (t0 - tile), 0, ext_len)
        pe_prev = addmod(carry_prev[g],
                         mulmod(rneg_prev[g], hl_prev.prefix(g, m_prev), 31))
        pe_b = jnp.where(is_end, end_lookup(pend_ref, g, e), pe_prev)
        pe = jnp.where(behind, pe_b, prefix_at(g, e))
        diff = addmod(pe, P31 - ps_g)
        fi = jnp.clip(e - 1 - t0, 0, ext_len - 1)
        rfac = mulmod(rpos[g], lanes.read_slot(postab_ref, fi, g), 31)
        wi = jnp.clip(t0 - (e - 1), 0, HL + 1)
        rf_b = jnp.where(
            is_end, end_lookup(rend_ref, g, e),
            mulmod(rpos[g], lanes.read_slot(wneg_ref, wi, g), 31),
        )
        rfac = jnp.where(behind, rf_b, rfac)
        return pe, mulmod(diff, rfac, 31)

    # -- packed boundary automaton: _scan_wide_packed's step per W-block -----
    def body(j, st):
        bstart = t0 + j * W
        bend = bstart + W

        def resolve_once(wst):
            k, c, s, cnt, se, ps0, ps1, _ = wst
            in_block = (k < bend) & (s < n_row) & (t0 // W + j < nb_split)
            kc, kt, cum_last = jax.lax.cond(
                in_block,
                lambda: _block_events(msk_ref, j, W, t0, k, c, T),
                lambda: (jnp.int32(_BIG), jnp.int32(_BIG), c),
            )
            new_k, new_s, emit, bound, any_event = _resolve(
                k, c, s, kc, kt, bend, in_block, se, p
            )
            new_c = jnp.where(any_event, 0, jnp.where(in_block, cum_last, c))

            def emit_chunk():
                pe0, fp0 = chunk_fp(0, ps0, bound)
                pe1, fp1 = chunk_fp(1, ps1, bound)
                out.put(cnt, bound, bound - s, fp0, fp1)
                # a bound on the segment end advances to the next segment:
                # the emit's own register updates are the next stream's
                # init state
                new_se = jnp.where(bound >= se, next_end(bound), se)
                return cnt + 1, pe0, pe1, new_se

            cnt, ps0, ps1, new_se = jax.lax.cond(
                emit, emit_chunk, lambda: (cnt, ps0, ps1, se))
            # clamp the post-emit position to the next pending cut, exactly
            # as _scan_wide_packed does: the min-size skip may overleap a
            # run of tiny segments (and their end cuts) entirely
            new_k = jnp.where(
                emit, jnp.minimum(new_k, new_se - (L - 1)), new_k
            )
            # a late segment-end cut resets the scan inside this block:
            # re-resolve until the position clears it (_scan_wide_packed's
            # inner loop, block-for-block)
            go = emit & (new_k < bend) & (new_s < n_row)
            return (new_k, new_c, new_s, cnt, new_se, ps0, ps1,
                    go.astype(jnp.int32))

        wst = jax.lax.while_loop(
            lambda wst: wst[-1] > 0, resolve_once, st + (jnp.int32(1),)
        )
        return wst[:-1]

    k, c, s, cnt, se, ps0, ps1 = jax.lax.fori_loop(
        0, tile // W, body,
        (st_ref[0], st_ref[1], st_ref[2], st_ref[3], st_ref[4], st_ref[7],
         st_ref[8]),
    )

    # -- final-boundary fixup: the row's payload end, dynamic here -----------
    need = (t0 == last_t0) & (out.last_bound(cnt) < n_row) & (n_row > 0)

    @pl.when(need)
    def _fixup():
        # past-payload bytes are zero padding, so the clipped read is exact
        # even when n_row is behind t0
        fps = [mulmod(addmod(prefix_at(g, n_row), P31 - ps),
                      end_lookup(rend_ref, g, n_row), 31)
               for g, ps in ((0, ps0), (1, ps1))]
        out.put(cnt, n_row, n_row - s, *fps)

    cnt = cnt + need.astype(jnp.int32)

    # -- persist state for the next tile --------------------------------------
    counts_ref[...] = jnp.full(counts_ref.shape, cnt, jnp.int32)
    for i, v in enumerate((k, c, s, cnt, se)):
        st_ref[i] = v
    for g in range(2):
        st_ref[5 + g] = addmod(
            carry[g], mulmod(rneg[g], hl.total(g, tile // GROUP), 31))
        st_ref[9 + g] = carry[g]
        st_ref[11 + g] = rneg[g]
    st_ref[7] = ps0
    st_ref[8] = ps1
    # this tile's hash lanes answer the next tile's behind-t0 max-size cuts
    wprev_ref[...] = w_ref[...]

    def copy_gp(i, _):
        gp_ref[2 * (groups + 1) + i] = gp_ref[i]
        return _

    jax.lax.fori_loop(0, 2 * (groups + 1), copy_gp, 0)


@functools.partial(
    jax.jit, static_argnames=("p", "max_chunks", "tile", "interpret")
)
def packed_pipeline_batch(
    data: jax.Array,
    seg_end_pos: jax.Array,
    ends: jax.Array,
    p: SeqCDCParams,
    *,
    max_chunks: int,
    tile: int = DEFAULT_TILE,
    interpret: bool,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Chunk + fingerprint a segment-packed ``(B, S)`` batch in one dispatch.

    Each row holds several streams concatenated back to back (``ends``:
    (B, G) nondecreasing exclusive segment ends padded with the row's
    payload end; ``seg_end_pos``: (B, S) the segment end governing each
    byte position).  Returns the same ``(bounds, counts, fps, lengths)``
    layout as :func:`fused_pipeline_batch` but in row coordinates with
    every segment end present as a bound — bit-identical, per segment, to
    chunking each stream alone (``seqcdc.boundaries_packed`` composed with
    ``chunk_fingerprints`` is the split-path oracle; ``ref.packed_pipeline``
    is the per-stream host oracle).

    The 62-bit fingerprint is translation invariant (bytes are weighted by
    offset from the *chunk end*), so packed-row fps equal per-stream fps
    with no correction; only the prefix bookkeeping inside the kernel needs
    the per-segment ``P(end)``/``r^(end-1)`` operands, computed here from
    the row bytes with 16-bit-limb cumulative sums (exact because
    ``S <= 65536``, enforced below — one packed row is at most the
    fingerprint kernel's own byte bound).
    """
    assert data.ndim == 2, data.shape
    B, n = data.shape
    G = ends.shape[-1]
    mc = max_chunks
    if n == 0:  # static: no chunks
        return _empty(B, mc)
    if p.max_size > MAX_CHUNK:
        raise ValueError(
            f"max_size {p.max_size} exceeds the fingerprint power-table "
            f"bound {MAX_CHUNK}"
        )
    if n > MAX_CHUNK:
        raise ValueError(
            f"packed row width {n} exceeds the limb-exactness bound "
            f"{MAX_CHUNK}; pack into narrower rows"
        )
    tile, nt, hrows, nb_split = _geometry(n, p, tile)
    # a max-size cut reaches skip_size positions into the previous tile,
    # whose hash lanes are kept one tile longer
    assert p.skip_size < tile, (p.skip_size, tile)
    R = tile // LANES
    ext_rows = R + hrows
    x = lanes.as_rows(data.astype(jnp.uint8), nt * R + hrows)
    # padding positions carry seg end 0: every clipped mask bit is false
    # there (pos >= n > 0 >= sep - L), matching the zero-pad bytes
    sep = lanes.as_rows(seg_end_pos.astype(jnp.int32), nt * R)
    wneg, postab = lanes.hash_tables(ext_rows)
    tsc = jnp.asarray(lanes.tile_scalars(nt, tile))

    # per-segment end operands: pend[b, g, i] = P_g(end_i) and
    # rend[b, g, i] = r_g^(end_i - 1) — row-wide limb prefix sums gathered
    # at the segment ends (uint32 cumsums of < 2^16 limbs over n <= 65536
    # entries: exact)
    from repro.dedup.fingerprint import _addmod, _byte_mulmod, _fold32, _rot31

    ends = ends.astype(jnp.int32)
    e_idx = jnp.clip(ends - 1, 0, n - 1)  # (B, G)
    full_pow = jnp.stack(
        [jnp.asarray(_pow_table_np(r)[:n]) for r in (R1, R2)]
    )  # (2, n): r^q for q < n; end - 1 < n always
    wneg_row = jnp.stack(
        [jnp.asarray(lanes.negpow_table(r, n)) for r in (R1, R2)]
    )
    pr, rr = [], []
    for g in range(2):
        w = _byte_mulmod(data.astype(jnp.uint32), wneg_row[g])  # (B, n)
        lo = jnp.cumsum(w & 0xFFFF, axis=-1, dtype=jnp.uint32)
        hi = jnp.cumsum(w >> 16, axis=-1, dtype=jnp.uint32)
        pg = _addmod(
            _fold32(jnp.take_along_axis(lo, e_idx, axis=-1)),
            _rot31(_fold32(jnp.take_along_axis(hi, e_idx, axis=-1)), 16),
        )
        pr.append(jnp.where(ends > 0, pg, jnp.uint32(0)))
        rr.append(jnp.where(ends > 0, full_pow[g][e_idx], jnp.uint32(0)))
    gr = lanes.round_up(G, GROUP) // LANES
    pend = lanes.as_rows(jnp.stack(pr, axis=1).astype(jnp.int32), gr)
    rend = lanes.as_rows(jnp.stack(rr, axis=1).astype(jnp.int32), gr)
    ends_rows = lanes.as_rows(ends, gr)  # zero padding: never an end > x
    out_specs, out_shape = _out_specs_shapes(B, mc)
    groups = ext_rows // 8

    outs = pl.pallas_call(
        functools.partial(
            _packed_pipeline_kernel, p=p, mc=mc, tile=tile, hrows=hrows,
            nb_split=nb_split, last_t0=(nt - 1) * tile,
        ),
        grid=(B, nt),
        in_specs=[
            pl.BlockSpec((None, 1, 8), lambda b, i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            *_byte_specs(R, hrows),
            pl.BlockSpec((None, R, LANES), lambda b, i: (b, i, 0)),  # sep
            pl.BlockSpec((None, gr, LANES), lambda b, i: (b, 0, 0)),  # ends
            pl.BlockSpec((None, 2, gr, LANES), lambda b, i: (b, 0, 0, 0)),
            pl.BlockSpec((None, 2, gr, LANES), lambda b, i: (b, 0, 0, 0)),
            *_table_specs(ext_rows),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.SMEM((13,), jnp.int32),  # scan + hash registers
            pltpu.SMEM((4 * (groups + 1),), jnp.int32),  # group P, 2 tiles
            pltpu.VMEM((2, ext_rows, LANES), jnp.int32),  # hash weights
            pltpu.VMEM((2, ext_rows, LANES), jnp.int32),  # previous tile's
            pltpu.VMEM((R, LANES), jnp.int32),  # packed mask lanes
        ],
        interpret=interpret,
        name="chunk_fused",
    )(tsc, x, x, sep, ends_rows, pend, rend, wneg, postab)
    return _unpack_outputs(*outs, mc)
