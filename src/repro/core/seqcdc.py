"""SeqCDC public API: chunk boundary computation in JAX.

Backends (all bit-identical, property-tested against the numpy oracle):

* ``two_phase``  — the TPU-native vectorized pipeline (DESIGN.md SS2):
  phase 1 candidate/opposing bitmaps (jnp reference or Pallas kernel),
  phase 2 W-block ``lax.scan`` automaton (``wide`` or ``gather`` step).
  This is the analogue of the paper's VSEQ.
* ``sequential`` — a ``lax.while_loop`` transcription of the scalar algorithm
  with true data-dependent skipping.  This is the analogue of the paper's
  unaccelerated SEQ and the baseline for the vector-speedup experiments.

Batched use: streams of equal length chunk independently; ``vmap`` over the
leading axis (used by the dedup ingest pipeline to keep the TPU busy).
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from . import automaton, masks, stages
from .params import SeqCDCParams

_BIG = jnp.int32(1 << 30)

MaskImpl = Literal["jnp", "pallas"]
StepImpl = Literal["wide", "gather", "event"]


def _compute_masks(data: jax.Array, p: SeqCDCParams, mask_impl: MaskImpl):
    if mask_impl == "jnp":
        return masks.seqcdc_masks(data, p.seq_length, p.mode)
    if mask_impl == "pallas":
        from repro.kernels import ops as kernel_ops

        return kernel_ops.seqcdc_masks(data, p.seq_length, p.mode)
    raise ValueError(mask_impl)


@functools.partial(
    jax.jit, static_argnames=("p", "mask_impl", "step_impl", "max_chunks")
)
def boundaries_two_phase(
    data: jax.Array,
    p: SeqCDCParams,
    *,
    mask_impl: MaskImpl = "jnp",
    step_impl: StepImpl = "wide",
    max_chunks: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Vectorized SeqCDC.  ``data``: (n,) uint8.  Returns (bounds, count)."""
    n = data.shape[-1]
    if n == 0:  # static: an empty stream has no chunks
        mc = max_chunks or automaton.max_chunks_for(n, p)
        return jnp.full((mc,), _BIG, dtype=jnp.int32), jnp.int32(0)
    with jax.named_scope(stages.MASKS):
        cand, opp = _compute_masks(data, p, mask_impl)
    with jax.named_scope(stages.AUTOMATON):
        return automaton.select_boundaries(
            cand, opp, n, p, step_impl=step_impl, max_chunks=max_chunks
        )


@functools.partial(
    jax.jit, static_argnames=("p", "mask_impl", "max_chunks")
)
def boundaries_packed(
    data: jax.Array,
    seg_end_pos: jax.Array,
    ends: jax.Array,
    p: SeqCDCParams,
    *,
    mask_impl: MaskImpl = "jnp",
    max_chunks: int,
) -> tuple[jax.Array, jax.Array]:
    """Chunk a packed row of concatenated streams, bit-identical per segment.

    ``data``: (S,) uint8 — several streams laid out back to back, zero
    padding after the last.  ``seg_end_pos``: (S,) int32 — for every byte
    position, the exclusive end of the segment it belongs to (the row
    payload end for padding positions).  ``ends``: (G,) int32 nondecreasing
    segment ends, padded with the payload end.

    The row-wide phase-1 bitmaps see cross-segment byte pairs (stream i's
    last byte against stream i+1's first), which a per-stream run never
    compares; clipping candidate bits to ``pos <= end - L`` and opposing
    bits to ``pos < end - 1`` of their own segment removes exactly those,
    leaving every surviving bit equal to the bit the segment's solo run
    would compute.  Phase 2 is the packed automaton
    (``automaton.select_boundaries_packed``), which resets at segment ends.
    Returned bounds are in row coordinates with every segment end present
    exactly once (``wide``-step semantics; packed rows have no ``step_impl``
    selector).
    """
    S = data.shape[-1]
    if S == 0:  # static: an empty row has no chunks
        return jnp.full((max_chunks,), _BIG, dtype=jnp.int32), jnp.int32(0)
    with jax.named_scope(stages.MASKS):
        cand, opp = _compute_masks(data, p, mask_impl)
        pos = jnp.arange(S, dtype=jnp.int32)
        cand = cand & (pos <= seg_end_pos - p.seq_length)
        opp = opp & (pos < seg_end_pos - 1)
    with jax.named_scope(stages.AUTOMATON):
        return automaton.select_boundaries_packed(
            cand, opp, ends, p, max_chunks=max_chunks
        )


def boundaries_packed_batch(
    data: jax.Array,
    seg_end_pos: jax.Array,
    ends: jax.Array,
    p: SeqCDCParams,
    *,
    mask_impl: MaskImpl = "jnp",
    max_chunks: int,
) -> tuple[jax.Array, jax.Array]:
    """Batched :func:`boundaries_packed` over (B, S) rows / (B, G) ends."""
    fn = functools.partial(
        boundaries_packed, p=p, mask_impl=mask_impl, max_chunks=max_chunks
    )
    return jax.vmap(lambda d, sep, e: fn(d, sep, e))(data, seg_end_pos, ends)


@functools.partial(jax.jit, static_argnames=("p", "max_chunks"))
def boundaries_sequential(
    data: jax.Array, p: SeqCDCParams, *, max_chunks: int | None = None
) -> tuple[jax.Array, jax.Array]:
    """Scalar SeqCDC via ``lax.while_loop`` (true data-dependent skipping).

    One loop iteration per *scanned* position: sub-minimum regions and
    content-defined skips advance the position without touching the data —
    exactly the paper's unaccelerated algorithm.
    """
    n = data.shape[-1]
    if max_chunks is None:
        max_chunks = automaton.max_chunks_for(n, p)
    L = p.seq_length
    T = jnp.int32(p.skip_trigger)
    inc = p.mode == "increasing"
    d = data.astype(jnp.uint8)
    lidx = jnp.arange(L - 1)

    def cond(st):
        k, c, s, cnt, out = st
        return s < n

    def body(st):
        k, c, s, cnt, out = st
        cut_b = jnp.minimum(s + p.max_size, n)
        cut_k = cut_b - (L - 1)
        hit_cut = k >= cut_k
        # candidate check: L bytes at k (safe: only used when k + L <= n)
        safe_k = jnp.minimum(k, jnp.int32(max(n - L, 0)))
        win = jax.lax.dynamic_slice(d, (safe_k,), (L,))
        mono = jnp.all(win[1:] > win[:-1]) if inc else jnp.all(win[1:] < win[:-1])
        is_cand = ~hit_cut & mono
        a = d[jnp.minimum(safe_k, n - 2)]
        b = d[jnp.minimum(safe_k + 1, n - 1)]
        is_opp = ~hit_cut & ~is_cand & ((b < a) if inc else (b > a))
        trig = is_opp & (c + 1 > T)

        emit = hit_cut | is_cand
        bound = jnp.where(hit_cut, cut_b, k + L)
        out = out.at[jnp.where(emit, cnt, max_chunks)].set(bound, mode="drop")
        cnt = cnt + emit.astype(jnp.int32)

        new_s = jnp.where(emit, bound, s)
        new_k = jnp.where(
            emit,
            bound + p.sub_min_skip,
            jnp.where(trig, k + p.skip_size, k + 1),
        )
        new_c = jnp.where(emit | trig, 0, c + is_opp.astype(jnp.int32))
        return (new_k, new_c, new_s, cnt, out)

    out0 = jnp.full((max_chunks,), _BIG, dtype=jnp.int32)
    init = (jnp.int32(p.sub_min_skip), jnp.int32(0), jnp.int32(0), jnp.int32(0), out0)
    if n == 0:
        return out0, jnp.int32(0)
    if n < max(L, 2):  # too short for any pair/run: single chunk (static)
        return out0.at[0].set(n), jnp.int32(1)
    _, _, _, cnt, out = jax.lax.while_loop(cond, body, init)
    return out, cnt


def boundaries_batch(
    data: jax.Array,
    p: SeqCDCParams,
    *,
    mask_impl: MaskImpl = "jnp",
    step_impl: StepImpl = "wide",
    max_chunks: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Batched two-phase SeqCDC over (B, n) streams -> ((B, max_chunks), (B,))."""
    fn = functools.partial(
        boundaries_two_phase,
        p=p,
        mask_impl=mask_impl,
        step_impl=step_impl,
        max_chunks=max_chunks or automaton.max_chunks_for(data.shape[-1], p),
    )
    return jax.vmap(fn)(data)


def bounds_to_numpy(bounds, count) -> "list":
    """Strip sentinel padding host-side -> python list(s) of int boundaries.

    Accepts either a single stream's ``(max_chunks,) + scalar count`` (returns
    a flat list) or the batched layout from :func:`boundaries_batch`,
    ``(B, max_chunks) + (B,)`` (returns a list of B lists) — the host-side
    exit point for both the single-stream and batch entry points.
    """
    import numpy as np

    b = np.asarray(bounds)
    c = np.asarray(count)
    if b.ndim == 1:
        return b[: int(c)].astype(np.int64).tolist()
    if b.ndim != 2 or c.shape != b.shape[:1]:
        raise ValueError(f"bad bounds/count shapes: {b.shape} / {c.shape}")
    return [row[: int(k)].astype(np.int64).tolist() for row, k in zip(b, c)]
