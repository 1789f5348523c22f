"""Jitted public wrappers for the Pallas kernels.

The backend decides how a kernel runs, and nothing overrides it: on the
CPU the kernel bodies execute in the Pallas interpreter (bit-exact
validation against ref.py in the tests); on a TPU they compile to Mosaic.
Any other backend is refused rather than interpreted, so a chip run can
never fall back to the interpreter unnoticed.
"""
from __future__ import annotations

import jax

from repro.core import stages

from . import extremum as _extremum
from . import gear_hash as _gear_hash
from . import seqcdc_masks as _seqcdc_masks


def _interpret() -> bool:
    """True on the CPU backend, False on a TPU; raises on any other."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise NotImplementedError(
        f"Pallas kernels run compiled on a TPU or interpreted on the CPU; "
        f"backend {backend!r} is neither"
    )


#: why the baseline-only kernels are refused on a TPU: the chip compiler's
#: own first refusal for each (docs/KERNELS.md, "compiles on v5e")
_NOT_ON_TPU = {
    "gear_hash": (
        "the Gear kernel does not compile for the TPU: its (1, 31) halo "
        "block is not (8, 128)-aligned, and its per-byte 256-entry table "
        "gather has no Mosaic lowering"
    ),
    "block_max": (
        "the block-max kernel does not compile for the TPU: Mosaic reduces "
        "no unsigned integers, and its 1-D uint8 blocks do not match the "
        "chip's (1024)(128)(4,1) byte layout"
    ),
}


def _interpret_or_refuse(kernel: str) -> bool:
    """Interpret on the CPU; refuse a kernel that has no chip build."""
    interpret = _interpret()
    if not interpret:
        raise NotImplementedError(_NOT_ON_TPU[kernel])
    return interpret


def seqcdc_masks(data, seq_length: int, mode: str = "increasing"):
    """(candidate, opposing) bitmaps via the Pallas phase-1 kernel."""
    return _seqcdc_masks.seqcdc_masks_pallas(
        data, seq_length, mode, interpret=_interpret()
    )


def gear_hash(data, table=None):
    """Per-position uint32 Gear hash via the parallel window-32 kernel."""
    return _gear_hash.gear_hash_pallas(
        data, table, interpret=_interpret_or_refuse("gear_hash"))


def block_max(data, block: int = 128):
    """Per-block byte maxima via the range-scan kernel."""
    return _extremum.block_max_pallas(
        data, block=block, interpret=_interpret_or_refuse("block_max"))


def flash_attention(q, k, v, **kw):
    """Causal flash attention via the Pallas kernel (VMEM score tiles)."""
    from . import flash_attn as _fa

    return _fa.flash_attention_pallas(q, k, v, interpret=_interpret(), **kw)


def chunk_fingerprints(data, bounds, count, *, max_chunks: int):
    """Fused per-chunk 62-bit fingerprints via the Pallas kernel.

    (Imported lazily: kernels/fingerprint.py pulls constants from
    repro.dedup.fingerprint, which in turn dispatches back here only
    inside function bodies — no import cycle.)
    """
    from . import fingerprint as _fp

    return _fp.fingerprint_pallas(
        data, bounds, count, max_chunks=max_chunks, interpret=_interpret()
    )


def fused_pipeline(data, p, *, max_chunks: int):
    """Single-dispatch chunk+fingerprint pipeline via the fused kernel.

    ``data``: ``(S,)`` or ``(B, S)`` uint8.  Returns
    ``(bounds, count(s), fps, lengths)`` bit-identical to the composed
    split path (``boundaries_batch`` + ``chunk_fingerprints``); the
    service scheduler selects it with ``pipeline_impl="fused"``.
    (Lazy import for the same no-cycle reason as ``chunk_fingerprints``.)
    """
    from . import fused_pipeline as _fpipe

    with jax.named_scope(stages.FUSED):
        if data.ndim == 1:
            return _fpipe.fused_pipeline(
                data, p, max_chunks=max_chunks, interpret=_interpret()
            )
        return _fpipe.fused_pipeline_batch(
            data, p, max_chunks=max_chunks, interpret=_interpret()
        )


def packed_pipeline(data, seg_end_pos, ends, p, *, max_chunks: int):
    """Segment-packed fused pipeline: many streams per device row.

    ``data``: ``(B, S)`` uint8 rows of concatenated streams;
    ``seg_end_pos``: ``(B, S)`` int32 per-position segment ends;
    ``ends``: ``(B, G)`` int32 nondecreasing segment ends padded with the
    row payload end.  Returns ``(bounds, counts, fps, lengths)`` in row
    coordinates, bit-identical per segment to chunking each stream alone
    (``ref.packed_pipeline`` is the host oracle; the packed split path is
    ``seqcdc.boundaries_packed_batch`` + ``chunk_fingerprints``).
    """
    from . import fused_pipeline as _fpipe

    with jax.named_scope(stages.FUSED):
        return _fpipe.packed_pipeline_batch(
            data, seg_end_pos, ends, p, max_chunks=max_chunks,
            interpret=_interpret(),
        )
