"""The device stages of chunking, as a profiler trace names them.

Each stage runs under ``jax.named_scope(<scope>)``, so the operations XLA
emits for it carry the scope in their ``op_name`` (a trace's ``tf_op``);
the Pallas kernels carry the same name, with ``_`` for ``.``.  Naming
changes only metadata, never the computation.  The compiler leaves some
operations without an ``op_name`` (loops, some fusions) but with the
source line they came from; ``SOURCES`` maps each stage to the modules
whose lines it runs, for a reduction to fall back on.
"""
from __future__ import annotations

#: the SeqCDC candidate/opposing bitmaps (phase 1)
MASKS = "chunk.masks"
#: the W-block boundary automaton (phase 2)
AUTOMATON = "chunk.automaton"
#: per-chunk 62-bit fingerprints
FINGERPRINT = "chunk.fingerprint"
#: the fused single-dispatch kernel: all three stages in one
FUSED = "chunk.fused"

#: stage (the scope's last component) -> the modules whose source lines
#: only that stage runs, as a path suffix
SOURCES = {
    "masks": ("repro/core/masks.py", "repro/kernels/seqcdc_masks.py"),
    "automaton": ("repro/core/automaton.py",),
    "fingerprint": ("repro/dedup/fingerprint.py",
                    "repro/kernels/fingerprint.py"),
    "fused": ("repro/kernels/fused_pipeline.py",),
}
