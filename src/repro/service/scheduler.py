"""Batched chunking scheduler: length-bucketed continuous batching for SeqCDC.

The serving problem: dedup traffic is a stream of *variable-length* byte
objects, but the TPU pipeline (``boundaries_batch`` — the vmapped two-phase
SeqCDC — plus vmapped ``chunk_fingerprints``) wants fixed ``(B, S)`` device
batches so one compiled XLA program stays hot.  This module bridges the two
with the same slot discipline as ``serve/engine.py``: requests queue per
*length bucket* (padded length from the half-octave grid {1, 1.5}x2^k —
two buckets per octave, capping row padding at 50%), a bucket dispatches
the moment its ``slots`` rows fill, and ``drain`` flushes partial buckets
padded with zero rows.  Distinct device shapes stay logarithmic (2 per
octave) in the stream-length range, so the jit cache is tiny and every
dispatch after warmup is a replay.

Exactness under padding (the part that is not just batching): chunking a
stream padded to bucket size S is *not* the same as chunking the stream —
the max-size/file-end cut consults the stream end.  But SeqCDC is memoryless
at chunk starts, so the decision for a chunk starting at ``s`` depends only
on bytes ``[s, s + max_size]``; while ``s + max_size <= n`` (true length),
the padded run and the exact run see identical windows and emit identical
boundaries.  The scheduler therefore keeps padded boundaries up to the last
chunk start with a full in-bounds window and re-chunks only the final
``< max_size`` tail with the event-driven host oracle (bit-identical to the
device pipeline by the tier-1 equivalence suite).  Result: boundaries (and
fingerprints) bit-identical to per-stream ``boundaries_two_phase``, at
device-batch throughput.

Both device stages have selectable backends (docs/KERNELS.md):
``mask_impl`` for the phase-1 bitmaps and ``fp_impl`` for chunk hashing
(the fused Pallas fingerprint kernel vs the gather/segment_sum reference;
left unset, ``fp_impl`` follows the platform: the kernel on a TPU, the
reference chain elsewhere), each guarded by a first-dispatch bit-identity
cross-check (``cross_check_masks`` / ``cross_check_fps``).  Above both sits
``pipeline_impl``: ``"split"`` runs the stages as separate dispatches,
``"fused"`` collapses mask + boundary scan + fingerprints into the single
``kernels/fused_pipeline.py`` dispatch (one byte read instead of three),
guarded by its own first-dispatch cross-check against the composed split
path (``cross_check_pipeline`` / ``PipelineDivergenceError``).  The
default comes from ``REPRO_PIPELINE_IMPL`` (else ``"split"``), which is
how CI runs the whole tier-1 suite through the fused path.

Sub-bucket streams and segment packing: the length-bucket grid bottoms out
at ``min_bucket``, so a 300-byte object occupies a 16 KiB device row —
sub-2% occupancy on small-object traffic no batching discipline can fix.
``packing_impl="segments"`` (default from ``REPRO_PACKING_IMPL``) routes
every sub-``min_bucket`` stream to a separate pack queue; when enough
payload accumulates (or at ``drain``), the streams are shelf-packed back
to back into shared ``min_bucket``-wide rows and dispatched once through
the segment-aware device pipeline (``seqcdc.boundaries_packed_batch`` or
the packed fused kernel), whose automaton resets at every segment end —
each packed stream's chunks and fingerprints are bit-identical to
chunking it alone, so the demuxed per-request results are *exact* and
skip the host tail redo entirely.  The first packed dispatch is replayed
stream-by-stream through the unpacked pipeline and compared bit-for-bit
(``cross_check_packing`` / ``PackingDivergenceError``), the same guard
discipline as every other impl knob.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import Any, Dict, List, Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import oracle
from repro.obs import MetricsRegistry, labeled, set_annotator, span
from repro.core.automaton import max_chunks_for
from repro.core.params import SeqCDCParams
from repro.core.seqcdc import MaskImpl, StepImpl, boundaries_batch
from repro.dedup.fingerprint import (
    MAX_CHUNK,
    FpImpl,
    chunk_fingerprints,
    fingerprints_numpy,
)

# every span and request phase also lands on the JAX profiler's timeline
# (as ``repro.<name>``); an annotation records nothing while no profiler
# session runs
set_annotator(jax.profiler.TraceAnnotation)

#: mirrors kernels/fused_pipeline.py's PipelineImpl — declared locally so
#: importing the service does not pull the Pallas toolchain in eagerly
#: (the kernel module is imported lazily, like every other kernel here)
PipelineImpl = Literal["split", "fused"]

PIPELINE_IMPLS = ("split", "fused")

#: sub-bucket stream handling: "off" pads every stream to its own bucket
#: row; "segments" packs sub-min_bucket streams into shared device rows
PackingImpl = Literal["off", "segments"]

PACKING_IMPLS = ("off", "segments")


def _default_pipeline_impl() -> str:
    """``REPRO_PIPELINE_IMPL`` (CI's fused tier-1 leg sets it), else split."""
    return os.environ.get("REPRO_PIPELINE_IMPL", "split")


def _default_packing_impl() -> str:
    """``REPRO_PACKING_IMPL`` (CI's packing-on leg sets it), else off."""
    return os.environ.get("REPRO_PACKING_IMPL", "off")


def _default_fp_impl() -> str:
    """The Pallas fingerprint kernel on a TPU, where it compiles to Mosaic;
    the jnp reference chain elsewhere (on the CPU the kernel would run in
    the Pallas interpreter).  Both give bit-identical fingerprints."""
    return "pallas" if jax.default_backend() == "tpu" else "reference"


def _run_fused(x, p, mc):
    """The fused single-dispatch pipeline (module-level so the divergence
    tests can interpose a corrupted kernel, like ``chunk_fingerprints``)."""
    from repro.kernels import ops as kernel_ops

    return kernel_ops.fused_pipeline(x, p, max_chunks=mc)


def _run_split(x, p, mc, mask_impl, step_impl, fp_impl):
    """The composed three-dispatch pipeline (the fused kernel's oracle)."""
    bounds, counts = boundaries_batch(
        x, p, mask_impl=mask_impl, step_impl=step_impl, max_chunks=mc
    )
    fps, lens = jax.vmap(
        lambda d, b, c: chunk_fingerprints(d, b, c, max_chunks=mc,
                                           fp_impl=fp_impl)
    )(x, bounds, counts)
    return bounds, counts, fps, lens


@functools.partial(
    jax.jit,
    static_argnames=("p", "mc", "mask_impl", "step_impl", "with_fp", "fp_impl",
                     "pipeline_impl"),
)
def _device_chunk(x, *, p, mc, mask_impl, step_impl, with_fp, fp_impl,
                  pipeline_impl="split"):
    """(B, S) uint8 -> (bounds, counts[, fps, lens]).  One module-level jit
    (not a per-scheduler closure) so the compile cache is shared: a device
    shape compiles once per process, not once per service instance.

    ``pipeline_impl="fused"`` runs the whole thing — masks, boundary scan,
    fingerprints — as the one ``kernels/fused_pipeline.py`` dispatch
    (``mask_impl``/``fp_impl`` then select only the cross-check replays);
    a fingerprint-less batch has nothing to fuse and takes the split path.
    """
    if pipeline_impl == "fused" and with_fp:
        return _run_fused(x, p, mc)
    if not with_fp:
        bounds, counts = boundaries_batch(
            x, p, mask_impl=mask_impl, step_impl=step_impl, max_chunks=mc
        )
        return bounds, counts, None, None
    return _run_split(x, p, mc, mask_impl, step_impl, fp_impl)


def _run_packed_fused(x, sep, ends, p, mc):
    """The packed fused kernel dispatch (module-level so the divergence
    tests can interpose a corrupted kernel, like ``_run_fused``)."""
    from repro.kernels import ops as kernel_ops

    return kernel_ops.packed_pipeline(x, sep, ends, p, max_chunks=mc)


def _run_packed_split(x, sep, ends, p, mc, mask_impl, fp_impl, with_fp):
    """The composed packed pipeline: segment-aware boundary scan plus the
    vmapped fingerprint stage (fps are translation invariant, so the packed
    bounds feed ``chunk_fingerprints`` with no correction)."""
    from repro.core.seqcdc import boundaries_packed_batch

    bounds, counts = boundaries_packed_batch(
        x, sep, ends, p, mask_impl=mask_impl, max_chunks=mc
    )
    if not with_fp:
        return bounds, counts, None, None
    fps, lens = jax.vmap(
        lambda d, b, c: chunk_fingerprints(d, b, c, max_chunks=mc,
                                           fp_impl=fp_impl)
    )(x, bounds, counts)
    return bounds, counts, fps, lens


@functools.partial(
    jax.jit,
    static_argnames=("p", "mc", "mask_impl", "with_fp", "fp_impl",
                     "pipeline_impl"),
)
def _device_chunk_packed(x, sep, ends, *, p, mc, mask_impl, with_fp,
                         fp_impl, pipeline_impl="split"):
    """(R, S) packed rows -> (bounds, counts[, fps, lens]) in row
    coordinates.  The packed twin of ``_device_chunk``: ``sep`` is the
    per-position segment-end operand, ``ends`` the (R, G) segment-end
    table.  Packed rows have no ``step_impl`` selector — the segment-
    resetting automaton only exists in ``wide`` form (which the packed
    fused kernel mirrors block-for-block)."""
    if pipeline_impl == "fused" and with_fp:
        return _run_packed_fused(x, sep, ends, p, mc)
    return _run_packed_split(x, sep, ends, p, mc, mask_impl, fp_impl,
                             with_fp)


def _trim_exact(data: np.ndarray, padded: np.ndarray,
                padded_fps: np.ndarray | None, p: SeqCDCParams):
    """Trim a padded-run boundary list to the exact per-stream result.

    Keeps every boundary whose chunk started with a full in-bounds
    ``max_size`` window (identical to the exact run by memorylessness) and
    re-chunks the remaining tail with the host oracle.  Returns
    ``(bounds, fps, lengths, tail_bytes)`` where ``tail_bytes`` is how many
    bytes the host redid (0 when the stream length fell on a boundary).
    Module-level so the packing cross-check can replay the unpacked
    pipeline end to end without a scheduler instance.
    """
    n = data.size
    kept = 0
    s = 0
    for b in padded:
        if s + p.max_size > n:
            break
        kept += 1
        s = int(b)
    if s == n:  # stream length hit a boundary exactly: nothing to redo
        bounds = padded[:kept].astype(np.int64)
        tail_rel = np.zeros(0, dtype=np.int64)
        tail_bytes = 0
    else:
        tail_rel = oracle.boundaries_numpy(data[s:], p)
        tail_bytes = n - s
        bounds = np.concatenate([padded[:kept].astype(np.int64),
                                 tail_rel + s])
    lengths = np.diff(np.concatenate([[0], bounds]))
    if padded_fps is None:
        fps = np.zeros((0, 2), dtype=np.uint32)
    elif tail_rel.size:
        fps = np.concatenate([
            padded_fps[:kept],
            fingerprints_numpy(data[s:], tail_rel),
        ])
    else:
        fps = padded_fps[:kept].copy()
    return bounds, fps, lengths, tail_bytes


class MaskDivergenceError(AssertionError):
    """The Pallas and lax mask kernels disagreed on a dispatched batch."""


class FingerprintDivergenceError(AssertionError):
    """The Pallas and reference fingerprint paths disagreed on a batch."""


class PipelineDivergenceError(AssertionError):
    """The fused and split pipelines disagreed on a dispatched batch.

    ``stage`` names what diverged first: ``"boundaries"`` (the mask/scan
    lanes emitted different chunking) or ``"fingerprints"`` (same chunks,
    different hashes) — the first question a kernel regression asks.
    """

    def __init__(self, message: str, stage: str):
        super().__init__(message)
        self.stage = stage


class PackingDivergenceError(AssertionError):
    """A packed-row dispatch disagreed with the per-stream unpacked replay.

    Raised by the first-packed-dispatch guard: every stream of the packed
    batch is rerun as its own unpacked device row and the demuxed packed
    results must match bit-for-bit — a divergence means the segment-reset
    bookkeeping (automaton ``se`` register, mask clipping, or the packed
    fingerprint prefix operands) regressed.
    """


@dataclasses.dataclass
class ChunkRequest:
    seq: int  # submission order (results are returned in this order)
    tag: Any
    data: np.ndarray  # (n,) uint8


@dataclasses.dataclass
class ChunkResult:
    """Exact chunking of one stream: what the store/restore path consumes."""

    tag: Any
    data: np.ndarray  # the original stream (uint8)
    bounds: np.ndarray  # (C,) int64 exclusive chunk ends, bounds[-1] == size
    fps: np.ndarray  # (C, 2) uint32 accelerator fingerprints
    lengths: np.ndarray  # (C,) int64 chunk lengths

    @property
    def size(self) -> int:
        return int(self.data.size)


@dataclasses.dataclass
class SchedulerStats:
    dispatches: int = 0
    padded_rows: int = 0  # zero rows used to square off partial batches
    device_rows: int = 0  # total device rows shipped (real + padded)
    device_bytes: int = 0  # bytes shipped to the device (incl. padding)
    stream_bytes: int = 0  # real payload bytes
    tail_bytes: int = 0  # bytes re-chunked host-side (exactness fixup)
    tail_s: float = 0.0  # wall seconds the host tail redo cost (inside drain)
    packed_streams: int = 0  # streams that rode a shared packed row

    @property
    def occupancy(self) -> float:
        """Real payload fraction of device traffic (batching efficiency)."""
        return self.stream_bytes / self.device_bytes if self.device_bytes else 0.0


class ChunkScheduler:
    """Length-bucketed continuous batching over the vmapped SeqCDC pipeline."""

    def __init__(
        self,
        params: SeqCDCParams | None = None,
        *,
        slots: int = 8,
        min_bucket: int = 1 << 14,
        max_batch_bytes: int = 8 << 20,
        mask_impl: MaskImpl = "jnp",
        step_impl: StepImpl = "wide",
        fp_impl: FpImpl | None = None,
        pipeline_impl: PipelineImpl | None = None,
        packing_impl: PackingImpl | None = None,
        with_fingerprints: bool = True,
        cross_check_masks: bool = False,
        cross_check_fps: bool = False,
        cross_check_pipeline: bool = False,
        cross_check_packing: bool = False,
        registry: MetricsRegistry | None = None,
    ):
        from repro.core.params import derived_params

        self.params = params or derived_params(8192)
        if with_fingerprints and self.params.max_size > MAX_CHUNK:
            raise ValueError(
                f"max_size {self.params.max_size} exceeds the fingerprint "
                f"limit {MAX_CHUNK}; pass with_fingerprints=False"
            )
        self.slots = slots
        self.max_batch_bytes = max_batch_bytes
        self.min_bucket = max(min_bucket, self.params.max_size)
        self.mask_impl = mask_impl
        self.step_impl = step_impl
        # resolved once: the dispatch histogram's ``fp=`` label then names
        # the path that ran
        self.fp_impl = fp_impl if fp_impl is not None else _default_fp_impl()
        if pipeline_impl is None:
            pipeline_impl = _default_pipeline_impl()
        if pipeline_impl not in PIPELINE_IMPLS:
            raise ValueError(
                f"pipeline_impl must be one of {PIPELINE_IMPLS}, "
                f"got {pipeline_impl!r}"
            )
        self.pipeline_impl = pipeline_impl
        if packing_impl is None:
            packing_impl = _default_packing_impl()
        if packing_impl not in PACKING_IMPLS:
            raise ValueError(
                f"packing_impl must be one of {PACKING_IMPLS}, "
                f"got {packing_impl!r}"
            )
        if packing_impl == "segments" and self.min_bucket > MAX_CHUNK:
            raise ValueError(
                f"packing_impl='segments' requires min_bucket <= "
                f"{MAX_CHUNK} (the packed-row limb-exactness bound), "
                f"got {self.min_bucket}"
            )
        self.packing_impl = packing_impl
        self.with_fingerprints = with_fingerprints
        # bit-identity guard for the Pallas hot path: the first dispatch of
        # every device shape is replayed through the other mask backend and
        # compared — a cheap one-time check per compiled program that turns a
        # kernel regression into a loud MaskDivergenceError instead of silent
        # chunk-boundary drift (which dedup would quietly absorb as a worse
        # ratio, the nastiest possible failure mode).
        self.cross_check_masks = cross_check_masks
        self._checked_buckets: set[int] = set()
        # the fingerprint twin: first dispatch per bucket replays the other
        # fp_impl and compares bit-for-bit (FingerprintDivergenceError) — a
        # silently wrong fingerprint would mis-route chunks across shards
        # and poison the estimator index, so it gets the same guard
        self.cross_check_fps = cross_check_fps
        self._fp_checked_buckets: set[int] = set()
        # and the pipeline-level guard: the first dispatch of every bucket
        # is replayed through the *other* pipeline (fused <-> composed
        # split) and compared bit-for-bit across bounds, counts, fps and
        # lengths — PipelineDivergenceError names the stage that diverged
        self.cross_check_pipeline = cross_check_pipeline
        self._pipeline_checked_buckets: set[int] = set()
        # packing guard: the first packed dispatch replays every stream as
        # its own unpacked device row and compares the demuxed results
        # bit-for-bit (PackingDivergenceError) — the packing layer's whole
        # contract is "identical to not packing", so it gets the same
        # one-time-per-process-shape check as every other impl knob
        self.cross_check_packing = cross_check_packing
        self._packing_checked = False
        self._pack_queue: List[ChunkRequest] = []
        self._pack_bytes = 0
        # dispatch the pack queue once it can fill a whole device batch of
        # packed rows (drain() flushes whatever is left)
        self._pack_capacity = (
            self._slots_for(self.min_bucket) * self.min_bucket
        )
        self.stats = SchedulerStats()
        # always-on metrics (docs/OBSERVABILITY.md): the owning service
        # passes its registry so scheduler metrics land in its snapshot;
        # a bare scheduler gets its own
        self.obs = registry if registry is not None else MetricsRegistry()
        # the dispatch-latency histogram is labeled by the static pipeline
        # configuration, so a BENCH trajectory can attribute a latency
        # shift to an impl flip; rendered once, not per dispatch
        self._dispatch_hist = labeled(
            "sched.dispatch_s", pipeline=self.pipeline_impl,
            mask=self.mask_impl, fp=self.fp_impl,
        )
        self._bucket_metric_names: Dict[Any, tuple[str, str, str]] = {}
        self._pending: Dict[int, List[ChunkRequest]] = {}
        self._ready: List[tuple[int, ChunkResult]] = []
        self._jit_cache: Dict[int, Any] = {}
        self._next_seq = 0

    # -- public -----------------------------------------------------------------
    def submit(self, data, tag: Any = None) -> int:
        """Queue one stream for chunking; dispatches when its bucket fills.

        ``data``: raw bytes-like (bytes/bytearray/memoryview) or anything
        ``np.ascontiguousarray`` turns into a uint8 vector.
        """
        if isinstance(data, (bytes, bytearray, memoryview)):
            arr = np.frombuffer(data, dtype=np.uint8)
        else:
            arr = np.ascontiguousarray(data, dtype=np.uint8).reshape(-1)
        seq = self._next_seq
        self._next_seq += 1
        self.stats.stream_bytes += arr.size
        if arr.size == 0:  # no chunks; never touches the device
            empty = np.zeros(0, dtype=np.int64)
            self._ready.append(
                (seq, ChunkResult(tag, arr, empty,
                                  np.zeros((0, 2), dtype=np.uint32), empty))
            )
            return seq
        if self.packing_impl == "segments" and arr.size < self.min_bucket:
            # sub-bucket streams share device rows instead of padding one
            # bucket row each; exactness comes from the segment-resetting
            # packed pipeline, not from this queue's geometry
            self._pack_queue.append(ChunkRequest(seq, tag, arr))
            self._pack_bytes += arr.size
            if self._pack_bytes >= self._pack_capacity:
                self._dispatch_packed()
            return seq
        bucket = self._bucket_for(arr.size)
        q = self._pending.setdefault(bucket, [])
        q.append(ChunkRequest(seq, tag, arr))
        if len(q) >= self._slots_for(bucket):
            self._dispatch(bucket)
        return seq

    def drain(self) -> List[ChunkResult]:
        """Flush every partial bucket and return all results, FIFO order."""
        if self._pack_queue:
            self._dispatch_packed()
        for bucket in sorted(self._pending):
            if self._pending[bucket]:
                self._dispatch(bucket)
        self._ready.sort(key=lambda t: t[0])
        out = [r for _, r in self._ready]
        self._ready.clear()
        return out

    # -- internals ----------------------------------------------------------------
    def _bucket_for(self, n: int) -> int:
        # two buckets per octave ({1, 1.5} x 2^k): caps row padding at 50%
        # while keeping the set of compiled device shapes logarithmic
        b = self.min_bucket
        while b < n:
            if n <= b + (b >> 1):
                return b + (b >> 1)
            b <<= 1
        return b

    def _slots_for(self, bucket: int) -> int:
        """Rows per device batch: ``slots``, capped so a batch stays within
        ``max_batch_bytes`` (big streams dispatch in small, even solo, rows
        rather than waiting to fill a huge batch)."""
        return max(1, min(self.slots, self.max_batch_bytes // bucket))

    def _device_fn(self, bucket: int):
        fn = self._jit_cache.get(bucket)
        if fn is None:
            fn = functools.partial(
                _device_chunk,
                p=self.params,
                mc=max_chunks_for(bucket, self.params),
                mask_impl=self.mask_impl,
                step_impl=self.step_impl,
                with_fp=self.with_fingerprints,
                fp_impl=self.fp_impl,
                pipeline_impl=self.pipeline_impl,
            )
            self._jit_cache[bucket] = fn
        return fn

    def _bucket_names(self, bucket: int,
                      packed: bool = False) -> tuple[str, str, str]:
        """(occupancy, pad_waste, batch_rows) gauge names for one bucket,
        rendered once per bucket rather than once per dispatch.  Packed
        dispatches get their own ``packed=1`` series so occupancy under
        packing is visible next to (not averaged into) the bucket rows."""
        key = (bucket, packed)
        names = self._bucket_metric_names.get(key)
        if names is None:
            labels = {"bucket": bucket, "packed": 1} if packed else {
                "bucket": bucket}
            names = (
                labeled("sched.occupancy", **labels),
                labeled("sched.pad_waste", **labels),
                labeled("sched.batch_rows", **labels),
            )
            self._bucket_metric_names[key] = names
        return names

    def _dispatch(self, bucket: int):
        # a partial batch (drain of a part-filled bucket) dispatches only
        # the rows it has — padding to the full slot count shipped zero
        # rows the device then chunked for nothing
        reqs = self._pending[bucket]
        rows = len(reqs)
        self._pending[bucket] = []
        payload = sum(r.data.size for r in reqs)
        with span("sched.pack", bucket=bucket, rows=rows):
            batch = np.zeros((rows, bucket), dtype=np.uint8)
            for row, r in enumerate(reqs):
                batch[row, : r.data.size] = r.data
        with span("sched.dispatch", bucket=bucket, rows=len(reqs),
                  payload_bytes=payload, device_bytes=batch.size):
            t0 = time.perf_counter()
            bounds, counts, fps, lens = self._device_fn(bucket)(
                jnp.asarray(batch)
            )
            # np.asarray forces device completion, so the elapsed time is
            # the real dispatch latency, not the async-enqueue cost
            bounds = np.asarray(bounds)
            counts = np.asarray(counts)
            if fps is not None:
                fps, lens = np.asarray(fps), np.asarray(lens)
            dispatch_s = time.perf_counter() - t0
        # cross-check replays are excluded from the latency histogram: they
        # are a one-time-per-bucket guard, not steady-state dispatch cost
        if self.cross_check_masks and bucket not in self._checked_buckets:
            self._checked_buckets.add(bucket)
            self.obs.inc(labeled("sched.cross_checks", kind="masks"))
            self._cross_check(bucket, batch, bounds, counts)
        if fps is not None:
            if self.cross_check_fps and bucket not in self._fp_checked_buckets:
                self._fp_checked_buckets.add(bucket)
                self.obs.inc(labeled("sched.cross_checks", kind="fps"))
                self._cross_check_fp(bucket, batch, bounds, counts, fps, lens)
            if (self.cross_check_pipeline
                    and bucket not in self._pipeline_checked_buckets):
                self._pipeline_checked_buckets.add(bucket)
                self.obs.inc(labeled("sched.cross_checks", kind="pipeline"))
                self._cross_check_pipeline(bucket, batch, bounds, counts,
                                           fps, lens)
        self.stats.dispatches += 1
        self.stats.device_bytes += batch.size
        self.stats.device_rows += rows
        self.obs.inc("sched.dispatches")
        self.obs.inc("sched.device_bytes", batch.size)
        self.obs.inc("sched.payload_bytes", payload)
        self.obs.observe(self._dispatch_hist, dispatch_s)
        occ_name, waste_name, rows_name = self._bucket_names(bucket)
        occ = payload / batch.size if batch.size else 0.0
        self.obs.set_gauge(occ_name, occ)
        self.obs.set_gauge(waste_name, 1.0 - occ)
        self.obs.set_gauge(rows_name, len(reqs))
        for row, r in enumerate(reqs):
            self._ready.append((r.seq, self._exactify(
                r, bounds[row, : counts[row]],
                fps[row] if fps is not None else None,
            )))

    def _dispatch_packed(self):
        """Shelf-pack the sub-bucket queue into shared rows and dispatch."""
        reqs = self._pack_queue
        self._pack_queue = []
        self._pack_bytes = 0
        if not reqs:
            return
        S = self.min_bucket
        # next-fit shelf packing in arrival order: a stream that no longer
        # fits opens a new row — keeps demux order equal to submission
        # order and the packing O(n), at a small fill cost vs best-fit
        rows: List[List[ChunkRequest]] = [[]]
        fill = 0
        for r in reqs:
            if fill + r.data.size > S:
                rows.append([])
                fill = 0
            rows[-1].append(r)
            fill += r.data.size
        slots = self._slots_for(S)
        for i in range(0, len(rows), slots):
            self._dispatch_packed_rows(rows[i:i + slots], S)

    def _dispatch_packed_rows(self, rows: List[List[ChunkRequest]], S: int):
        """One packed device dispatch: R rows of back-to-back segments."""
        R = len(rows)
        G = 4  # segment-table width rounded to a power of two: the jit
        while G < max(len(rr) for rr in rows):  # cache stays logarithmic
            G <<= 1  # in the per-row stream count
        layout: List[List[tuple[ChunkRequest, int, int]]] = []
        payload = 0
        with span("sched.pack", bucket=S, rows=R, packed=1):
            batch = np.zeros((R, S), dtype=np.uint8)
            sep = np.zeros((R, S), dtype=np.int32)
            ends = np.zeros((R, G), dtype=np.int32)
            for ri, rr in enumerate(rows):
                off = 0
                row_layout = []
                for gi, r in enumerate(rr):
                    m = r.data.size
                    batch[ri, off:off + m] = r.data
                    sep[ri, off:off + m] = off + m
                    ends[ri, gi] = off + m
                    row_layout.append((r, off, off + m))
                    off += m
                sep[ri, off:] = off  # padding: its own (empty) tail segment
                ends[ri, len(rr):] = off  # pad entries carry the payload end
                layout.append(row_layout)
                payload += off
        # per-segment bound on chunks: sum of per-stream max_chunks_for
        mc = S // self.params.min_size + 2 * G + 2
        key = ("packed", G)
        fn = self._jit_cache.get(key)
        if fn is None:
            fn = functools.partial(
                _device_chunk_packed,
                p=self.params,
                mc=mc,
                mask_impl=self.mask_impl,
                with_fp=self.with_fingerprints,
                fp_impl=self.fp_impl,
                pipeline_impl=self.pipeline_impl,
            )
            self._jit_cache[key] = fn
        with span("sched.dispatch", bucket=S, rows=R, packed=1,
                  payload_bytes=payload, device_bytes=batch.size):
            t0 = time.perf_counter()
            bounds, counts, fps, lens = fn(
                jnp.asarray(batch), jnp.asarray(sep), jnp.asarray(ends)
            )
            bounds = np.asarray(bounds)
            counts = np.asarray(counts)
            if fps is not None:
                fps = np.asarray(fps)
            dispatch_s = time.perf_counter() - t0
        # demux: each stream's chunks are the row bounds in (off, end] —
        # exact results (the packed automaton consulted the true segment
        # ends), so no host tail redo
        results: List[tuple[ChunkRequest, ChunkResult]] = []
        for ri, row_layout in enumerate(layout):
            bs = bounds[ri, : counts[ri]]
            for r, off, end in row_layout:
                i0 = int(np.searchsorted(bs, off, side="right"))
                i1 = int(np.searchsorted(bs, end, side="right"))
                rb = bs[i0:i1].astype(np.int64) - off
                lengths = np.diff(np.concatenate([[0], rb]))
                rf = (fps[ri, i0:i1].copy() if fps is not None
                      else np.zeros((0, 2), dtype=np.uint32))
                results.append(
                    (r, ChunkResult(r.tag, r.data, rb, rf, lengths))
                )
        if self.cross_check_packing and not self._packing_checked:
            self._packing_checked = True
            self.obs.inc(labeled("sched.cross_checks", kind="packing"))
            self._cross_check_packing(S, results)
        self.stats.dispatches += 1
        self.stats.device_bytes += batch.size
        self.stats.device_rows += R
        self.stats.packed_streams += len(results)
        self.obs.inc("sched.dispatches")
        self.obs.inc("sched.device_bytes", batch.size)
        self.obs.inc("sched.payload_bytes", payload)
        self.obs.inc("sched.packed_streams", len(results))
        self.obs.observe(self._dispatch_hist, dispatch_s)
        occ_name, waste_name, rows_name = self._bucket_names(S, packed=True)
        occ = payload / batch.size if batch.size else 0.0
        self.obs.set_gauge(occ_name, occ)
        self.obs.set_gauge(waste_name, 1.0 - occ)
        self.obs.set_gauge(rows_name, R)
        for r, res in results:
            self._ready.append((r.seq, res))

    def _cross_check_packing(self, S: int,
                             results: List[tuple[ChunkRequest, ChunkResult]]):
        """Replay every packed stream as its own unpacked device row and
        compare the demuxed packed results bit-for-bit.  The replay goes
        through ``_device_chunk`` + the host tail trim — the exact pipeline
        a ``packing_impl="off"`` scheduler would run — so this guard pins
        the packing layer's whole contract: packed == not packed."""
        reqs = [r for r, _ in results]
        xb = np.zeros((len(reqs), S), dtype=np.uint8)
        for i, r in enumerate(reqs):
            xb[i, : r.data.size] = r.data
        mc = max_chunks_for(S, self.params)
        b2, c2, f2, l2 = _device_chunk(
            jnp.asarray(xb),
            p=self.params,
            mc=mc,
            mask_impl=self.mask_impl,
            step_impl=self.step_impl,
            with_fp=self.with_fingerprints,
            fp_impl=self.fp_impl,
            pipeline_impl=self.pipeline_impl,
        )
        b2, c2 = np.asarray(b2), np.asarray(c2)
        if f2 is not None:
            f2 = np.asarray(f2)
        bad = []
        for i, (r, res) in enumerate(results):
            eb, ef, el, _ = _trim_exact(
                r.data, b2[i, : c2[i]],
                f2[i] if f2 is not None else None, self.params,
            )
            if not (np.array_equal(res.bounds, eb)
                    and np.array_equal(res.fps, ef)
                    and np.array_equal(res.lengths, el)):
                bad.append(i)
        if bad:
            raise PackingDivergenceError(
                f"packed dispatch diverged from the per-stream unpacked "
                f"replay on streams {bad} (row width {S}): the segment-"
                f"packed pipeline no longer chunks each stream exactly as "
                f"it would chunk alone"
            )

    def _cross_check(self, bucket: int, batch: np.ndarray,
                     bounds: np.ndarray, counts: np.ndarray):
        """Replay one batch through the other mask backend; raise on any bit."""
        from repro.core.seqcdc import boundaries_batch

        other = "jnp" if self.mask_impl == "pallas" else "pallas"
        b2, c2 = boundaries_batch(
            jnp.asarray(batch), self.params, mask_impl=other,
            step_impl=self.step_impl,
            max_chunks=max_chunks_for(bucket, self.params),
        )
        b2, c2 = np.asarray(b2), np.asarray(c2)
        if not (np.array_equal(counts, c2) and np.array_equal(bounds, b2)):
            rows = np.nonzero(
                (counts != c2) | (bounds != b2).any(axis=-1)
            )[0].tolist()
            raise MaskDivergenceError(
                f"mask_impl={self.mask_impl!r} and {other!r} diverged on "
                f"bucket {bucket} (rows {rows}): the Pallas phase-1 kernel "
                f"no longer matches the lax reference bit-for-bit"
            )

    def _cross_check_fp(self, bucket: int, batch: np.ndarray,
                        bounds: np.ndarray, counts: np.ndarray,
                        fps: np.ndarray, lens: np.ndarray):
        """Replay one batch's fingerprints through the other fp backend;
        raise on any differing bit (the ``_cross_check`` twin for fps)."""
        other = "reference" if self.fp_impl == "pallas" else "pallas"
        mc = max_chunks_for(bucket, self.params)
        f2, l2 = jax.vmap(
            lambda d, b, c: chunk_fingerprints(d, b, c, max_chunks=mc,
                                               fp_impl=other)
        )(jnp.asarray(batch), jnp.asarray(bounds), jnp.asarray(counts))
        f2, l2 = np.asarray(f2), np.asarray(l2)
        if not (np.array_equal(fps, f2) and np.array_equal(lens, l2)):
            rows = np.nonzero(
                (fps != f2).any(axis=(-2, -1)) | (lens != l2).any(axis=-1)
            )[0].tolist()
            raise FingerprintDivergenceError(
                f"fp_impl={self.fp_impl!r} and {other!r} diverged on bucket "
                f"{bucket} (rows {rows}): the Pallas fingerprint kernel no "
                f"longer matches the gather-chain reference bit-for-bit"
            )

    def _cross_check_pipeline(self, bucket: int, batch: np.ndarray,
                              bounds: np.ndarray, counts: np.ndarray,
                              fps: np.ndarray, lens: np.ndarray):
        """Replay one batch through the *other* pipeline (fused <-> composed
        split) and compare everything bit-for-bit; the raised error names
        the first stage that diverged — a wrong boundary and a wrong hash
        point at different kernel lanes."""
        mc = max_chunks_for(bucket, self.params)
        x = jnp.asarray(batch)
        if self.pipeline_impl == "fused":
            other = "split"
            b2, c2, f2, l2 = _run_split(x, self.params, mc, self.mask_impl,
                                        self.step_impl, self.fp_impl)
        else:
            other = "fused"
            b2, c2, f2, l2 = _run_fused(x, self.params, mc)
        b2, c2 = np.asarray(b2), np.asarray(c2)
        f2, l2 = np.asarray(f2), np.asarray(l2)
        if not (np.array_equal(counts, c2) and np.array_equal(bounds, b2)):
            rows = np.nonzero(
                (counts != c2) | (bounds != b2).any(axis=-1)
            )[0].tolist()
            raise PipelineDivergenceError(
                f"pipeline_impl={self.pipeline_impl!r} and {other!r} "
                f"diverged on bucket {bucket} (rows {rows}) in the "
                f"boundary stage: the fused kernel's mask/scan lanes no "
                f"longer match the split path bit-for-bit",
                stage="boundaries",
            )
        if not (np.array_equal(fps, f2) and np.array_equal(lens, l2)):
            rows = np.nonzero(
                (fps != f2).any(axis=(-2, -1)) | (lens != l2).any(axis=-1)
            )[0].tolist()
            raise PipelineDivergenceError(
                f"pipeline_impl={self.pipeline_impl!r} and {other!r} "
                f"diverged on bucket {bucket} (rows {rows}) in the "
                f"fingerprint stage: identical chunk boundaries but the "
                f"fused kernel's hash limb path no longer matches",
                stage="fingerprints",
            )

    def _exactify(self, req: ChunkRequest, padded: np.ndarray,
                  padded_fps: np.ndarray | None) -> ChunkResult:
        """Trim a padded-run boundary list to the exact per-stream result."""
        t0 = time.perf_counter()
        with span("sched.tail", bytes=req.data.size):
            bounds, fps, lengths, tail_bytes = _trim_exact(
                req.data, padded, padded_fps, self.params
            )
        if tail_bytes:
            # tail_s counts only redos that did work: the kept-boundary
            # trim is O(chunks) bookkeeping, the oracle re-chunk is the
            # latency phase (the service reattributes it out of its
            # chunk-dispatch phase via this accumulator's delta)
            self.stats.tail_bytes += tail_bytes
            self.stats.tail_s += time.perf_counter() - t0
            self.obs.inc("sched.tail_bytes", tail_bytes)
        return ChunkResult(req.tag, req.data, bounds, fps, lengths)
