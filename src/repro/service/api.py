"""DedupService: the streaming deduplication service (put/get/stat/delete).

One object ties the repo's pieces into a serving system:

    submit/put --> ChunkScheduler (length-bucketed device batches,
                   vmapped two-phase SeqCDC + fingerprints)
               --> BlockStore     (SHA-256 content-addressed, refcounted)
               --> RecipeTable    (object -> chunk keys + object digest)
    get        --> reassemble from recipe, SHA-256 verify
    delete     --> release refcounts; gc() mark-and-sweeps crash orphans

Ingest is continuous-batching style: ``submit`` enqueues without blocking,
``flush`` drains the scheduler and commits recipes, ``put`` is the one-shot
convenience (submit + flush).  Submitting many objects before flushing is
what keeps device batches full — the estimator CLI and benchmarks do that.

Accounting: the store's SHA-256 keys give *exact* dedup (logical vs stored
bytes); the accelerator's 62-bit fingerprints feed a ``FingerprintIndex``
whose savings estimate is reported alongside — the paper's fast fingerprint
as an estimator, the collision-resistant hash as ground truth.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import os
import threading
import time
from collections import Counter
from typing import Dict, List, Optional

from repro.core.params import SeqCDCParams, derived_params
from repro.dedup import BlockStore, DirBlockStore, FingerprintIndex
from repro.dedup.store import BlockCorruptionError
from repro.obs import (
    MetricsRegistry,
    PhaseClock,
    labeled,
    merge_snapshots,
    span,
)

from .objects import ObjectRecipe, RecipeTable
from .scheduler import ChunkResult, ChunkScheduler

#: the calling thread's active request (one per thread: requests on the
#: public surface don't nest except put = submit+flush, which reuses the
#: outer request so its phases attribute to op=put, not op=flush)
_REQ_TLS = threading.local()


@dataclasses.dataclass
class _Request:
    """One in-flight request: its id, op label, and phase partition clock."""

    op: str
    rid: str
    clock: PhaseClock


class IntegrityError(RuntimeError):
    """Restore produced bytes whose digest does not match the recipe."""


def verify_restore(r: ObjectRecipe, data: bytes) -> bytes:
    """The one restore-verification rule, shared by both services: length
    and whole-object SHA-256 must match the recipe or nothing is returned."""
    if len(data) != r.size or hashlib.sha256(data).hexdigest() != r.sha256:
        raise IntegrityError(
            f"object {r.name!r}: restored {len(data)}B, digest mismatch "
            f"(expected {r.size}B sha256={r.sha256[:12]}...)"
        )
    return data


def sweep_store(store: BlockStore, live: Dict[str, int]) -> "GCStats":
    """One store's mark-and-sweep pass, shared by both services.

    ``live`` is the recomputed truth (key -> reference count from the recipe
    roots).  The pass itself is :meth:`~repro.dedup.BlockStore.sweep` —
    store-local so a remote store (``transport/client.py``) runs it next to
    its data in one RPC instead of one round trip per key.
    """
    return GCStats(*store.sweep(live))


def pack_fps(fps) -> List[int]:
    """Per-chunk 62-bit fingerprints packed to ``(h1 << 32) | h2`` ints for
    the recipe (``ObjectRecipe.fps``).

    Recording them is what makes a depot *reshardable*: routing is by
    ``owner_of(fp.h1, N)``, which the SHA-256 key cannot reproduce, so an
    N→M repartition (scripts/reshard.py) would otherwise have to re-chunk
    and re-hash every object.
    """
    import numpy as np

    return [(int(h1) << 32) | int(h2) for h1, h2 in np.asarray(fps).tolist()]


def recipe_totals(recipes: RecipeTable) -> tuple[int, int, Dict[int, int]]:
    """(logical_bytes, total_chunks, log2-bucket histogram) over a table —
    the recipe-derived half of ServiceStats, shared by both services."""
    hist: Counter = Counter()
    logical = 0
    total_chunks = 0
    for r in recipes:
        logical += r.size
        total_chunks += len(r.keys)
        for ln in r.chunk_lens:
            hist[max(0, int(ln).bit_length() - 1)] += 1
    return logical, total_chunks, dict(sorted(hist.items()))


@dataclasses.dataclass
class ObjectStat:
    name: str
    size: int
    chunks: int
    sha256: str
    mean_chunk: float

    @classmethod
    def of(cls, r: ObjectRecipe) -> "ObjectStat":
        return cls(name=r.name, size=r.size, chunks=len(r.keys), sha256=r.sha256,
                   mean_chunk=r.size / len(r.keys) if r.keys else 0.0)


@dataclasses.dataclass
class ServiceStats:
    objects: int
    logical_bytes: int  # sum of live object sizes
    stored_bytes: int  # unique chunk bytes on disk/in memory
    total_chunks: int
    unique_chunks: int
    chunk_size_hist: Dict[int, int]  # log2-bucket -> live chunk refs
    fp_estimated_savings: float  # 62-bit fp estimate, cumulative over ingests
    batches: int
    batch_occupancy: float
    #: payload bytes the store actually holds (== stored_bytes when the
    #: store codec is "none"; smaller under compression)
    compressed_bytes: int = 0
    codec: str = "none"  # the store's write codec

    @property
    def dedup_ratio(self) -> float:
        return self.logical_bytes / self.stored_bytes if self.stored_bytes else 1.0

    @property
    def compressed_ratio(self) -> float:
        """End-to-end reduction: logical bytes per *payload* byte held —
        dedup x compression (== :attr:`dedup_ratio` for codec-less stores),
        the ratio the exemplar estimators report."""
        if not self.compressed_bytes:
            return self.dedup_ratio
        return self.logical_bytes / self.compressed_bytes

    @property
    def space_savings(self) -> float:
        if not self.logical_bytes:
            return 0.0
        return (self.logical_bytes - self.stored_bytes) / self.logical_bytes


@dataclasses.dataclass
class GCStats:
    freed_blocks: int
    freed_bytes: int
    repaired_refs: int


class ServiceBase:
    """The scheduler-facing ingest/serve surface shared by both services.

    Subclasses (:class:`DedupService`, single store;
    :class:`~repro.service.sharded.ShardedDedupService`, fingerprint
    partitioned) provide ``recipes``, ``scheduler``, an ``_in_flight`` name
    set, and their own ``flush``/``get``/``delete``/``gc``; everything here
    is backend-agnostic, so the two services cannot drift on the ingest
    contract (name collisions, in-flight bookkeeping, stat/names shape).
    """

    recipes: RecipeTable
    scheduler: "ChunkScheduler"
    _in_flight: set
    #: the service-wide MetricsRegistry every layer under this service
    #: reports into (scheduler, writers, transport clients)
    obs: MetricsRegistry

    def submit(self, name: str, data, *, overwrite: bool = False) -> int:
        """Queue one object for ingest; returns its ticket (a sequence id).

        Nothing is chunked, stored, or committed until :meth:`flush` — the
        object is not restorable and not visible in :meth:`names` yet.
        ``data`` is raw bytes or anything numpy turns into a uint8 vector;
        raises ``KeyError`` if ``name`` already exists (committed or
        in-flight) and ``overwrite`` is False.  Submitting many objects
        before one flush is what fills device batches (continuous batching).
        """
        if not overwrite and (name in self.recipes or name in self._in_flight):
            raise KeyError(f"object {name!r} already exists (overwrite=False)")
        seq = self.scheduler.submit(data, tag=name)
        self._in_flight.add(name)
        return seq

    def put(self, name: str, data, *, overwrite: bool = False) -> ObjectStat:
        """Store one object now (submit + flush); returns its ObjectStat.

        Convenience for interactive/one-shot use — batched ingest via
        :meth:`submit` + :meth:`flush` is the throughput path.  After
        ``put`` returns, the object is durable (for file-backed stores)
        and restorable via ``get``.
        """
        with self._request("put", object=name):
            self.submit(name, data, overwrite=overwrite)
            return self.flush()[-1]

    def flush(self) -> List[ObjectStat]:
        raise NotImplementedError

    def stat(self, name: str) -> ObjectStat:
        """Recipe-level summary of one committed object (size, chunk count,
        digest, mean chunk) without touching block data.  ``KeyError`` for
        unknown or not-yet-flushed names."""
        return ObjectStat.of(self.recipes.get(name))

    def names(self) -> List[str]:
        """Sorted names of all committed objects (in-flight ones excluded)."""
        return self.recipes.names()

    # -- request attribution ----------------------------------------------------
    @contextlib.contextmanager
    def _request(self, op: str, **attrs):
        """Root of one public-surface request (put/get/delete/flush/gc).

        Opens a ``request`` root span carrying a fresh request id (every
        span under it — scheduler dispatches, writer tasks, shard RPCs,
        server-side ops — shares its ``trace_id``) and a
        :class:`~repro.obs.PhaseClock` whose partition lands in the
        ``req.latency_s{op=,phase=}`` histograms at close, plus
        ``req.total_s{op=}`` and a ``req.requests{op=}`` counter.  The
        clock tiles the request's wall time exactly, so the per-phase sums
        reconcile with the root span's ``wall_s``.

        Re-entrant per thread: a request started while another is active
        on the same thread joins it (``put`` = submit + ``flush``; the
        phases attribute to the outer op).  Error paths still record — a
        failed request's time is the tail latency you most want to see.
        """
        active = getattr(_REQ_TLS, "active", None)
        if active is not None:
            yield active
            return
        req = _Request(op=op, rid=os.urandom(6).hex(), clock=PhaseClock())
        _REQ_TLS.active = req
        try:
            with span("request", op=op, req=req.rid, **attrs) as sp:
                try:
                    yield req
                finally:
                    # stop() is idempotent: the same partition recorded on
                    # the root span here lands in the histograms below, so
                    # a trace file alone carries the phase attribution
                    _, phases = req.clock.stop()
                    sp["phases"] = {p: round(s, 6)
                                    for p, s in phases.items()}
        finally:
            _REQ_TLS.active = None
            total, phases = req.clock.stop()
            self.obs.inc(labeled("req.requests", op=op))
            self.obs.observe(labeled("req.total_s", op=op), total)
            for ph, secs in phases.items():
                self.obs.observe(
                    labeled("req.latency_s", op=op, phase=ph), secs
                )

    def _phase(self, name: str):
        """Attribute the ``with`` body's wall time to phase ``name`` of the
        thread's active request; a plain no-op outside any request, so
        helpers shared by instrumented and bare call paths need no guard."""
        active = getattr(_REQ_TLS, "active", None)
        if active is None:
            return contextlib.nullcontext()
        return active.clock.phase(name)

    def _move_phase(self, src: str, dst: str, seconds: float):
        """Reattribute seconds between phases of the active request (the
        scheduler's host tail redo runs *inside* the drain call, so its
        self-reported seconds move chunk-dispatch -> tail after the fact)."""
        active = getattr(_REQ_TLS, "active", None)
        if active is not None:
            active.clock.move(src, dst, seconds)

    # -- observability ----------------------------------------------------------
    def metrics(self) -> dict:
        """Live telemetry snapshot (docs/OBSERVABILITY.md has the catalog).

        ``service`` is this process's registry — ingest/restore counters,
        scheduler occupancy and dispatch latency, writer backpressure,
        client-side RPC metrics.  ``shards`` holds one server-side snapshot
        per shard store (remote transport only: fetched live over the wire
        via the ``metrics`` op; empty otherwise), with ``None`` standing in
        for an unreachable server.  ``aggregate`` merges the reachable
        shard snapshots: counters sum, histograms merge bucket-wise and
        re-derive their percentiles.
        """
        shards = self._shard_metric_snapshots()
        return {
            "service": self.obs.snapshot(),
            "shards": shards,
            "aggregate": merge_snapshots(shards) if shards else None,
        }

    def _shard_metric_snapshots(self) -> List[Optional[dict]]:
        """Per-shard server-side snapshots; base services have none."""
        return []


class DedupService(ServiceBase):
    """Streaming dedup: batched chunking in front of a GC-capable chunk store."""

    def __init__(
        self,
        store: Optional[BlockStore] = None,
        params: Optional[SeqCDCParams] = None,
        *,
        avg_chunk: int = 8192,
        slots: int = 8,
        min_bucket: int = 1 << 14,
        recipes: Optional[RecipeTable] = None,
        mask_impl: str = "jnp",
        step_impl: str = "wide",
        fp_impl: str | None = None,
        pipeline_impl: str | None = None,
        packing_impl: str | None = None,
        with_fingerprints: bool = True,
        cross_check_masks: bool = False,
        cross_check_fps: bool = False,
        cross_check_pipeline: bool = False,
        cross_check_packing: bool = False,
        codec: Optional[str] = None,
    ):
        self.params = params or derived_params(avg_chunk)
        # codec applies to the default store only; an explicit ``store``
        # arrives already configured (None resolves $REPRO_STORE_CODEC)
        self.store = store if store is not None else BlockStore(codec=codec)
        self.recipes = recipes if recipes is not None else RecipeTable()
        # per-service (not global) registry: tests and side-by-side services
        # never share counters; the scheduler reports into the same one
        self.obs = MetricsRegistry()
        if hasattr(self.store, "attach_obs"):
            self.store.attach_obs(self.obs)
        self.scheduler = ChunkScheduler(
            self.params, registry=self.obs, slots=slots, min_bucket=min_bucket,
            mask_impl=mask_impl, step_impl=step_impl, fp_impl=fp_impl,
            pipeline_impl=pipeline_impl,
            packing_impl=packing_impl,
            with_fingerprints=with_fingerprints,
            cross_check_masks=cross_check_masks,
            cross_check_fps=cross_check_fps,
            cross_check_pipeline=cross_check_pipeline,
            cross_check_packing=cross_check_packing,
        )
        # ingest-cumulative: tracks every chunk ever ingested (the estimator
        # semantics); deletes/overwrites do not shrink it, unlike the exact
        # store accounting
        self.fp_index = FingerprintIndex()
        self._in_flight: set[str] = set()  # names submitted, not yet flushed

    @classmethod
    def open(cls, root: str, *, codec: Optional[str] = None,
             hot_bytes: int = 0, **kwargs) -> "DedupService":
        """File-backed service at ``root``: blocks + recipes survive restarts.

        ``codec`` selects the store's write codec (None: the depot's
        manifest codec, else ``$REPRO_STORE_CODEC``); ``hot_bytes`` enables
        cold tiering on the underlying :class:`DirBlockStore`.
        """
        os.makedirs(root, exist_ok=True)
        store = DirBlockStore(root, codec=codec, hot_bytes=hot_bytes)
        recipes = RecipeTable(os.path.join(root, "recipes.json"))
        return cls(store=store, recipes=recipes, **kwargs)

    # -- ingest -----------------------------------------------------------------
    def flush(self) -> List[ObjectStat]:
        """Drain the scheduler, store chunks, commit recipes.  FIFO order.

        Durability order: new blocks and recipes are synced *before* any
        block superseded by an overwrite is released, so a crash mid-flush
        leaves orphan blocks (reclaimable by :meth:`gc`), never a committed
        recipe pointing at missing blocks.
        """
        # whatever drain() does — return results, or lose requests to a
        # device-side error — the submitted names are no longer pending, so
        # they must stop blocking resubmission
        with self._request("flush"):
            with span("service.flush") as sp:
                tail0 = self.scheduler.stats.tail_s
                with self._phase("chunk-dispatch"):
                    try:
                        results = self.scheduler.drain()
                    finally:
                        self._in_flight.clear()
                # the host tail redo ran inside drain(); reattribute its
                # self-reported seconds so tail latency is its own phase
                self._move_phase("chunk-dispatch", "tail",
                                 self.scheduler.stats.tail_s - tail0)
                out = []
                stale: List[str] = []
                with self._phase("commit"):
                    for res in results:
                        stat, old_keys = self._commit(res)
                        out.append(stat)
                        stale.extend(old_keys)
                with self._phase("sync"):
                    self.sync()
                if stale:
                    for k in stale:
                        self.store.release(k)
                    with self._phase("sync"):
                        self.sync()
                sp["objects"] = len(out)
            return out

    def _commit(self, res: ChunkResult) -> tuple[ObjectStat, List[str]]:
        """Store one result; returns (stat, keys superseded by an overwrite).

        Superseded keys are *not* released here — the caller releases them
        only after the new recipes are durable (see :meth:`flush`).
        """
        name = str(res.tag)
        old = self.recipes.get(name) if name in self.recipes else None
        with span("commit.object", bytes=res.size) as sp:
            before = self.store.unique_chunks
            keys = self.store.put_stream(res.data, res.bounds.tolist())
            # a dedup hit = a chunk whose key the store already held;
            # measured by the unique-count delta so no second hash pass runs
            new_chunks = self.store.unique_chunks - before
            sp["chunks"] = len(keys)
            sp["new_chunks"] = new_chunks
            self.obs.inc("ingest.objects")
            self.obs.inc("ingest.bytes", res.size)
            self.obs.inc("ingest.chunks", len(keys))
            self.obs.inc("ingest.dedup_hit_chunks", len(keys) - new_chunks)
            t0 = time.perf_counter()
            digest = hashlib.sha256(res.data).hexdigest()
            self.obs.inc("commit.digest_s", time.perf_counter() - t0)
            recipe = ObjectRecipe(
                name=name,
                size=res.size,
                sha256=digest,
                keys=keys,
                chunk_lens=res.lengths.astype(int).tolist(),
                # recorded when the scheduler fingerprinted
                # (reshardability); with_fingerprints=False leaves the
                # field absent
                fps=(pack_fps(res.fps) if res.fps.shape[0] == len(keys)
                     else None),
            )
            if res.fps.size:
                with self._phase("fp"):
                    self.fp_index.add_batch(res.fps, res.lengths)
            self.recipes.add(recipe)
        return ObjectStat.of(recipe), (old.keys if old is not None else [])

    # -- serve ------------------------------------------------------------------
    def get(self, name: str) -> bytes:
        """Reassemble an object from its chunks, end-to-end verified.

        Both the restored length and the whole-object SHA-256 must match
        the recipe; any mismatch (corrupt block, recipe naming the right
        chunks in the wrong order) raises :class:`IntegrityError` rather
        than returning wrong bytes.  ``KeyError`` for unknown names.
        """
        r = self.recipes.get(name)
        with self._request("get", object=name):
            with span("service.get", object=name, bytes=r.size):
                # "rpc" = the block-gather seam; for this single-store
                # service it is the same seam served in-process
                with self._phase("rpc"):
                    try:
                        data = self.store.get_stream(r.keys)
                    except BlockCorruptionError as e:
                        # a block that fails to decode is the same contract
                        # breach as a digest mismatch: corrupt storage
                        raise IntegrityError(
                            f"object {name!r}: {e}"
                        ) from e
                with self._phase("verify"):
                    data = verify_restore(r, data)
            self.obs.inc("restore.objects")
            self.obs.inc("restore.bytes", r.size)
            return data

    # -- delete / GC ------------------------------------------------------------
    def delete(self, name: str) -> int:
        """Remove an object; returns stored bytes actually reclaimed.

        The recipe removal is made durable *before* any block file is
        unlinked: a crash mid-delete leaves orphan blocks for :meth:`gc`,
        never a surviving recipe pointing at missing blocks.
        """
        with self._request("delete", object=name):
            r = self.recipes.remove(name)  # KeyError for unknown objects
            with self._phase("sync"):
                self.recipes.sync()
            freed = 0
            with self._phase("commit"):
                for k, ln in zip(r.keys, r.chunk_lens):
                    if self.store.release(k):
                        freed += ln
            with self._phase("sync"):
                self.sync()
            return freed

    def gc(self) -> GCStats:
        """Mark-and-sweep: recipes are roots; everything else is garbage.

        Sweeps :meth:`~repro.dedup.BlockStore.scan_keys` — which for
        file-backed stores includes block files the refcount manifest never
        recorded — so it reclaims blocks orphaned by a crash at any point
        (the write order everywhere is blocks-then-recipes, so orphans,
        never dangling recipes, are the one reachable inconsistency).  Also
        repairs refcount drift against the recomputed truth.
        """
        live: Counter = Counter()
        for r in self.recipes:
            live.update(r.keys)
        stats = sweep_store(self.store, live)
        self.sync()
        return stats

    def sync(self):
        """Persist recipes + store manifest (no-op for in-memory backends)."""
        with span("sync.recipes"):
            self.recipes.sync()
        with span("sync.manifest"):
            self.store.sync()

    # -- accounting -------------------------------------------------------------
    def stats(self) -> ServiceStats:
        logical, total_chunks, hist = recipe_totals(self.recipes)
        sched = self.scheduler.stats
        return ServiceStats(
            objects=len(self.recipes),
            logical_bytes=logical,
            stored_bytes=self.store.stored_bytes,
            total_chunks=total_chunks,
            unique_chunks=self.store.unique_chunks,
            chunk_size_hist=hist,
            fp_estimated_savings=self.fp_index.savings,
            batches=sched.dispatches,
            batch_occupancy=sched.occupancy,
            compressed_bytes=self.store.compressed_bytes,
            codec=self.store.codec,
        )
