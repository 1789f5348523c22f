#!/usr/bin/env python3
"""Run the dedup service end to end on a TPU and check every result.

    python chip_smoke.py             # one chip: the service's main path
    python chip_smoke.py --chips 4   # four chips: mesh routing only

One chip.  A seeded corpus goes through ``DedupService`` on a file-backed
depot in a temporary directory (submit -> flush -> get -> stat -> delete ->
gc) on the default served path:

* a backup chain: 4 snapshots of a 64 MiB image
  (``snapshot_series(base_bytes=64 << 20, snapshots=4, seed=0)``);
* the ``container_images`` scenario at its ``full`` budget (6 versions of a
  tar-like image of 128 files);
* 256 seeded small objects of 4-96 KiB (that scenario's file sizes).

Checks: every object's chunk boundaries equal ``core/oracle.boundaries_numpy``;
``stored_bytes`` and ``unique_chunks`` equal a dict-of-SHA-256 reference
built from those boundaries; every ``get`` returns the submitted bytes;
deleting everything and running ``gc`` returns the store to zero.

Then each kernel path the scheduler can select ingests the same corpus and
must give recipes (chunk keys, lengths, packed fingerprints) bit-identical
to the default path's: ``pipeline_impl="fused"``, ``mask_impl="pallas"``
with ``fp_impl="pallas"``, ``packing_impl="segments"`` with fused, and the
reference fingerprint chain (``fp_impl="reference"``).  The
default path leaves ``fp_impl`` to the platform, which on a TPU is the
fingerprint kernel.  On the chip every path that holds a kernel must run it
compiled: the dispatched program holds a Mosaic kernel.

Last, ``ShardedDedupService.open(root, 2, transport="remote")`` spawns two
shard-server processes from this process, which holds the chip, ingests the
container images and is checked against a one-shard service.

Four chips.  Only the mesh path: ``ShardedDedupService(4, mesh=...)``
routes fingerprints through an all_to_all across the chips; it must agree
with the same service routing on the host (recipes, accounting, per-shard
fingerprint-index contents), and the routed tables must equal the host
partition.  The script reports where each routed table lives.

Each phase prints one JSON line (bytes, dedup ratio, compiles, peak device
memory, wall seconds: informative only).  The last line is
``{"ok": true, "device": {...}}``; any failed check raises, exits non-zero
and prints no such line.  The script refuses to run when JAX's first device
is not a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))


@dataclasses.dataclass(frozen=True)
class Sizes:
    snapshot_bytes: int = 64 << 20
    snapshots: int = 4
    scenario_budget: str = "full"
    small_objects: int = 256


#: the small objects' sizes: the container_images scenario's file sizes
SMALL_LO, SMALL_HI = 4 << 10, 96 << 10


#: the served path's defaults, pinned so no environment variable moves them;
#: ``fp_impl`` is left to the scheduler, which chooses it by platform
DEFAULT_PATH = dict(mask_impl="jnp", step_impl="wide", fp_impl=None,
                    pipeline_impl="split", packing_impl="off")
#: every other path the scheduler can select, each held to the default
OTHER_PATHS = {
    "fused": dict(DEFAULT_PATH, pipeline_impl="fused"),
    "pallas-masks-fps": dict(DEFAULT_PATH, mask_impl="pallas",
                             fp_impl="pallas"),
    "packed-fused": dict(DEFAULT_PATH, pipeline_impl="fused",
                         packing_impl="segments"),
    "reference-fps": dict(DEFAULT_PATH, fp_impl="reference"),
}


class CheckFailed(AssertionError):
    pass


def check(cond, msg: str):
    if not cond:
        raise CheckFailed(msg)


# -- corpus ---------------------------------------------------------------------


def small_objects(sizes: Sizes, seed: int = 1):
    """Seeded 4-96 KiB objects: text-like rows, random bytes, zero runs."""
    from repro.scenarios import edits

    rng = np.random.default_rng(seed)
    out = []
    for i in range(sizes.small_objects):
        n = int(rng.integers(SMALL_LO, SMALL_HI))
        kind = i % 3
        if kind == 0:
            data = edits.structured_rows(rng, n, start_id=i * 1000)
        elif kind == 1:
            data = rng.integers(0, 256, n, dtype=np.uint8)
        else:
            data = np.zeros(n, dtype=np.uint8)
        out.append((f"small-{i:04d}", data))
    return out


def container_images(sizes: Sizes):
    from repro.scenarios import generate

    return list(generate("container_images", sizes.scenario_budget).objects)


def build_corpus(sizes: Sizes):
    from repro.data.corpus import snapshot_series

    snaps = snapshot_series(base_bytes=sizes.snapshot_bytes,
                            snapshots=sizes.snapshots, seed=0)
    corpus = [(f"snap-{i}", s) for i, s in enumerate(snaps)]
    return corpus + container_images(sizes) + small_objects(sizes)


# -- measurement ----------------------------------------------------------------


class Compiles:
    """Counts backend compiles and their seconds (JAX's monitoring hook)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def mark(self):
        return self.count, self.seconds


def peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def report(phase: str, t0: float, compiles: Compiles, mark, **fields):
    n, s = compiles.mark()
    line = dict(phase=phase, **fields, compiles=n - mark[0],
                compile_s=round(s - mark[1], 3),
                peak_bytes_in_use=peak_bytes(),
                wall_s=round(time.perf_counter() - t0, 3))
    print(json.dumps(line), flush=True)


# -- checks ---------------------------------------------------------------------


def ingest(svc, corpus):
    for name, data in corpus:
        svc.submit(name, data)
    return svc.flush()


def recipes_of(svc):
    return {r.name: (r.keys, r.chunk_lens, r.fps, r.sha256)
            for r in svc.recipes}


def check_exact(svc, corpus, params):
    """Oracle boundaries, dict-of-SHA-256 accounting, byte-exact gets."""
    from repro.core import oracle

    ref = {}
    for name, data in corpus:
        r = svc.recipes.get(name)
        want = oracle.boundaries_numpy(data, params)
        got = np.cumsum(np.asarray(r.chunk_lens, dtype=np.int64))
        check(np.array_equal(got, want),
              f"{name}: chunk boundaries differ from the oracle")
        buf = data.tobytes()
        keys, s = [], 0
        for e in want.tolist():
            k = hashlib.sha256(buf[s:e]).hexdigest()
            ref[k] = e - s
            keys.append(k)
            s = e
        check(r.keys == keys, f"{name}: chunk keys differ from SHA-256")
        check(svc.get(name) == buf, f"{name}: get returned other bytes")
        st = svc.stat(name)
        check(st.size == data.size and st.chunks == len(want),
              f"{name}: stat disagrees with the object")
    stats = svc.stats()
    check(stats.stored_bytes == sum(ref.values()),
          f"stored_bytes {stats.stored_bytes} != reference "
          f"{sum(ref.values())}")
    check(stats.unique_chunks == len(ref),
          f"unique_chunks {stats.unique_chunks} != reference {len(ref)}")
    check(stats.logical_bytes == sum(d.size for _, d in corpus),
          "logical_bytes differs from the corpus")
    return stats


def check_gc_to_zero(svc, names):
    for name in names:
        svc.delete(name)
    svc.gc()
    stats = svc.stats()
    check(stats.objects == 0 and stats.stored_bytes == 0
          and stats.unique_chunks == 0,
          f"store not empty after delete + gc: {stats}")


def holds_kernel(sched) -> bool:
    """Whether the scheduler's resolved path dispatches a Pallas kernel."""
    return ("pallas" in (sched.mask_impl, sched.fp_impl)
            or sched.pipeline_impl == "fused")


def check_compiled(sched):
    """The scheduler's device program holds a Mosaic kernel (on the chip
    the kernels compile; nothing falls back to the interpreter)."""
    import jax
    import jax.numpy as jnp

    from repro.core.automaton import max_chunks_for
    from repro.kernels import ops
    from repro.service.scheduler import _device_chunk

    check(not ops._interpret(), "Pallas kernels would run interpreted")
    bucket = 1 << 14
    path = dict(mask_impl=sched.mask_impl, step_impl=sched.step_impl,
                fp_impl=sched.fp_impl, pipeline_impl=sched.pipeline_impl)
    lowered = jax.jit(lambda x: _device_chunk(
        x, p=sched.params, mc=max_chunks_for(bucket, sched.params),
        with_fp=True, **path,
    )).lower(jax.ShapeDtypeStruct((1, bucket), jnp.uint8))
    check("tpu_custom_call" in lowered.as_text(),
          f"no Mosaic kernel in the {path} device program")


# -- phases ---------------------------------------------------------------------


def run_one_chip(sizes: Sizes, root: str, *, require_compiled: bool = True):
    """The one-chip phases; returns the default path's service stats."""
    from repro.service import DedupService, ShardedDedupService

    compiles = Compiles()
    corpus = build_corpus(sizes)
    total = sum(int(d.size) for _, d in corpus)

    t0, mark = time.perf_counter(), compiles.mark()
    svc = DedupService.open(os.path.join(root, "default"), **DEFAULT_PATH)
    if require_compiled:
        check(svc.scheduler.fp_impl == "pallas",
              f"the default path on a TPU fingerprints with "
              f"{svc.scheduler.fp_impl!r}, not the kernel")
        check_compiled(svc.scheduler)
    ingest(svc, corpus)
    stats = check_exact(svc, corpus, svc.params)
    want = recipes_of(svc)
    check_gc_to_zero(svc, [n for n, _ in corpus])
    report("default", t0, compiles, mark, objects=len(corpus), bytes=total,
           stored_bytes=stats.stored_bytes,
           unique_chunks=stats.unique_chunks,
           dedup_ratio=round(stats.dedup_ratio, 4),
           fp_impl=svc.scheduler.fp_impl,
           buckets=sorted({svc.scheduler._bucket_for(d.size)
                           for _, d in corpus}))

    for name, path in OTHER_PATHS.items():
        t0, mark = time.perf_counter(), compiles.mark()
        svc = DedupService.open(os.path.join(root, name), **path)
        if require_compiled and holds_kernel(svc.scheduler):
            check_compiled(svc.scheduler)
        ingest(svc, corpus)
        got = recipes_of(svc)
        for obj, rec in want.items():
            check(got[obj] == rec,
                  f"{name}: recipe of {obj} differs from the default path")
        st = svc.stats()
        check((st.stored_bytes, st.unique_chunks)
              == (stats.stored_bytes, stats.unique_chunks),
              f"{name}: accounting differs from the default path")
        report(name, t0, compiles, mark, objects=len(corpus), bytes=total,
               dedup_ratio=round(st.dedup_ratio, 4),
               packed_streams=svc.scheduler.stats.packed_streams)

    t0, mark = time.perf_counter(), compiles.mark()
    images = container_images(sizes)
    one = ShardedDedupService.open(os.path.join(root, "one-shard"), 1,
                                   **DEFAULT_PATH)
    remote = ShardedDedupService.open(os.path.join(root, "remote"), 2,
                                      transport="remote", **DEFAULT_PATH)
    try:
        ingest(one, images)
        ingest(remote, images)
        a, b = one.stats(), remote.stats()
        check((a.stored_bytes, a.unique_chunks)
              == (b.stored_bytes, b.unique_chunks),
              "remote 2-shard accounting differs from one shard")
        ra, rb = recipes_of(one), recipes_of(remote)
        check(ra == rb, "remote 2-shard recipes differ from one shard")
        for name, data in images:
            check(remote.get(name) == data.tobytes(),
                  f"remote get of {name} returned other bytes")
        pids = [h.proc.pid for h in remote._servers]
        check_gc_to_zero(remote, [n for n, _ in images])
    finally:
        remote.close()
        one.close()
    report("remote-2-shards", t0, compiles, mark, objects=len(images),
           bytes=sum(int(d.size) for _, d in images),
           dedup_ratio=round(b.dedup_ratio, 4), server_pids=pids)
    return stats


def run_mesh(sizes: Sizes, mesh):
    """Mesh all_to_all routing against host routing, same corpus."""
    from repro.dedup.dist_index import route_host
    from repro.service import ShardedDedupService

    compiles = Compiles()
    t0, mark = time.perf_counter(), compiles.mark()
    corpus = container_images(sizes) + small_objects(sizes)
    ns = mesh.shape["data"]
    host = ShardedDedupService(ns, **DEFAULT_PATH)
    meshed = ShardedDedupService(ns, mesh=mesh, **DEFAULT_PATH)
    try:
        ingest(host, corpus)
        ingest(meshed, corpus)
        check(meshed.overflow_rerouted == 0,
              f"{meshed.overflow_rerouted} records overflowed the mesh "
              f"route and went host-side")
        check(recipes_of(meshed) == recipes_of(host),
              "mesh-routed recipes differ from host routing")
        check([r.shards for r in meshed.recipes]
              == [r.shards for r in host.recipes],
              "mesh-routed shard maps differ from host routing")
        a, b = meshed.stats(), host.stats()
        check((a.stored_bytes, a.unique_chunks, a.fp_estimated_savings)
              == (b.stored_bytes, b.unique_chunks, b.fp_estimated_savings),
              "mesh-routed accounting differs from host routing")
        for s in range(ns):
            check(meshed.fp_index[s].seen == host.fp_index[s].seen,
                  f"shard {s}: mesh fp-index contents differ from host")

        # the routed tables themselves: where they live, and that each
        # owner's slab is exactly the host partition
        fps = np.concatenate([np.asarray([[f >> 32, f & 0xFFFFFFFF]
                                          for f in r.fps], dtype=np.uint32)
                              for r in meshed.recipes])
        lengths = np.concatenate([np.asarray(r.chunk_lens, np.int32)
                                  for r in meshed.recipes])
        pad = -len(lengths) % ns
        fps = np.concatenate([fps, np.zeros((pad, 2), np.uint32)])
        lengths = np.concatenate([lengths, np.zeros(pad, np.int32)])
        with mesh:
            tables, overflow = meshed._routed_fn(fps, lengths)
        placement = {
            str(sh.device): [[sl.start, sl.stop] for sl in sh.index[:1]]
            for sh in tables.addressable_shards
        }
        check(int(overflow) == 0, "the routed tables overflowed")
        real = len(lengths) - pad
        owners = route_host(fps[:real], ns)
        records = np.concatenate(
            [fps[:real], lengths[:real, None].astype(np.uint32)], axis=1)
        for s, slab in enumerate(np.asarray(tables)):
            flat = slab.reshape(-1, 3)
            got = sorted(map(tuple, flat[flat[:, 2] > 0].tolist()))
            want = sorted(map(tuple, records[owners == s].tolist()))
            check(got == want, f"owner {s}: routed table != host partition")
        report("mesh-routing", t0, compiles, mark, objects=len(corpus),
               bytes=sum(int(d.size) for _, d in corpus),
               shards=ns, overflow_rerouted=meshed.overflow_rerouted,
               table_placement=placement,
               overflow_placement=[str(d) for d in overflow.devices()],
               dedup_ratio=round(a.dedup_ratio, 4))
    finally:
        meshed.close()
        host.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the service end to end; 4: mesh routing only")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    from repro.runtime import enable_compile_cache

    enable_compile_cache()
    if args.chips == 4:
        if len(devices) < 4:
            print(f"chip_smoke: --chips 4 needs 4 devices, JAX found "
                  f"{len(devices)}", file=sys.stderr)
            return 2
        run_mesh(Sizes(), jax.make_mesh((4,), ("data",)))
    else:
        with tempfile.TemporaryDirectory(prefix="chip-smoke-") as root:
            run_one_chip(Sizes(), root)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
