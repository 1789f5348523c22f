"""Corpus dedupe estimator: how much would chunk-level dedup save on this data?

Walks files (or generates a synthetic file-version series), drives every
object through the streaming DedupService — batched SeqCDC chunking, SHA-256
content-addressed store — and reports logical vs stored bytes, the dedup
ratio, and the chunk-size distribution, in the spirit of the related
dedupe-estimator tools' ``de stats``.

    python scripts/dedupe_estimate.py PATH [PATH...]     # files / directories
    python scripts/dedupe_estimate.py --synthetic 8      # 8 synthetic versions
    python scripts/dedupe_estimate.py PATH --avg-chunk 4096 --json
    python scripts/dedupe_estimate.py PATH --store /tmp/depot  # persistent

With --store the chunk store and recipes persist, so re-running over new
file versions estimates *incremental* transfer (only new chunk bytes), the
cross-revision workload of the related repos.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.service import DedupService  # noqa: E402


def iter_files(paths, max_file_bytes: int, skipped: dict | None = None):
    """Deterministic walk: (object name, path) for every regular file.

    Names are unique across all roots (root label prefix when several paths
    are given, ``#N`` suffix on residual collisions) so same-named files
    never silently overwrite each other in the estimate.

    Symlinks, files over ``max_file_bytes``, and unreadable entries are
    excluded from the walk — and *counted* into ``skipped`` (keys ``files``
    / ``bytes``) when given, so the report can say what the estimate omits
    instead of silently under-measuring.
    """
    seen: dict = {}

    def unique(name: str) -> str:
        if name not in seen:
            seen[name] = 1
            return name
        # probe until free: a generated "<name>#N" can itself collide with a
        # real file literally named that way, so record every result in seen
        while True:
            seen[name] += 1
            candidate = f"{name}#{seen[name]}"
            if candidate not in seen:
                seen[candidate] = 1
                return candidate

    multi = len(paths) > 1
    for root in paths:
        label = os.path.basename(os.path.normpath(root))
        if os.path.isfile(root):
            yield unique(label), root
            continue
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames.sort()
            for fn in sorted(filenames):
                path = os.path.join(dirpath, fn)
                try:
                    if os.path.islink(path) or os.path.getsize(path) > max_file_bytes:
                        if skipped is not None:
                            skipped["files"] += 1
                            if not os.path.islink(path):
                                skipped["bytes"] += os.path.getsize(path)
                        continue
                except OSError:
                    if skipped is not None:
                        skipped["files"] += 1
                    continue
                rel = os.path.relpath(path, root)
                yield unique(os.path.join(label, rel) if multi else rel), path


def synthetic_versions(count: int, base_mb: int, edit_rate: float, seed: int):
    from repro.data.corpus import snapshot_series

    series = snapshot_series(base_bytes=base_mb << 20, snapshots=count,
                             edit_rate=edit_rate, seed=seed)
    for i, snap in enumerate(series):
        yield f"v{i:03d}.bin", snap


def human(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024
    return f"{n:.1f} GiB"


def print_report(st, ingested: int, with_fp: bool = True):
    print(f"objects          {st.objects} ({ingested} ingested this run)")
    print(f"logical bytes    {st.logical_bytes:>14,}  ({human(st.logical_bytes)})")
    print(f"stored bytes     {st.stored_bytes:>14,}  ({human(st.stored_bytes)})")
    print(f"dedup ratio      {st.dedup_ratio:14.2f}x")
    if st.codec != "none":
        # compressed_ratio = dedup x compression, the estimators' headline
        print(f"compressed bytes {st.compressed_bytes:>14,}  "
              f"({human(st.compressed_bytes)}, codec={st.codec})")
        print(f"compressed ratio {st.compressed_ratio:14.2f}x  "
              "(dedup x compression)")
    print(f"space savings    {st.space_savings:14.1%}")
    print(f"chunks           {st.total_chunks:>14,}  ({st.unique_chunks:,} unique)")
    if st.total_chunks:
        mean = st.logical_bytes / st.total_chunks
        print(f"mean chunk       {mean:14.0f}  bytes")
    if with_fp:
        print(f"fp-estimated     {st.fp_estimated_savings:14.1%}  "
              "(62-bit fingerprint, cumulative over all ingests)")
    print(f"device batches   {st.batches:>14,}  ({st.batch_occupancy:.0%} occupancy)")
    if st.chunk_size_hist:
        print("\nchunk-size distribution (log2 buckets):")
        peak = max(st.chunk_size_hist.values())
        for b, cnt in st.chunk_size_hist.items():
            bar = "#" * max(1, round(40 * cnt / peak))
            lo, hi = 1 << b, (1 << (b + 1)) - 1
            print(f"  {human(lo):>9} - {human(hi):>9}  {cnt:>9,}  {bar}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*", help="files or directories to estimate")
    ap.add_argument("--avg-chunk", type=int, default=8192)
    ap.add_argument("--store", default=None,
                    help="persistent store directory (default: in-memory)")
    ap.add_argument("--synthetic", type=int, default=0, metavar="N",
                    help="ingest N synthetic file versions instead of paths")
    ap.add_argument("--synthetic-mb", type=int, default=4)
    ap.add_argument("--edit-rate", type=float, default=5e-5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-file-mb", type=int, default=256)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--flush-every", type=int, default=64,
                    help="commit cadence (objects buffered per flush)")
    ap.add_argument("--codec", default=None, choices=["none", "zlib", "lz4"],
                    help="per-chunk store codec (default: the depot's "
                         "manifest codec, else $REPRO_STORE_CODEC)")
    ap.add_argument("--no-fp", action="store_true",
                    help="skip accelerator fingerprints (faster on CPU; "
                         "drops only the fp-estimated line)")
    ap.add_argument("--json", action="store_true", help="machine-readable output")
    args = ap.parse_args(argv)

    if not args.paths and not args.synthetic:
        ap.error("give PATHs or --synthetic N")
    for path in args.paths:
        if not os.path.exists(path):
            ap.error(f"path does not exist: {path}")

    kw = dict(avg_chunk=args.avg_chunk, slots=args.slots,
              with_fingerprints=not args.no_fp, codec=args.codec)
    if args.store:
        svc = DedupService.open(args.store, **kw)
    else:
        svc = DedupService(**kw)

    skipped = {"files": 0, "bytes": 0}
    if args.synthetic:
        objects = synthetic_versions(args.synthetic, args.synthetic_mb,
                                     args.edit_rate, args.seed)
    else:
        objects = iter_files(args.paths, args.max_file_mb << 20, skipped)

    ingested = 0
    queued = 0
    for name, src in objects:
        if isinstance(src, str):
            with open(src, "rb") as f:
                data = np.frombuffer(f.read(), dtype=np.uint8)
        else:
            data = src
        svc.submit(name, data, overwrite=True)
        ingested += 1
        queued += 1
        if queued >= args.flush_every:
            svc.flush()
            queued = 0
    svc.flush()

    st = svc.stats()
    if args.json:
        out = {
            "objects": st.objects,
            "ingested": ingested,
            "logical_bytes": st.logical_bytes,
            "stored_bytes": st.stored_bytes,
            "dedup_ratio": st.dedup_ratio,
            "codec": st.codec,
            "compressed_bytes": st.compressed_bytes,
            "compressed_ratio": st.compressed_ratio,
            "space_savings": st.space_savings,
            "total_chunks": st.total_chunks,
            "unique_chunks": st.unique_chunks,
            "chunk_size_hist": {str(k): v for k, v in st.chunk_size_hist.items()},
        }
        if not args.no_fp:
            out["fp_estimated_savings"] = st.fp_estimated_savings
        out["skipped_files"] = skipped["files"]
        out["skipped_bytes"] = skipped["bytes"]
        print(json.dumps(out, indent=2))
    else:
        print_report(st, ingested, with_fp=not args.no_fp)
        if skipped["files"]:
            print(f"\nskipped          {skipped['files']} files "
                  f"({human(skipped['bytes'])}) — symlinks, > --max-file-mb, "
                  f"or unreadable; the estimate excludes them")
    return 0


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
