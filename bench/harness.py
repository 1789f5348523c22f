"""Run one benchmark cell: set up a depot, drive the measured window through
the dedup service's default served path, check what it produced against the
plain reference, and reduce the run to the cell's metrics.

Everything a cell is made of is found by name under this directory, so a
new configuration, traffic mix, tree model or metric comes as new files and
new entries of ``BENCHMARK.json`` (at the checkout's root), which names the
cell's configuration file and traffic mix: ``traffic/<traffic>.json`` holds
the mix's parameters for the one generator (``generator.py``), the
configuration's ``model`` names its tree model ``models/<model>.py``, and
every metric is read by ``metrics/<name>.py``, whose ``read(rec)`` returns
the number or None when the run has nothing for it to read.  Beside it a
metric file keeps ``CASE = (fields, value)``: the fields it adds to the
handmade record of ``tests/test_bench_metrics.py`` and what ``read`` returns
on the result.  ``rec`` holds the run's records:

* ``setup_s``; ``ingest_s`` and ``ingest_bytes`` (wall seconds from the
  first ``submit`` of each flush group to the return of its ``flush``, and
  the logical bytes those flushes acknowledged); ``get_latencies_s``;
* ``sched``: the scheduler's counts over the window (``stream_bytes``,
  ``device_bytes``, ``tail_bytes``, ``payload_bytes``);
* ``phases``: seconds of each request phase over the window, by operation
  (``phases["flush"]["commit"]``), from the service's phase clock;
* ``counters``: the window's delta of every counter of the service's
  registry (``counters["store.block_write_s"]``); a counter the program
  does not keep is absent;
* ``trace``: only in a traced run, the reduction of the profiler trace of
  the window, which a traced run makes one whole version long
  (:func:`reduce_trace`): ``busy_s``, ``window_s``,
  ``device_ops`` and ``idle_gaps`` (``tracefile.py``), ``stages`` (device
  seconds of each named stage), ``idle_spans`` and ``idle_under`` (idle
  seconds by program span, ``timeline.py``);
* ``peaks``: the device's row of ``peaks.json``.

Set-up puts versions ``0 .. get_versions - 1`` of the traffic, then warms
up every device shape the tree's object sizes can dispatch.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
from pathlib import Path
from typing import Dict, List

import numpy as np

import reference
import timeline
import tracefile
from byname import load
from generator import Traffic

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

#: environment variables that would silently move the served path
REFUSED_ENV = ("REPRO_PIPELINE_IMPL", "REPRO_PACKING_IMPL",
               "REPRO_STORE_CODEC", "REPRO_TRACE")
#: objects whose fingerprints the reference recomputes, at most this many
#: bytes of them (a sample drawn from the seed; boundaries, keys and the
#: accounting cover every object)
FP_SAMPLE_BYTES = 200 << 20


class Refused(RuntimeError):
    """The run cannot be made here; nothing is printed as a result."""


def refused_env(environ=os.environ) -> List[str]:
    return [k for k in REFUSED_ENV if environ.get(k)]


# -- the cell, found by name ---------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    metrics: Dict[str, dict]  # name -> BENCHMARK.json entry (both kinds)
    end_to_end: List[str]
    per_layer: List[str]


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json "
                      f"(has {sorted(cells)})")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    traffic = _load_json(HERE / "traffic" / f"{w['traffic']}.json")

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    return Cell(name=name, chips=int(w["chips"]),
                config=_load_json(root / cfg["file"]), traffic=traffic,
                metrics=metrics,
                end_to_end=[m["name"] for m in bench["end_to_end"]
                            if applies(m)],
                per_layer=[m["name"] for m in bench["per_layer"]
                           if applies(m)])


def reader(metric: str):
    return load("metrics", metric).read


def device_peaks(kind: str) -> dict:
    table = _load_json(HERE / "peaks.json")["devices"]
    if kind not in table:
        raise Refused(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


# -- measurement helpers --------------------------------------------------------


class CompileCounter:
    """Counts backend compiles (a program loaded from the persistent cache
    counts too, with its load time) and persistent-cache hits and misses,
    through JAX's monitoring hooks."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HITS = "/jax/compilation_cache/cache_hits"
    MISSES = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        self.cache = {self.HITS: 0, self.MISSES: 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event in self.cache:
            self.cache[event] += 1

    def info(self) -> dict:
        return {"compiles": self.count, "compile_s": self.seconds,
                "cache_hits": self.cache[self.HITS],
                "cache_misses": self.cache[self.MISSES]}


def _span(name: str):
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(f"bench.{name}")


def _phase_seconds(snapshot: dict) -> Dict[str, Dict[str, float]]:
    """``req.latency_s{op=,phase=}`` sums, as ``{op: {phase: s}}``."""
    out: Dict[str, Dict[str, float]] = {}
    for key, h in snapshot["histograms"].items():
        if not key.startswith("req.latency_s{"):
            continue
        labels = dict(kv.split("=", 1) for kv in key[14:-1].split(","))
        out.setdefault(labels["op"], {})[labels["phase"]] = h["sum"]
    return out


def _phase_delta(a, b):
    return {op: {ph: s - a.get(op, {}).get(ph, 0.0) for ph, s in phs.items()}
            for op, phs in b.items()}


def counter_delta(a: dict, b: dict) -> Dict[str, float]:
    """Each counter of snapshot ``b`` less its value in the earlier
    snapshot ``a`` (0 where ``a`` lacks it)."""
    return {k: v - a["counters"].get(k, 0) for k, v in b["counters"].items()}


def reduce_trace(path: str) -> dict | None:
    """The profiler trace under ``path``, read once (``timeline.parse``):
    ``tracefile.reduce`` of its ``bench.*`` spans and device operations,
    with ``stages``, ``idle_spans`` and ``idle_under`` of
    ``timeline.reduce``; None when no operation ran on a device inside the
    window."""
    parsed = timeline.parse(path)
    out = tracefile.reduce({
        "spans": [s for s in parsed["spans"]
                  if s[0].startswith(tracefile.SPAN_PREFIX)],
        "devices": {plane: [op[:3] for op in ops]
                    for plane, ops in parsed["devices"].items()}})
    named = timeline.reduce(parsed)
    if out is None or named is None:
        return None
    out.update({k: named[k] for k in ("stages", "idle_spans", "idle_under")})
    return out


def _sched(svc) -> Dict[str, int]:
    st = svc.scheduler.stats
    return {"stream_bytes": st.stream_bytes, "device_bytes": st.device_bytes,
            "tail_bytes": st.tail_bytes,
            "payload_bytes": int(svc.obs.counter("sched.payload_bytes"))}


def warm_up(params, sizes) -> int:
    """Compile (or load from the cache) every device shape that objects of
    the given sizes can dispatch: each of their length buckets at each row
    count up to a full batch, through a throwaway in-memory service fed
    zero bytes.  The buckets and row counts come from the scheduler; the
    number of shapes warmed is returned and printed with the set-up."""
    from repro.service import DedupService

    svc = DedupService(params=params)
    sch = svc.scheduler
    shapes = 0
    for b in sorted({sch._bucket_for(int(n)) for n in sizes}):
        zeros = np.zeros(b, dtype=np.uint8)
        for rows in range(1, sch._slots_for(b) + 1):
            for i in range(rows):
                svc.submit(f"w{b}-{rows}-{i}", zeros)
            svc.flush()
            shapes += 1
    return shapes


# -- the run ---------------------------------------------------------------------


@dataclasses.dataclass
class Window:
    ingest_s: float = 0.0
    ingest_bytes: int = 0
    puts: int = 0
    groups: int = 0
    gen_s: float = 0.0
    get_latencies_s: List[float] = dataclasses.field(default_factory=list)
    get_mismatch: int = 0
    failed: int = 0
    attempted: int = 0
    wall_s: float = 0.0


def _ingest(svc, group) -> int:
    with _span("submit"):
        for name, data in group.puts:
            svc.submit(name, data)
    with _span("flush"):
        stats = svc.flush()
    return sum(s.size for s in stats)


def drive(svc, groups, seconds: float, acked: Dict[str, np.ndarray],
          expected: Dict[str, np.ndarray]) -> Window:
    """The measured window: whole versions (a backup of the tree, each flush
    group with the gets after it) until ``seconds`` have passed; the version
    in flight when time is up finishes, so every run does whole backups."""
    w = Window()
    t_start = time.perf_counter()
    with _span("window"):
        while True:
            with _span("generate"):
                group = next(groups)
            w.gen_s += group.gen_s
            w.attempted += len(group.puts)
            t0 = time.perf_counter()
            try:
                w.ingest_bytes += _ingest(svc, group)
                w.groups += 1
                w.puts += len(group.puts)
                acked.update(group.puts)
            except Exception as e:  # a failed flush counts, the run goes on
                print(f"put group failed: {e!r}", file=sys.stderr)
                w.failed += len(group.puts)
            finally:
                w.ingest_s += time.perf_counter() - t0
            for name in group.gets:
                w.attempted += 1
                t0 = time.perf_counter()
                try:
                    with _span("get"):
                        got = svc.get(name)
                except Exception as e:
                    print(f"get {name} failed: {e!r}", file=sys.stderr)
                    w.failed += 1
                    continue
                w.get_latencies_s.append(time.perf_counter() - t0)
                with _span("check"):
                    if got != expected[name].tobytes():
                        w.get_mismatch += 1
            if group.last and time.perf_counter() - t_start >= seconds:
                break
    w.wall_s = time.perf_counter() - t_start
    return w


def compare(svc, chunking, acked: Dict[str, np.ndarray], seed: int,
            window: Window) -> Dict[str, dict]:
    """Every acknowledged object against the plain reference, and the
    store's accounting against the reference's dict of SHA-256 keys.  Each
    number is a count of disagreements or a gap, held to its limit."""
    store = reference.Store(chunking)
    names = sorted(acked)
    rng = np.random.default_rng(
        np.random.SeedSequence([seed & ((1 << 64) - 1), 7]))
    fp_names, budget = set(), FP_SAMPLE_BYTES
    for i in rng.permutation(len(names)).tolist():
        if acked[names[i]].size <= budget:
            fp_names.add(names[i])
            budget -= acked[names[i]].size
    missing = bounds_bad = keys_bad = fps_bad = fps_checked = 0
    for name in names:
        ref = store.add(acked[name], with_fps=name in fp_names)
        if name not in svc.recipes:
            missing += 1
            continue
        r = svc.recipes.get(name)
        got = np.cumsum(np.asarray(r.chunk_lens, dtype=np.int64)).tolist()
        bounds_bad += got != ref.bounds
        keys_bad += (r.keys != ref.keys or r.sha256 != ref.sha256
                     or r.size != ref.size)
        if ref.fps is not None:
            fps_checked += 1
            fps_bad += r.fps != ref.fps
    st = svc.stats()
    logical = sum(int(acked[n].size) for n in names)
    return {
        "failed_ops": {"value": window.failed, "limit": 0},
        "objects_missing": {"value": missing, "limit": 0},
        "objects_extra": {"value": abs(st.objects - len(names)), "limit": 0},
        "boundaries_differ": {"value": bounds_bad, "limit": 0},
        "keys_differ": {"value": keys_bad, "limit": 0},
        "fingerprints_differ": {"value": fps_bad, "limit": 0,
                                "of": fps_checked},
        "stored_bytes_gap": {"value": abs(st.stored_bytes
                                          - store.stored_bytes), "limit": 0},
        "unique_chunks_gap": {"value": abs(st.unique_chunks
                                           - store.unique_chunks),
                              "limit": 0},
        "logical_bytes_gap": {"value": abs(st.logical_bytes - logical),
                              "limit": 0},
        "gets_differ": {"value": window.get_mismatch, "limit": 0,
                        "of": len(window.get_latencies_s)},
    }


def _peak_memory(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
             workdir: str, compiles: CompileCounter, peaks: dict | None,
             log=print) -> dict:
    """One run of one cell; returns the result line's object.  ``t0`` is
    the perf_counter at which the process started, so ``setup_s`` counts
    JAX's start-up too; ``peaks`` is the device's row of ``peaks.json``."""
    import jax

    from repro.core.params import SeqCDCParams
    from repro.service import DedupService

    device = jax.devices()[0]
    params = SeqCDCParams(**cell.config["chunking"])
    traffic = Traffic(cell.config, cell.traffic, seed)
    svc = DedupService.open(os.path.join(workdir, "depot"), params=params)
    acked: Dict[str, np.ndarray] = {}
    for group in traffic.groups(0, traffic.get_versions):
        _ingest(svc, group)
        acked.update(group.puts)
    shapes = warm_up(params, traffic.model.sizes())
    setup_s = time.perf_counter() - t0
    log(json.dumps({"info": "setup", "setup_s": setup_s,
                    "warmed_shapes": shapes, **compiles.info()}))

    groups = traffic.groups(traffic.get_versions)
    sched0, snap0 = _sched(svc), svc.obs.snapshot()
    compiles0 = compiles.count
    trace_dir = os.path.join(workdir, "trace")
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        # a traced window is one version: the profiler's export and the
        # trace's reading grow with the device operations it holds (about
        # two minutes a version on a v5e), and a run has six minutes
        window = drive(svc, groups, 0.0 if trace else seconds, acked,
                       traffic.objects)
    finally:
        if trace:
            jax.profiler.stop_trace()
    in_window = compiles.count - compiles0
    sched1, snap1 = _sched(svc), svc.obs.snapshot()
    memory_peak = _peak_memory(device)
    log(json.dumps({"info": "window", "wall_s": window.wall_s,
                    "groups": window.groups, "puts": window.puts,
                    "gets": len(window.get_latencies_s),
                    "ingest_s": window.ingest_s,
                    "ingest_bytes": window.ingest_bytes,
                    "client_generate_s": window.gen_s,
                    "compiles_in_window": in_window}))

    rec = {
        "setup_s": setup_s,
        "ingest_s": window.ingest_s,
        "ingest_bytes": window.ingest_bytes,
        "get_latencies_s": window.get_latencies_s,
        "sched": {k: sched1[k] - sched0[k] for k in sched1},
        "phases": _phase_delta(_phase_seconds(snap0), _phase_seconds(snap1)),
        "counters": counter_delta(snap0, snap1),
        "trace": None,
        "peaks": peaks,
    }
    if trace:
        t_read = time.perf_counter()
        rec["trace"] = reduce_trace(trace_dir)
        log(json.dumps({"info": "trace", "read_s":
                        time.perf_counter() - t_read,
                        "devices": (rec["trace"] or {}).get("devices")}))

    names = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for name in names:
        value = reader(name)(rec)
        if value is not None:
            metrics[name] = {"value": value, "unit": cell.metrics[name]["unit"]}

    t_ref = time.perf_counter()
    checks = compare(svc, reference.Chunking(**cell.config["chunking"]),
                     acked, seed, window)
    log(json.dumps({"info": "reference", "seconds":
                    time.perf_counter() - t_ref, "objects": len(acked)}))
    devices = jax.devices()
    result = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": window.attempted,
        "failed": window.failed,
        "metrics": metrics,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(devices), "memory_peak_bytes": memory_peak},
    }
    if trace and rec["trace"] is not None:
        result["device"]["busy_s"] = rec["trace"]["busy_s"]
        result["device"]["window_s"] = rec["trace"]["window_s"]
        result["breakdown"] = {k: rec["trace"][k]
                               for k in ("device_ops", "idle_gaps")}
    result["checks"] = checks
    return result
