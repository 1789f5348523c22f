"""repro.obs + its wiring: registry math, tracing, end-to-end telemetry.

Covers the observability contract (docs/OBSERVABILITY.md):

* registry units — counter/gauge/histogram arithmetic, log-bucket
  resolution, label rendering, snapshot merging (including the
  disjoint-bucket and empty-snapshot edges), and the per-family labeled
  series cardinality cap;
* tracing — off by default and free, JSONL records when ``REPRO_TRACE``
  names a file, *bit-identical results* with tracing on, causal
  trace/span/parent linkage across threads and processes, and sink
  durability (per-line flush, torn tail lines, atexit close);
* layer wiring — scheduler dispatch metrics, service ingest/restore
  counters, writer metrics through a real flush, and per-request
  ``req.latency_s{op=,phase=}`` attribution whose phases tile the
  request's wall time;
* the wire — a remote sharded service's ``metrics()`` aggregates live
  per-server snapshots whose RPC counts and byte totals agree exactly
  with the client side, op by op; and a remote ``put``/``get`` emits
  spans forming a single connected tree per request (protocol v3 trace
  meta propagation).
"""
import json
import os
import threading

import numpy as np
import pytest

from repro.core.params import SeqCDCParams
from repro.obs import (
    BUCKETS_PER_OCTAVE,
    MetricsRegistry,
    PhaseClock,
    bucket_index,
    bucket_value,
    current_context,
    enabled,
    labeled,
    merge_snapshots,
    scope,
    span,
)
from repro.obs import trace as obs_trace
from repro.service import DedupService, ShardedDedupService

P = SeqCDCParams(avg_size=256, seq_length=3, skip_trigger=6, skip_size=32,
                 min_size=64, max_size=512)


def _mk_service(**kw):
    return DedupService(params=P, slots=4, min_bucket=1024, **kw)


def _corpus(rng, n=60000):
    data = rng.integers(0, 256, n, dtype=np.uint8)
    return [data, np.concatenate([data[: n // 2], data[: n // 2]])]


# -- registry units -------------------------------------------------------------
class TestRegistry:
    def test_counters_and_gauges(self):
        r = MetricsRegistry()
        r.inc("c")
        r.inc("c", 4)
        r.set_gauge("g", 2)
        r.set_gauge("g", 9)  # last write wins
        assert r.counter("c") == 5
        assert r.gauge("g") == 9
        assert r.counter("missing") == 0
        assert r.gauge("missing", 7.5) == 7.5

    def test_bucket_roundtrip_resolution(self):
        # geometric buckets: the representative value of any value's bucket
        # is within half an octave step (~9%) of the value
        ratio = 2.0 ** (1.0 / BUCKETS_PER_OCTAVE)
        for v in (1e-6, 0.003, 0.5, 1.0, 7.0, 1234.5):
            rep = bucket_value(bucket_index(v))
            assert rep / v < ratio ** 0.5 + 1e-9
            assert v / rep < ratio ** 0.5 + 1e-9
        assert bucket_value(bucket_index(0.0)) == 0.0
        assert bucket_value(bucket_index(-3.0)) == 0.0

    def test_histogram_percentiles(self):
        r = MetricsRegistry()
        for _ in range(98):
            r.observe("h", 0.001)
        r.observe("h", 1.0)
        r.observe("h", 2.0)
        h = r.snapshot()["histograms"]["h"]
        assert h["count"] == 100
        assert h["min"] == 0.001 and h["max"] == 2.0
        assert 0.0009 < h["p50"] < 0.0011
        assert 0.0009 < h["p95"] < 0.0011
        assert 0.9 < h["p99"] < 1.1
        assert h["sum"] == pytest.approx(98 * 0.001 + 3.0)

    def test_time_context_manager(self):
        r = MetricsRegistry()
        with r.time("t_s"):
            pass
        h = r.snapshot()["histograms"]["t_s"]
        assert h["count"] == 1 and h["max"] < 1.0

    def test_labeled_rendering(self):
        assert labeled("x") == "x"
        assert labeled("x", shard=3, op="put") == "x{op=put,shard=3}"
        # sorted keys: the same labels always render the same string
        assert labeled("x", b=1, a=2) == labeled("x", a=2, b=1) == "x{a=2,b=1}"

    def test_merge_snapshots(self):
        r = MetricsRegistry()
        r.inc("n", 3)
        r.set_gauge("depth", 2)
        r.observe("h", 0.5)
        r.observe("h", 4.0)
        s = r.snapshot()
        m = merge_snapshots([s, s, None])  # None = unreachable shard
        assert m["counters"]["n"] == 6
        assert m["gauges"]["depth"] == 4  # gauges sum (fleet backlog)
        assert m["histograms"]["h"]["count"] == 4
        assert m["histograms"]["h"]["min"] == 0.5
        assert m["histograms"]["h"]["max"] == 4.0
        # merged quantiles come from the union's buckets, not an average
        assert m["histograms"]["h"]["p99"] == pytest.approx(
            s["histograms"]["h"]["p99"])

    def test_clear(self):
        r = MetricsRegistry()
        r.inc("a")
        r.observe("b", 1)
        r.clear()
        snap = r.snapshot()
        assert snap["counters"] == {} and snap["histograms"] == {}

    def test_snapshot_json_serializable(self):
        r = MetricsRegistry()
        r.inc("a", 2)
        r.observe("b", 0.25)
        json.dumps(r.snapshot())  # must not raise


# -- tracing --------------------------------------------------------------------
class TestTracing:
    def test_off_by_default_and_null_span(self, monkeypatch):
        monkeypatch.delenv("REPRO_TRACE", raising=False)
        assert not enabled()
        sp = span("x", a=1)
        with sp as s:
            s["b"] = 2  # attrs on a span that writes nothing are dropped
        # the service installed the profiler hook at import; without one,
        # a span with tracing off is the shared no-op instance
        monkeypatch.setattr(obs_trace, "_annotator", None)
        assert span("y") is span("z")

    def test_jsonl_records(self, tmp_path, monkeypatch):
        trace = tmp_path / "t.jsonl"
        monkeypatch.setenv("REPRO_TRACE", str(trace))
        assert enabled()
        with span("unit.work", bytes=64) as sp:
            sp["rows"] = 3
        with pytest.raises(ValueError):
            with span("unit.fail"):
                raise ValueError("boom")
        recs = [json.loads(l) for l in trace.read_text().splitlines()]
        assert [r["name"] for r in recs] == ["unit.work", "unit.fail"]
        ok = recs[0]
        assert ok["bytes"] == 64 and ok["rows"] == 3
        assert ok["wall_s"] >= 0 and ok["cpu_s"] >= 0
        assert ok["pid"] == os.getpid()
        assert recs[1]["error"] == "ValueError"

    def test_tracing_does_not_change_results(self, rng, tmp_path, monkeypatch):
        """The acceptance contract: same stores, same restored bytes,
        tracing on vs off."""
        corpus = _corpus(rng)

        def run():
            svc = _mk_service()
            for i, v in enumerate(corpus):
                svc.submit(f"o{i}", v)
            svc.flush()
            st = svc.stats()
            return ([svc.get(f"o{i}") for i in range(len(corpus))],
                    st.stored_bytes, st.unique_chunks)

        monkeypatch.delenv("REPRO_TRACE", raising=False)
        base = run()
        monkeypatch.setenv("REPRO_TRACE", str(tmp_path / "t.jsonl"))
        assert run() == base
        names = {json.loads(l)["name"]
                 for l in (tmp_path / "t.jsonl").read_text().splitlines()}
        assert {"sched.dispatch", "service.flush", "service.get"} <= names


# -- layer wiring ---------------------------------------------------------------
class TestServiceMetrics:
    def test_ingest_and_restore_counters(self, rng):
        svc = _mk_service()
        corpus = _corpus(rng)
        total = sum(int(v.size) for v in corpus)
        for i, v in enumerate(corpus):
            svc.submit(f"o{i}", v)
        svc.flush()
        svc.get("o0")
        m = svc.metrics()
        c = m["service"]["counters"]
        assert c["ingest.objects"] == len(corpus)
        assert c["ingest.bytes"] == total
        assert c["ingest.chunks"] > 0
        # corpus[1] is half-repeated, so hits must exist
        assert 0 < c["ingest.dedup_hit_chunks"] < c["ingest.chunks"]
        assert c["restore.objects"] == 1
        assert c["restore.bytes"] == int(corpus[0].size)
        assert m["shards"] == [] and m["aggregate"] is None

    def test_scheduler_dispatch_metrics(self, rng):
        svc = _mk_service()
        svc.put("a", rng.integers(0, 256, 50000, dtype=np.uint8))
        snap = svc.metrics()["service"]
        assert snap["counters"]["sched.dispatches"] >= 1
        assert snap["counters"]["sched.device_bytes"] >= 50000
        hname = labeled("sched.dispatch_s", pipeline=svc.scheduler.pipeline_impl,
                        mask=svc.scheduler.mask_impl, fp=svc.scheduler.fp_impl)
        h = snap["histograms"][hname]
        assert h["count"] == snap["counters"]["sched.dispatches"]
        occ = [g for g in snap["gauges"] if g.startswith("sched.occupancy{")]
        assert occ, "no per-bucket occupancy gauge was set"
        assert all(0 < snap["gauges"][g] <= 1 for g in occ)

    def test_dispatch_label_names_resolved_fp_impl(self, rng, monkeypatch):
        """The ``fp=`` label names the fingerprint path that ran: the
        reference chain on the CPU, the kernel where the backend is a TPU
        (resolved at construction; nothing is dispatched there)."""
        import jax

        svc = _mk_service()
        svc.put("a", rng.integers(0, 256, 5000, dtype=np.uint8))
        hists = svc.metrics()["service"]["histograms"]
        assert [h for h in hists if h.startswith("sched.dispatch_s{")] == [
            labeled("sched.dispatch_s", pipeline=svc.scheduler.pipeline_impl,
                    mask="jnp", fp="reference")]
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        sched = _mk_service().scheduler
        assert sched._dispatch_hist == labeled(
            "sched.dispatch_s", pipeline=sched.pipeline_impl, mask="jnp",
            fp="pallas")

    def test_flush_and_get_latency_histograms(self, rng):
        svc = _mk_service()
        svc.submit("a", rng.integers(0, 256, 30000, dtype=np.uint8))
        svc.flush()
        svc.get("a")
        hists = svc.metrics()["service"]["histograms"]
        assert hists[labeled("req.total_s", op="flush")]["count"] == 1
        assert hists[labeled("req.total_s", op="get")]["count"] == 1

    def test_registries_are_per_service(self, rng):
        a, b = _mk_service(), _mk_service()
        a.put("x", rng.integers(0, 256, 20000, dtype=np.uint8))
        assert a.obs.counter("ingest.objects") == 1
        assert b.obs.counter("ingest.objects") == 0

    def test_sharded_local_metrics(self, rng):
        svc = ShardedDedupService(2, params=P, slots=4, min_bucket=1024)
        try:
            corpus = _corpus(rng)
            for i, v in enumerate(corpus):
                svc.submit(f"o{i}", v)
            svc.flush()
            svc.get("o0")
            m = svc.metrics()
            c = m["service"]["counters"]
            assert c["ingest.objects"] == len(corpus)
            assert c["ingest.fp_dup_chunks"] > 0  # the repeated half
            # writer metrics are labeled per shard and both shards wrote
            wrote = [s for s in range(2)
                     if c.get(labeled("writer.tasks", shard=s), 0) > 0]
            assert wrote == [0, 1]
            assert m["shards"] == []  # local transport: no server processes
        finally:
            svc.close()


# -- the wire -------------------------------------------------------------------
@pytest.mark.timeout(120)
class TestRemoteMetrics:
    def test_metrics_op_and_client_server_agreement(self, rng, tmp_path):
        """The acceptance test: ``metrics()`` on a remote sharded service
        returns live per-shard-server snapshots, and the client- and
        server-side RPC counters agree exactly, op by op — calls, and the
        symmetric blob-byte accounting."""
        svc = ShardedDedupService.open(str(tmp_path / "depot"), 2,
                                       transport="remote", params=P,
                                       slots=4, min_bucket=1024)
        try:
            corpus = _corpus(rng)
            for i, v in enumerate(corpus):
                svc.submit(f"o{i}", v)
            svc.flush()
            for i in range(len(corpus)):
                svc.get(f"o{i}")
            m = svc.metrics()
            assert len(m["shards"]) == 2
            assert all(s is not None for s in m["shards"])
            cc = m["service"]["counters"]
            sc = m["aggregate"]["counters"]
            pairs = [("rpc.client.calls{", "rpc.server.calls{"),
                     ("rpc.client.send_bytes{", "rpc.server.recv_bytes{"),
                     ("rpc.client.recv_bytes{", "rpc.server.send_bytes{")]
            checked = 0
            for k, v in cc.items():
                for mine, theirs in pairs:
                    if not k.startswith(mine):
                        continue
                    if mine == "rpc.client.recv_bytes{" and "op=metrics" in k:
                        # a snapshot is taken *inside* the metrics dispatch,
                        # so it cannot include its own response bytes
                        continue
                    assert sc.get(theirs + k[len(mine):]) == v, k
                    checked += 1
            assert checked >= 6  # at least put_blocks/get_blocks/metrics
            # real traffic flowed both ways
            assert cc[labeled("rpc.client.calls", op="put_blocks")] >= 2
            assert cc[labeled("rpc.client.send_bytes", op="put_blocks")] > 0
            assert cc[labeled("rpc.client.recv_bytes", op="get_blocks")] > 0
            # server-side exact dedup hits: corpus[1]'s repeated half
            assert sc["store.dedup_hit_chunks"] > 0
            # per-op server latency histograms exist for the hot ops
            assert m["aggregate"]["histograms"][
                labeled("rpc.server.latency_s", op="put_blocks")]["count"] >= 2
        finally:
            svc.close()

    def test_dead_server_degrades_to_none(self, rng, tmp_path):
        svc = ShardedDedupService.open(str(tmp_path / "depot"), 2,
                                       transport="remote", params=P,
                                       slots=4, min_bucket=1024)
        try:
            svc.put("x", rng.integers(0, 256, 20000, dtype=np.uint8))
            svc._servers[1].kill()
            m = svc.metrics()
            assert m["shards"][0] is not None
            assert m["shards"][1] is None
            # aggregate still builds from the reachable shard
            assert m["aggregate"]["counters"]
        finally:
            svc.close()

    def test_protocol_rejects_version_mismatch(self):
        # the reserved "trace" meta entry shipped with VERSION 3 (a v2
        # peer would pass it into op handler kwargs) and the codec
        # handshake + pre-compressed put_blocks meta with VERSION 4 (a v3
        # server would store compressed payloads as raw chunk bytes), so
        # mixed deployments must fail loudly at the first frame, not on a
        # surprise argument or silently corrupted store
        from repro.service.transport import protocol as proto
        assert proto.VERSION == 4
        assert proto.OP_NAMES[proto.OP_METRICS] == "metrics"
        assert proto.OP_NAMES[proto.OP_HELLO] == "hello"


def _report_mod():
    """scripts/obs_report.py, imported the way its CLI runs."""
    import sys
    scripts = os.path.join(os.path.dirname(__file__), "..", "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    import obs_report
    return obs_report


# -- cardinality guard ----------------------------------------------------------
class TestCardinalityGuard:
    def test_labeled_series_capped_with_overflow_counter(self):
        r = MetricsRegistry(max_labeled_series=4)
        for i in range(10):
            r.inc(labeled("g", bucket=i))
        snap = r.snapshot()
        kept = [k for k in snap["counters"] if k.startswith("g{")]
        assert len(kept) == 4  # first four admitted, in arrival order
        assert snap["counters"][
            labeled("obs.series_dropped", family="g")] == 6

    def test_existing_series_and_unlabeled_names_never_dropped(self):
        r = MetricsRegistry(max_labeled_series=1)
        r.inc(labeled("g", bucket=0))
        r.inc(labeled("g", bucket=1))  # over the cap: dropped
        r.inc(labeled("g", bucket=0), 5)  # existing: still counts
        r.inc("plain", 3)  # unlabeled: exempt from the guard
        assert r.counter(labeled("g", bucket=0)) == 6
        assert r.counter(labeled("g", bucket=1)) == 0
        assert r.counter("plain") == 3

    def test_cap_is_per_family_and_per_kind(self):
        r = MetricsRegistry(max_labeled_series=2)
        for i in range(3):
            r.inc(labeled("a", i=i))
            r.inc(labeled("b", i=i))
            r.observe(labeled("a", i=i), 1.0)
            r.set_gauge(labeled("a", i=i), 1.0)
        snap = r.snapshot()
        assert len([k for k in snap["counters"] if k.startswith("a{")]) == 2
        assert len([k for k in snap["counters"] if k.startswith("b{")]) == 2
        assert len([k for k in snap["histograms"] if k.startswith("a{")]) == 2
        assert len([k for k in snap["gauges"] if k.startswith("a{")]) == 2
        # one drop per kind for a's third label set, one for b's
        assert snap["counters"][
            labeled("obs.series_dropped", family="a")] == 3
        assert snap["counters"][
            labeled("obs.series_dropped", family="b")] == 1

    def test_clear_resets_family_budgets(self):
        r = MetricsRegistry(max_labeled_series=1)
        r.set_gauge(labeled("q", s=0), 1.0)
        r.set_gauge(labeled("q", s=1), 2.0)  # dropped
        assert r.gauge(labeled("q", s=1), -1.0) == -1.0
        r.clear()
        r.set_gauge(labeled("q", s=1), 2.0)  # budget is fresh again
        assert r.gauge(labeled("q", s=1)) == 2.0

    def test_service_registries_carry_the_default_cap(self):
        assert _mk_service().obs._max_labeled_series == \
            MetricsRegistry.DEFAULT_MAX_LABELED_SERIES


# -- merge_snapshots edges -------------------------------------------------------
class TestMergeSnapshotEdges:
    def test_disjoint_bucket_sets(self):
        # shards whose latencies never overlap: the union's percentiles
        # must span both tails, and min/max come from different shards
        a, b = MetricsRegistry(), MetricsRegistry()
        for _ in range(50):
            a.observe("h", 0.001)
        for _ in range(50):
            b.observe("h", 100.0)
        a.inc("only_a", 1)
        b.inc("only_b", 2)
        m = merge_snapshots([a.snapshot(), b.snapshot()])
        h = m["histograms"]["h"]
        assert h["count"] == 100
        assert h["min"] == 0.001 and h["max"] == 100.0
        assert h["sum"] == pytest.approx(50 * 0.001 + 50 * 100.0)
        assert h["p50"] == pytest.approx(0.001, rel=0.1)  # low shard
        assert h["p99"] == pytest.approx(100.0, rel=0.1)  # high shard
        assert m["counters"] == {"only_a": 1, "only_b": 2}

    def test_empty_and_none_only_snapshots(self):
        assert merge_snapshots([]) == {
            "counters": {}, "gauges": {}, "histograms": {}}
        assert merge_snapshots([None, None]) == {
            "counters": {}, "gauges": {}, "histograms": {}}
        fresh = MetricsRegistry().snapshot()
        m = merge_snapshots([None, fresh, {}])
        assert m["histograms"] == {} and m["counters"] == {}

    def test_zero_count_histogram_does_not_poison_min_max(self):
        r = MetricsRegistry()
        r.observe("h", 2.0)
        empty = {"counters": {}, "gauges": {},
                 "histograms": {"h": {"count": 0, "sum": 0.0, "min": 0.0,
                                      "max": 0.0, "buckets": {}}}}
        h = merge_snapshots([empty, r.snapshot()])["histograms"]["h"]
        assert h["count"] == 1
        assert h["min"] == 2.0 and h["max"] == 2.0  # not clamped to 0.0

    def test_percentiles_rederive_as_a_single_merged_registry(self):
        # the acceptance property: merging shard snapshots must equal one
        # registry that saw every observation (bucket-exact, not averaged)
        vals = [0.0003 * (1.31 ** i) for i in range(60)]
        parts = [MetricsRegistry() for _ in range(3)]
        union = MetricsRegistry()
        for i, v in enumerate(vals):
            parts[i % 3].observe("h", v)
            union.observe("h", v)
        merged = merge_snapshots([p.snapshot() for p in parts])
        mh, uh = merged["histograms"]["h"], union.snapshot()["histograms"]["h"]
        assert mh["buckets"] == uh["buckets"]
        for stat in ("count", "min", "max", "p50", "p95", "p99"):
            assert mh[stat] == uh[stat], stat
        assert mh["sum"] == pytest.approx(uh["sum"])


# -- phase clock -----------------------------------------------------------------
class TestPhaseClock:
    def test_phases_tile_the_total_exactly(self):
        c = PhaseClock()
        with c.phase("a"):
            with c.phase("b"):  # nested: b owns its time, not a
                pass
        total, phases = c.stop()
        assert set(phases) == {PhaseClock.OTHER, "a", "b"}
        assert all(s >= 0.0 for s in phases.values())
        assert sum(phases.values()) == pytest.approx(total, rel=1e-9,
                                                     abs=1e-12)
        # idempotent: a second stop returns the same partition
        assert c.stop() == (total, phases)

    def test_move_reattributes_and_clamps(self):
        c = PhaseClock()
        with c.phase("a"):
            pass
        c.move("a", "tail", 999.0)  # clamped to what a actually holds
        c.move("missing", "x", 1.0)  # no-op: nothing to move
        total, phases = c.stop()
        assert phases["a"] == 0.0
        assert phases["tail"] > 0.0
        assert "x" not in phases
        assert sum(phases.values()) == pytest.approx(total, rel=1e-9,
                                                     abs=1e-12)

    def test_stop_drains_abandoned_phases(self):
        # an error path can leave phases open; stop() closes them so the
        # partition still tiles the total
        c = PhaseClock()
        c.phase("a").__enter__()
        c.phase("b").__enter__()
        total, phases = c.stop()
        assert {"a", "b"} <= set(phases)
        assert sum(phases.values()) == pytest.approx(total, rel=1e-9,
                                                     abs=1e-12)


# -- causal tracing --------------------------------------------------------------
class TestCausalTracing:
    def test_parent_linkage_and_trace_ids(self, tmp_path, monkeypatch):
        trace = tmp_path / "t.jsonl"
        monkeypatch.setenv("REPRO_TRACE", str(trace))
        with span("outer"):
            ctx = current_context()
            assert set(ctx) == {"trace_id", "span_id"}
            with span("inner"):
                inner_ctx = current_context()
                assert inner_ctx["trace_id"] == ctx["trace_id"]
                assert inner_ctx["span_id"] != ctx["span_id"]
        with span("second"):
            pass
        recs = {json.loads(l)["name"]: json.loads(l)
                for l in trace.read_text().splitlines()}
        outer, inner, second = recs["outer"], recs["inner"], recs["second"]
        assert inner["trace_id"] == outer["trace_id"]
        assert inner["parent_id"] == outer["span_id"]
        assert "parent_id" not in outer  # a root span
        assert second["trace_id"] != outer["trace_id"]  # new root, new trace

    def test_context_is_none_outside_spans_and_when_off(self, tmp_path,
                                                        monkeypatch):
        monkeypatch.delenv("REPRO_TRACE", raising=False)
        assert current_context() is None
        with span("nullspan"):
            assert current_context() is None  # null spans push nothing
        monkeypatch.setenv("REPRO_TRACE", str(tmp_path / "t.jsonl"))
        assert current_context() is None  # on, but no span open

    def test_scope_adopts_context_across_a_thread(self, tmp_path,
                                                  monkeypatch):
        trace = tmp_path / "t.jsonl"
        monkeypatch.setenv("REPRO_TRACE", str(trace))
        seen = {}
        with span("root"):
            ctx = current_context()

            def work():
                seen["inherited"] = current_context()  # fresh thread: none
                with scope(ctx), span("child"):
                    pass

            t = threading.Thread(target=work, name="seam")
            t.start()
            t.join()
        assert seen["inherited"] is None
        recs = {json.loads(l)["name"]: json.loads(l)
                for l in trace.read_text().splitlines()}
        child, root = recs["child"], recs["root"]
        assert child["trace_id"] == root["trace_id"]
        assert child["parent_id"] == root["span_id"]
        assert child["thread"] == "seam"

    def test_scope_tolerates_none_and_malformed_contexts(self, monkeypatch):
        monkeypatch.delenv("REPRO_TRACE", raising=False)
        with scope(None):
            assert current_context() is None
        with scope({"trace_id": "half"}):  # no span_id: ignored
            assert current_context() is None


# -- sink durability -------------------------------------------------------------
class TestSinkDurability:
    def test_flushed_per_line_and_close_reopens(self, tmp_path, monkeypatch):
        from repro.obs import trace as trace_mod
        path = tmp_path / "t.jsonl"
        monkeypatch.setenv("REPRO_TRACE", str(path))
        with span("a"):
            pass
        # flushed per record: the line is on disk while the cached handle
        # stays open (a concurrent reader sees whole lines, never buffers)
        assert path.read_text().endswith("\n")
        assert len(path.read_text().splitlines()) == 1
        trace_mod._close_sink()
        trace_mod._close_sink()  # idempotent (atexit may run it again)
        with span("b"):
            pass  # reopens the sink transparently, in append mode
        names = [json.loads(l)["name"] for l in path.read_text().splitlines()]
        assert names == ["a", "b"]

    def test_torn_tail_line_is_skipped_by_the_report(self, tmp_path,
                                                     monkeypatch):
        path = tmp_path / "t.jsonl"
        monkeypatch.setenv("REPRO_TRACE", str(path))
        with span("whole", bytes=5):
            pass
        with open(path, "a", encoding="utf-8") as f:
            f.write('{"name": "torn", "wall_')  # process killed mid-write
        rep = _report_mod()
        recs = rep.load_trace(str(path))
        assert [r["name"] for r in recs] == ["whole"]
        rows = rep.trace_summary(recs)
        assert rows[0]["span"] == "whole" and rows[0]["count"] == 1

    def test_unwritable_sink_never_raises(self, tmp_path, monkeypatch):
        # REPRO_TRACE pointing at a directory: the emit fails with OSError,
        # which tracing swallows — observability must not take work down
        monkeypatch.setenv("REPRO_TRACE", str(tmp_path))
        with span("x", a=1) as sp:
            sp["b"] = 2


# -- per-request latency attribution ---------------------------------------------
class TestRequestAttribution:
    @staticmethod
    def _phases_of(hists: dict, op: str) -> dict:
        prefix = f"req.latency_s{{op={op},phase="
        return {k[len(prefix):-1]: v for k, v in hists.items()
                if k.startswith(prefix)}

    def test_put_and_get_phases_reconcile_single_store(self, rng):
        svc = _mk_service()
        for i in range(3):
            svc.put(f"o{i}", rng.integers(0, 256, 30000, dtype=np.uint8))
        svc.get("o0")
        snap = svc.metrics()["service"]
        c, h = snap["counters"], snap["histograms"]
        assert c[labeled("req.requests", op="put")] == 3
        assert c[labeled("req.requests", op="get")] == 1
        # put = submit + flush joins the outer request: no op=flush series
        assert labeled("req.requests", op="flush") not in c
        for op in ("put", "get"):
            total = h[labeled("req.total_s", op=op)]
            phases = self._phases_of(h, op)
            assert phases, f"no phase series for op={op}"
            # the acceptance property: the phase partition tiles each
            # request's wall time, so the sums reconcile exactly
            assert sum(v["sum"] for v in phases.values()) == pytest.approx(
                total["sum"], rel=1e-6, abs=1e-9)
            assert all(v["count"] == total["count"]
                       for v in phases.values())
        assert {"chunk-dispatch", "commit", "sync"} <= set(
            self._phases_of(h, "put"))
        assert {"rpc", "verify"} <= set(self._phases_of(h, "get"))

    def test_sharded_phases_include_routing_and_queue_wait(self, rng):
        svc = ShardedDedupService(2, params=P, slots=4, min_bucket=1024)
        try:
            svc.put("a", rng.integers(0, 256, 60000, dtype=np.uint8))
            for i, v in enumerate(_corpus(rng)):
                svc.submit(f"o{i}", v)
            svc.flush()  # a standalone flush is its own op
            svc.get("o0")
            svc.delete("o1")
            snap = svc.metrics()["service"]
            c, h = snap["counters"], snap["histograms"]
            assert c[labeled("req.requests", op="put")] == 1
            assert c[labeled("req.requests", op="flush")] == 1
            assert c[labeled("req.requests", op="delete")] == 1
            assert {"chunk-dispatch", "routing", "writer-queue-wait",
                    "commit", "fp", "sync"} <= set(self._phases_of(h, "put"))
            assert {"routing", "rpc", "verify"} <= set(
                self._phases_of(h, "get"))
            for op in ("put", "flush", "get", "delete"):
                total = h[labeled("req.total_s", op=op)]
                phases = self._phases_of(h, op)
                assert sum(v["sum"] for v in phases.values()) == \
                    pytest.approx(total["sum"], rel=1e-6, abs=1e-9)
        finally:
            svc.close()

    def test_request_root_span_carries_id_and_phase_partition(
            self, rng, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_TRACE", str(tmp_path / "t.jsonl"))
        svc = _mk_service()
        svc.put("a", rng.integers(0, 256, 30000, dtype=np.uint8))
        recs = [json.loads(l) for l in
                (tmp_path / "t.jsonl").read_text().splitlines()]
        roots = [r for r in recs if r["name"] == "request"]
        assert len(roots) == 1 and roots[0]["op"] == "put"
        root = roots[0]
        assert len(root["req"]) == 12  # 6 random bytes, hex
        # the recorded partition reconciles with the root's wall time
        # (small skew: the clock brackets the span, both ways, by ns)
        assert sum(root["phases"].values()) == pytest.approx(
            root["wall_s"], abs=0.05)
        # every other span this request emitted descends from the root
        assert all(r["trace_id"] == root["trace_id"] for r in recs)


# -- the profiler timeline and the commit's counters -----------------------------
class _FakeAnnotator:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each
    annotation entered and checks that it is left."""

    def __init__(self):
        self.entered = []
        self.open = 0

    def __call__(self, name):
        fake = self

        class _Annotation:
            def __enter__(self):
                fake.entered.append(name)
                fake.open += 1

            def __exit__(self, *exc):
                fake.open -= 1
                return False

        return _Annotation()


class TestProfilerTimeline:
    @pytest.mark.parametrize("traced", [False, True])
    def test_every_span_and_phase_is_annotated(self, rng, tmp_path,
                                               monkeypatch, traced):
        fake = _FakeAnnotator()
        monkeypatch.setattr(obs_trace, "_annotator", fake)
        sink = tmp_path / "t.jsonl"
        if traced:
            monkeypatch.setenv("REPRO_TRACE", str(sink))
        else:
            monkeypatch.delenv("REPRO_TRACE", raising=False)
        svc = DedupService.open(str(tmp_path / "depot"), params=P, slots=4,
                                min_bucket=1024)
        for i, v in enumerate(_corpus(rng)):
            svc.submit(f"o{i}", v)
        svc.flush()
        svc.get("o0")
        assert fake.open == 0
        names = set(fake.entered)
        assert {"repro.request", "repro.service.flush", "repro.sched.pack",
                "repro.sched.dispatch", "repro.sched.tail",
                "repro.commit.object", "repro.sync.recipes",
                "repro.sync.manifest", "repro.service.get"} <= names
        h = svc.obs.snapshot()["histograms"]
        phases = {k.split("phase=", 1)[1][:-1] for k in h
                  if k.startswith("req.latency_s{")}
        # "other" is the clock's remainder and "tail" is moved in after
        # the fact; every phase entered is annotated
        assert {f"repro.phase.{p}" for p in phases - {"other", "tail"}} \
            <= names
        assert {"repro.phase.commit", "repro.phase.fp", "repro.phase.rpc",
                "repro.phase.verify"} <= names
        if traced:
            recs = [json.loads(line) for line in sink.read_text().splitlines()]
            assert {f"repro.{r['name']}" for r in recs} <= names
        else:
            assert not sink.exists()

    def test_obs_imports_no_jax(self):
        import subprocess
        import sys

        code = ("import sys, repro.obs; "
                "assert 'jax' not in sys.modules, 'jax'; "
                "assert 'numpy' not in sys.modules, 'numpy'")
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        env = dict(os.environ, PYTHONPATH=src)
        subprocess.run([sys.executable, "-c", code], check=True, env=env)


class TestCommitCounters:
    def test_block_writes_only_for_new_chunks(self, rng, tmp_path):
        data = rng.integers(0, 256, 60000, dtype=np.uint8)
        root = str(tmp_path / "depot")
        first = DedupService.open(root, params=P, slots=4, min_bucket=1024)
        first.submit("a", data)
        first.flush()
        c = first.obs.snapshot()["counters"]
        assert c["ingest.dedup_hit_chunks"] < c["ingest.chunks"]
        assert c["store.block_write_s"] > 0
        assert c["store.key_hash_s"] > 0 and c["commit.digest_s"] > 0
        again = DedupService.open(root, params=P, slots=4, min_bucket=1024)
        again.submit("b", data)
        again.flush()
        c = again.obs.snapshot()["counters"]
        assert c["ingest.dedup_hit_chunks"] == c["ingest.chunks"] > 0
        assert c["store.block_write_s"] == 0
        assert c["store.key_hash_s"] > 0 and c["commit.digest_s"] > 0

    def test_counters_fit_inside_the_commit_phase(self, rng, tmp_path):
        svc = DedupService.open(str(tmp_path / "depot"), params=P, slots=4,
                                min_bucket=1024)
        for i, v in enumerate(_corpus(rng)):
            svc.submit(f"o{i}", v)
        svc.flush()
        snap = svc.obs.snapshot()
        c = snap["counters"]
        commit = snap["histograms"][labeled(
            "req.latency_s", op="flush", phase="commit")]["sum"]
        parts = (c["store.key_hash_s"] + c["store.block_write_s"]
                 + c["commit.digest_s"])
        assert 0 < parts <= commit


# -- the wire: causal trees across processes -------------------------------------
@pytest.mark.timeout(120)
class TestRemoteTraceTree:
    def test_remote_put_emits_one_connected_tree(self, rng, tmp_path,
                                                 monkeypatch):
        """The acceptance test: with ``REPRO_TRACE`` set, one remote-
        transport ``put`` yields spans — client threads, writer threads,
        shard-server processes — that reconstruct into a single tree:
        every ``writer.task`` and ``rpc.server`` span carries the request's
        ``trace_id`` and a ``parent_id`` resolving inside the file."""
        trace_path = tmp_path / "trace.jsonl"
        # set before open: the spawned shard servers inherit the env
        monkeypatch.setenv("REPRO_TRACE", str(trace_path))
        svc = ShardedDedupService.open(str(tmp_path / "depot"), 2,
                                       transport="remote", params=P,
                                       slots=4, min_bucket=1024)
        try:
            svc.put("obj", rng.integers(0, 256, 60000, dtype=np.uint8))
            svc.get("obj")
        finally:
            svc.close()
        rep = _report_mod()
        recs = rep.load_trace(str(trace_path))
        by_id = {r["span_id"]: r for r in recs}
        roots = {r["op"]: r for r in recs if r["name"] == "request"}
        assert set(roots) == {"put", "get"}
        for op, root in roots.items():
            members = [r for r in recs if r["trace_id"] == root["trace_id"]]
            # connected: every non-root member's parent is in the file and
            # on the same trace — walking up always reaches the root
            for r in members:
                if r["span_id"] == root["span_id"]:
                    assert "parent_id" not in r
                    continue
                hops = 0
                node = r
                while node["span_id"] != root["span_id"]:
                    node = by_id[node["parent_id"]]
                    assert node["trace_id"] == root["trace_id"]
                    hops += 1
                    assert hops < 50
            names = {r["name"] for r in members}
            assert {"request", "rpc.client", "rpc.server"} <= names, op
            # the tree crosses process boundaries: server spans carry a
            # different pid than the client's
            pids = {r["pid"] for r in members}
            assert os.getpid() in pids and len(pids) >= 2, op
        # the put tree owns the flush work and the writer seam
        put_members = [r for r in recs
                       if r["trace_id"] == roots["put"]["trace_id"]]
        put_names = {r["name"] for r in put_members}
        assert {"service.flush", "sched.dispatch", "writer.task"} <= put_names
        # every writer.task in the file descends from the put request
        # (submit happens inside its flush; queue-wait is attributed there)
        tasks = [r for r in recs if r["name"] == "writer.task"]
        assert tasks
        assert all(r["trace_id"] == roots["put"]["trace_id"] for r in tasks)
        assert all("queue_wait_s" in r for r in tasks)
        # ops issued outside any request (shutdown at close) root their own
        # traces rather than being orphaned into a request's tree
        for r in recs:
            if r["name"] == "rpc.server" and r.get("op") == "shutdown":
                assert r["trace_id"] not in {
                    roots["put"]["trace_id"], roots["get"]["trace_id"]}

    def test_report_renders_critical_path_and_request_rows(
            self, rng, tmp_path, monkeypatch, capsys):
        trace_path = tmp_path / "trace.jsonl"
        monkeypatch.setenv("REPRO_TRACE", str(trace_path))
        svc = ShardedDedupService.open(str(tmp_path / "depot"), 2,
                                       transport="remote", params=P,
                                       slots=4, min_bucket=1024)
        try:
            svc.put("obj", rng.integers(0, 256, 60000, dtype=np.uint8))
            svc.get("obj")
        finally:
            svc.close()
        rep = _report_mod()
        recs = rep.load_trace(str(trace_path))
        rows = rep.request_rows(recs)
        by_op = {r["op"]: r for r in rows}
        assert {"put", "get"} <= set(by_op)
        for r in rows:
            assert r["count"] >= 1
            assert 0.0 < r["p50_s"] <= r["p95_s"] <= r["p99_s"] <= r["max_s"]
            assert r["dominant_phase"] != "?"
            assert 0.0 < r["dominant_share"] <= 1.0
        paths = rep.critical_path_views(recs)
        assert {"put", "get"} <= set(paths)
        put_path = paths["put"]
        assert put_path[0]["span"].startswith("request op=put")
        assert put_path[0]["frac_of_root"] == pytest.approx(1.0)
        assert len(put_path) >= 3  # descends through flush into real work
        top_wall = put_path[0]["wall_s"]
        for row in put_path:
            assert 0.0 <= row["self_s"] <= row["wall_s"] <= top_wall + 1e-9
        # and the CLI renders it without tripping over the artifact kind
        assert rep.main([str(trace_path)]) == 0
        out = capsys.readouterr().out
        assert "request latency (end-to-end, per op)" in out
        assert "critical path: slowest 'put' request" in out
