"""The stage and idle reduction (``timeline.py``) on handmade intervals and
on two traces recorded on a TPU v5e: ``vm-tiny.xplane.pb`` (see
``test_bench_tracefile.py``), from before the program named its stages and
spans, and ``tiny.xplane.pb``, 3 s of the ``file-backup.first`` cell at the
tests' size recorded by ``record_trace.py``, with the stages named and the
program's spans on the timeline, then cut by ``trim_trace.py`` to its
device operations of 10 us or more and its ``bench.*``/``repro.*`` host
events (busy time moved by 0.2%)."""
import os

import pytest

import timeline
import tracefile

DATA = os.path.join(os.path.dirname(__file__), "data")
OLD = os.path.join(DATA, "vm-tiny.xplane.pb")
NEW = os.path.join(DATA, "tiny.xplane.pb")
S = 1e9  # ns per second

FP = "jit(_device_chunk)/vmap(jit(chunk_fingerprints))/chunk.fingerprint/"
MASKS = "jit(_device_chunk)/vmap(jit(boundaries_two_phase))/chunk.masks/"
SRC = "/somewhere/src/repro/"


def _trace(ops, spans=()):
    return {"spans": [("bench.window", 0, 20 * S), *spans],
            "devices": {"/device:TPU:0": ops}}


@pytest.mark.parametrize("tf_op, source, stage", [
    (FP + "gather", "", "fingerprint"),
    (MASKS + "and", SRC + "core/automaton.py:87", "masks"),
    ("jit(f)/chunk.fused/pallas_call", "", "fused"),
    ("", SRC + "core/automaton.py:129", "automaton"),
    ("", SRC + "dedup/fingerprint.py:133", "fingerprint"),
    ("", SRC + "kernels/seqcdc_masks.py:76", "masks"),
    ("jit(f)/vmap(jit(boundaries_two_phase))/and", SRC + "core/masks.py:56",
     "masks"),
    ("jit(f)/chunk.fingerprints_x/add", "", "other"),
    ("jit(f)/add", SRC + "service/scheduler.py:119", "other"),
    ("", "", "other"),
])
def test_stage_of(tf_op, source, stage):
    assert timeline.stage_of(tf_op, source) == stage


def test_loop_and_its_body_count_once_in_their_stage():
    ops = [("while.30", 0, 10 * S, "", SRC + "dedup/fingerprint.py:133"),
           ("fusion.78", 2 * S, 4 * S, FP + "gather", ""),
           ("fusion.78", 6 * S, 8 * S, FP + "gather", ""),
           ("fusion.26", 10 * S, 12 * S, MASKS + "and", "")]
    r = timeline.reduce(_trace(ops))
    assert r["stages"] == {"fingerprint": pytest.approx(10.0),
                           "masks": pytest.approx(2.0)}
    assert r["busy_s"] == pytest.approx(12.0)
    assert r["other_ops"] == []
    three = [(n, s, e) for n, s, e, _, _ in ops]
    assert tracefile.reduce(
        {"spans": [("bench.window", 0, 20 * S)],
         "devices": {"/device:TPU:0": three}})["busy_s"] == r["busy_s"]


def test_source_fallback_and_other():
    ops = [("while.29", 0, 4 * S, "", SRC + "core/automaton.py:129"),
           # an unnamed op inside a named loop takes the loop's stage
           ("fusion.9", 1 * S, 2 * S, "", ""),
           ("fusion.5", 5 * S, 6 * S, "", ""),
           ("copy.1", 6 * S, 6.5 * S, "jit(f)/copy", SRC + "other.py:1"),
           # overlaps the window's end: clipped to it
           ("fusion.5", 19 * S, 21 * S, "", "")]
    r = timeline.reduce(_trace(ops))
    assert r["stages"] == {"automaton": pytest.approx(4.0),
                           "other": pytest.approx(2.5)}
    assert r["other_ops"] == [["fusion.5", pytest.approx(2.0)],
                              ["copy.1", pytest.approx(0.5)]]
    assert sum(r["stages"].values()) == pytest.approx(r["busy_s"])


def test_idle_goes_to_the_innermost_program_span():
    spans = [("bench.flush", 0, 20 * S),
             ("repro.request", 0, 20 * S),
             ("repro.phase.chunk-dispatch", 0, 6 * S),
             ("repro.sched.dispatch", 1 * S, 5 * S),
             ("repro.phase.commit", 6 * S, 14 * S),
             ("repro.commit.object", 7 * S, 13 * S),
             ("repro.phase.fp", 12 * S, 13 * S)]
    ops = [("fusion.1", 2 * S, 4 * S, FP + "gather", "")]
    r = timeline.reduce(_trace(ops, spans))
    want = {"sched.dispatch": 2.0, "phase.chunk-dispatch": 2.0,
            "phase.commit": 2.0, "commit.object": 5.0, "phase.fp": 1.0,
            "request": 6.0}
    assert r["idle_spans"] == pytest.approx(want)
    assert r["idle_by_client"] == {"flush": pytest.approx(want)}
    assert sum(r["idle_spans"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    # the commit phase holds its nested spans: 6 s .. 14 s, all idle
    assert r["idle_under"]["phase.commit"] == pytest.approx(8.0)
    assert r["idle_under"]["sched.dispatch"] == pytest.approx(2.0)
    assert r["idle_under"]["request"] == pytest.approx(18.0)


def test_idle_outside_every_span():
    spans = [("bench.get", 10 * S, 12 * S),
             ("repro.phase.rpc", 10.5 * S, 11 * S)]
    r = timeline.reduce(_trace([("fusion.1", 0, 10 * S, FP + "x", "")],
                               spans))
    assert r["idle_spans"] == pytest.approx({"none": 9.5, "phase.rpc": 0.5})
    assert r["idle_by_client"] == {
        "get": pytest.approx({"none": 1.5, "phase.rpc": 0.5}),
        "between": pytest.approx({"none": 8.0})}


def test_nothing_on_the_device():
    assert timeline.reduce({"spans": [("bench.window", 0, 10)],
                            "devices": {}}) is None
    assert timeline.reduce(_trace([("fusion.1", 30 * S, 31 * S, "", "")])) \
        is None


@pytest.mark.parametrize("path", [OLD, NEW])
def test_wire_reader_agrees_with_profile_data(path):
    t = timeline.parse(path)
    ref = tracefile.read(path)
    assert sorted(s for s in t["spans"] if s[0].startswith("bench.")) \
        == sorted(ref["spans"])
    assert set(t["devices"]) == set(ref["devices"])
    for plane, ops in t["devices"].items():
        assert sorted(o[:3] for o in ops) == sorted(ref["devices"][plane])


@pytest.mark.parametrize("path", [OLD, NEW])
def test_wire_reader_agrees_with_the_protobuf_module(path):
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    xs = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        xs.ParseFromString(f.read())
    want = {}
    for plane in xs.planes:
        if not tracefile.DEVICE_PLANE.match(plane.name):
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        ops = []
        for line in plane.lines:
            if line.name != tracefile.OPS_LINE:
                continue
            for ev in line.events:
                md = plane.event_metadata[ev.metadata_id]
                st = {names[s.metadata_id]: s.str_value
                      or names.get(s.ref_value, "") for s in md.stats}
                ops.append((tracefile.op_name(md.name),
                            st.get("tf_op", ""), st.get("source", "")))
        want[plane.name] = ops
    got = timeline.parse(path)["devices"]
    assert {p: [(o[0], o[3], o[4]) for o in ops]
            for p, ops in got.items()} == want


def test_trace_from_before_the_stages_were_named():
    """Source lines alone name most of the old trace's device time; the
    four fingerprint segment sums carry neither op_name nor source."""
    r = timeline.reduce(timeline.parse(OLD))
    assert r["busy_s"] == pytest.approx(1.931368608, abs=1e-9)
    assert sum(r["stages"].values()) == pytest.approx(r["busy_s"], rel=1e-9)
    assert r["stages"]["fingerprint"] > r["stages"]["automaton"] > 0
    assert [n for n, _ in r["other_ops"][:4]] == [
        "fusion.5", "fusion.7", "fusion.6", "fusion.8"]
    assert set(r["idle_spans"]) == {"none"}


def test_recorded_trace_names_stages_and_spans():
    r = timeline.reduce(timeline.parse(NEW))
    assert r["busy_s"] == tracefile.reduce(tracefile.read(NEW))["busy_s"]
    assert r["busy_s"] == pytest.approx(1.045225861, abs=1e-9)
    assert sum(r["stages"].values()) == pytest.approx(r["busy_s"], rel=1e-9)
    st = r["stages"]
    assert st["fingerprint"] > st["automaton"] > st["masks"] > 0
    # what neither a scope nor a source line names: the segment sums of
    # the fingerprint stage, custom fusions the compiler made
    assert {n for n, _ in r["other_ops"][:6]} == {
        "fusion.5", "fusion.6", "fusion.7", "fusion.8", "fusion.9",
        "fusion.10"}
    assert sum(r["idle_spans"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9)
    flush = r["idle_by_client"]["flush"]
    named = sum(v for k, v in flush.items()
                if k not in ("none", "request", "service.flush"))
    assert named >= 0.9 * sum(flush.values())
    assert r["idle_under"]["phase.commit"] >= r["idle_spans"][
        "commit.object"] > 0
    assert r["idle_spans"]["sched.dispatch"] > 0


@pytest.mark.parametrize("path", [OLD, NEW])
def test_run_record_reads_the_trace_once_for_both_reductions(path):
    import harness

    rec = harness.reduce_trace(path)
    ref = tracefile.reduce(tracefile.read(path))
    named = timeline.reduce(timeline.parse(path))
    assert set(rec) == set(ref) | {"stages", "idle_spans", "idle_under"}
    for key in ("busy_s", "window_s", "devices"):
        assert rec[key] == pytest.approx(ref[key], rel=1e-12)
    for key in ("device_ops", "idle_gaps"):
        assert [n for n, _ in rec[key]] == [n for n, _ in ref[key]]
        assert [t for _, t in rec[key]] == pytest.approx(
            [t for _, t in ref[key]], rel=1e-9)
    for key in ("stages", "idle_spans", "idle_under"):
        assert rec[key] == named[key]
    assert sum(rec["stages"].values()) == pytest.approx(rec["busy_s"],
                                                         rel=1e-9)
