"""Reduce a JAX profiler trace to the device time of each named chunking
stage and the device-idle time under each of the program's own spans.

The program names its device stages with ``jax.named_scope``
(``repro.core.stages``: ``chunk.masks``, ``chunk.automaton``,
``chunk.fingerprint``, ``chunk.fused``) and puts its spans and request
phases on the profiler's timeline as ``repro.<name>`` annotations
(``repro.obs.set_annotator``).  ``ProfileData`` gives a device operation
only its name and times, so this module reads the ``.xplane.pb`` protobuf
itself, with a small reader of the wire format: each ``XLA Ops`` event of a
``/device:TPU:<n>`` plane with its ``tf_op`` (the op_name path) and
``source`` (file:line), and every host event named ``bench.*`` or
``repro.*``.

* ``stages``: stage -> device seconds in the window.  An operation's stage
  is the ``chunk.<stage>`` component of its ``tf_op``; failing that, the
  stage whose module its ``source`` names (``stages.SOURCES``); failing
  both, ``other``.  A loop and the operations of its body overlap, so time
  is a partition, not a sum: each instant of busy time goes to the
  innermost operation running then that has a named stage (the innermost
  one, ``other``, if none has).  The stages sum to the busy seconds of
  ``tracefile.reduce``, averaged over the chips the same way;
  ``other_ops`` names the operations that make up ``other``.
* ``idle_spans``: innermost ``repro.*`` span or phase -> seconds in which
  the first device ran nothing (``none`` outside every program span);
  ``idle_by_client`` splits the same seconds by the benchmark span the
  client was in (``bench.flush`` -> ``flush``; ``between`` outside them).
* ``idle_under``: ``repro.*`` name -> device-idle seconds inside any span of
  that name, the spans nested in it included (``phase.commit`` holds the
  ``commit.object`` and ``phase.fp`` spans of the commit).

The window is ``bench.window``, as in ``tracefile.py``, else the extent of
the device operations.

    python bench/timeline.py <trace dir or .xplane.pb>   # prints the reduction
"""
from __future__ import annotations

import json
import re
import sys
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

HERE = Path(__file__).resolve().parent
for _path in (str(HERE), str(HERE.parent / "src")):
    if _path not in sys.path:
        sys.path.append(_path)

from repro.core import stages as stage_names  # noqa: E402

import tracefile  # noqa: E402

PROGRAM_PREFIX = "repro."
CLIENT_PREFIX = tracefile.SPAN_PREFIX
NONE = "none"
OTHER = "other"
#: the stage component of a tf_op: ``.../chunk.fingerprint/...``
STAGE_IN_OP = re.compile(r"(?:^|/)chunk\.(%s)(?:/|$)"
                         % "|".join(stage_names.SOURCES))

Span = Tuple[str, float, float]
Op = Tuple[str, float, float, str, str]  # name, start, end, tf_op, source


# -- the protobuf wire format, as much of XSpace as the reduction reads ---------


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, i: int = 0, end: Optional[int] = None
            ) -> Iterator[Tuple[int, int, int]]:
    """``(field number, value, end)`` of each field of one message: an
    integer for a varint (``end`` -1), else the payload's start (the
    payload runs to ``end``; fixed-width fields are skipped)."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield field, v, -1
        elif wire == 2:
            n, i = _varint(buf, i)
            yield field, i, i + n
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")


def _str(buf: bytes, a: int, b: int) -> str:
    return buf[a:b].decode("utf-8", "replace")


def _int64(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _map_entry(buf: bytes, a: int, b: int) -> Tuple[int, int, int]:
    """A ``map<int64, message>`` entry -> (key, value start, value end)."""
    key, va, vb = 0, a, a
    for f, v, e in _fields(buf, a, b):
        if f == 1:
            key = _int64(v)
        elif f == 2:
            va, vb = v, e
    return key, va, vb


def _plane(buf: bytes, a: int, b: int, device: bool):
    """One XPlane -> (name, [(line name, timestamp_ns, [(metadata id,
    offset_ps, duration_ps)])], {metadata id: (name, stats slice)},
    {stat id: name})."""
    name, lines, raw_meta, stat_names = "", [], {}, {}
    for f, v, e in _fields(buf, a, b):
        if f == 2:
            name = _str(buf, v, e)
        elif f == 3:
            lines.append((v, e))
        elif f == 4:
            k, va, vb = _map_entry(buf, v, e)
            raw_meta[k] = (va, vb)
        elif f == 5:
            k, va, vb = _map_entry(buf, v, e)
            for g, w, x in _fields(buf, va, vb):
                if g == 2:
                    stat_names[k] = _str(buf, w, x)
    meta = {}
    for k, (va, vb) in raw_meta.items():
        mname, stats = "", []
        for f, v, e in _fields(buf, va, vb):
            if f == 2:
                mname = _str(buf, v, e)
            elif f == 5 and device:
                stats.append((v, e))
        meta[k] = (mname, stats)
    out_lines = []
    for la, lb in lines:
        lname, ts, events = "", 0, []
        for f, v, e in _fields(buf, la, lb):
            if f == 2:
                lname = _str(buf, v, e)
            elif f == 3:
                ts = _int64(v)
            elif f == 4:
                events.append((v, e))
        evs = []
        for ea, eb in events:
            mid = off = dur = 0
            for f, v, _ in _fields(buf, ea, eb):
                if f == 1:
                    mid = _int64(v)
                elif f == 2:
                    off = _int64(v)
                elif f == 3:
                    dur = _int64(v)
            evs.append((mid, off, dur))
        out_lines.append((lname, ts, evs))
    return name, out_lines, meta, stat_names


def _op_stats(buf: bytes, stats, stat_names) -> Dict[str, str]:
    """The string stats of an event's metadata (``str_value`` or a
    ``ref_value`` naming a stat metadata entry)."""
    out = {}
    for a, b in stats:
        sid, val = 0, None
        for f, v, e in _fields(buf, a, b):
            if f == 1:
                sid = _int64(v)
            elif f == 5:
                val = _str(buf, v, e)
            elif f == 7:
                val = stat_names.get(v, "")
        if val is not None and sid in stat_names:
            out[stat_names[sid]] = val
    return out


def parse(path: str) -> dict:
    """The trace at ``path``: host spans named ``bench.*``/``repro.*`` as
    ``(name, start_ns, end_ns)`` and, per device plane, the ``XLA Ops``
    events as ``(op name, start_ns, end_ns, tf_op, source)``; times in whole
    nanoseconds, as ``ProfileData`` gives them to ``tracefile.read``."""
    with open(tracefile.find_trace(path), "rb") as f:
        buf = f.read()
    spans: List[Span] = []
    devices: Dict[str, List[Op]] = {}
    for field, a, b in _fields(buf):
        if field != 1:
            continue
        pname = ""
        for f, v, e in _fields(buf, a, b):
            if f == 2:
                pname = _str(buf, v, e)
                break
        device = bool(tracefile.DEVICE_PLANE.match(pname))
        if not device and not pname.startswith("/host:"):
            continue
        name, lines, meta, stat_names = _plane(buf, a, b, device)
        if device:
            ops, info = [], {}
            for lname, ts, evs in lines:
                if lname != tracefile.OPS_LINE:
                    continue
                for mid, off, dur in evs:
                    if mid not in info:
                        mname, stats = meta.get(mid, ("", []))
                        st = _op_stats(buf, stats, stat_names)
                        info[mid] = (tracefile.op_name(mname),
                                     st.get("tf_op", ""),
                                     st.get("source", ""))
                    op, tf_op, source = info[mid]
                    s = float(ts + off // 1000)
                    ops.append((op, s, s + dur // 1000, tf_op, source))
            devices[name] = ops
            continue
        for _, ts, evs in lines:
            for mid, off, dur in evs:
                sname = meta.get(mid, ("", []))[0].split("#", 1)[0]
                if sname.startswith((PROGRAM_PREFIX, CLIENT_PREFIX)):
                    s = float(ts + off // 1000)
                    spans.append((sname, s, s + dur // 1000))
    return {"spans": spans, "devices": devices}


# -- the reduction ---------------------------------------------------------------


def stage_of(tf_op: str, source: str) -> str:
    """The stage of one device operation (see the module docstring)."""
    m = STAGE_IN_OP.search(tf_op)
    if m:
        return m.group(1)
    path = source.rsplit(":", 1)[0]
    for stage, modules in stage_names.SOURCES.items():
        if path.endswith(modules):
            return stage
    return OTHER


def _sweep(intervals: List[Tuple[float, float, object]]
           ) -> Iterator[Tuple[float, float, list]]:
    """Split the union of ``intervals`` into stretches with one set of
    covering intervals; yields ``(start, end, covering)``, the covering
    ones innermost first (latest start, then earliest end)."""
    points = []
    for i, (s, e, _) in enumerate(intervals):
        if e > s:
            points.append((s, 1, i))
            points.append((e, 0, i))
    points.sort()
    active: Dict[int, None] = {}
    prev = None
    for t, starts, i in points:
        if active and t > prev:
            yield prev, t, sorted((intervals[j] for j in active),
                                  key=lambda iv: (-iv[0], iv[1]))
        if starts:
            active[i] = None
        else:
            del active[i]
        prev = t


def _window(trace: dict) -> Optional[Tuple[float, float]]:
    win = [(s, e) for name, s, e in trace["spans"]
           if name == tracefile.WINDOW_SPAN]
    if win:
        return min(s for s, _ in win), max(e for _, e in win)
    ops = [(s, e) for evs in trace["devices"].values() for _, s, e, _, _ in evs]
    if not ops:
        return None
    return min(s for s, _ in ops), max(e for _, e in ops)


def _intersect(a: List[Tuple[float, float]], b: List[Tuple[float, float]]
               ) -> List[Tuple[float, float]]:
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _add(acc: Dict[str, float], key: str, ns: float):
    acc[key] = acc.get(key, 0.0) + ns / 1e9


def reduce(trace: dict) -> Optional[dict]:
    """Stage seconds and idle attribution; None when no operation ran on a
    device inside the window."""
    window = _window(trace)
    if window is None:
        return None
    w0, w1 = window
    stage_ns: Dict[str, float] = {}
    other_ns: Dict[str, float] = {}
    busy: Dict[str, List[Tuple[float, float]]] = {}
    for plane, evs in sorted(trace["devices"].items()):
        clipped = [(max(s, w0), min(e, w1), (stage_of(tf_op, source), name))
                   for name, s, e, tf_op, source in evs if e > w0 and s < w1]
        if not clipped:
            continue
        busy[plane] = tracefile.merge([(s, e) for s, e, _ in clipped])
        for s, e, covering in _sweep(clipped):
            named = [st for _, _, (st, _) in covering if st != OTHER]
            if named:
                _add(stage_ns, named[0], e - s)
            else:
                _add(stage_ns, OTHER, e - s)
                _add(other_ns, covering[0][2][1], e - s)
    if not busy:
        return None
    n = len(busy)
    idle = tracefile.gaps(busy[sorted(busy)[0]], window)
    program = [(s, e, name[len(PROGRAM_PREFIX):])
               for name, s, e in trace["spans"]
               if name.startswith(PROGRAM_PREFIX) and e > w0 and s < w1]
    client = [(s, e, name[len(CLIENT_PREFIX):])
              for name, s, e in trace["spans"]
              if name.startswith(CLIENT_PREFIX)
              and name != tracefile.WINDOW_SPAN and e > w0 and s < w1]
    idle_spans: Dict[str, float] = {}
    by_client: Dict[str, Dict[str, float]] = {}
    marks = ([(s, e, ("idle", None)) for s, e in idle]
             + [(s, e, ("program", name)) for s, e, name in program]
             + [(s, e, ("client", name)) for s, e, name in client])
    for s, e, covering in _sweep(marks):
        kinds = [tag for _, _, tag in covering]
        if ("idle", None) not in kinds:
            continue
        inner = next((nm for k, nm in kinds if k == "program"), NONE)
        outer = next((nm for k, nm in kinds if k == "client"), "between")
        _add(idle_spans, inner, e - s)
        _add(by_client.setdefault(outer, {}), inner, e - s)
    idle_under: Dict[str, float] = {}
    for name in sorted({nm for _, _, nm in program}):
        spans = tracefile.merge([(s, e) for s, e, nm in program
                                 if nm == name])
        idle_under[name] = sum(e - s for s, e in _intersect(idle, spans)) / 1e9
    return {
        "busy_s": sum(sum(e - s for s, e in b) for b in busy.values())
        / n / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "stages": {k: v / n for k, v in sorted(stage_ns.items())},
        "other_ops": [[k, v / n] for k, v in sorted(
            other_ns.items(), key=lambda kv: -kv[1])[:tracefile.TOP]],
        "idle_spans": dict(sorted(idle_spans.items(), key=lambda kv: -kv[1])),
        "idle_by_client": {c: dict(sorted(d.items(), key=lambda kv: -kv[1]))
                           for c, d in sorted(by_client.items())},
        "idle_under": idle_under,
    }


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__.strip().splitlines()[-1], file=sys.stderr)
        return 2
    print(json.dumps(reduce(parse(args[0])), indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
