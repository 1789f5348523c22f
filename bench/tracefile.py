"""Reduce a JAX profiler trace to device busy time, the costliest device
operations and the longest idle gaps, each gap named by what the benchmark's
client was doing in it.

The client marks its own calls with ``jax.profiler.TraceAnnotation`` spans
named ``bench.<what>`` (``bench.window`` around the measured window,
``bench.submit``, ``bench.flush``, ``bench.get`` and so on); those land on
the host planes of the same trace.  Device work is read from the ``XLA Ops``
line of each ``/device:TPU:<n>`` plane, which lists every operation,
those inside a loop's body once per iteration besides the loop itself.
Busy time is the union of the operation intervals inside the window, so
nested and overlapping operations count once; it is averaged over the chips
that ran anything.  The top operations are summed by name, so a loop and
the operations of its body each appear with their own total.

    python bench/tracefile.py <trace dir or .xplane.pb>   # prints the reduction
"""
from __future__ import annotations

import glob
import json
import os
import re
import sys
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
TOP = 10

Interval = Tuple[float, float]


def find_trace(path: str) -> str:
    """The ``.xplane.pb`` file at ``path`` or the newest one under it."""
    if os.path.isfile(path):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return max(found, key=os.path.getmtime)


def merge(intervals: List[Interval]) -> List[Interval]:
    """Union of intervals, as disjoint sorted intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def gaps(busy: List[Interval], window: Interval) -> List[Interval]:
    """The stretches of ``window`` that no interval of ``busy`` covers."""
    out, t = [], window[0]
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if window[1] > t:
        out.append((t, window[1]))
    return out


def _events(plane, line_filter=None):
    for line in plane.lines:
        if line_filter is not None and line.name != line_filter:
            continue
        for ev in line.events:
            yield ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)


def op_name(hlo: str) -> str:
    """``%fusion.78 = s32[...] fusion(...)`` -> ``fusion.78``: the device
    event names are whole HLO instructions."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def read(path: str) -> dict:
    """Load a trace: host spans of the benchmark and device op intervals."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_trace(path))
    spans: List[Tuple[str, float, float]] = []
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            spans.extend(ev for ev in _events(plane)
                         if ev[0].startswith(SPAN_PREFIX))
        elif DEVICE_PLANE.match(plane.name):
            devices[plane.name] = [(op_name(n), s, e)
                                   for n, s, e in _events(plane, OPS_LINE)]
    return {"spans": spans, "devices": devices}


def reduce(trace: dict) -> dict | None:
    """Busy and window seconds, top device ops and idle gaps; None when no
    operation ran on a device inside the window."""
    spans, devices = trace["spans"], trace["devices"]
    win = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if win:
        window = (min(s for s, _ in win), max(e for _, e in win))
    else:
        ops = [(s, e) for evs in devices.values() for _, s, e in evs]
        if not ops:
            return None
        window = (min(s for s, _ in ops), max(e for _, e in ops))
    w0, w1 = window
    per_op: Dict[str, float] = {}
    busy_per_device: Dict[str, List[Interval]] = {}
    for plane, evs in sorted(devices.items()):
        clipped = [(name, max(s, w0), min(e, w1)) for name, s, e in evs
                   if e > w0 and s < w1]
        if not clipped:
            continue
        for name, s, e in clipped:
            per_op[name] = per_op.get(name, 0.0) + (e - s)
        busy_per_device[plane] = merge([(s, e) for _, s, e in clipped])
    if not busy_per_device:
        return None
    busy_ns = [sum(e - s for s, e in b) for b in busy_per_device.values()]
    first = busy_per_device[sorted(busy_per_device)[0]]
    host = sorted(((s, e, name) for name, s, e in spans
                   if name != WINDOW_SPAN), key=lambda t: (t[0], -t[1]))

    def doing(t: float) -> str:
        inner = None
        for s, e, name in host:
            if s > t:
                break
            if e >= t and (inner is None or s >= inner[0]):
                inner = (s, e, name)
        return inner[2][len(SPAN_PREFIX):] if inner else "between"

    idle = sorted(gaps(first, window), key=lambda g: g[0] - g[1])[:TOP]
    return {
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "devices": len(busy_per_device),
        "device_ops": [[n, t / 1e9] for n, t in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[doing((s + e) / 2), (e - s) / 1e9] for s, e in idle],
    }


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__.strip().splitlines()[-1], file=sys.stderr)
        return 2
    print(json.dumps(reduce(read(args[0])), indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
