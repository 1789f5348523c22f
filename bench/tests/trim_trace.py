"""Cut a trace that ``record_trace.py`` recorded down to what the tests read.

    python bench/tests/trim_trace.py <in.xplane.pb> <out.xplane.pb> [min_us]

Keeps the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane, without the
operations shorter than ``min_us`` microseconds (default 10: they run inside
loops that remain, so busy time moves by a fraction of a percent), and the
host events named ``bench.*`` or ``repro.*``; drops every other plane, line
and event, and the metadata nothing kept refers to.  Reads and writes the
protobuf through the ``xplane_pb2`` module that the installed TensorFlow
ships.
"""
from __future__ import annotations

import sys


def trim(src: str, dst: str, min_us: float = 10.0) -> tuple[int, int]:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    xs = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        xs.ParseFromString(f.read())
    out = xplane_pb2.XSpace()
    min_ps = int(min_us * 1e6)
    for p in xs.planes:
        device = p.name.startswith("/device:TPU:")
        if not device and not p.name.startswith("/host:"):
            continue
        q = out.planes.add()
        q.id, q.name = p.id, p.name
        used = set()
        for line in p.lines:
            if device and line.name != "XLA Ops":
                continue
            if device:
                keep = [ev for ev in line.events if ev.duration_ps >= min_ps]
            else:
                keep = [ev for ev in line.events if p.event_metadata[
                    ev.metadata_id].name.startswith(("bench.", "repro."))]
            if not keep:
                continue
            kept = q.lines.add()
            kept.CopyFrom(line)
            del kept.events[:]
            for ev in keep:
                kept.events.add().CopyFrom(ev)
                used.add(ev.metadata_id)
        for k in used:
            q.event_metadata[k].CopyFrom(p.event_metadata[k])
        for k, v in p.stat_metadata.items():
            q.stat_metadata[k].CopyFrom(v)
    data = out.SerializeToString()
    with open(dst, "wb") as f:
        f.write(data)
    return xs.ByteSize(), len(data)


if __name__ == "__main__":
    if len(sys.argv) not in (3, 4):
        print(__doc__.strip().splitlines()[2], file=sys.stderr)
        raise SystemExit(2)
    print(trim(*sys.argv[1:3], *map(float, sys.argv[3:])))
