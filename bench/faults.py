"""Faults planted in the program under test, to show that the comparison
with the plain reference catches them (``correct`` comes out false).

Each is a context manager that patches the program while it is open:

* ``tail``: the control.  The scheduler keeps the boundaries of the padded
  device row instead of redoing the last ``< max_size`` bytes of each stream
  on the host, cutting the last chunk at the stream's end.  This is the step
  a later change that drops the host tail redo would take; the last chunk's
  fingerprint then covers padding.
* ``unchanged``: ``flush`` drains the scheduler and acknowledges every object
  but commits nothing, so the depot is left as it was.
* ``half``: the scheduler hands back only the first half (rounded down) of
  each drain's results; the rest are left out.
* ``boundary``: the first chunk boundary of every stream moves one byte.
* ``fingerprint``: one bit of every stream's first fingerprint flips.
* ``get``: every restore returns one byte altered, after its own check.

The exchange between chips is not among them: both cells run on one chip.
"""
from __future__ import annotations

import contextlib

import numpy as np


@contextlib.contextmanager
def _patched(owner, name, value):
    old = getattr(owner, name)
    setattr(owner, name, value)
    try:
        yield
    finally:
        setattr(owner, name, old)


def _wrap_trim(change):
    from repro.service import scheduler

    orig = scheduler._trim_exact

    def trim(data, padded, padded_fps, p):
        return change(*orig(data, padded, padded_fps, p))

    return _patched(scheduler, "_trim_exact", trim)


def tail():
    from repro.service import scheduler

    def trim(data, padded, padded_fps, p):
        n = int(data.size)
        kept = np.asarray(padded, dtype=np.int64)
        kept = kept[kept < n]
        bounds = np.concatenate([kept, [n]]).astype(np.int64)
        lengths = np.diff(np.concatenate([[0], bounds]))
        fps = (np.zeros((0, 2), dtype=np.uint32) if padded_fps is None
               else np.asarray(padded_fps)[:bounds.size].copy())
        return bounds, fps, lengths, 0

    return _patched(scheduler, "_trim_exact", trim)


def unchanged():
    from repro.service import DedupService
    from repro.service.api import ObjectStat

    def flush(self):
        results = self.scheduler.drain()
        self._in_flight.clear()
        return [ObjectStat(name=str(r.tag), size=r.size, chunks=0,
                           sha256="", mean_chunk=0.0) for r in results]

    return _patched(DedupService, "flush", flush)


def half():
    from repro.service.scheduler import ChunkScheduler

    orig = ChunkScheduler.drain

    def drain(self):
        out = orig(self)
        return out[:len(out) // 2]

    return _patched(ChunkScheduler, "drain", drain)


def boundary():
    def change(bounds, fps, lengths, tail_bytes):
        if bounds.size >= 2 and bounds[1] - bounds[0] > 1:
            bounds = bounds.copy()
            bounds[0] += 1
            lengths = np.diff(np.concatenate([[0], bounds]))
        return bounds, fps, lengths, tail_bytes

    return _wrap_trim(change)


def fingerprint():
    def change(bounds, fps, lengths, tail_bytes):
        if fps.size:
            fps = fps.copy()
            fps[0, 0] ^= 1
        return bounds, fps, lengths, tail_bytes

    return _wrap_trim(change)


def get():
    from repro.service import DedupService

    orig = DedupService.get

    def get_(self, name):
        data = bytearray(orig(self, name))
        if data:
            data[len(data) // 2] ^= 0xFF
        return bytes(data)

    return _patched(DedupService, "get", get_)


FAULTS = {f.__name__: f for f in (tail, unchanged, half, boundary,
                                  fingerprint, get)}
