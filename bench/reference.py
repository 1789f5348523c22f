"""Plain reference of the dedup store's semantics, kept with the benchmark.

It imports nothing of the program under test and takes nothing the program
made.  Three parts, each a straightforward reading of the published rules:

* SeqCDC chunk boundaries (arXiv:2505.21194, section III): a chunk starting
  at ``s`` ignores its first ``min_size - seq_length`` bytes, then scans byte
  by byte.  A window of ``seq_length`` strictly monotone bytes (in the
  configured direction) ends the chunk after the window; every pair ordered
  against the direction counts, and the ``skip_trigger + 1``-th such pair
  jumps the scan ``skip_size`` bytes ahead and resets the count.  The chunk
  is cut at ``s + max_size`` (checked first) or at the end of the stream.
  :func:`boundaries_scalar` is that loop, byte by byte;
  :func:`boundaries` jumps from event to event over precomputed positions
  and is the one run at the benchmark's sizes (the tests hold the two equal).
* 62-bit chunk fingerprints: two polynomial hashes modulo ``2**31 - 1``,
  ``h_r = sum_i b_i * r**(len - 1 - i)``, with ``r`` = 1103515245 and
  747796405, recorded per chunk as ``(h1 << 32) | h2``.
* A dict of SHA-256 keys: each chunk is keyed by the SHA-256 of its bytes; the
  store holds each key once, so ``stored_bytes`` is the sum of the unique
  chunks' lengths and ``unique_chunks`` their count.

Boundaries are exclusive ends: chunk ``i`` is ``data[b[i-1]:b[i]]`` and the
last bound is the stream length.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, List, Sequence

import numpy as np

P31 = (1 << 31) - 1
R1 = 1_103_515_245
R2 = 747_796_405
#: longest chunk the fingerprint tables cover
MAX_FP_CHUNK = 1 << 16


@dataclasses.dataclass(frozen=True)
class Chunking:
    """SeqCDC parameters as a configuration states them."""

    avg_size: int
    seq_length: int
    skip_trigger: int
    skip_size: int
    min_size: int
    max_size: int
    mode: str = "increasing"

    def __post_init__(self):
        if self.mode not in ("increasing", "decreasing"):
            raise ValueError(f"mode must be increasing or decreasing, "
                             f"got {self.mode!r}")
        if not (2 <= self.seq_length <= self.min_size <= self.max_size):
            raise ValueError(f"inconsistent chunk sizes: {self}")


def boundaries_scalar(data: bytes, p: Chunking) -> List[int]:
    """SeqCDC boundaries, one byte at a time (small inputs: tests)."""
    d = bytes(data)
    n = len(d)
    L = p.seq_length
    inc = p.mode == "increasing"
    out: List[int] = []
    s = 0
    while s < n:
        k = s + p.min_size - L
        count = 0
        while True:
            if k + L > s + p.max_size:
                b = min(s + p.max_size, n)
                break
            if k + L > n:
                b = n
                break
            run = all((d[k + j + 1] > d[k + j]) if inc
                      else (d[k + j + 1] < d[k + j]) for j in range(L - 1))
            if run:
                b = k + L
                break
            against = d[k + 1] < d[k] if inc else d[k + 1] > d[k]
            if against:
                count += 1
                if count > p.skip_trigger:
                    k += p.skip_size
                    count = 0
                    continue
            k += 1
        out.append(b)
        s = b
    return out


def boundaries(data: np.ndarray, p: Chunking) -> np.ndarray:
    """SeqCDC boundaries by events: the same rule as
    :func:`boundaries_scalar`, with the monotone windows and the opposing
    pairs found once for the whole stream."""
    d = np.asarray(data, dtype=np.uint8).reshape(-1)
    n = int(d.size)
    if n == 0:
        return np.zeros(0, dtype=np.int64)
    L = p.seq_length
    a, b = d[:-1], d[1:]
    along = (b > a) if p.mode == "increasing" else (b < a)
    against = (b < a) if p.mode == "increasing" else (b > a)
    # run[k]: bytes k .. k+L-1 strictly monotone, i.e. pairs k .. k+L-2 along
    m = n - L + 1
    if m > 0:
        run = along[:m].copy()
        for j in range(1, L - 1):
            run &= along[j:j + m]
        run_pos = np.flatnonzero(run)
    else:
        run_pos = np.zeros(0, dtype=np.int64)
    opp_pos = np.flatnonzero(against)
    never = n + p.max_size + 1
    T = p.skip_trigger
    out: List[int] = []
    s = 0
    while s < n:
        end = min(s + p.max_size, n)
        last = end - L  # the last scan position whose window fits
        k = s + p.min_size - L
        while True:
            if k > last:
                cut = end
                break
            i = int(np.searchsorted(run_pos, k))
            kr = int(run_pos[i]) if i < run_pos.size else never
            j = int(np.searchsorted(opp_pos, k)) + T
            ko = int(opp_pos[j]) if j < opp_pos.size else never
            if kr <= last and kr < ko:
                cut = kr + L
                break
            if ko <= last:
                k = ko + p.skip_size
                continue
            cut = end
            break
        out.append(cut)
        s = cut
    return np.asarray(out, dtype=np.int64)


def _power_table(r: int) -> np.ndarray:
    out = np.empty(MAX_FP_CHUNK, dtype=np.uint64)
    acc = 1
    for e in range(MAX_FP_CHUNK):
        out[e] = acc
        acc = acc * r % P31
    return out


class Fingerprinter:
    """62-bit chunk fingerprints; builds its power tables once."""

    def __init__(self):
        self._tables = (_power_table(R1), _power_table(R2))

    def __call__(self, data: np.ndarray, bounds: Sequence[int],
                 block: int = 8 << 20) -> List[int]:
        """Packed ``(h1 << 32) | h2`` per chunk, in blocks of whole chunks
        of about ``block`` bytes so the products stay small in memory."""
        d = np.asarray(data, dtype=np.uint8).reshape(-1)
        ends = np.asarray(bounds, dtype=np.int64)
        starts = np.concatenate([[0], ends[:-1]]).astype(np.int64)
        if (ends - starts).max(initial=0) > MAX_FP_CHUNK:
            raise ValueError("chunk longer than the fingerprint tables")
        out: List[int] = []
        i = 0
        while i < ends.size:
            j = int(np.searchsorted(ends, starts[i] + block, side="right"))
            j = max(j, i + 1)
            lo, hi = int(starts[i]), int(ends[j - 1])
            idx = np.arange(lo, hi, dtype=np.int64)
            chunk_end = np.repeat(ends[i:j], ends[i:j] - starts[i:j])
            exp = chunk_end - 1 - idx
            byte = d[lo:hi].astype(np.uint64)
            rel = starts[i:j] - lo
            h = [np.add.reduceat(byte * t[exp], rel) % P31
                 for t in self._tables]
            out.extend(((h[0] << np.uint64(32)) | h[1]).tolist())
            i = j
        return [int(x) for x in out]


def sha256_keys(data: np.ndarray, bounds: Sequence[int]) -> List[str]:
    buf = memoryview(np.ascontiguousarray(data, dtype=np.uint8)).cast("B")
    keys, s = [], 0
    for e in bounds:
        e = int(e)
        keys.append(hashlib.sha256(buf[s:e]).hexdigest())
        s = e
    return keys


@dataclasses.dataclass
class Recipe:
    """What the reference says one object is made of."""

    size: int
    sha256: str
    bounds: List[int]
    keys: List[str]
    fps: List[int] | None = None


class Store:
    """A dict of SHA-256 keys: the exact accounting of a dedup store."""

    def __init__(self, chunking: Chunking):
        self.chunking = chunking
        self.chunks: Dict[str, int] = {}
        self._fp = None

    def add(self, data: np.ndarray, *, with_fps: bool) -> Recipe:
        d = np.asarray(data, dtype=np.uint8).reshape(-1)
        b = boundaries(d, self.chunking)
        keys = sha256_keys(d, b)
        for k, e, s in zip(keys, b, np.concatenate([[0], b[:-1]])):
            self.chunks[k] = int(e - s)
        fps = None
        if with_fps:
            if self._fp is None:
                self._fp = Fingerprinter()
            fps = self._fp(d, b)
        return Recipe(size=int(d.size),
                      sha256=hashlib.sha256(d.tobytes()).hexdigest(),
                      bounds=b.tolist(), keys=keys, fps=fps)

    @property
    def stored_bytes(self) -> int:
        return sum(self.chunks.values())

    @property
    def unique_chunks(self) -> int:
        return len(self.chunks)
