"""The plain reference: its event-driven boundaries equal its byte-by-byte
loop, its fingerprints equal the definition, its store counts exactly, and
the served path agrees with it at tiny sizes."""
import hashlib

import numpy as np
import pytest

import reference as ref

SMALL = ref.Chunking(avg_size=256, seq_length=3, skip_trigger=6,
                     skip_size=32, min_size=64, max_size=512)
PAPER = ref.Chunking(avg_size=8192, seq_length=5, skip_trigger=50,
                     skip_size=256, min_size=4096, max_size=16384)


def _data(kind, n, rng):
    if kind == "random":
        return rng.integers(0, 256, n, dtype=np.uint8)
    if kind == "low":
        return rng.integers(0, 3, n, dtype=np.uint8)
    if kind == "ramp":
        return (np.arange(n) % 251).astype(np.uint8)
    return np.zeros(n, dtype=np.uint8)


@pytest.mark.parametrize("mode", ["increasing", "decreasing"])
@pytest.mark.parametrize("kind", ["random", "low", "ramp", "zeros"])
def test_event_boundaries_equal_scalar_loop(kind, mode):
    rng = np.random.default_rng([3, len(kind), len(mode)])
    for params in (SMALL, PAPER):
        p = ref.Chunking(**{**params.__dict__, "mode": mode})
        for n in (0, 1, 5, 63, 64, 65, 511, 512, 513, 3000, 40000):
            d = _data(kind, n, rng)
            assert ref.boundaries(d, p).tolist() == \
                ref.boundaries_scalar(d.tobytes(), p), (n, p)


def test_boundaries_cover_the_stream_within_limits():
    rng = np.random.default_rng(11)
    d = rng.integers(0, 256, 200_000, dtype=np.uint8)
    b = ref.boundaries(d, PAPER)
    lens = np.diff(np.concatenate([[0], b]))
    assert b[-1] == d.size and (lens > 0).all()
    assert (lens[:-1] >= PAPER.min_size).all()
    assert (lens <= PAPER.max_size).all()


def _fp_by_definition(chunk: bytes, r: int) -> int:
    h = 0
    for byte in chunk:
        h = (h * r + byte) % ref.P31
    return h


def test_fingerprints_follow_the_definition():
    rng = np.random.default_rng(5)
    d = rng.integers(0, 256, 5000, dtype=np.uint8)
    b = ref.boundaries(d, SMALL)
    fps = ref.Fingerprinter()(d, b, block=700)
    s = 0
    for e, fp in zip(b.tolist(), fps):
        chunk = d[s:e].tobytes()
        assert fp == (_fp_by_definition(chunk, ref.R1) << 32
                      | _fp_by_definition(chunk, ref.R2))
        s = e


def test_store_counts_each_unique_chunk_once():
    rng = np.random.default_rng(9)
    d = rng.integers(0, 256, 50_000, dtype=np.uint8)
    store = ref.Store(SMALL)
    a = store.add(d, with_fps=False)
    b = store.add(d.copy(), with_fps=True)
    assert a.keys == b.keys and b.fps is not None and a.fps is None
    assert store.stored_bytes == d.size
    assert store.unique_chunks == len(set(a.keys))
    assert a.sha256 == hashlib.sha256(d.tobytes()).hexdigest()


@pytest.mark.parametrize("path", [
    {},  # the default served path
    {"pipeline_impl": "fused"},  # the Pallas kernel, interpreted on the CPU
], ids=["default", "fused-pallas"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_served_path_agrees_with_reference(seed, path, tmp_path):
    from repro.core.params import SeqCDCParams
    from repro.service import DedupService

    rng = np.random.default_rng(seed)
    svc = DedupService.open(str(tmp_path), params=SeqCDCParams(**{
        k: v for k, v in SMALL.__dict__.items()}), **path)
    store = ref.Store(SMALL)
    objs = {}
    for i, n in enumerate([1, 40, 600, 5000, 20_000, 70_000]):
        objs[f"o{i}"] = rng.integers(0, 256, n, dtype=np.uint8)
    objs["dup"] = objs["o4"].copy()
    for name, data in objs.items():
        svc.submit(name, data)
    svc.flush()
    for name, data in objs.items():
        want = store.add(data, with_fps=True)
        r = svc.recipes.get(name)
        assert np.cumsum(r.chunk_lens).tolist() == want.bounds
        assert r.keys == want.keys and r.fps == want.fps
        assert r.sha256 == want.sha256
        assert svc.get(name) == data.tobytes()
    st = svc.stats()
    assert (st.stored_bytes, st.unique_chunks) == (store.stored_bytes,
                                                   store.unique_chunks)
