"""Each cell at a tiny size with the timed path broken underneath: the
comparison with the reference must come out false, naming the fault; the
sound run must come out true."""
import pytest

import harness
from generator import Traffic
from tiny import CELLS, run_tiny, tiny_cell

CAUGHT_BY = {
    "tail": "fingerprints_differ",
    "unchanged": "objects_missing",
    "half": "objects_missing",
    "boundary": "boundaries_differ",
    "fingerprint": "fingerprints_differ",
    "get": "gets_differ",
}


def _gets(cell: str) -> bool:
    return bool(tiny_cell(cell).traffic.get("gets_per_group"))


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    r = run_tiny(cell)
    assert r["correct"], r["checks"]
    assert r["checks"]["fingerprints_differ"]["of"] > 0
    assert set(r["metrics"]) == set(harness.load_cell(cell).end_to_end)
    gets = r["checks"]["gets_differ"]["of"]
    assert (gets > 0) == _gets(cell) == ("get_p95_ms" in r["metrics"])
    c = tiny_cell(cell)
    tree = len(Traffic(c.config, c.traffic, 0).version(0))
    assert (r["attempted"] - gets) % tree == 0  # whole trees


@pytest.mark.parametrize("cell,fault", [
    (c, f) for c in CELLS for f in sorted(CAUGHT_BY)
    if f != "get" or _gets(c)])
def test_fault_is_caught(cell, fault):
    r = run_tiny(cell, fault)
    assert not r["correct"]
    assert r["checks"][CAUGHT_BY[fault]]["value"] > 0, r["checks"]
