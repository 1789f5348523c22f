"""Each cell at a tiny size with the timed path broken underneath: the
comparison with the reference must come out false, naming the fault; the
sound run must come out true."""
import pytest

from tiny import CELLS, run_tiny

CAUGHT_BY = {
    "tail": "fingerprints_differ",
    "unchanged": "objects_missing",
    "half": "objects_missing",
    "boundary": "boundaries_differ",
    "fingerprint": "fingerprints_differ",
    "get": "gets_differ",
}
#: metrics of a run without a trace, by cell
END_TO_END = {
    "file-backup.weekly": {"ingest_MBps", "get_p50_ms", "get_p95_ms",
                           "setup_s"},
    "file-backup.first": {"ingest_MBps.first", "setup_s"},
}


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    r = run_tiny(cell)
    assert r["correct"], r["checks"]
    assert r["checks"]["fingerprints_differ"]["of"] > 0
    assert END_TO_END[cell] == set(r["metrics"])
    gets = r["checks"]["gets_differ"]["of"]
    assert (gets > 0) == ("get_p95_ms" in r["metrics"])
    assert (r["attempted"] - gets) % 32 == 0  # whole trees of 32 files


@pytest.mark.parametrize("cell,fault", [
    (c, f) for c in CELLS for f in sorted(CAUGHT_BY)
    if not (f == "get" and c == "file-backup.first")])
def test_fault_is_caught(cell, fault):
    r = run_tiny(cell, fault)
    assert not r["correct"]
    assert r["checks"][CAUGHT_BY[fault]]["value"] > 0, r["checks"]
