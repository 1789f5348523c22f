"""Device kernels: seconds of the ``chunk.fingerprint`` stage (the 62-bit
chunk fingerprints; on a TPU the Mosaic op ``chunk_fingerprint``) over the
device's busy seconds in the traced window.  The trace reduction gives each
busy instant to one stage (``timeline.py``), so the stages' shares sum to
100%."""

STAGE = "fingerprint"


def read(rec):
    t = rec["trace"]
    if not t or t["busy_s"] <= 0 or STAGE not in t.get("stages", {}):
        return None
    return 100.0 * t["stages"][STAGE] / t["busy_s"]


CASE = ({"trace": {"stages": {"fingerprint": 0.0075, "automaton": 0.24,
                              "masks": 0.001, "other": 0.0015}}},
        100.0 * 0.0075 / 0.25)
