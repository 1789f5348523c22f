"""Host commit in first full backups, every chunk new: the ``commit`` and
``fp`` phases of the window's ingest requests (chunk store puts with their
SHA-256, recipes, fingerprint index) over the ingest wall seconds."""

OPS = ("flush", "put")


def read(rec):
    if rec["ingest_s"] <= 0:
        return None
    ph = rec["phases"]
    s = sum(ph.get(op, {}).get(p, 0.0) for op in OPS for p in ("commit", "fp"))
    return 100.0 * s / rec["ingest_s"]


CASE = ({}, 100.0 * (1.0 + 0.2) / 4.0)
