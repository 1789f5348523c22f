"""Host metadata in first full backups, every chunk new: the ``sync`` phase
of the window's ingest requests (rewriting the recipe table and the store
manifest) over the ingest wall seconds."""

OPS = ("flush", "put")


def read(rec):
    if rec["ingest_s"] <= 0:
        return None
    s = sum(rec["phases"].get(op, {}).get("sync", 0.0) for op in OPS)
    return 100.0 * s / rec["ingest_s"]


CASE = ({}, 100.0 * 0.6 / 4.0)
