"""Logical bytes acknowledged by ``flush`` (10**6 B) over the wall seconds
of every ingest operation in the window: first ``submit`` of a flush group
through the return of its ``flush``."""


def read(rec):
    if rec["ingest_s"] <= 0 or rec["ingest_bytes"] <= 0:
        return None
    return rec["ingest_bytes"] / 1e6 / rec["ingest_s"]


CASE = ({}, 20e6 / 1e6 / 4.0)
