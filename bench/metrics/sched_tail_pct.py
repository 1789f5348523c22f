"""Scheduler: share of the window's stream bytes re-chunked on the host to
make padded device rows exact (``SchedulerStats.tail_bytes / stream_bytes``)."""


def read(rec):
    s = rec["sched"]
    if s["stream_bytes"] <= 0:
        return None
    return 100.0 * s["tail_bytes"] / s["stream_bytes"]


CASE = ({}, 100.0 * 150 / 600)
