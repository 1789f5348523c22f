"""Scheduler: payload bytes over bytes shipped to the device, padding
included (``SchedulerStats.stream_bytes / device_bytes`` over the window)."""


def read(rec):
    s = rec["sched"]
    if s["device_bytes"] <= 0:
        return None
    return 100.0 * s["stream_bytes"] / s["device_bytes"]


CASE = ({}, 100.0 * 600 / 1000)
