"""Median latency of every ``get`` in the window, in milliseconds."""
import statistics


def read(rec):
    lat = rec["get_latencies_s"]
    return statistics.median(lat) * 1e3 if lat else None


CASE = ({}, 50.5)
