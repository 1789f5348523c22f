"""95th percentile (nearest rank) of every ``get`` latency in the window, in
milliseconds."""
import math


def read(rec):
    lat = sorted(rec["get_latencies_s"])
    if not lat:
        return None
    return lat[math.ceil(0.95 * len(lat)) - 1] * 1e3


CASE = ({}, 95.0)
