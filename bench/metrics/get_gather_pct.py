"""Restore path: the ``rpc`` phase of the window's ``get`` requests (reading
and joining the object's chunks from the store) over the ``get`` wall
seconds."""


def read(rec):
    total = sum(rec["get_latencies_s"])
    if total <= 0:
        return None
    return 100.0 * rec["phases"].get("get", {}).get("rpc", 0.0) / total


CASE = ({}, 100.0 * 0.0505 / 5.05)
