"""Restore path: the ``verify`` phase of the window's ``get`` requests
(length and whole-object SHA-256 against the recipe) over the ``get`` wall
seconds."""


def read(rec):
    total = sum(rec["get_latencies_s"])
    if total <= 0:
        return None
    return 100.0 * rec["phases"].get("get", {}).get("verify", 0.0) / total


CASE = ({}, 100.0 * 0.0101 / 5.05)
