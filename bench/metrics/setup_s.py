"""Set-up seconds: process start (JAX's start-up included) through the
set-up ingest and the warm-up, to the start of the measured window."""


def read(rec):
    return rec["setup_s"]


CASE = ({}, 12.5)
