"""Host commit in first full backups, every chunk new: seconds spent
writing block files (``store.block_write_s``, a program counter) over the
ingest wall seconds."""

COUNTER = "store.block_write_s"


def read(rec):
    c = rec["counters"]
    if rec["ingest_s"] <= 0 or COUNTER not in c:
        return None
    return 100.0 * c[COUNTER] / rec["ingest_s"]


CASE = ({"counters": {"store.key_hash_s": 0.1, "commit.digest_s": 0.02,
                      "store.block_write_s": 1.0}},
        100.0 * 1.0 / 4.0)
