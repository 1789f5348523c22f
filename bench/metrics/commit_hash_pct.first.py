"""Host commit in first full backups, every chunk new: seconds spent on
SHA-256 (the chunk keys, ``store.key_hash_s``, and each object's digest,
``commit.digest_s``; program counters) over the ingest wall seconds."""

COUNTERS = ("store.key_hash_s", "commit.digest_s")


def read(rec):
    c = rec["counters"]
    if rec["ingest_s"] <= 0 or not all(k in c for k in COUNTERS):
        return None
    return 100.0 * sum(c[k] for k in COUNTERS) / rec["ingest_s"]


CASE = ({"counters": {"store.key_hash_s": 0.1, "commit.digest_s": 0.02,
                      "store.block_write_s": 1.0}},
        100.0 * (0.1 + 0.02) / 4.0)
