"""The warm-up dispatches every (bucket, rows) shape that objects of the
given sizes can reach, and only those."""
import repro.service
from repro.core.params import SeqCDCParams
from repro.service.scheduler import ChunkScheduler

import harness

PARAMS = SeqCDCParams(avg_size=8192, seq_length=5, skip_trigger=50,
                      skip_size=256, min_size=4096, max_size=16384)


class Recorder:
    """Stands in for the service: records the rows of each flush."""

    def __init__(self, params):
        self.scheduler = ChunkScheduler(params)
        self.pending, self.flushed = [], []

    def submit(self, name, data):
        self.pending.append(int(data.size))

    def flush(self):
        self.flushed.append(tuple(self.pending))
        self.pending = []


def test_every_row_count_of_every_bucket(monkeypatch):
    made = []
    monkeypatch.setattr(repro.service, "DedupService",
                        lambda params: made.append(Recorder(params))
                        or made[-1])
    # 16 KiB and 24 KiB buckets take 8 rows, 12 MiB takes one
    shapes = harness.warm_up(PARAMS, [10, 16384, 20000, 9_000_000])
    assert shapes == 17
    flushed = made[0].flushed
    assert sorted(set(flushed)) == sorted(
        {(b,) * r for b in (16384, 24576) for r in range(1, 9)}
        | {(12 << 20,)})
    assert len(flushed) == 17
