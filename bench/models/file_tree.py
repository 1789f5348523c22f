"""A file tree (configuration keys): ``files`` objects named
``d<dir>/f<id>`` over ``dirs`` directories, of random bytes, whose sizes sit
at evenly spaced quantiles of a lognormal (``size_log_mean``,
``size_log_sigma``; natural log of bytes), shuffled over the paths in an
order of each version's own that is the same for every seed."""
from __future__ import annotations

import math
import statistics
from typing import Dict

import numpy as np

from generator import CONTENT, FIXED, STRUCTURE, rng

#: configuration keys that cut a tree to a size a CPU test run holds: the
#: configuration's spread of sizes about a smaller median, 10 B to 400 KB
TINY = {"files": 32, "dirs": 4, "size_log_mean": math.log(2000)}


class FileTree:
    def __init__(self, config: dict, seed: int):
        self.cfg, self.seed = config, seed

    def sizes(self) -> np.ndarray:
        """The tree's sizes at evenly spaced quantiles of the lognormal, in
        increasing order (at least one byte)."""
        c = self.cfg
        count = int(c["files"])
        dist = statistics.NormalDist(float(c["size_log_mean"]),
                                     float(c["size_log_sigma"]))
        raw = np.exp([dist.inv_cdf((i + 0.5) / count) for i in range(count)])
        return np.maximum(raw, 1).astype(np.int64)

    def tree(self, version: int) -> Dict[str, np.ndarray]:
        sizes = rng(FIXED, STRUCTURE, version).permutation(self.sizes())
        content = rng(self.seed, CONTENT, version)
        fanout = int(self.cfg["dirs"])
        return {f"d{i % fanout:02d}/f{i:06d}":
                np.frombuffer(content.bytes(int(n)), dtype=np.uint8)
                for i, n in enumerate(sizes.tolist())}


Model = FileTree
