"""The benchmark's one traffic generator: versions of a tree, put as flush
groups, with restores drawn between them.

A configuration file says what a tree is and which model makes it; a
traffic file says how a client puts its versions and what it reads back.
Everything is drawn from the seed: version ``k`` is the same bytes for the
same seed however many versions a run gets through, because each version
draws from random streams of its own.  The seed draws the bytes and the
order of the gets, never the work: every seed puts the same sizes under the
same paths in the same flush groups, and gets the same objects the same
number of times.

A tree model is ``models/<model>.py``, named by the configuration's
``model`` key (``file_tree`` where the key is absent), found by name: its
``Model(config, seed)`` has ``sizes()``, every object size a version can
hold (the warm-up compiles the device shapes of these), and
``tree(version)``, the version's ``{path: uint8 array}``.  A model draws
from :func:`rng`, one stream per version and purpose: ``STRUCTURE`` with the
seed replaced by ``FIXED`` for what every seed shares (sizes, paths),
``CONTENT`` for the bytes.

Traffic keys: ``name`` (the object name, formatted with ``version`` and
``path``); ``next``: ``"same"`` (every version is version 0 again: a full
backup of a tree that has not changed) or ``"new"`` (every version is a tree
of its own: the first full backup of another machine); ``group_max_objects``
/ ``group_max_bytes`` (a flush group closes at either; ``null`` for no
limit); ``gets_per_group``, ``get_zipf`` and ``get_versions`` (after each
flush group, that many ``get`` calls of the objects of the last
``get_versions`` (default 1) versions before the one being put, all of whose
objects have been flushed.  A version's gets are a fixed multiset: each of
those objects is read its Zipf share (popularity of that constant) of them,
rounded by largest remainder, with popularity ranks a fixed shuffle of the
objects' order by size and name; the seed shuffles that multiset and deals
it out to the groups).  The set-up puts versions ``0 .. get_versions - 1``
and the measured window starts at version ``get_versions``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Iterator, List, Tuple

import numpy as np

from byname import load

#: random streams a version draws from, each its own
STRUCTURE, CONTENT, GETS = 1, 2, 3
#: stands for the seed in the streams that must not depend on it
FIXED = 0x5EEDF1EE


def rng(seed: int, stream: int, version: int, part: int = 0):
    return np.random.default_rng(
        np.random.SeedSequence([seed & ((1 << 64) - 1), stream, version, part]))


def model(config: dict):
    """The tree model module the configuration names."""
    return load("models", config.get("model", "file_tree"))


@dataclasses.dataclass
class Group:
    """One flush group and the restores that follow it."""

    version: int
    puts: List[Tuple[str, np.ndarray]]
    gets: List[str]
    #: seconds the generator spent making this group (the client's own time)
    gen_s: float = 0.0
    #: the version's last group: its backup is whole once this is flushed
    last: bool = False


class Traffic:
    """Versions of one configuration's tree, put as one traffic file says;
    :meth:`groups` yields the flush groups of versions ``start`` on."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        if traffic["next"] not in ("same", "new"):
            raise ValueError(f"next must be same or new, got "
                             f"{traffic['next']!r}")
        self.get_versions = int(traffic.get("get_versions", 1))
        if self.get_versions < 1:
            raise ValueError(f"get_versions must be at least 1, got "
                             f"{self.get_versions}")
        self.model = model(config).Model(config, seed)
        self._tree: Tuple[int, Dict[str, np.ndarray]] | None = None
        #: (version, files) of the last ``get_versions`` versions whose
        #: groups have all been yielded
        self._readable: List[Tuple[int, Dict[str, np.ndarray]]] = []
        #: every object put so far (name -> bytes), for the checks
        self.objects: Dict[str, np.ndarray] = {}

    def name(self, version: int, path: str) -> str:
        return self.traffic["name"].format(version=version, path=path)

    def version(self, k: int) -> Dict[str, np.ndarray]:
        """The files of version ``k``."""
        v = 0 if self.traffic["next"] == "same" else k
        if self._tree is None or self._tree[0] != v:
            self._tree = (v, self.model.tree(v))
        return self._tree[1]

    def _split(self, files: Dict[str, np.ndarray]):
        t = self.traffic
        max_n = t.get("group_max_objects") or len(files) or 1
        max_b = t.get("group_max_bytes") or float("inf")
        group, size = [], 0
        for path in sorted(files):
            data = files[path]
            if group and (len(group) >= max_n or size + data.size > max_b):
                yield group
                group, size = [], 0
            group.append((path, data))
            size += int(data.size)
        if group:
            yield group

    def _get_plan(self, version: int, readable: Dict[str, np.ndarray],
                  groups: int) -> List[List[str]]:
        """The gets after each of a version's ``groups`` flush groups: a
        fixed multiset of ``gets_per_group * groups`` names, in an order
        drawn from the seed."""
        count = int(self.traffic.get("gets_per_group", 0))
        if not count or not readable:
            return [[] for _ in range(groups)]
        paths = sorted(readable, key=lambda p: (readable[p].size, p))
        rank = np.random.default_rng(len(paths)).permutation(len(paths))
        weights = 1.0 / np.arange(1, len(paths) + 1) ** float(
            self.traffic["get_zipf"])
        share = weights / weights.sum() * count * groups
        reads = np.floor(share).astype(np.int64)
        rest = count * groups - int(reads.sum())
        reads[np.argsort(-(share - reads), kind="stable")[:rest]] += 1
        order = rng(self.seed, GETS, version).permutation(
            np.repeat(np.arange(len(paths)), reads))
        names = [paths[int(rank[i])] for i in order.tolist()]
        return [names[g * count:(g + 1) * count] for g in range(groups)]

    def groups(self, start: int, stop: int | None = None) -> Iterator[Group]:
        """Flush groups of versions ``start`` .. ``stop - 1`` (no end when
        ``stop`` is None).  Gets read the ``get_versions`` versions before
        the one being put, whose objects are all flushed once their last
        groups have been."""
        k = start
        while stop is None or k < stop:
            t0 = time.perf_counter()
            files = self.version(k)
            prev = {self.name(j, p): d
                    for j, tree in self._readable for p, d in tree.items()}
            gen_s = time.perf_counter() - t0
            split = list(self._split(files))
            plan = self._get_plan(k, prev, len(split))
            for gi, group in enumerate(split):
                t0 = time.perf_counter()
                puts = [(self.name(k, p), d) for p, d in group]
                gets = plan[gi]
                for name, data in puts:
                    self.objects[name] = data
                yield Group(k, puts, gets, gen_s + time.perf_counter() - t0,
                            last=gi == len(split) - 1)
                gen_s = 0.0
            self._readable = (self._readable
                              + [(k, files)])[-self.get_versions:]
            k += 1
