"""The traffic generator: seeded, deterministic, and shaped as its files
say."""
import math

import numpy as np
import pytest

from generator import Traffic

TREE = {"files": 200, "dirs": 8, "size_log_mean": 9.48,
        "size_log_sigma": 2.46}
WEEKLY = {"name": "week-{version:03d}/{path}", "next": "same",
          "group_max_objects": 64, "group_max_bytes": 1 << 20,
          "gets_per_group": 16, "get_zipf": 0.99}
FIRST = {"name": "host-{version:03d}/{path}", "next": "new",
         "group_max_objects": 64, "group_max_bytes": 1 << 20,
         "gets_per_group": 0}
SEEDS = [0, 2**31 + 7, 2**40 + 3, -5]
MIXES = pytest.mark.parametrize("traffic", [WEEKLY, FIRST],
                                ids=["same", "new"])


def _versions(traffic, seed, n):
    t = Traffic(TREE, traffic, seed)
    return [{p: d.tobytes() for p, d in t.version(k).items()}
            for k in range(n)]


@MIXES
@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_bytes(traffic, seed):
    assert _versions(traffic, seed, 3) == _versions(traffic, seed, 3)


@MIXES
def test_other_seed_other_bytes(traffic):
    assert _versions(traffic, 1, 2) != _versions(traffic, 2, 2)


@MIXES
def test_groups_do_not_change_the_versions(traffic):
    """Version k is the same whether it was reached by groups or directly."""
    a = Traffic(TREE, traffic, 42)
    puts = {}
    for g in a.groups(0, 3):
        puts.update({n: d.tobytes() for n, d in g.puts})
    b = Traffic(TREE, traffic, 42)
    want = {}
    for k in (2, 0, 1):
        want.update({a.name(k, p): d.tobytes()
                     for p, d in b.version(k).items()})
    assert puts == want


def test_same_puts_one_tree_again_and_new_a_tree_of_its_own():
    same, new = _versions(WEEKLY, 9, 3), _versions(FIRST, 9, 3)
    assert same[0] == same[1] == same[2] == new[0]
    assert set(new[1]) == set(new[0])  # the same paths, other content
    assert all(new[1][p] != new[0][p] for p in new[0] if len(new[0][p]) > 8)


def test_tree_groups_and_gets():
    t = Traffic(TREE, WEEKLY, 7)
    groups = list(t.groups(0, 2))
    for g in groups:
        assert len(g.puts) <= 64
        assert sum(d.size for _, d in g.puts) <= (1 << 20) or len(g.puts) == 1
        names = [n for n, _ in g.puts]
        assert names == sorted(names)
    assert all(not g.gets for g in groups if g.version == 0)
    for k in (0, 1):  # one last group a version, its final one
        assert [g.last for g in groups if g.version == k][-1]
        assert sum(g.last for g in groups if g.version == k) == 1
    week1 = [g for g in groups if g.version == 1]
    gets = [n for g in week1 for n in g.gets]
    assert len(gets) == 16 * len(week1)
    assert all(n.startswith("week-000/") for n in gets)
    assert len(set(gets)) < len(gets)  # Zipf popularity repeats objects
    assert not any(g.gets for g in Traffic(TREE, FIRST, 7).groups(0, 2))


def test_sizes_follow_the_lognormal():
    sizes = Traffic(TREE, WEEKLY, 3).model.sizes()
    assert sizes.size == 200 and (np.diff(sizes) >= 0).all()
    median = float(np.median(sizes))
    assert median == pytest.approx(math.exp(9.48), rel=0.02)
    # the share of files under the lognormal's first quartile
    q1 = math.exp(9.48 - 0.6745 * 2.46)
    assert (sizes < q1).mean() == pytest.approx(0.25, abs=0.01)


def test_seeds_share_sizes_and_hot_object_sizes():
    """Seeds change content and the order of the gets, not the work: the
    same file sizes under the same paths, the same flush groups, and the
    same objects read the same number of times."""
    a, b = Traffic(TREE, WEEKLY, 1), Traffic(TREE, WEEKLY, 2**33 + 1)
    ga, gb = list(a.groups(0, 3)), list(b.groups(0, 3))
    assert [[(n, d.size) for n, d in g.puts] for g in ga] == \
        [[(n, d.size) for n, d in g.puts] for g in gb]
    assert [n for g in ga for n, _ in g.puts] != []
    for k in (1, 2):
        gets_a = [n for g in ga if g.version == k for n in g.gets]
        gets_b = [n for g in gb if g.version == k for n in g.gets]
        assert sorted(gets_a) == sorted(gets_b)
        assert gets_a != gets_b


@pytest.mark.parametrize("seed", SEEDS)
def test_gets_follow_zipf_shares(seed):
    """Each object is read its Zipf share of a version's gets, to within
    one read, and the most popular object the most often."""
    t = Traffic(TREE, WEEKLY, seed)
    groups = [g for g in t.groups(0, 2) if g.version == 1]
    gets = [n for g in groups for n in g.gets]
    files = {t.name(0, p): d for p, d in t.version(0).items()}
    paths = sorted(files, key=lambda p: (files[p].size, p))
    rank = np.random.default_rng(len(paths)).permutation(len(paths))
    weights = 1.0 / np.arange(1, len(paths) + 1) ** 0.99
    share = weights / weights.sum() * len(gets)
    reads = {n: gets.count(n) for n in set(gets)}
    for r, i in enumerate(rank.tolist()):
        assert abs(reads.get(paths[i], 0) - share[r]) < 1
    assert max(reads, key=reads.get) == paths[int(rank[0])]


def test_unknown_next_is_refused():
    with pytest.raises(ValueError, match="next"):
        Traffic(TREE, dict(WEEKLY, next="edit"), 1)
