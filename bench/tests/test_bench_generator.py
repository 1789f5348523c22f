"""The traffic generator: seeded, deterministic, and shaped as its files
say."""
import hashlib
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


#: sha256 of every group of versions 0-2 (version, last, names, gets and
#: bytes), taken before the file tree moved to ``models/file_tree.py``
DIGESTS = {
    ("same", 0): "9bafa854ad39c51867252b3f4774ea06"
                 "64eb3b7770f90cd12a91465a73147867",
    ("same", 2**31 + 7): "1ddcaa473dfdf23974d0e7ce9bdae571"
                         "ed0ee6de3770098f5813aa87ca7f6a47",
    ("same", 2**40 + 3): "f8a213aee30b2d24d173de85823cbe33"
                         "78d96dcad58ab65a1dc6c1454bdcb45a",
    ("same", -5): "ea83732f2769e172836c9a7f0a3d9649"
                  "822b31132cdc60ec1a1f855bcdbd3c3f",
    ("new", 0): "95a2e0d830fa5bfa15bf3bec56f50add"
                "7d12530f9c5b74b00d80d4b108c72643",
    ("new", 2**31 + 7): "51f9ca526943b7f6dd1ebc3238ec8fcf"
                        "d6038719f21e2a9a9cffaad4386805e2",
    ("new", 2**40 + 3): "7f3b5536c85ad90ba8abdfcc81db633b"
                        "0a2db8e4ffa710403954475dea479244",
    ("new", -5): "e8ef5e9064725019c2494ee0c3c83a17"
                 "b98444edd07e822a03b1f83eb1d47a30",
}


@MIXES
@pytest.mark.parametrize("seed", SEEDS)
def test_file_tree_model_puts_the_bytes_it_always_did(traffic, seed):
    h = hashlib.sha256()
    for g in Traffic(TREE, traffic, seed).groups(0, 3):
        h.update(repr((g.version, g.last, [n for n, _ in g.puts],
                       g.gets)).encode())
        for _, d in g.puts:
            h.update(d.tobytes())
    assert h.hexdigest() == DIGESTS[traffic["next"], seed]


def test_model_is_found_by_name_and_an_unknown_one_refused():
    default = Traffic(TREE, WEEKLY, 5).model
    named = Traffic(dict(TREE, model="file_tree"), WEEKLY, 5).model
    for m in (default, named):
        assert (type(m).__module__, type(m).__name__) == (
            "bench_models_file_tree", "FileTree")
    assert {p: d.tobytes() for p, d in named.tree(1).items()} == \
        {p: d.tobytes() for p, d in default.tree(1).items()}
    for bad in ("vm_image", "../generator", "file_tree.py", ""):
        with pytest.raises(ValueError, match="models/"):
            Traffic(dict(TREE, model=bad), WEEKLY, 5)


RESTORE = dict(WEEKLY, group_max_objects=200, group_max_bytes=None,
               gets_per_group=512, get_versions=4)


def _plan(seed):
    """The set-up's groups (versions 0-3) and the window's (4-5)."""
    t = Traffic(TREE, RESTORE, seed)
    set_up = list(t.groups(0, 4))
    return t, set_up, list(t.groups(4, 6))


@pytest.mark.parametrize("seed", SEEDS)
def test_gets_over_several_versions_follow_zipf_shares(seed):
    """With ``get_versions`` 4 a version's gets are a fixed multiset over
    the 4 x 200 names of the four versions before it, each its Zipf share
    of the 512, popularity ranks a fixed shuffle of their (size, name)
    order."""
    t, set_up, window = _plan(seed)
    assert [(g.version, g.last) for g in set_up] == [
        (k, True) for k in range(4)]  # one flush group a version
    for g in window:
        k = g.version
        names = {t.name(j, p): d for j in range(k - 4, k)
                 for p, d in t.version(j).items()}
        assert len(names) == 800 and len(g.gets) == 512
        assert set(g.gets) <= set(names)
        order = sorted(names, key=lambda n: (names[n].size, n))
        rank = np.random.default_rng(800).permutation(800)
        weights = 1.0 / np.arange(1, 801) ** 0.99
        share = weights / weights.sum() * 512
        for r, i in enumerate(rank.tolist()):
            assert abs(g.gets.count(order[i]) - share[r]) < 1
    _, _, other = _plan(seed + 1)
    for a, b in zip(window, other):
        assert sorted(a.gets) == sorted(b.gets) and a.gets != b.gets


def test_one_get_version_reads_the_version_before_as_it_did():
    t = Traffic(TREE, dict(WEEKLY, get_versions=1), 3)
    u = Traffic(TREE, WEEKLY, 3)
    assert [g.gets for g in t.groups(0, 3)] == [g.gets for g in u.groups(0, 3)]
    with pytest.raises(ValueError, match="get_versions"):
        Traffic(TREE, dict(WEEKLY, get_versions=0), 3)


def test_restore_cell_sets_up_four_versions_and_reads_them(monkeypatch):
    """Weekly backups read back over the last 4 weeks, one flush group a
    week, run end to end at a CPU size."""
    import harness
    from tiny import run_tiny

    seen = []
    ingest = harness._ingest

    def record(svc, group):
        seen.append((group.version, list(group.gets)))
        return ingest(svc, group)

    monkeypatch.setattr(harness, "_ingest", record)
    r = run_tiny("file-backup.weekly", traffic={
        "group_max_objects": 256, "group_max_bytes": None,
        "gets_per_group": 64, "get_versions": 4})
    assert r["correct"], r["checks"]
    versions = [v for v, _ in seen]
    first = versions.index(4)
    assert sorted(set(versions[:first])) == [0, 1, 2, 3]
    assert versions[:first] == sorted(versions[:first])
    gets = [n for v, names in seen[first:] for n in names]
    assert len(gets) == r["checks"]["gets_differ"]["of"] > 0
    for v, names in seen[first:]:
        assert {n.split("/", 1)[0] for n in names} <= {
            f"week-{j:03d}" for j in range(v - 4, v)}
