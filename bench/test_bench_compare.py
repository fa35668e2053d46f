"""``compare.numbers`` on hand-made trees: each round is judged from its
own start, a leaf the reference leaves still is not counted, and the
worst round is named."""
import numpy as np
import pytest

import compare


def leaves(tree):
    return sorted(tree.items())


def _tree(rng, scale=1.0):
    return {"a": scale * rng.standard_normal(64).astype(np.float32),
            "b": scale * rng.standard_normal((8, 8)).astype(np.float32),
            "c": scale * rng.standard_normal(16).astype(np.float32),
            "frozen": scale * rng.standard_normal(4).astype(np.float32)}


def _rounds(n, seed=0):
    """Program starts and the reference's ends of ``n`` rounds; the
    reference moves every leaf but ``frozen``, by steps of unequal size."""
    rng = np.random.default_rng(seed)
    starts, refs = [_tree(rng)], []
    for _ in range(n):
        step = _tree(rng, 0.01)
        step["c"] *= 0.1
        step["frozen"] *= 0
        refs.append({k: starts[-1][k] + step[k] for k in step})
        starts.append({k: v + 0.001 for k, v in refs[-1].items()})
    return starts[:-1], refs


def _scaled(starts, refs, factor):
    return [{k: s[k] + np.float32(factor) * (r[k] - s[k]) for k in s}
            for s, r in zip(starts, refs)]


@pytest.mark.parametrize("factor, reads", [(1.0, 0.0), (1.1, 0.1),
                                           (0.0, 1.0)])
def test_a_round_scaled_from_the_reference_reads_its_scale(factor, reads):
    """Equal to the reference reads 0, an update scaled by 1.1 reads 0.1,
    and a round that returns its state unchanged reads 1."""
    starts, refs = _rounds(1)
    got = compare.numbers(leaves, starts, _scaled(starts, refs, factor),
                          refs)
    assert set(got) == {"gap", "dist"}
    for name in got:
        assert got[name][0] == pytest.approx(reads, rel=1e-4, abs=1e-6)


def test_each_round_is_judged_from_its_own_start():
    """Rounds 1 and 3 equal the reference; round 2's update is scaled by
    1.1.  The worst round is the second, and its rounds do not add up."""
    starts, refs = _rounds(3)
    progs = _scaled(starts, refs, 1.0)
    progs[1] = _scaled(starts[1:2], refs[1:2], 1.1)[0]
    per = compare.by_round(leaves, starts, progs, refs)
    assert [rd["gap"][0] for rd in per] == pytest.approx([0, 0.1, 0],
                                                         abs=1e-5)
    got = compare.numbers(leaves, starts, progs, refs)
    assert got["gap"][0] == pytest.approx(0.1, rel=1e-4)
    assert got["gap"][1].startswith("round 2, ")
    assert got["dist"] == (pytest.approx(0.1, rel=1e-4), "round 2, all")


def test_a_leaf_the_reference_leaves_still_is_not_counted():
    """The program moves ``frozen``, which the reference leaves still in
    the first round: ``gap`` does not count it, ``dist`` sees it."""
    starts, refs = _rounds(1)
    progs = _scaled(starts, refs, 1.0)
    progs[0]["frozen"] = progs[0]["frozen"] + 1.0
    got = compare.numbers(leaves, starts, progs, refs)
    assert got["gap"][0] == 0.0
    assert got["dist"][0] > 1.0


def test_a_nan_round_is_the_worst():
    starts, refs = _rounds(2)
    progs = _scaled(starts, refs, 1.1)
    progs[1]["a"] = np.full_like(progs[1]["a"], np.nan)
    got = compare.numbers(leaves, starts, progs, refs)
    assert np.isnan(got["gap"][0]) and got["gap"][1] == "round 2, a"
    assert np.isnan(got["dist"][0])
