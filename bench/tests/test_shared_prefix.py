"""The shared-prefix generator: every seed asks the same work of the
system (document lengths dealt to popularity ranks in one fixed order,
the same deck of ranks, tails and outputs), with its own token ids."""
import numpy as np

from bench.lib import registry


def _make(seed):
    mix = registry.traffic("shared-prefix")
    return registry.generator(mix["generator"]).make(mix, seed, 151936)


def test_document_lengths_follow_rank_not_seed():
    a, b = _make(3), _make(2**31 + 77)
    lens = [len(d) for d in a.docs]
    assert lens == [len(d) for d in b.docs]
    # centre out: the most asked document sits just above the median
    # length, the least asked at the shortest
    assert lens[0] == sorted(lens)[len(lens) // 2]
    assert lens[-1] == min(lens)
    assert not any(np.array_equal(x, y) for x, y in zip(a.docs, b.docs))


def test_popularity_weighted_length_near_the_mean():
    g = _make(5)
    lens = np.array([len(d) for d in g.docs], np.float64)
    draws = [int(g.doc.draw()) for _ in range(g.doc.values.size)]
    weighted = lens[draws].mean()
    assert abs(weighted - lens.mean()) < 0.05 * lens.mean()


def test_seed_repeats_exactly():
    a, b = _make(2**31 + 5), _make(2**31 + 5)
    pa = [s.prompt for s in a.first_wave() + a.after(0)]
    pb = [s.prompt for s in b.first_wave() + b.after(0)]
    assert all(np.array_equal(x, y) for x, y in zip(pa, pb))
