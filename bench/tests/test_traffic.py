"""The clipped-lognormal generator: seeded, the same work for every seed,
and a closed loop that starts in steady state."""
import numpy as np
import pytest

from bench.lib import registry
from bench.lib.traffic import lengths, residual_lengths, strata

MIXES = ["batch-decode", "long-prompt"]


def _make(mix, seed, vocab):
    return registry.generator(mix["generator"]).make(mix, seed, vocab)


def _draw(mix, seed, n=64):
    t = _make(mix, seed, vocab=151936)
    if mix["loop"] == "closed":
        specs = t.first_wave() + [t.after(i)[0] for i in range(n)]
    else:
        it = t.arrivals()
        specs = [next(it) for _ in range(n)]
    return specs


@pytest.mark.parametrize("name", MIXES)
def test_seed_repeats_exactly(name):
    mix = registry.traffic(name)
    a, b = _draw(mix, 2**31 + 5), _draw(mix, 2**31 + 5)
    assert [s.max_new_tokens for s in a] == [s.max_new_tokens for s in b]
    assert [s.due_s for s in a] == [s.due_s for s in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_seeds_differ(name):
    mix = registry.traffic(name)
    a, b = _draw(mix, 1), _draw(mix, 2)
    assert [len(s.prompt) for s in a] != [len(s.prompt) for s in b]
    assert not np.array_equal(a[0].prompt[:16], b[0].prompt[:16])


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_sizes(name):
    """One pass through the deck is the same multiset for any seed."""
    mix = dict(registry.traffic(name), deck=32)
    if mix["loop"] == "closed":
        mix["clients"] = 8

    def deck(seed):
        t = _make(mix, seed, vocab=1000)
        return (sorted(t.prompt.draw() for _ in range(32)),
                sorted(t.output.draw() for _ in range(32)))
    assert deck(3) == deck(2**31 + 77)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_within_clip_and_median(name):
    mix = registry.traffic(name)
    for key in ("prompt", "output"):
        d = mix[key]
        x = lengths(d, strata(1024))
        assert x.min() >= d["min"] and x.max() <= d["max"]
        assert abs(np.median(x) - d["median"]) <= 1


def test_residual_first_draw_is_length_biased():
    """A request caught mid-flight has, on average, E[L^2] / (2 E[L]) still
    to come (the inspection paradox), not E[L] / 2."""
    d = registry.traffic("batch-decode")["output"]
    rest = residual_lengths(d, 4096, np.random.default_rng(0))
    grid = lengths(d, strata(1024)).astype(np.float64)
    want = (grid ** 2).mean() / (2 * grid.mean())
    assert rest.min() >= 1 and rest.max() <= d["max"]
    assert abs(rest.mean() - want) / want < 0.03
    assert rest.mean() > 0.5 * grid.mean() * 1.05


def test_open_loop_rate():
    mix = dict(registry.traffic("long-prompt"), loop="open", rate_per_s=0.5,
               deck=1024)
    t = _make(mix, 9, vocab=1000)
    it = t.arrivals()
    due = [next(it).due_s for _ in range(2048)]
    rate = len(due) / due[-1]
    assert abs(rate - mix["rate_per_s"]) / mix["rate_per_s"] < 0.02
    assert all(b > a for a, b in zip(due, due[1:]))
