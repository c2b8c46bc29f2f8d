"""What traffic generators share.

A mix is a data file of parameters (``bench/traffic/<mix>.json``) that
names its generator, ``bench/generators/<generator>.py``.  A generator
module has ``make(mix, seed, vocab)``, which returns an object with

  * ``first_wave()``: the requests sent when the load starts;
  * ``after(index)``: the requests sent when request ``index`` has its
    last token (a closed loop's next request, a session's next turn);
  * ``arrivals()``: an iterator of requests due at ``Spec.due_s`` seconds
    after the load starts, in order (an open loop), possibly empty.

The harness (``lib/harness.py`` ``serve``) drives any such object; a new
kind of traffic is a new generator file and a mix that names it.  The
pieces below keep every seed's work the same: stratified quantiles of a
distribution, dealt from a deck that each seed shuffles.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np

DECK = 1024


@dataclass
class Spec:
    """One request as generated: its prompt, its output length and, in an
    open loop, the time it is due (seconds after the load starts)."""
    index: int
    prompt: np.ndarray
    max_new_tokens: int
    due_s: float = 0.0


def _norm_ppf(p: np.ndarray) -> np.ndarray:
    """Inverse standard normal CDF (Acklam's rational approximation,
    relative error under 1.2e-9), so that no scipy is needed."""
    a = (-3.969683028665376e+01, 2.209460984245205e+02, -2.759285104469687e+02,
         1.383577518672690e+02, -3.066479806614716e+01, 2.506628277459239e+00)
    b = (-5.447609879822406e+01, 1.615858368580409e+02, -1.556989798598866e+02,
         6.680131188771972e+01, -1.328068155288572e+01)
    c = (-7.784894002430293e-03, -3.223964580411365e-01, -2.400758277161838e+00,
         -2.549732539343734e+00, 4.374664141464968e+00, 2.938163982698783e+00)
    d = (7.784695709041462e-03, 3.224671290700398e-01, 2.445134137142996e+00,
         3.754408661907416e+00)
    p = np.asarray(p, np.float64)
    out = np.empty_like(p)
    lo, hi = p < 0.02425, p > 1 - 0.02425
    mid = ~(lo | hi)
    q = np.sqrt(-2 * np.log(p[lo]))
    out[lo] = ((((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
                + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1))
    q = np.sqrt(-2 * np.log(1 - p[hi]))
    out[hi] = -((((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
                 + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1))
    q = p[mid] - 0.5
    r = q * q
    out[mid] = ((((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r
                 + a[5]) * q / (((((b[0] * r + b[1]) * r + b[2]) * r + b[3])
                                 * r + b[4]) * r + 1))
    return out


def lengths(dist: dict, u: np.ndarray) -> np.ndarray:
    """Lengths at quantiles ``u`` of a clipped lognormal
    ``{"median", "sigma", "min", "max"}``."""
    x = dist["median"] * np.exp(dist["sigma"] * _norm_ppf(u))
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


class Deck:
    """An endless stream of a fixed multiset of values, each pass shuffled
    by ``rng``."""

    def __init__(self, values: np.ndarray, rng: np.random.Generator):
        self.values, self.rng = np.asarray(values), rng
        self.order: List[int] = []

    def draw(self):
        if not self.order:
            self.order = list(self.rng.permutation(len(self.values)))
        return self.values[self.order.pop()]


def residual_lengths(dist: dict, n: int, rng: np.random.Generator
                     ) -> np.ndarray:
    """Output lengths still to come for ``n`` requests caught mid-flight:
    a running request is drawn with probability in proportion to its length
    (length bias), and is a uniform share of the way through it."""
    grid = lengths(dist, strata(DECK))
    cdf = np.cumsum(grid) / grid.sum()
    full = grid[np.searchsorted(cdf, strata(n))]
    rest = np.ceil(full * strata(n)[rng.permutation(n)]).astype(np.int64)
    return np.maximum(rest, 1)[rng.permutation(n)]
