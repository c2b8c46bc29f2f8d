"""Clipped-lognormal lengths, as a closed loop or with Poisson arrivals.

Mix keys: ``loop`` (``"closed"``: ``clients`` that each send their next
request when the last one finishes; ``"open"``: arrivals at
``rate_per_s``, due whether or not earlier requests are done), ``prompt``
and ``output`` (``{"median", "sigma", "min", "max"}``), and ``deck``.

Every seed gets the same multiset of sizes and gaps, in another order, so
that two seeds ask the same work of the system and differ only in which
request comes when and in the token ids:

  * a length distribution is sampled at the stratified quantiles
    ``(i + 0.5) / deck`` of the clipped lognormal, and each pass through
    that deck is shuffled by the seed;
  * open-loop gaps are the stratified quantiles of an exponential at
    ``rate_per_s``, shuffled the same way;
  * a closed loop starts each client in steady state: its first output
    length is the residual of a length-biased draw (the part of a running
    request still to come), on stratified quantiles too.

Prompts are token ids drawn from the seed, unique per request.
"""
from __future__ import annotations

from typing import Iterator, List

import numpy as np

from bench.lib.traffic import DECK, Deck, Spec, lengths, residual_lengths, strata


class Lognormal:
    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.vocab = mix, vocab
        self.rng = np.random.default_rng(seed)
        deck = mix.get("deck", DECK)
        self.prompt = Deck(lengths(mix["prompt"], strata(deck)), self.rng)
        self.output = Deck(lengths(mix["output"], strata(deck)), self.rng)
        self.count = 0
        self.closed = mix["loop"] == "closed"
        if mix["loop"] == "open":
            rate = float(mix["rate_per_s"])
            self.gap = Deck(-np.log1p(-strata(deck)) / rate, self.rng)
            self.t = 0.0
        elif not self.closed:
            raise ValueError(f"unknown loop {mix['loop']!r}")

    def _spec(self, new_tokens: int, due: float = 0.0) -> Spec:
        n = int(self.prompt.draw())
        toks = self.rng.integers(0, self.vocab, n).astype(np.int32)
        spec = Spec(self.count, toks, int(new_tokens), due)
        self.count += 1
        return spec

    def first_wave(self) -> List[Spec]:
        """Closed loop: one request per client, in steady state."""
        if not self.closed:
            return []
        n = int(self.mix["clients"])
        rest = residual_lengths(self.mix["output"], n, self.rng)
        return [self._spec(r) for r in rest]

    def after(self, index: int) -> List[Spec]:
        """Closed loop: the client whose request finished sends the next."""
        return [self._spec(self.output.draw())] if self.closed else []

    def arrivals(self) -> Iterator[Spec]:
        """Open loop: requests in the order they are due."""
        while not self.closed:
            self.t += float(self.gap.draw())
            yield self._spec(self.output.draw(), self.t)


make = Lognormal
