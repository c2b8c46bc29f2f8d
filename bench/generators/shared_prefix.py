"""Prompts that share documents: each is one of a few shared documents
followed by a unique tail, as a closed loop.

Mix keys: ``clients`` (each sends its next request when the last one
finishes), ``documents`` (how many), ``zipf`` (popularity exponent: the
document of rank r is asked about in proportion to r ** -zipf),
``document`` (``{"min", "max"}`` of the document lengths), ``tail`` and
``output`` (``{"median", "sigma", "min", "max"}`` clipped lognormals), and
``deck``.

Every seed gets the same sizes and popularities, so that two seeds ask
the same work of the system:

  * the documents' lengths are the stratified quantiles ``(i + 0.5) / n``
    of the uniform ``[min, max]``, dealt to the popularity ranks in one
    fixed order, centre out: the most asked document has the length just
    above the median, the next ones alternately below and above it, so
    the popularity-weighted length stays near the plain mean; their token
    ids are drawn once per seed;
  * which document a request asks about is dealt from a deck of ``deck``
    stratified quantiles of the Zipf distribution, each pass shuffled;
  * tails and outputs are stratified quantiles of their lognormals, dealt
    the same way, and a closed loop starts each client in steady state
    (``lib/traffic.residual_lengths``).

Tail token ids are drawn from the seed, unique per request.
"""
from __future__ import annotations

from typing import Iterator, List

import numpy as np

from bench.lib.traffic import Deck, Spec, lengths, residual_lengths, strata


class SharedPrefix:
    def __init__(self, mix: dict, seed: int, vocab: int):
        if mix["loop"] != "closed":
            raise ValueError(f"unknown loop {mix['loop']!r}")
        self.mix, self.vocab = mix, vocab
        self.rng = np.random.default_rng(seed)
        n = int(mix["documents"])
        lo, hi = mix["document"]["min"], mix["document"]["max"]
        sizes = np.rint(lo + strata(n) * (hi - lo)).astype(np.int64)
        k = np.arange(n)
        centre_out = np.where(k % 2 == 0, n // 2 + k // 2, n // 2 - (k + 1) // 2)
        self.docs = [self.rng.integers(0, vocab, int(s)).astype(np.int32)
                     for s in sizes[centre_out]]
        pop = np.arange(1, n + 1, dtype=np.float64) ** -float(mix["zipf"])
        cdf = np.cumsum(pop) / pop.sum()
        deck = int(mix["deck"])
        self.doc = Deck(np.minimum(np.searchsorted(cdf, strata(deck)), n - 1),
                        self.rng)
        self.tail = Deck(lengths(mix["tail"], strata(deck)), self.rng)
        self.output = Deck(lengths(mix["output"], strata(deck)), self.rng)
        self.count = 0

    def _spec(self, new_tokens: int) -> Spec:
        doc = self.docs[int(self.doc.draw())]
        tail = self.rng.integers(0, self.vocab, int(self.tail.draw()))
        prompt = np.concatenate([doc, tail.astype(np.int32)])
        spec = Spec(self.count, prompt, int(new_tokens))
        self.count += 1
        return spec

    def first_wave(self) -> List[Spec]:
        n = int(self.mix["clients"])
        rest = residual_lengths(self.mix["output"], n, self.rng)
        return [self._spec(r) for r in rest]

    def after(self, index: int) -> List[Spec]:
        return [self._spec(self.output.draw())]

    def arrivals(self) -> Iterator[Spec]:
        return iter(())


make = SharedPrefix
