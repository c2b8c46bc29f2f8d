"""Shared NLL/logprob core: ONE implementation for every quality number.

The scorecard's central claim is comparability: the serving-path NLL (chunk
logits through the paged engine), the dense-forward reference NLL, and the
training-side ``benchmarks.common.eval_loss`` must all come from the same
math, so a quality delta is always attributable to the *runtime path*
(INT8/INT4 pool, frozen K scales, codec dequant) and never to a second
log-softmax implementation drifting on its own.

``gold_logprobs`` is therefore deliberately host-side numpy float64: applied
to bitwise-identical logits rows it returns bitwise-identical logprobs, which
is what lets the parity tests demand serving NLL == dense NLL *exactly* for
W8A8 single-chunk scoring (the chunk logits themselves are bitwise equal to
``forward_train``'s — verified property of ``forward_prefill_chunk``).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.transformer import forward_train


def gold_logprobs(logits, tokens) -> np.ndarray:
    """Per-position ``log P(tokens[i])`` under ``logits`` row ``i``.

    logits: (..., T, V) any float dtype (bf16 device arrays welcome);
    tokens: (..., T) ints.  Log-softmax runs in float64 on host — exact and
    deterministic, so equal logits always produce equal logprobs regardless
    of which engine produced them.
    """
    x = np.asarray(logits).astype(np.float64)
    t = np.asarray(tokens).astype(np.int64)
    m = x.max(axis=-1, keepdims=True)
    lse = m[..., 0] + np.log(np.exp(x - m).sum(axis=-1))
    gold = np.take_along_axis(x, t[..., None], axis=-1)[..., 0]
    return gold - lse


def mean_nll(logprobs) -> float:
    """Mean negative log-likelihood of a logprob array (nats/token)."""
    lp = np.asarray(logprobs, np.float64)
    return float(-lp.mean()) if lp.size else 0.0


def perplexity(nll: float) -> float:
    return float(np.exp(nll))


def batch_nll(logits, labels) -> float:
    """Mean NLL over a (B, S, V) logits / (B, S) labels batch — the
    training-side evaluation (``benchmarks.common.eval_loss``) routed
    through the same ``gold_logprobs`` core as the serving scorecard."""
    return mean_nll(gold_logprobs(logits, labels))


# jitted dense forwards, one per config (mirrors the scheduler's step cache)
_DENSE_FNS: Dict[ModelConfig, any] = {}


def dense_logits(params, cfg: ModelConfig, tokens) -> jax.Array:
    """(B, S) tokens -> (B, S, V) logits of one dense ``forward_train``."""
    fn = _DENSE_FNS.get(cfg)
    if fn is None:
        fn = jax.jit(lambda p, t: forward_train(p, t, cfg)[0])
        _DENSE_FNS[cfg] = fn
    return fn(params, tokens)


def dense_sequence_logprobs(params, cfg: ModelConfig, target,
                            score_from: int) -> np.ndarray:
    """Teacher-forced reference: ``log P(target[t] | target[:t])`` for every
    ``t in [score_from, S)`` from one dense ``forward_train`` pass.

    This is the oracle the serving scoring mode is tested against: row
    ``t - 1`` of the (B=1) train logits predicts token ``t``.  Requires
    ``score_from >= 1`` (the first token has no predecessor row).
    """
    t = np.asarray(target, np.int32)
    s = int(t.shape[-1])
    if not 1 <= score_from < s:
        raise ValueError(f"score_from={score_from} outside [1, {s})")
    logits = dense_logits(params, cfg, jnp.asarray(t)[None])
    rows = logits[0, score_from - 1:s - 1]
    return gold_logprobs(rows, t[score_from:])


def dense_score(params, cfg: ModelConfig, prompt, continuation) -> np.ndarray:
    """Dense-engine logprobs of ``continuation`` given ``prompt`` — the
    same contract as ``Request(score_tokens=...)`` through the paged
    engine, for baselines and parity tests."""
    prompt = np.asarray(prompt, np.int32)
    cont = np.asarray(continuation, np.int32)
    target = np.concatenate([prompt, cont], axis=-1)
    return dense_sequence_logprobs(params, cfg, target,
                                   int(prompt.shape[-1]))
