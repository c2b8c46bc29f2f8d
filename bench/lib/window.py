"""Window accounting: what the end-to-end metrics are taken over.

A request counts if it was due (open loop) or sent (closed loop) inside the
window ``[t0, t1)``; its time to first token runs from that moment.  A
token gap counts if it ends inside the window.  ``output_tokens_s`` counts
the tokens emitted inside the window over the window's length.  A counted
request that has no first token when the serving after the window stops
(at the cap) is missing: it is failed, and it sits above every percentile.

Percentiles are exact over the samples (linear interpolation between order
statistics), never read from histogram buckets.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np


@dataclass
class Record:
    """What the host saw of one request: when it was due or sent, when it
    was handed to the engine, and when each output token came back."""
    index: int
    start: float                 # due (open loop) or sent (closed loop)
    added: float = 0.0           # handed to the engine
    prompt_len: int = 0
    max_new: int = 0
    tokens: List[float] = field(default_factory=list)   # emission times

    @property
    def first(self) -> Optional[float]:
        return self.tokens[0] if self.tokens else None


def percentile(values, q: float) -> float:
    """Exact ``q``-th percentile (0..100) by linear interpolation."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("no samples")
    pos = (v.size - 1) * q / 100.0
    lo = int(np.floor(pos))
    hi = min(lo + 1, v.size - 1)
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


def summarize(records: List[Record], t0: float, t1: float,
              t_stop: Optional[float] = None) -> Dict:
    """End-to-end metrics of the window ``[t0, t1)``; serving stopped at
    ``t_stop`` (a request still without its first token then is missing,
    and counts as the longest wait of any)."""
    counted = [r for r in records if t0 <= r.start < t1]
    ttft = [r.first - r.start for r in counted if r.first is not None]
    missing = len(counted) - len(ttft)
    if missing:
        waited = [(t_stop if t_stop is not None else t1) - r.start
                  for r in counted if r.first is None]
        ttft = ttft + [max(ttft + waited)] * missing
    gaps = [b - a for r in records for a, b in zip(r.tokens, r.tokens[1:])
            if t0 <= b < t1]
    emitted = sum(1 for r in records for t in r.tokens if t0 <= t < t1)
    out = {"attempted": len(counted), "failed": missing,
           "output_tokens_s": emitted / (t1 - t0),
           "tokens_in_window": emitted, "gaps": len(gaps)}
    if ttft:
        out.update(ttft_p50_ms=1e3 * percentile(ttft, 50),
                   ttft_p90_ms=1e3 * percentile(ttft, 90))
    if gaps:
        out.update(itl_p50_ms=1e3 * percentile(gaps, 50),
                   itl_p95_ms=1e3 * percentile(gaps, 95))
    return out


def lateness(records: List[Record], t0: float, t1: float) -> Dict:
    """How late the generator handed counted requests to the engine."""
    late = [r.added - r.start for r in records if t0 <= r.start < t1]
    if not late:
        return {"n": 0}
    return {"n": len(late), "p50_ms": 1e3 * percentile(late, 50),
            "p99_ms": 1e3 * percentile(late, 99), "max_ms": 1e3 * max(late)}


def stalls(steps: List[tuple], collections: List[tuple], t0: float,
           t1: float, top: int = 5) -> Dict:
    """Where the window's wall time went between and inside the engine's
    steps: ``steps`` holds ``(start, end, prefilled, switches)`` of each
    step call, ``switches`` the process's involuntary context switches
    during it (the host taking the CPU away), and ``collections``
    ``(start, end, generation)`` of each garbage collection.  A stall that
    every running request sees once is too rare for a percentile of the
    token gaps and still costs throughput."""
    inside = [s for s in steps if t0 <= s[0] < t1]
    if not inside:
        return {"steps": 0}
    walls = [s[1] - s[0] for s in inside]
    loop = [b[0] - a[1] for a, b in zip(inside, inside[1:])]
    longest = sorted(inside, key=lambda s: s[0] - s[1])[:top]
    gcs = [c for c in collections if t0 <= c[0] < t1]
    return {"steps": len(inside),
            "prefill_steps": sum(1 for s in inside if s[2]),
            "step_p50_ms": 1e3 * percentile(walls, 50),
            "longest_steps": [[round(a - t0, 3), round(1e3 * (b - a), 3), p, n]
                              for a, b, p, n in longest],
            "switches": sum(s[3] for s in inside),
            "between_steps_max_ms": 1e3 * max(loop) if loop else 0.0,
            "between_steps_sum_ms": 1e3 * sum(loop),
            "gc": {"n": len(gcs),
                   "full": sum(1 for c in gcs if c[2] == 2),
                   "sum_ms": 1e3 * sum(b - a for a, b, _ in gcs),
                   "max_ms": 1e3 * max((b - a for a, b, _ in gcs),
                                       default=0.0)}}
