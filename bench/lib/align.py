"""Map the engine's tracer spans onto the device trace's clock.

The tracer's spans are ``perf_counter`` seconds; the device trace's events
are nanoseconds on the profiler's own clock.  The engine opens a
``paged_step`` annotation over exactly the interval of each ``device_step``
span (``Tracer(jax_profiler=True)``), so the two clocks meet there: the
``device_step`` spans from the window's start, in order, pair with the
``paged_step`` annotations of the trace, and one offset maps the first onto
the second.  The pairing holds where every pair's start and end agree
under that offset (and so do their durations) within ``TOL_NS``.
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Sequence, Tuple

TOL_NS = 100_000.0


def fit(spans: Sequence, annotations: List[Tuple[float, float]],
        tol_ns: float = TOL_NS) -> Optional[Tuple[float, float, list]]:
    """Pair the ``device_step`` spans of ``spans`` (tracer spans, from the
    window's start) with ``annotations`` (``(start, end)`` ns of the trace's
    ``paged_step``s) in order.  -> ``(offset_ns, worst_residual_ns,
    paired_spans)``, where a span at ``t`` seconds sits at ``t * 1e9 +
    offset_ns`` on the trace's clock, or None where there is nothing to
    pair or a pair disagrees by more than ``tol_ns``."""
    steps = sorted((s for s in spans if s.kind == "device_step"),
                   key=lambda s: s.ts)[:len(annotations)]
    if not steps or len(steps) < len(annotations):
        return None
    offset = statistics.median(a - s.ts * 1e9
                               for s, (a, _) in zip(steps, annotations))
    worst = 0.0
    for s, (a, b) in zip(steps, annotations):
        lo = s.ts * 1e9 + offset
        hi = (s.ts + s.dur) * 1e9 + offset
        worst = max(worst, abs(a - lo), abs(b - hi))
    if worst > tol_ns:
        return None
    return offset, worst, steps


def on_trace(span, offset_ns: float) -> Tuple[float, float]:
    """A tracer span's interval in the trace's nanoseconds."""
    a = span.ts * 1e9 + offset_ns
    return a, a + span.dur * 1e9


def idle_by_kind(run, kinds: Sequence[str]
                 ) -> Optional[Tuple[Dict[str, float], int]]:
    """Device-idle nanoseconds inside the spans of each of ``kinds`` over
    the steps whose dispatch the device trace holds, and the number of
    those steps; None where the run has no device plane or its spans do
    not align with the trace."""
    if run.plane is None:
        return None
    fitted = fit(run.spans, run.trace.spans.get("paged_step", []))
    if fitted is None:
        return None
    offset, _, paired = fitted
    traced = {s.args["step"] for s in paired}
    idle = {k: 0.0 for k in kinds}
    for s in run.spans:
        if s.kind in idle and s.args and s.args.get("step") in traced:
            a, b = on_trace(s, offset)
            idle[s.kind] += (b - a) - run.trace.busy(run.plane, a, b)
    return idle, len(traced)
