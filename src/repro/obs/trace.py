"""Low-overhead serving tracer: bounded ring buffer + Chrome-trace export.

The serving stack's control plane is host-side Python; answering "where did
this request's time go?" needs per-phase spans, not flat counters.  This
module is the single timing authority for the hot path:

  * ``clock()`` — the monotonic clock (``time.perf_counter``) every serving
    module reads *through this module* (``tools/check_obs.py`` statically
    bans direct ``perf_counter`` calls in the scoped hot-path modules, so
    timing semantics can never silently fork).
  * :class:`Tracer` — a bounded ring buffer (``collections.deque(maxlen)``)
    of :class:`Span` records.  Recording is O(1) host work: one clock read
    plus a deque append; the buffer drops the *oldest* spans under pressure
    so a long run keeps its most recent window.
  * ``NULL_TRACER`` — the disabled singleton.  Engines default to it, every
    record method is a no-op, and the hot path pays a single attribute
    branch (``if tracer.enabled``) before building any event arguments.
  * ``Tracer.export_chrome_trace(path)`` — Chrome-trace/Perfetto JSON: one
    process (pid) per replica track, thread (tid) 0 for the scheduler's
    phase spans and tid ``lane + 1`` for per-request slot events.  Open the
    file at https://ui.perfetto.dev or chrome://tracing.
  * ``validate_chrome_trace(obj)`` — the schema check the benchmark gate
    and the tier-1 tests share.

Span taxonomy (``SCHED_SPANS``), one set per engine step, every span
tagged ``step=<ordinal>``: ``schedule`` (host admission + scheduling,
holding ``stage``: the step's host arrays built and put on the device),
``device_step`` (async dispatch of the fused jitted step), ``consume``
(holding ``device_wait``: the host blocked on device results, ``publish``:
prefix-index publishing, and ``retire``: the rest of consume's host work),
``decode_step`` / ``spec_round`` / ``prefill_chunk`` (the step's work items,
spanning dispatch -> consumed).  Lifecycle events (``LIFECYCLE_EVENTS``)
mark request milestones on the slot tracks: ``enqueue`` -> ``admit`` ->
``prefix_hit``/``partial_hit`` -> ``first_token`` -> ``preempt``/``resume``
-> ``finish``, plus allocator traffic (``cow_copy``, ``demote``,
``promote``, ``draft_prefill``, ``draft_bootstrap``).

With ``Tracer(jax_profiler=True)`` every ``span()`` also opens a
``jax.profiler.TraceAnnotation`` over the same interval, so a device
profile captured around the run holds the phases on its own clock.
"""
from __future__ import annotations

import contextlib
import json
import os
import time
from collections import deque
from itertools import islice
from typing import Any, Dict, List, Optional

from repro.obs import compiles

# scheduler-phase span kinds (duration spans on the scheduler track)
SCHED_SPANS = ("schedule", "stage", "device_step", "consume",
               "device_wait", "publish", "retire", "spec_round",
               "prefill_chunk", "decode_step")
# instant lifecycle / allocator events (request slot tracks where lane >= 0)
LIFECYCLE_EVENTS = ("enqueue", "admit", "prefix_hit", "partial_hit",
                    "first_token", "preempt", "resume", "finish",
                    "cow_copy", "demote", "promote",
                    "draft_prefill", "draft_bootstrap")


def clock() -> float:
    """The serving stack's monotonic clock (seconds).  Every hot-path module
    times through this function so the tracer, the histograms and the
    engines' wall accounting can never disagree on the time base."""
    return time.perf_counter()


class Span:
    """One recorded event: a duration span (``dur`` in seconds) or an
    instant event (``dur is None``).  ``track`` is the replica index (one
    Chrome-trace process per replica), ``lane`` the request slot (-1 =
    the scheduler's own track)."""

    __slots__ = ("kind", "ts", "dur", "track", "lane", "args")

    def __init__(self, kind: str, ts: float, dur: Optional[float],
                 track: int, lane: int, args: Optional[Dict[str, Any]]):
        self.kind = kind
        self.ts = ts
        self.dur = dur
        self.track = track
        self.lane = lane
        self.args = args

    def __repr__(self):  # pragma: no cover - debugging aid
        d = f" dur={self.dur * 1e3:.3f}ms" if self.dur is not None else ""
        return f"<Span {self.kind} t={self.ts:.6f}{d} track={self.track}>"


class _SpanCtx:
    """Context manager for ``Tracer.span``: records the block as a span,
    tags it ``compiles=n`` where JAX built executables inside it, opens a
    profiler annotation over the same interval where the tracer asks for
    one, and with ``rest`` records the parts of the block that no span
    recorded inside it on the same track covers as ``rest`` spans."""

    __slots__ = ("tr", "kind", "track", "lane", "args", "rest", "ann",
                 "t0", "c0", "mark")

    def __init__(self, tr, kind, track, lane, args, rest, annotation):
        self.tr, self.kind, self.track, self.lane, self.args = \
            tr, kind, track, lane, args
        self.rest = rest
        self.ann = None
        if tr.jax_profiler:
            import jax
            self.ann = jax.profiler.TraceAnnotation(annotation or kind)

    def __enter__(self):
        tr = self.tr
        self.mark = len(tr.events) + tr.dropped
        self.c0 = compiles.count()
        if self.ann is not None:
            self.ann.__enter__()
        self.t0 = clock()
        return self

    def __exit__(self, *exc):
        t1 = clock()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        tr, args = self.tr, dict(self.args or {})
        n = compiles.count() - self.c0
        if n:
            args["compiles"] = n
        if self.rest is not None:
            inside = len(tr.events) + tr.dropped - self.mark
            held = sorted((e.ts, e.ts + e.dur)
                          for e in islice(reversed(tr.events), inside)
                          if e.dur is not None and e.track == self.track)
            t = self.t0
            for a, b in held + [(t1, t1)]:
                if a > t:
                    tr.add_span(self.rest, t, a - t, track=self.track,
                                lane=self.lane, **(self.args or {}))
                t = max(t, b)
        tr.add_span(self.kind, self.t0, t1 - self.t0, track=self.track,
                    lane=self.lane, **args)
        return False


class Tracer:
    """Bounded ring buffer of monotonic-clock spans/events."""

    enabled = True

    def __init__(self, capacity: int = 65536, jax_profiler: bool = False):
        """``capacity``: ring size — oldest spans are dropped beyond it.
        ``jax_profiler``: also open a ``jax.profiler.TraceAnnotation`` over
        each ``span()``, so the phases show up inside an XLA profile on the
        profiler's clock."""
        self.capacity = int(capacity)
        self.events: "deque[Span]" = deque(maxlen=self.capacity)
        self.jax_profiler = bool(jax_profiler)
        self.t0 = clock()
        self.dropped = 0                 # spans pushed out of the ring

    def __len__(self) -> int:
        return len(self.events)

    # -- recording ------------------------------------------------------------
    def event(self, kind: str, track: int = 0, lane: int = -1, **args) -> None:
        """Record an instant event at now."""
        if len(self.events) == self.capacity:
            self.dropped += 1
        self.events.append(Span(kind, clock(), None, track, lane,
                                args or None))

    def add_span(self, kind: str, t0: float, dur: float, track: int = 0,
                 lane: int = -1, **args) -> None:
        """Record a completed duration span that started at ``t0``."""
        if len(self.events) == self.capacity:
            self.dropped += 1
        self.events.append(Span(kind, t0, max(dur, 0.0), track, lane,
                                args or None))

    def span(self, kind: str, track: int = 0, lane: int = -1, *,
             annotation: Optional[str] = None, rest: Optional[str] = None,
             **args):
        """Context manager recording the wrapped block as a span.  With
        ``jax_profiler`` it also opens a profiler annotation named
        ``annotation`` (default: ``kind``) over the same interval.  ``rest``
        names the spans recorded over the parts of the block that no span
        recorded inside it (on ``track``) covers."""
        return _SpanCtx(self, kind, track, lane, args or None, rest,
                        annotation)

    def clear(self) -> None:
        self.events.clear()
        self.dropped = 0

    # -- introspection / export -----------------------------------------------
    def kinds(self) -> Dict[str, int]:
        """Event count per kind (for tests and the bench gate)."""
        out: Dict[str, int] = {}
        for e in self.events:
            out[e.kind] = out.get(e.kind, 0) + 1
        return out

    def to_chrome_trace(self) -> Dict[str, Any]:
        """Chrome-trace JSON object: one pid per replica track, tid 0 for
        scheduler-phase spans, tid ``lane + 1`` for request-slot events;
        timestamps in microseconds relative to tracer construction."""
        events: List[Dict[str, Any]] = []
        tracks = sorted({e.track for e in self.events})
        lanes = sorted({(e.track, e.lane) for e in self.events if e.lane >= 0})
        for t in tracks:
            events.append({"ph": "M", "name": "process_name", "pid": t,
                           "tid": 0, "args": {"name": f"replica {t}"}})
            events.append({"ph": "M", "name": "thread_name", "pid": t,
                           "tid": 0, "args": {"name": "scheduler"}})
        for t, lane in lanes:
            events.append({"ph": "M", "name": "thread_name", "pid": t,
                           "tid": lane + 1, "args": {"name": f"slot {lane}"}})
        for e in self.events:
            ev: Dict[str, Any] = {
                "name": e.kind, "cat": "serving",
                "ph": "X" if e.dur is not None else "i",
                "ts": (e.ts - self.t0) * 1e6,
                "pid": e.track,
                "tid": 0 if e.lane < 0 else e.lane + 1,
            }
            if e.dur is not None:
                ev["dur"] = e.dur * 1e6
            else:
                ev["s"] = "t"            # instant-event scope: thread
            if e.args:
                ev["args"] = {k: (v if isinstance(v, (int, float, str, bool))
                                  else str(v)) for k, v in e.args.items()}
            events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"dropped_spans": self.dropped,
                              "capacity": self.capacity}}

    def export_chrome_trace(self, path: str) -> Dict[str, Any]:
        """Write the Chrome-trace JSON to ``path`` and return the object."""
        obj = self.to_chrome_trace()
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(obj, f)
        return obj


class _NullTracer(Tracer):
    """Disabled tracer: every record method is a no-op, so instrumented
    code pays one ``enabled`` branch (or one empty method call)."""

    enabled = False
    _NULL_CTX = contextlib.nullcontext()

    def __init__(self):
        super().__init__(capacity=1)

    def event(self, kind, track=0, lane=-1, **args):
        pass

    def add_span(self, kind, t0, dur, track=0, lane=-1, **args):
        pass

    def span(self, kind, track=0, lane=-1, *, annotation=None, rest=None,
             **args):
        return self._NULL_CTX


NULL_TRACER = _NullTracer()


# -- schema validation ---------------------------------------------------------
_PHASES = {"X", "i", "M"}


def validate_chrome_trace(obj: Any) -> List[str]:
    """Validate a Chrome-trace JSON object (as loaded / as exported).
    Returns a list of human-readable schema errors — empty means valid.
    Checked: the ``traceEvents`` envelope, per-event required fields and
    types, non-negative ``dur`` on complete ("X") events."""
    errs: List[str] = []
    if not isinstance(obj, dict):
        return [f"trace root must be an object, got {type(obj).__name__}"]
    events = obj.get("traceEvents")
    if not isinstance(events, list):
        return ["trace is missing the traceEvents list"]
    if not events:
        errs.append("traceEvents is empty")
    for i, ev in enumerate(events):
        if len(errs) >= 20:
            errs.append("... further errors suppressed")
            break
        if not isinstance(ev, dict):
            errs.append(f"event {i}: not an object")
            continue
        where = f"event {i} ({ev.get('name', '?')})"
        if not isinstance(ev.get("name"), str):
            errs.append(f"{where}: missing/invalid name")
        ph = ev.get("ph")
        if ph not in _PHASES:
            errs.append(f"{where}: ph {ph!r} not in {sorted(_PHASES)}")
            continue
        for field in ("pid", "tid"):
            if not isinstance(ev.get(field), int):
                errs.append(f"{where}: missing/invalid {field}")
        if ph == "M":
            continue                     # metadata events carry no ts/dur
        if not isinstance(ev.get("ts"), (int, float)):
            errs.append(f"{where}: missing/invalid ts")
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                errs.append(f"{where}: X event needs dur >= 0, got {dur!r}")
    return errs
