"""Tracer ring buffer, disabled-singleton no-op contract, and Chrome-trace
export / schema validation."""
import json

import pytest

from repro.obs import (LIFECYCLE_EVENTS, NULL_TRACER, SCHED_SPANS, Span,
                       Tracer, clock, validate_chrome_trace)


def test_clock_is_monotonic():
    a = clock()
    b = clock()
    assert b >= a


def test_event_and_span_recording():
    tr = Tracer(capacity=16)
    tr.event("enqueue", track=0, lane=2, uid=7)
    t0 = clock()
    tr.add_span("schedule", t0, 0.001, track=0)
    with tr.span("consume", track=0, batch=3):
        pass
    assert len(tr) == 3
    kinds = tr.kinds()
    assert kinds == {"enqueue": 1, "schedule": 1, "consume": 1}
    ev = tr.events[0]
    assert ev.dur is None and ev.lane == 2 and ev.args == {"uid": 7}
    sp = tr.events[2]
    assert sp.dur is not None and sp.dur >= 0.0
    assert sp.args == {"batch": 3}


def test_negative_duration_clamps_to_zero():
    tr = Tracer(capacity=4)
    tr.add_span("schedule", clock(), -1e-3)
    assert tr.events[0].dur == 0.0


def test_ring_buffer_wraps_and_counts_drops():
    tr = Tracer(capacity=8)
    for i in range(20):
        tr.event("enqueue", uid=i)
    assert len(tr) == 8                      # bounded
    assert tr.dropped == 12                  # oldest 12 pushed out
    kept = [e.args["uid"] for e in tr.events]
    assert kept == list(range(12, 20))       # most recent window survives
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


def test_null_tracer_is_a_noop():
    assert NULL_TRACER.enabled is False
    before = len(NULL_TRACER)
    NULL_TRACER.event("enqueue", uid=1)
    NULL_TRACER.add_span("schedule", clock(), 0.001)
    with NULL_TRACER.span("consume", rest="retire"):
        pass
    with NULL_TRACER.span("device_step", annotation="paged_step"):
        pass
    assert len(NULL_TRACER) == before == 0
    # the disabled span is the one shared null context: nothing is built
    assert NULL_TRACER.span("stage", step=1) is NULL_TRACER.span("consume")


def test_enabled_flag_gates_argument_construction():
    # the hot path's contract: one attribute read decides everything
    tr = Tracer(capacity=4)
    assert tr.enabled is True
    assert NULL_TRACER.enabled is False


def test_annotate_without_profiler_is_null_context():
    """Without ``jax_profiler`` a span names an annotation but opens none:
    it records the span alone."""
    tr = Tracer(capacity=4)
    ctx = tr.span("device_step", annotation="paged_step", step=3)
    assert ctx.ann is None
    with ctx:
        pass
    assert tr.kinds() == {"device_step": 1}
    assert tr.events[0].args == {"step": 3}


def test_span_opens_a_profiler_annotation():
    """With ``jax_profiler`` each span holds a ``TraceAnnotation`` (the
    names it gives them are read back from a real profile in
    ``bench/tests/test_align.py``)."""
    import jax
    tr = Tracer(capacity=8, jax_profiler=True)
    for ctx in (tr.span("consume"),
                tr.span("device_step", annotation="paged_step")):
        assert isinstance(ctx.ann, jax.profiler.TraceAnnotation)
        with ctx:
            pass
    assert tr.kinds() == {"consume": 1, "device_step": 1}


def test_rest_fills_the_uncovered_parts_of_a_span():
    """``rest`` records the parts of a span that no span recorded inside
    it on the same track covers: the children and the rest tile it."""
    tr = Tracer(capacity=64)
    with tr.span("consume", track=0, rest="retire", step=5):
        with tr.span("device_wait", track=0, step=5):
            pass
        tr.event("finish", track=0, lane=1)          # instants hold nothing
        tr.add_span("other_track", clock(), 1.0, track=1)
        with tr.span("publish", track=0, step=5):
            pass
    spans = [e for e in tr.events if e.dur is not None and e.track == 0]
    consume = [e for e in spans if e.kind == "consume"]
    assert len(consume) == 1
    c = consume[0]
    inner = sorted((e.ts, e.ts + e.dur) for e in spans if e is not c)
    assert {e.kind for e in spans} == {"consume", "device_wait", "publish",
                                       "retire"}
    assert all(e.args == {"step": 5} for e in spans)
    # tiled: each piece starts where the last ended, first at the parent's
    # start and last at its end
    assert inner[0][0] == c.ts and inner[-1][1] == c.ts + c.dur
    for (_, b), (a, _) in zip(inner, inner[1:]):
        assert a == b


def test_span_counts_the_compiles_it_held():
    import jax
    import jax.numpy as jnp
    from repro.obs import compiles
    compiles.install()
    tr = Tracer(capacity=8)
    f = jax.jit(lambda x: x * 3 + 1)
    with tr.span("device_step", step=1):
        f(jnp.ones((7, 3))).block_until_ready()
    with tr.span("device_step", step=2):
        f(jnp.ones((7, 3))).block_until_ready()
    first, again = tr.events
    assert first.args["compiles"] >= 1
    assert again.args == {"step": 2}


def test_chrome_trace_export_round_trip(tmp_path):
    tr = Tracer(capacity=64)
    tr.event("enqueue", track=0, lane=0, uid=1)
    tr.event("admit", track=1, lane=3, uid=1)
    t0 = clock()
    tr.add_span("schedule", t0, 0.002, track=0)
    tr.add_span("prefill_chunk", t0, 0.004, track=0, lane=1, tokens=32)
    path = tmp_path / "trace.json"
    obj = tr.export_chrome_trace(str(path))
    assert validate_chrome_trace(obj) == []
    loaded = json.loads(path.read_text())
    assert validate_chrome_trace(loaded) == []
    assert loaded == obj

    evs = loaded["traceEvents"]
    data = [e for e in evs if e["ph"] != "M"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert len(data) == 4
    # one process per track, named metadata rows present
    assert {e["pid"] for e in data} == {0, 1}
    names = {(e["pid"], e["tid"], e["args"]["name"]) for e in meta
             if e["name"] in ("process_name", "thread_name")}
    assert (0, 0, "replica 0") in names
    assert (1, 4, "slot 3") in names         # tid = lane + 1
    # spans are X with dur, instants are i with scope
    by_name = {e["name"]: e for e in data}
    assert by_name["schedule"]["ph"] == "X"
    assert by_name["schedule"]["dur"] == pytest.approx(2000.0)
    assert by_name["schedule"]["tid"] == 0
    assert by_name["enqueue"]["ph"] == "i" and by_name["enqueue"]["s"] == "t"
    assert by_name["prefill_chunk"]["args"]["tokens"] == 32
    # timestamps are microseconds relative to tracer construction
    assert all(e["ts"] >= 0 for e in data)
    assert loaded["otherData"]["dropped_spans"] == 0


def test_non_json_args_are_stringified():
    tr = Tracer(capacity=4)
    tr.event("finish", key=b"\x01\x02")
    obj = tr.to_chrome_trace()
    ev = [e for e in obj["traceEvents"] if e["ph"] != "M"][0]
    assert isinstance(ev["args"]["key"], str)
    assert validate_chrome_trace(obj) == []


def test_validate_rejects_malformed_traces():
    assert validate_chrome_trace([]) != []               # wrong root type
    assert validate_chrome_trace({}) != []               # no traceEvents
    assert validate_chrome_trace({"traceEvents": []}) != []
    good = {"ph": "X", "name": "a", "pid": 0, "tid": 0, "ts": 0.0, "dur": 1.0}
    assert validate_chrome_trace({"traceEvents": [good]}) == []
    for mutation in (
        {"ph": "Q"},                                     # unknown phase
        {"name": None},                                  # bad name
        {"dur": -1.0},                                   # negative duration
        {"ts": None},                                    # missing timestamp
        {"pid": "0"},                                    # stringly pid
    ):
        bad = {**good, **mutation}
        assert validate_chrome_trace({"traceEvents": [bad]}) != [], mutation


def test_validate_caps_error_list():
    evs = [{"ph": "Q"} for _ in range(100)]
    errs = validate_chrome_trace({"traceEvents": evs})
    assert len(errs) <= 21
    assert errs[-1].startswith("...")


def test_span_taxonomy_is_declared():
    # the bench gate and docs key off these tuples — keep them in sync
    assert "prefill_chunk" in SCHED_SPANS and "spec_round" in SCHED_SPANS
    for k in ("enqueue", "first_token", "preempt", "demote", "cow_copy"):
        assert k in LIFECYCLE_EVENTS
    assert set(SCHED_SPANS).isdisjoint(LIFECYCLE_EVENTS)


def test_span_repr_smoke():
    s = Span("schedule", 0.0, 0.001, 0, -1, None)
    assert "schedule" in repr(s)
