"""The readers of the step's phases and the program's counters, on a
synthetic run: two steps with known spans, device ops and counters; and
the same run as a program without the phase split and counters leaves,
where every one of them finds nothing to read."""
import pytest

from bench.lib import align, registry, view, xtrace
from repro.obs.trace import Span

NEW = ("stage_ms", "device_wait_ms", "retire_ms", "host_exposed_ms",
       "decode_kv_live_share", "step_compiles")
PLANE = "/device:TPU:0"
OFFSET = 5e7                    # the profiler clock runs 50 ms ahead
MS = 1e-3


def _span(kind, a_ms, b_ms, **args):
    return Span(kind, 100.0 + a_ms * MS, (b_ms - a_ms) * MS, 0, -1,
                args or None)


def _step(n, at):
    """One step at ``at`` ms: schedule 1 ms holding a 0.25 ms stage,
    dispatch 0.1 ms, consume 8.9 ms = retire 0.1 + device_wait 7.8 +
    publish 0.5 + retire 0.5."""
    s = lambda kind, a, b: _span(kind, at + a, at + b, step=n)
    return [s("stage", 0.5, 0.75), s("schedule", 0.0, 1.0),
            s("device_step", 1.0, 1.1),
            s("retire", 1.1, 1.2), s("device_wait", 1.2, 9.0),
            s("publish", 9.0, 9.5), s("retire", 9.5, 10.0),
            s("consume", 1.1, 10.0), s("decode_step", 1.0, 10.0)]


def _ns(ms):
    return (100.0 + ms * MS) * 1e9 + OFFSET


def _run(split=True, counters=True):
    spans = _step(1, 0.0) + _step(2, 10.0)
    # the device runs from 1.05 ms to 9.05 ms into each step
    ops = [xtrace.Op("fusion.1", _ns(at + 1.05), _ns(at + 9.05))
           for at in (0.0, 10.0)]
    dev = [s for s in spans if s.kind == "device_step"]
    ann = [align.on_trace(s, OFFSET) for s in dev]
    if not split:                 # the program before the phase split
        spans = [s for s in spans
                 if s.kind in ("schedule", "device_step", "consume",
                               "decode_step")]
        for s in spans:
            s.args = None
    trace = xtrace.Trace({PLANE: ops}, {"paged_step": ann}, _ns(0), _ns(20))
    c = {"decode_kv_blocks_live": 43, "decode_kv_blocks_passed": 200,
         "compiles": 0} if counters else {}
    return view.RunView(None, None, None, spans, c, trace, PLANE,
                        trace.lo, trace.hi, [], 0, {})


def _read(name, run):
    return registry.layer_metric(name).read(run)


def test_phase_means_per_step():
    r = _run()
    assert _read("stage_ms", r) == pytest.approx(0.25)
    assert _read("device_wait_ms", r) == pytest.approx(7.8)
    assert _read("retire_ms", r) == pytest.approx(8.9 - 7.8)


def test_host_exposed_reads_idle_inside_the_host_phases():
    """Per step: schedule 1.0 ms idle, dispatch 0.05, retire 0 + 0.5,
    publish 0.45 (the device ends 0.05 ms into it): 2.0 ms; the idle time
    inside device_wait is left out."""
    assert _read("host_exposed_ms", _run()) == pytest.approx(2.0, abs=1e-6)


def test_counter_readers():
    r = _run()
    assert _read("decode_kv_live_share", r) == pytest.approx(21.5)
    assert _read("step_compiles", r) == 0


def test_align_fit_recovers_the_offset():
    r = _run()
    offset, worst, paired = align.fit(r.spans, r.trace.spans["paged_step"])
    assert offset == pytest.approx(OFFSET, abs=1.0)
    assert worst < 1.0 and len(paired) == 2


def test_align_refuses_pairs_that_disagree():
    r = _run()
    a, b = r.trace.spans["paged_step"][1]
    shifted = [r.trace.spans["paged_step"][0], (a + 3e5, b + 3e5)]
    assert align.fit(r.spans, shifted) is None
    assert align.fit(r.spans, []) is None


@pytest.mark.parametrize("name", NEW)
def test_without_the_split_and_counters_nothing_is_read(name):
    assert _read(name, _run(split=False, counters=False)) is None


def test_traced_tiny_run_reports_the_phase_metrics(tmp_path, monkeypatch):
    """A whole traced run of the tiny cell on the CPU, through the harness:
    the span and counter readers read; the device one finds no TPU plane
    and is left out."""
    import time

    from bench.lib import harness, peaks
    from bench.tests import tiny
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    w = tiny.make(tmp_path)
    bench = registry.benchmark(registry.BENCH.parent)
    r = harness.run(w["closed"], 2**31 + 29, 1.0, True, time.perf_counter(),
                    bench["end_to_end"], bench["per_layer"], base=tmp_path)
    m = {k: v["value"] for k, v in r["metrics"].items()}
    for name in ("stage_ms", "device_wait_ms", "retire_ms"):
        assert m[name] > 0, name
    assert 0 < m["decode_kv_live_share"] <= 100
    assert m["step_compiles"] == 0
    assert "host_exposed_ms" not in m
    assert r["correct"] is True
