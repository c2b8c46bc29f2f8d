"""The trace reduction, on a trace recorded on a TPU v5e: three seconds of
the qwen3-1.7b long-prompt cell (an early, overloaded rate)."""
from pathlib import Path

import pytest

from bench.lib import hlo, view, xtrace
from bench.lib.peaks import peak

DATA = Path(__file__).parent / "data" / "qwen3-long-prompt.xplane.pb.gz"


@pytest.fixture(scope="module")
def run():
    t = xtrace.Trace.load(str(DATA), view.HOST_SPANS)
    steps = t.spans["bench.step"]

    class Run:                     # the parts of a RunView the readers use
        trace, plane = t, "/device:TPU:0"
        trace_lo, trace_hi = t.lo, t.hi
        peaks = peak("TPU v5 lite")
    Run.steps = steps
    Run.ops_in_window = lambda: view.RunView.ops_in_window(Run)
    Run.window_s = lambda: view.RunView.window_s(Run)
    Run.busy_s = lambda: view.RunView.busy_s(Run)
    return Run


def test_planes_and_spans(run):
    assert list(run.trace.ops) == ["/device:TPU:0"]
    assert len(run.steps) == 7 and len(run.trace.spans["paged_step"]) == 7
    assert len(run.trace.ops[run.plane]) == 49874


def test_busy_within_window(run):
    """The window is the trace's own extent, which holds every step."""
    w, b = run.window_s(), run.busy_s()
    assert w == pytest.approx(3.039546062, abs=1e-6)
    assert run.trace_lo <= run.steps[0][0] and run.steps[-1][1] <= run.trace_hi
    assert 0.9 * w < b <= w


def test_union_length():
    assert xtrace.union_length([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == 4
    assert xtrace.union_length([]) == 0


def test_idle_gaps_and_covering():
    ops = [xtrace.Op("a", 1, 2), xtrace.Op("b", 4, 5)]
    assert xtrace.idle_gaps(ops, 0, 6) == [(2, 4), (0, 1), (5, 6)]
    spans = {"outer": [(0, 10)], "inner": [(3, 5)]}
    assert xtrace.covering(spans, 3.5) == "inner"
    assert xtrace.covering(spans, 7) == "outer"
    assert xtrace.covering(spans, 11) is None


def test_w8a8_launches_parse(run):
    ops = [hlo.parse(o.name) for o in run.ops_in_window()]
    w8 = [p for p in ops if p is not None and p.base == "w8a8_matmul"]
    assert len(w8) == 2744
    shapes = {(p.args[0][1], p.args[1][1]) for p in w8}
    assert ((128, 2048), (2048, 1024)) in shapes      # prefill chunk, wk
    assert ((16, 6144), (6144, 2048)) in shapes       # decode batch, w_out
    ops_, nbytes = hlo.w8a8_cost(next(p for p in w8
                                      if p.args[0][1] == (128, 2048)
                                      and p.args[1][1] == (2048, 1024)))
    assert ops_ == 2 * 128 * 2048 * 1024


def test_roofline_is_a_share(run):
    r = view.kernel_roofline(run, "w8a8_matmul")
    assert 0 < r <= 100


def test_breakdown_names_kernels_and_pool_copies(run):
    b = view.RunView.breakdown(run)
    names = [n for n, _ in b["device_ops"]]
    assert names[0].startswith("paged_kv_decode_attention")
    assert any(n.startswith("copy.") for n in names)
    assert not any(" while " in n for n in names)
    assert len(b["idle_gaps"]) == view.TOP
    assert all(s >= 0 for _, s in b["idle_gaps"])
