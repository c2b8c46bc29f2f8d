"""The reader of ``decode_kv_blocks_per_step``: live KV blocks over the
decode kernel's live grid steps, both program counters over the window.
It reads nothing from a program that does not count the kernel's steps,
and P or less through the harness on the tiny cell."""
import time

import pytest

from bench.lib import registry, view

NAME = "decode_kv_blocks_per_step"


def _run(counters):
    return view.RunView(None, None, None, [], counters, None, None,
                        0.0, 0.0, [], 0, {})


def _read(run):
    return registry.layer_metric(NAME).read(run)


@pytest.mark.parametrize("live, steps, want", [
    (43, 5, 8.6), (160, 10, 16.0), (29, 29, 1.0)])
def test_reads_live_blocks_per_live_step(live, steps, want):
    r = _run({"decode_kv_blocks_live": live, "decode_kv_blocks_passed": 200,
              "decode_kv_steps_live": steps, "compiles": 0})
    assert _read(r) == pytest.approx(want)


@pytest.mark.parametrize("counters", [
    {"decode_kv_blocks_live": 43, "decode_kv_blocks_passed": 200},
    {"decode_kv_blocks_live": 0, "decode_kv_steps_live": 0},
    {}], ids=["no-steps-counter", "no-decode", "no-counters"])
def test_reads_nothing_without_live_steps(counters):
    """The program before the grouped decode walk counts live and passed
    blocks but no kernel steps; a window with no decode has no steps."""
    assert _read(_run(counters)) is None


def test_tiny_run_reads_at_most_the_tables_width(tmp_path, monkeypatch):
    """A traced run of the tiny cell on the CPU, through the harness:
    its 8-block tables cap the decode kernel's group at 8 blocks."""
    from bench.lib import harness, peaks
    from bench.tests import tiny
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    w = tiny.make(tmp_path)
    bench = registry.benchmark(registry.BENCH.parent)
    r = harness.run(w["closed"], 2**31 + 41, 1.0, True, time.perf_counter(),
                    bench["end_to_end"], bench["per_layer"], base=tmp_path)
    assert 1 < r["metrics"][NAME]["value"] <= 8
    assert r["correct"] is True
