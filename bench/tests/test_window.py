"""Window accounting: due-time TTFT, the cap counting as failed, gaps that
end in the window, tokens emitted in it, exact percentiles."""
import numpy as np
import pytest

from bench.lib.window import Record, lateness, percentile, stalls, summarize


@pytest.mark.parametrize("q", [0, 10, 50, 90, 95, 100])
def test_percentile_is_exact(q):
    v = np.random.default_rng(1).exponential(size=501)
    assert percentile(v, q) == pytest.approx(np.percentile(v, q), abs=1e-12)


def test_ttft_runs_from_due_time():
    recs = [Record(0, start=10.0, added=10.5, tokens=[11.0, 11.1]),
            Record(1, start=10.2, added=10.5, tokens=[12.2])]
    s = summarize(recs, 10.0, 20.0)
    assert s["attempted"] == 2 and s["failed"] == 0
    assert s["ttft_p50_ms"] == pytest.approx(1500.0)
    assert lateness(recs, 10.0, 20.0)["max_ms"] == pytest.approx(500.0)


def test_requests_outside_window_do_not_count():
    recs = [Record(0, start=9.9, tokens=[10.5]),
            Record(1, start=20.0, tokens=[20.5]),
            Record(2, start=15.0, tokens=[15.25])]
    s = summarize(recs, 10.0, 20.0)
    assert s["attempted"] == 1
    assert s["ttft_p50_ms"] == pytest.approx(250.0)


def test_cut_request_is_failed_and_above_every_percentile():
    recs = [Record(i, start=10.0 + i, tokens=[10.0 + i + 0.1])
            for i in range(9)]
    recs.append(Record(9, start=10.5, tokens=[]))      # never served
    s = summarize(recs, 10.0, 20.0, t_stop=10.6)
    assert s["failed"] == 1 and s["attempted"] == 10
    assert s["ttft_p90_ms"] > 100.0                    # the tail sees it
    assert s["ttft_p50_ms"] == pytest.approx(100.0)


def test_all_cut_reads_the_wait():
    recs = [Record(0, start=10.0, tokens=[]), Record(1, start=11.0)]
    s = summarize(recs, 10.0, 20.0, t_stop=30.0)
    assert s["failed"] == 2
    assert s["ttft_p50_ms"] == pytest.approx(20000.0)


def test_gaps_and_tokens_in_window():
    recs = [Record(0, start=5.0, tokens=[9.0, 10.5, 11.0, 20.5])]
    s = summarize(recs, 10.0, 20.0)
    # gaps ending inside: 9.0->10.5 and 10.5->11.0; 11.0->20.5 ends after
    assert s["gaps"] == 2
    assert s["itl_p50_ms"] == pytest.approx(1000.0)
    assert s["tokens_in_window"] == 2
    assert s["output_tokens_s"] == pytest.approx(0.2)


def test_stalls_name_the_longest_steps():
    steps = [(10.0, 10.2, False, 0), (10.2, 11.2, True, 7),
             (11.3, 11.5, False, 1), (25.0, 25.2, False, 0)]
    gcs = [(10.5, 10.6, 2), (30.0, 30.1, 0)]
    s = stalls(steps, gcs, 10.0, 20.0, top=2)
    assert s["steps"] == 3 and s["prefill_steps"] == 1
    assert s["longest_steps"][0] == [0.2, 1000.0, True, 7]
    assert s["switches"] == 8
    assert s["between_steps_max_ms"] == pytest.approx(100.0)
    assert s["gc"]["n"] == 1 and s["gc"]["full"] == 1
    assert s["gc"]["max_ms"] == pytest.approx(100.0)
