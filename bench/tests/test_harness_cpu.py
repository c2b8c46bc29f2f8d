"""A whole run of a tiny cell on the CPU, past the harness's look for a
chip: a sound run comes out correct, the int4 control comes out not
correct through the run's own decision, and a run with the timed path
broken underneath comes out not correct: a token altered where it is
produced, or a step that hands back the KV pool unchanged."""
import time

import pytest

from bench.lib import harness, registry
from bench.tests import tiny

SEED = 2**31 + 17


@pytest.fixture(scope="module")
def cells(tmp_path_factory):
    base = tmp_path_factory.mktemp("tiny")
    return base, tiny.make(base)


BENCH = registry.benchmark(registry.BENCH.parent)


def _run(cells, mix, trace=False, **kw):
    base, w = cells
    return harness.run(w[mix], SEED, 1.0, trace, time.perf_counter(),
                       BENCH["end_to_end"], BENCH["per_layer"], base=base,
                       **kw)


@pytest.mark.parametrize("mix", ["closed", "open"])
def test_sound_run_is_correct(cells, mix):
    r = _run(cells, mix)
    assert r["correct"] is True
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"itl_p50_ms", "itl_p95_ms",
                                 "output_tokens_s", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "checks"
    assert r["device"]["count"] >= 1


def test_traced_run_reads_the_program_metrics(cells, monkeypatch):
    """On the CPU the trace holds no TPU plane: the device metrics find
    nothing to read and are left out; the span and counter ones read (the
    CPU gets the v5e's peaks here, so that the harness runs on)."""
    from bench.lib import peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    r = _run(cells, "closed", trace=True)
    m = r["metrics"]
    assert 0 < m["decode_occupancy"]["value"] <= 100
    assert m["schedule_ms"]["value"] > 0
    assert 0 < m["step_mfu.batch"]["value"] < 100
    assert "w8a8_roofline.batch" not in m and "device_idle_share" not in m
    assert r["breakdown"] == {"device_ops": [], "idle_gaps": []}
    assert r["correct"] is True


def test_control_reads_above_the_limit(cells):
    """The int4 control goes through the run's own decision and comes out
    not correct, beside a program that comes out correct."""
    r = _run(cells, "closed", controls=("int4", "int4-weights"))
    assert r["correct"] is True
    limit = r["checks"]["widest_logit_gap"]["limit"]
    assert r["controls"]["int4"]["correct"] is False
    assert r["controls"]["int4"]["gap"] > limit
    assert set(r["controls"]) == {"int4", "int4-weights"}
    assert list(r)[-1] == "checks"


def test_altered_token_is_not_correct(cells, monkeypatch):
    """Every request's third token is replaced where the scheduler emits
    it."""
    from repro.serving import scheduler
    emit = scheduler.Scheduler._emit

    def altered(self, run, tok, first):
        if len(run.req.generated) == 2:
            tok = (tok + 1) % self.cfg.vocab_size
        return emit(self, run, tok, first)
    monkeypatch.setattr(scheduler.Scheduler, "_emit", altered)
    r = _run(cells, "closed")
    assert r["correct"] is False
    assert r["checks"]["widest_logit_gap"]["value"] > \
        r["checks"]["widest_logit_gap"]["limit"]


def test_pool_left_unchanged_is_not_correct(cells, monkeypatch):
    """The fused step hands back the KV pool it was given: nothing a
    prefill chunk or a decode step writes reaches the cache."""
    import jax
    from repro.serving import scheduler
    make = scheduler._step_fn_for

    def frozen(*a, **kw):
        fn = make(*a, **kw)

        def step(params, pool, spool, *args, **static):
            keep = jax.tree.map(lambda x: x.copy(), pool)
            pf_logits, dec_logits, _, spool = fn(params, pool, spool, *args,
                                                 **static)
            return pf_logits, dec_logits, keep, spool
        return step
    monkeypatch.setattr(scheduler, "_step_fn_for", frozen)
    r = _run(cells, "closed")
    assert r["correct"] is False
    assert r["checks"]["widest_logit_gap"]["value"] > \
        r["checks"]["widest_logit_gap"]["limit"]
