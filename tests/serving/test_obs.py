"""Serving observability: golden tracing-transparency, the metrics() wall
guard, merged fleet percentiles, trace export from the engines, and the
debug snapshot."""
import json

import jax
import numpy as np
import pytest

from repro.models import ModelConfig, init_params
from repro.obs import (Histogram, MetricsRegistry, Tracer,
                       validate_chrome_trace)
from repro.serving.engine import PagedServeEngine, Request
from repro.serving.replica import ReplicaConfig, ReplicatedServeEngine
from repro.serving.scheduler import SchedulerConfig
from repro.serving.spec_decode import SpecConfig

CFG = ModelConfig(name="t", vocab_size=128, d_model=64, n_layers=2, n_heads=4,
                  n_kv_heads=2, d_ff=128, attn_chunk=16)
KEY = jax.random.PRNGKey(0)
PARAMS = init_params(CFG, KEY)

PROMPTS = [(np.arange(16, dtype=np.int32) * 3) % 128,
           (np.arange(32, dtype=np.int32) * 7) % 128,
           (np.arange(48, dtype=np.int32) * 5) % 128,
           (np.arange(16, dtype=np.int32) * 11) % 128]


def _scfg(**kw):
    defaults = dict(block_size=16, num_blocks=24, max_batch=4,
                    max_blocks_per_req=8, prefill_chunk=16, token_budget=64)
    defaults.update(kw)
    return SchedulerConfig(**defaults)


def _paged(tracer=None, **kw):
    return PagedServeEngine(PARAMS, CFG, _scfg(**kw), tracer=tracer)


def _drive(eng, max_new=8):
    for i, p in enumerate(PROMPTS):
        eng.add_request(Request(uid=i, prompt=p.copy(), max_new_tokens=max_new))
    eng.run()
    return {r.uid: r.generated for r in eng.finished}


# -- golden: tracing must be observationally transparent -----------------------

def test_tracing_on_matches_tracing_off_token_for_token():
    off = _drive(_paged(tracer=None))
    on = _drive(_paged(tracer=Tracer(capacity=4096)))
    assert on == off


def test_tracing_on_matches_tracing_off_on_the_spec_path():
    off = _drive(_paged(tracer=None, spec=SpecConfig(gamma=3)))
    tr = Tracer(capacity=8192)
    on = _drive(_paged(tracer=tr, spec=SpecConfig(gamma=3)))
    assert on == off
    assert tr.kinds().get("spec_round", 0) > 0


# -- the step's phases ---------------------------------------------------------

PARENT = {"stage": "schedule", "device_wait": "consume",
          "publish": "consume", "retire": "consume"}


@pytest.mark.parametrize("spec", [None, SpecConfig(gamma=3)],
                         ids=["plain", "spec"])
def test_step_phase_spans_nest_and_share_the_step(spec):
    """Every phase span appears, each child lies inside its parent of the
    same step, and every span of a step carries that step's ordinal; the
    children and ``retire`` tile ``consume``."""
    tr = Tracer(capacity=1 << 14)
    eng = _paged(tracer=tr, spec=spec)
    _drive(eng, max_new=6)
    spans = [e for e in tr.events if e.dur is not None]
    kinds = {e.kind for e in spans}
    assert {"schedule", "stage", "device_step", "consume", "device_wait",
            "publish", "retire"} <= kinds
    assert all("step" in e.args for e in spans)
    steps = eng.scheduler.stats["steps"]
    by = {}
    for e in spans:
        by.setdefault(e.args["step"], []).append(e)
    assert sorted(by) == list(range(1, steps + 1))
    for step, group in by.items():
        top = {k: [e for e in group if e.kind == k]
               for k in ("schedule", "device_step", "consume")}
        assert all(len(v) == 1 for v in top.values()), (step, top)
        sch, dev, con = (top[k][0] for k in ("schedule", "device_step",
                                             "consume"))
        # the three phases follow one another
        assert sch.ts + sch.dur <= dev.ts
        assert dev.ts + dev.dur <= con.ts
        for e in group:
            if e.kind in PARENT:
                p = top[PARENT[e.kind]][0]
                assert p.ts <= e.ts and e.ts + e.dur <= p.ts + p.dur, \
                    (step, e.kind)
        inner = sorted((e.ts, e.ts + e.dur) for e in group
                       if PARENT.get(e.kind) == "consume")
        assert inner[0][0] == con.ts
        assert inner[-1][1] == con.ts + con.dur
        for (_, b), (a, _) in zip(inner, inner[1:]):
            assert a == b


def test_admit_carries_its_queue_wait():
    tr = Tracer(capacity=4096)
    _drive(_paged(tracer=tr, max_batch=2), max_new=4)
    admits = [e for e in tr.events if e.kind == "admit"]
    assert len(admits) == len(PROMPTS)
    waits = [e.args["waited_ms"] for e in admits]
    assert all(w >= 0 for w in waits)
    # two slots for four requests: the last two wait for a slot to free
    assert max(waits) > min(waits)


# -- counters ------------------------------------------------------------------

def test_decode_kv_block_counters_match_a_hand_count():
    """One 30-token prompt, chunks of 16, five new tokens: two prefill
    steps (the second emits the first token), then four decode steps that
    attend to ctx + 1 = 31, 32, 33, 34 tokens: 2 + 2 + 3 + 3 = 10 blocks
    of 16, against 4 x max_batch 4 x 8 table entries passed."""
    eng = _paged(prefill_chunk=16, token_budget=16)
    prompt = (np.arange(30, dtype=np.int32) * 3) % 128
    eng.add_request(Request(uid=0, prompt=prompt, max_new_tokens=5))
    eng.run()
    st = eng.scheduler.stats
    assert st["prefill_chunks"] == 2 and st["decode_steps"] == 4
    assert st["decode_kv_blocks_live"] == 10
    assert st["decode_kv_blocks_passed"] == 4 * 4 * 8
    # head_dim 16: 32-byte code rows take the decode kernel's one-block walk
    assert st["decode_kv_steps_live"] == 10


@pytest.mark.parametrize("hd,p", [(64, 16), (16, 1)])
def test_decode_kv_steps_live_match_a_hand_count(hd, p):
    """The decode kernel scores P blocks a step, P from the shapes: 16 for
    two kv heads of 64 (128-byte code rows) under 16-block tables, 1 for
    32-byte rows.  Lanes at ctx 0, 15, 255 and 300 attend to ctx + 1
    tokens: 0, 1, 16 and 19 blocks, so 0 + 1 + 1 + 2 = 4 steps of 16."""
    cfg = ModelConfig(name=f"t-steps{hd}", vocab_size=128, d_model=4 * hd,
                      n_layers=1, n_heads=4, n_kv_heads=2, d_ff=128,
                      attn_chunk=16)
    sch = PagedServeEngine(init_params(cfg, KEY), cfg,
                           _scfg(num_blocks=64, max_blocks_per_req=16)
                           ).scheduler
    assert sch._decode_p == p
    sch._count_kv_blocks(np.array([0, 15, 255, 300], np.int32), 1,
                         sch._decode_p)
    st = sch.stats
    assert st["decode_kv_blocks_live"] == 36
    assert st["decode_kv_steps_live"] == {16: 4, 1: 36}[p]


def test_scheduler_counts_the_compiles_of_its_steps():
    """The first engine of a shape compiles its programs inside its steps;
    a second engine of the same shapes finds them compiled."""
    cfg = ModelConfig(name="t-compiles", vocab_size=96, d_model=64,
                      n_layers=1, n_heads=4, n_kv_heads=2, d_ff=128,
                      attn_chunk=16)
    params = init_params(cfg, KEY)
    counts = []
    for _ in range(2):
        eng = PagedServeEngine(params, cfg, _scfg())
        eng.add_request(Request(uid=0, prompt=PROMPTS[1] % 96,
                                max_new_tokens=3))
        eng.run()
        counts.append(eng.scheduler.stats["compiles"])
    assert counts[0] > 0
    assert counts[1] == 0


# -- satellite (a): wall-clock guard ------------------------------------------

def test_metrics_before_any_step_reports_explicit_zeros():
    """Regression: metrics() on an engine whose step() never ran used to
    compute `_t_last - _t_start` with `_t_start` unset, faking an epoch-sized
    wall.  It must report zeros explicitly."""
    eng = _paged()
    eng.add_request(Request(uid=0, prompt=PROMPTS[0].copy(),
                            max_new_tokens=4))
    m = eng.metrics()                       # enqueued but never stepped
    assert m["wall_s"] == 0.0
    assert m["tokens_per_s"] == 0.0
    assert m["score_tokens_per_s"] == 0.0
    assert eng.scheduler._t_start is None


def test_replicated_metrics_before_any_step_reports_explicit_zeros():
    fleet = ReplicatedServeEngine(PARAMS, CFG, _scfg(),
                                  ReplicaConfig(n_replicas=2))
    m = fleet.metrics()
    assert m["wall_s"] == 0.0
    assert m["tokens_per_s"] == 0.0
    assert m["score_tokens_per_s"] == 0.0


def test_metrics_wall_becomes_positive_after_steps():
    eng = _paged()
    _ = _drive(eng, max_new=4)
    m = eng.metrics()
    assert m["wall_s"] > 0.0
    assert m["tokens_per_s"] > 0.0


# -- percentile keys on the single engine -------------------------------------

def test_engine_metrics_exposes_latency_percentiles():
    eng = _paged()
    _ = _drive(eng, max_new=8)
    m = eng.metrics()
    for name in ("ttft", "tpot", "queue_wait", "step_wall"):
        assert m[f"{name}_p50_s"] > 0.0, name
        assert m[f"{name}_p50_s"] <= m[f"{name}_p90_s"] <= m[f"{name}_p99_s"]
    assert m["ttft_count"] == len(PROMPTS)          # one TTFT per request
    assert m["queue_wait_count"] == len(PROMPTS)    # one admit per request
    assert m["tpot_count"] == len(PROMPTS) * 7      # 7 inter-token gaps each
    assert m["step_wall_count"] > 0
    # the legacy finished-request keys keep their definitions alongside
    assert m["ttft_max_s"] >= m["ttft_avg_s"] > 0.0
    assert m["score_latency_p50_s"] == 0.0          # nothing scored


def test_legacy_metrics_keys_survive():
    """The observability refactor extends metrics() — every pre-existing
    consumer key must still be present."""
    m = _paged().metrics()
    for key in ("requests_finished", "ttft_avg_s", "ttft_max_s",
                "tokens_per_s", "cache_util_avg", "cache_util_peak",
                "cache_nbytes", "preemptions", "failed_alloc",
                "decode_steps", "prefill_chunks", "prefix_hits",
                "prefix_hit_rate", "cached_blocks", "cow_copies",
                "demotions", "promotions", "int4_blocks",
                "effective_cache_bytes", "score_requests",
                "score_tokens_per_s", "spec_rounds", "spec_accept_rate",
                "spec_draft_nbytes", "state_pool_nbytes"):
        assert key in m, key


# -- satellite (b): fleet percentiles are merged, not averaged ----------------

def test_replicated_metrics_merges_per_replica_histograms():
    tr = Tracer(capacity=8192)
    fleet = ReplicatedServeEngine(PARAMS, CFG, _scfg(),
                                  ReplicaConfig(n_replicas=2,
                                                policy="round_robin"),
                                  tracer=tr)
    for i, p in enumerate(PROMPTS):
        fleet.add_request(Request(uid=i, prompt=p.copy(), max_new_tokens=6))
    fleet.run()
    m = fleet.metrics()
    # every request's TTFT counted exactly once across the fleet
    assert m["ttft_count"] == len(PROMPTS)
    assert 0.0 < m["ttft_p50_s"] <= m["ttft_p99_s"]
    assert 0.0 < m["tpot_p50_s"] <= m["tpot_p99_s"]
    # the fleet percentile is the pooled-histogram percentile, not a mean
    # of per-replica percentiles
    pooled = Histogram.merged([r.mreg.hist("ttft") for r in fleet.replicas])
    assert m["ttft_p50_s"] == pooled.percentile(0.50)
    assert m["ttft_p99_s"] == pooled.percentile(0.99)
    # both replicas actually served traffic onto their own trace tracks
    tracks = {e.track for e in tr.events}
    assert tracks == {0, 1}


def test_unequal_load_merge_is_pooled_not_averaged():
    """Synthetic two-replica skew: the loaded replica's distribution must
    dominate the fleet p50 in proportion to its sample count."""
    a, b = MetricsRegistry(), MetricsRegistry()
    for _ in range(90):
        a.observe("ttft", 1.0)              # busy replica: slow
    for _ in range(10):
        b.observe("ttft", 1e-3)             # idle replica: fast
    merged = MetricsRegistry.merged([a, b]).summary(["ttft"])
    assert merged["ttft_count"] == 100.0
    assert merged["ttft_p50_s"] == pytest.approx(1.0, rel=0.25)
    naive = (a.summary(["ttft"])["ttft_p50_s"]
             + b.summary(["ttft"])["ttft_p50_s"]) / 2
    assert abs(naive - merged["ttft_p50_s"]) > 0.3


# -- trace export from the engines --------------------------------------------

def test_engine_trace_export_has_lifecycle_and_phase_spans(tmp_path):
    tr = Tracer(capacity=8192)
    eng = _paged(tracer=tr)
    _ = _drive(eng, max_new=6)
    path = tmp_path / "trace.json"
    obj = eng.export_chrome_trace(str(path))
    assert validate_chrome_trace(obj) == []
    assert validate_chrome_trace(json.loads(path.read_text())) == []
    kinds = tr.kinds()
    for k in ("enqueue", "admit", "first_token", "finish",
              "schedule", "device_step", "consume",
              "prefill_chunk", "decode_step"):
        assert kinds.get(k, 0) > 0, k
    assert kinds["enqueue"] == kinds["finish"] == len(PROMPTS)
    assert kinds["first_token"] == len(PROMPTS)


def test_engine_without_tracer_refuses_export(tmp_path):
    eng = _paged()
    with pytest.raises(ValueError, match="tracer"):
        eng.export_chrome_trace(str(tmp_path / "t.json"))
    fleet = ReplicatedServeEngine(PARAMS, CFG, _scfg(),
                                  ReplicaConfig(n_replicas=2))
    with pytest.raises(ValueError, match="tracer"):
        fleet.export_chrome_trace(str(tmp_path / "t.json"))


def test_preemption_shows_up_in_the_trace():
    tr = Tracer(capacity=8192)
    # a pool small enough that two 56-token requests cannot coexist
    eng = _paged(tracer=tr, num_blocks=8, max_batch=2, max_blocks_per_req=8,
                 prefill_chunk=16, token_budget=64)
    for i in range(3):
        p = (np.arange(56, dtype=np.int32) * (3 + i)) % 128
        eng.add_request(Request(uid=i, prompt=p, max_new_tokens=16))
    eng.run()
    assert eng.scheduler.stats["preemptions"] > 0
    kinds = tr.kinds()
    assert kinds.get("preempt", 0) == eng.scheduler.stats["preemptions"]
    assert kinds.get("resume", 0) > 0


# -- debug snapshot ------------------------------------------------------------

def test_debug_snapshot_is_json_serializable_and_consistent():
    eng = _paged()
    eng.add_request(Request(uid=0, prompt=PROMPTS[1].copy(),
                            max_new_tokens=6))
    eng.step()
    snap = eng.debug_snapshot()
    json.dumps(snap)                        # must be a pure-JSON postmortem
    alloc = snap["alloc"]
    counts = {}
    for b in alloc["blocks"]:
        counts[b["state"]] = counts.get(b["state"], 0) + 1
    # conservation: every block accounted for in exactly one state
    assert sum(counts.values()) == eng.scheduler.scfg.num_blocks
    assert counts.get("FREE", 0) == len(alloc["free_list"])
    live = [s for s in snap["slots"] if s is not None]
    assert live and live[0]["uid"] == 0


def test_replicated_debug_snapshot_covers_every_replica():
    fleet = ReplicatedServeEngine(PARAMS, CFG, _scfg(),
                                  ReplicaConfig(n_replicas=2))
    snap = fleet.debug_snapshot()
    json.dumps(snap)
    assert len(snap["replicas"]) == 2
