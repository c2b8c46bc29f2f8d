"""Paged scheduler: golden parity vs the dense engine, chunked prefill,
preemption, sampling and engine-frontend behaviour."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import ModelConfig, init_params
from repro.models.config import LayerSpec
from repro.serving.engine import (EngineConfig, PagedServeEngine, Request,
                                  ServeEngine)
from repro.serving.kv_cache import cache_nbytes
from repro.serving.scheduler import (SchedulerConfig, _chunk_bucket,
                                     ensure_kernels_fit)

CFG = ModelConfig(name="t", vocab_size=128, d_model=64, n_layers=2, n_heads=4,
                  n_kv_heads=2, d_ff=128, attn_chunk=16)
KEY = jax.random.PRNGKey(0)
PARAMS = init_params(CFG, KEY)

# bucket-exact lengths: the dense engine's left-pad hack is a no-op there,
# so dense and paged must agree token-for-token
GOLDEN_PROMPTS = [(np.arange(16, dtype=np.int32) * 3) % 128,
                  (np.arange(32, dtype=np.int32) * 7) % 128,
                  (np.arange(64, dtype=np.int32) * 5) % 128,
                  (np.arange(16, dtype=np.int32) * 11) % 128]


def _dense(max_slots=4, smax=128):
    return ServeEngine(PARAMS, CFG, EngineConfig(max_slots=max_slots, smax=smax))


def _paged(**kw):
    defaults = dict(block_size=16, num_blocks=24, max_batch=4,
                    max_blocks_per_req=8, prefill_chunk=64, token_budget=128)
    defaults.update(kw)
    return PagedServeEngine(PARAMS, CFG, SchedulerConfig(**defaults))


def test_golden_paged_matches_dense_greedy():
    """Mixed-length batch: greedy outputs identical token-for-token, while
    the paged pool allocates fewer KV bytes than the dense max_slots*smax
    layout (the tentpole acceptance criterion)."""
    dense = _dense()
    paged = _paged()
    for i, p in enumerate(GOLDEN_PROMPTS):
        dense.add_request(Request(uid=i, prompt=p.copy(), max_new_tokens=8))
        paged.add_request(Request(uid=i, prompt=p.copy(), max_new_tokens=8))
    dense.run()
    paged.run()
    d = {r.uid: r.generated for r in dense.finished}
    g = {r.uid: r.generated for r in paged.finished}
    assert d == g
    assert cache_nbytes(dense._cache) > paged.cache_nbytes()


def test_chunked_prefill_completes_and_is_bounded():
    """A 48-token prompt over 16-token chunks: 3 chunks, full generation,
    and bounded divergence vs a single-chunk run (K scales freeze at chunk 1
    instead of over the whole prompt)."""
    p48 = (np.arange(48, dtype=np.int32) * 11) % 128
    multi = _paged(block_size=8, num_blocks=32, max_batch=2,
                   max_blocks_per_req=10, prefill_chunk=16, token_budget=32)
    multi.add_request(Request(uid=0, prompt=p48.copy(), max_new_tokens=8))
    multi.run()
    single = _paged(block_size=8, num_blocks=32, max_batch=2,
                    max_blocks_per_req=10, prefill_chunk=64, token_budget=128)
    single.add_request(Request(uid=0, prompt=p48.copy(), max_new_tokens=8))
    single.run()
    assert multi.stats["prefill_chunks"] == 3
    a = multi.finished[0].generated
    b = single.finished[0].generated
    assert len(a) == len(b) == 8
    # bounded divergence, not equality: an untrained random model amplifies
    # the frozen-scale delta, so only demand the streams stay correlated
    agree = sum(int(x == y) for x, y in zip(a, b)) / len(a)
    assert agree >= 0.25, (a, b)


def test_chunked_prefill_coscheduled_with_decode():
    """While one request decodes, another's prompt prefills chunk-by-chunk —
    the decode stream must not stall for the whole prompt."""
    eng = _paged(block_size=8, num_blocks=32, max_batch=2,
                 max_blocks_per_req=10, prefill_chunk=16, token_budget=24)
    eng.add_request(Request(uid=0, prompt=GOLDEN_PROMPTS[0].copy(),
                            max_new_tokens=12))
    # step until request 0 is decoding, then enqueue a long prompt
    while not any(r is not None and r.state == "decode"
                  for r in eng.scheduler.slots):
        eng.step()
    tokens_before = len(eng.scheduler.slots[0].req.generated)
    p48 = (np.arange(48, dtype=np.int32) * 13) % 128
    eng.add_request(Request(uid=1, prompt=p48, max_new_tokens=4))
    eng.step()                       # one fused step: chunk + decode together
    assert eng.stats["prefill_chunks"] >= 1
    assert len(eng.scheduler.slots[0].req.generated) == tokens_before + 1
    done = eng.run()
    assert sorted(len(r.generated) for r in done) == [4, 12]


def test_preemption_under_tiny_pool():
    """Pool too small for all requests at once: the youngest is preempted
    (recompute) and every request still finishes with full output length."""
    eng = _paged(block_size=8, num_blocks=8, max_batch=3,
                 max_blocks_per_req=6, prefill_chunk=16, token_budget=64)
    for i in range(3):
        eng.add_request(Request(
            uid=i, prompt=((np.arange(16) + i) % 128).astype(np.int32),
            max_new_tokens=12))
    done = eng.run()
    m = eng.metrics()
    assert len(done) == 3
    assert all(len(r.generated) == 12 for r in done)
    assert m["preemptions"] >= 1
    # every block reclaimable at the end: unreferenced, either free or held
    # only as cached prefix entries
    alloc = eng.scheduler.alloc
    assert alloc.num_free + alloc.num_cached == 8
    alloc.check()


def test_oversized_request_rejected_with_clear_error():
    eng = _paged(block_size=8, num_blocks=8, max_batch=2,
                 max_blocks_per_req=4)           # 32 tokens/request cap
    with pytest.raises(ValueError, match="paged cache capacity"):
        eng.add_request(Request(uid=0, prompt=np.arange(40, dtype=np.int32) % 128,
                                max_new_tokens=8))


def test_pool_past_the_decode_kernels_vmem_refused_at_build():
    """The GQA decode kernel holds a layer's V scale and zero leaves whole
    in VMEM (128-byte code rows: two kv heads of 64), so a pool past its
    line is refused when the engine is built, before any pool exists, and
    the error names the largest pool that fits."""
    cfg = ModelConfig(name="t-wide", vocab_size=128, d_model=256,
                      n_layers=1, n_heads=4, n_kv_heads=2, d_ff=128,
                      attn_chunk=16)
    kw = dict(block_size=16, max_batch=4, max_blocks_per_req=8,
              prefill_chunk=64, token_budget=128)
    with pytest.raises(ValueError, match="decode attention") as err:
        PagedServeEngine(init_params(cfg, KEY), cfg,
                         SchedulerConfig(num_blocks=400_000, **kw))
    fit = int(re.search(r"at most num_blocks=(\d+)", str(err.value))[1])
    assert 100_000 < fit < 400_000
    ensure_kernels_fit(cfg, SchedulerConfig(num_blocks=fit, **kw))
    with pytest.raises(ValueError, match="decode attention"):
        ensure_kernels_fit(cfg, SchedulerConfig(num_blocks=fit + 1, **kw))


def test_streaming_callback_and_metrics():
    seen = []
    eng = _paged()
    eng.add_request(Request(uid=0, prompt=GOLDEN_PROMPTS[0].copy(),
                            max_new_tokens=6,
                            on_token=lambda req, tok: seen.append(tok)))
    eng.run()
    assert seen == eng.finished[0].generated
    m = eng.metrics()
    assert m["requests_finished"] == 1
    assert m["ttft_avg_s"] > 0
    assert m["tokens_per_s"] > 0
    assert 0 < m["cache_util_peak"] <= 1
    assert eng.finished[0].ttft_s > 0


def test_paged_mla_matches_dense():
    """MLA latent pool path agrees with the dense engine token-for-token."""
    cfg = ModelConfig(name="mla", vocab_size=128, d_model=64, n_layers=2,
                      n_heads=4, d_ff=128, q_lora_rank=32, kv_lora_rank=16,
                      qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                      layer_pattern=(LayerSpec("mla", "dense"),),
                      attn_chunk=16)
    params = init_params(cfg, jax.random.PRNGKey(1))
    prompt = (np.arange(16, dtype=np.int32) * 3) % 128
    dense = ServeEngine(params, cfg, EngineConfig(max_slots=2, smax=64))
    paged = PagedServeEngine(params, cfg, SchedulerConfig(
        block_size=16, num_blocks=8, max_batch=2, max_blocks_per_req=4,
        prefill_chunk=16, token_budget=64))
    for e in (dense, paged):
        e.add_request(Request(uid=0, prompt=prompt.copy(), max_new_tokens=6))
        e.run()
    assert dense.finished[0].generated == paged.finished[0].generated


def test_paged_capability_detection():
    """SSM patterns are served now (ISSUE 4: state pool); only genuinely
    unsupported layouts — prefix-LM image prefixes — are rejected, with a
    clear error naming the dense fallback."""
    ssm_cfg = ModelConfig(name="s", vocab_size=64, d_model=64, n_layers=1,
                          n_heads=4, d_ff=128, ssm_state=16, ssm_head_dim=32,
                          layer_pattern=(LayerSpec("ssm", "none"),))
    eng = PagedServeEngine({}, ssm_cfg, SchedulerConfig())   # constructs fine
    assert set(eng.scheduler.spool) == {"p0"}
    plm_cfg = ModelConfig(name="plm", vocab_size=64, d_model=32, n_layers=1,
                          n_heads=2, d_ff=64, n_img_patches=4, prefix_lm=True)
    with pytest.raises(NotImplementedError, match="prefix-LM"):
        PagedServeEngine({}, plm_cfg, SchedulerConfig())


def test_chunk_bucket():
    assert _chunk_bucket(1, 64) == 16
    assert _chunk_bucket(16, 64) == 16
    assert _chunk_bucket(17, 64) == 32
    assert _chunk_bucket(60, 64) == 64
    assert _chunk_bucket(60, 48) == 60           # cap never truncates c


# -- scheduler bugfix regressions (ISSUE 3) ----------------------------------

def test_preempted_decode_slot_filtered_before_device_step():
    """A slot already scheduled for decode can be vacated before the device
    step runs: victim selection is a global min over ``(priority, -order)``,
    so a later slot's multi-eviction cascade can reach an earlier-scheduled
    slot (tiny pool, three priorities).  The step must drop the vacated slot
    from the decode batch instead of dereferencing ``None`` in _build_args —
    the cascade is forced at its narrowest point here."""
    eng = _paged(block_size=8, num_blocks=8, max_batch=3,
                 max_blocks_per_req=6, prefill_chunk=16, token_budget=64)
    sched = eng.scheduler
    for i, prio in enumerate([2, 1, 0]):
        eng.add_request(Request(
            uid=i, prompt=((np.arange(16) + i) % 128).astype(np.int32),
            max_new_tokens=6, priority=prio))
    while sum(1 for r in sched.slots
              if r is not None and r.state == "decode") < 2:
        eng.step()
    orig = sched._schedule_decode
    fired = []

    def cascade():
        out = orig()
        if not fired and len(out) >= 2:
            fired.append(out[0])
            sched._preempt(out[0])      # the eviction reaches a scheduled slot
        return out

    sched._schedule_decode = cascade
    eng.step()                          # pre-fix: AttributeError on None slot
    sched._schedule_decode = orig
    assert fired
    done = eng.run()
    assert sorted(r.uid for r in done) == [0, 1, 2]
    assert all(len(r.generated) == 6 for r in done)
    sched.alloc.check()


MG_CFG = ModelConfig(name="mg", vocab_size=128, d_model=64, n_layers=2,
                     n_heads=4, n_kv_heads=4, d_ff=128, n_codebooks=4,
                     act_fn="gelu", layer_pattern=(LayerSpec("attn", "dense"),),
                     attn_chunk=16)
MG_PARAMS = init_params(MG_CFG, jax.random.PRNGKey(2))
MG_PROMPT = (np.arange(64, dtype=np.int32).reshape(4, 16) * 3) % 128


def _mg_paged(eos_id):
    return PagedServeEngine(MG_PARAMS, MG_CFG, SchedulerConfig(
        block_size=16, num_blocks=16, max_batch=2, max_blocks_per_req=4,
        prefill_chunk=16, token_budget=64, eos_id=eos_id))


def test_multicodebook_eos_stops_paged_engine():
    """Per-codebook tokens are lists; the old ``tok == eos_id`` compare was
    always False, so MusicGen-pattern requests never stopped early.  Policy:
    stop when codebook 0 emits EOS."""
    ref = _mg_paged(-1)
    ref.add_request(Request(uid=0, prompt=MG_PROMPT.copy(), max_new_tokens=8))
    ref.run()
    gen = ref.finished[0].generated
    assert len(gen) == 8 and isinstance(gen[0], list)
    eos = gen[3][0]                     # a token codebook 0 actually emits
    expect = next(i for i, t in enumerate(gen) if t[0] == eos) + 1
    assert expect < 8                   # early stop is really exercised
    eng = _mg_paged(eos)
    eng.add_request(Request(uid=0, prompt=MG_PROMPT.copy(), max_new_tokens=8))
    eng.run()
    assert eng.finished[0].generated == gen[:expect]


def test_multicodebook_eos_stops_dense_engine():
    ecfg = EngineConfig(max_slots=2, smax=32, eos_id=-1)
    ref = ServeEngine(MG_PARAMS, MG_CFG, ecfg)
    ref.add_request(Request(uid=0, prompt=MG_PROMPT.copy(), max_new_tokens=8))
    ref.run()
    gen = ref.finished[0].generated
    assert len(gen) == 8 and isinstance(gen[0], list)
    eos = gen[3][0]
    expect = next(i for i, t in enumerate(gen) if t[0] == eos) + 1
    assert expect < 8
    eng = ServeEngine(MG_PARAMS, MG_CFG,
                      EngineConfig(max_slots=2, smax=32, eos_id=eos))
    eng.add_request(Request(uid=0, prompt=MG_PROMPT.copy(), max_new_tokens=8))
    eng.run()
    assert eng.finished[0].generated == gen[:expect]


def test_tokens_per_s_counts_inflight_first_tokens():
    """The throughput numerator must include the prefill-sampled first token
    of still-running requests, not just finished ones."""
    eng = _paged()
    r0 = Request(uid=0, prompt=GOLDEN_PROMPTS[0].copy(), max_new_tokens=6)
    eng.add_request(r0)
    eng.run()
    r1 = Request(uid=1, prompt=GOLDEN_PROMPTS[1].copy(), max_new_tokens=8)
    eng.add_request(r1)
    while not r1.generated:             # first token emitted, not finished
        eng.step()
    assert not r1.done
    sched = eng.scheduler
    m = eng.metrics()
    wall = sched._t_last - sched._t_start
    counted = m["tokens_per_s"] * wall
    emitted = len(r0.generated) + len(r1.generated)
    assert np.isclose(counted, emitted), (counted, emitted)
    assert sched.stats["first_tokens"] == 2
    eng.run()


# -- priority aging (ISSUE 4 satellite) ---------------------------------------

def _sustained_high_priority(age_steps, max_steps=60):
    """One slot, a sustained stream of priority-5 requests, and one
    priority-0 request stuck behind them; returns the low-prio request."""
    eng = _paged(max_batch=1, num_blocks=24,
                 priority_age_steps=age_steps)
    hi_uid = [0]

    def inject():
        eng.add_request(Request(
            uid=hi_uid[0], prompt=GOLDEN_PROMPTS[0].copy(),
            max_new_tokens=2, priority=5))
        hi_uid[0] += 1

    inject()
    eng.step()                             # high-prio occupies the only slot
    low = Request(uid=999, prompt=GOLDEN_PROMPTS[3].copy(),
                  max_new_tokens=2, priority=0)
    eng.add_request(low)
    for _ in range(max_steps):
        if low.done:
            break
        if eng.scheduler.num_waiting < 2:  # keep a fresh high-prio queued
            inject()
        eng.step()
    return low


def test_priority_aging_admits_starved_request():
    """Effective priority grows with wait age: under sustained priority-5
    load the priority-0 request eventually outranks fresh arrivals and
    finishes.  Without aging (the pre-PR behaviour) it starves forever —
    both halves asserted so the regression cannot silently return."""
    assert not _sustained_high_priority(age_steps=0).done     # starves
    assert _sustained_high_priority(age_steps=2).done         # aged in


def test_priority_aging_does_not_ratchet_across_preemption():
    """The age absorbed into ``run.priority`` at admission is *consumed*:
    time spent running, and the already-absorbed wait, must not be re-added
    at a preempt/re-admit cycle — otherwise every cycle ratchets the request
    above genuinely higher-priority traffic and makes it un-evictable."""
    eng = _paged(max_batch=1, priority_age_steps=1)
    sched = eng.scheduler
    eng.add_request(Request(uid=0, prompt=GOLDEN_PROMPTS[0].copy(),
                            max_new_tokens=10, priority=0))
    for _ in range(6):                   # admit + decode a while
        eng.step()
    before = sched.slots[0].priority
    assert before == 0                   # no wait before first admission
    sched._preempt(0)
    eng.step()                           # re-admitted next step
    run = sched.slots[0]
    assert run is not None and run.req.uid == 0
    # pre-fix: priority jumped to ~steps//age (the whole running time
    # counted as "waiting"); post-fix only the 1-step requeue wait ages
    assert run.priority <= before + 1, run.priority
    eng.run()


# -- router-facing accessors / drain hook ------------------------------------

def test_live_token_and_occupancy_accessors():
    eng = _paged()
    sched = eng.scheduler
    assert sched.live_tokens == 0 and sched.num_running == 0
    eng.add_request(Request(uid=0, prompt=GOLDEN_PROMPTS[2].copy(),
                            max_new_tokens=4))
    assert sched.num_waiting == 1 and sched.live_tokens == 64
    eng.step()
    assert sched.num_running == 1 and sched.num_waiting == 0
    assert sched.live_tokens >= 64
    assert 0 < sched.occupancy <= 1
    eng.run()
    assert sched.live_tokens == 0 and sched.occupancy == 0.0


def test_drain_hands_back_waiting_requests():
    """drain() returns the not-yet-admitted queue (for re-routing) and runs
    only the in-flight work to completion."""
    eng = _paged(max_batch=1)
    eng.add_request(Request(uid=0, prompt=GOLDEN_PROMPTS[0].copy(),
                            max_new_tokens=3))
    eng.add_request(Request(uid=1, prompt=GOLDEN_PROMPTS[3].copy(),
                            max_new_tokens=3))
    eng.step()                           # uid 0 admitted, uid 1 still queued
    handed = eng.scheduler.drain()
    assert [r.uid for r in handed] == [1]
    assert not eng.scheduler.has_work
    assert [r.uid for r in eng.finished] == [0]


# -- dense-engine satellite fixes -------------------------------------------

def test_dense_per_request_temperature():
    """Greedy and hot requests co-batched: the greedy one must match a solo
    greedy run (regression: decode ignored per-request temperature)."""
    prompt = (np.arange(16, dtype=np.int32) * 3) % 128
    both = _dense(max_slots=2, smax=64)
    both.add_request(Request(uid=0, prompt=prompt.copy(), max_new_tokens=12,
                             temperature=0.0))
    both.add_request(Request(uid=1, prompt=prompt.copy(), max_new_tokens=12,
                             temperature=5.0))
    both.run()
    solo = _dense(max_slots=2, smax=64)
    solo.add_request(Request(uid=0, prompt=prompt.copy(), max_new_tokens=12))
    solo.run()
    outs = {r.uid: r.generated for r in both.finished}
    assert outs[0] == solo.finished[0].generated
    assert outs[1] != outs[0]


def test_dense_oversized_prompt():
    eng = _dense(max_slots=2, smax=64)
    with pytest.raises(ValueError, match="exceeds the cache capacity"):
        eng.add_request(Request(uid=0, prompt=np.arange(65, dtype=np.int32) % 128))
    trunc = ServeEngine(PARAMS, CFG, EngineConfig(max_slots=2, smax=64,
                                                  truncate_prompts=True))
    trunc.add_request(Request(uid=0, prompt=np.arange(100, dtype=np.int32) % 128,
                              max_new_tokens=4))
    # truncation reserves room for every appended decode token: smax-max_new+1
    assert trunc.queue[-1].prompt.shape[-1] == 61
    done = trunc.run()
    assert len(done[0].generated) == 4
