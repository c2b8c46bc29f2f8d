"""Cache codec + bit ladder (ISSUE 8 tentpole).

Contracts asserted here:

  * codec primitives are exact or boundedly lossy by construction —
    nibble pack/unpack roundtrips every int4 code, the ladder's code-space
    requant errs by at most 8 int8 codes with exact endpoints, and the bf16
    pair carrier keeps ~3 significant digits of both scale rows;
  * the packed-int4 pool really halves the value-leaf bytes, and an engine
    built on it serves end-to-end with warm == cold prefix goldens *within*
    the codec (bit-identity across codecs is never claimed);
  * the ladder is inert without pressure (bit-identical to ladder-off) and
    under pressure demotes CACHED pairs / promotes them back on a hit while
    the allocator conservation invariant holds throughout;
  * hybrid state snapshots give SSM+attention configs warm == cold prefix
    hits (state-aware matching satellite);
  * ``weight_budget_mb`` assigns mixed per-layer weight bitwidths at engine
    build and surfaces them in metrics().
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bitwidth_search import assign_weight_bitwidths
from repro.core.qtensor import QTensor, pack_nibbles, unpack_nibbles
from repro.models import ModelConfig, init_params
from repro.models.config import LayerSpec
from repro.serving.codec import (CODECS, demote_codes, demote_pair_blocks,
                                 get_codec, pack_f32_pair, promote_block,
                                 promote_codes, promote_codes_full,
                                 unpack_f32_pair)
from repro.serving.engine import PagedServeEngine, Request
from repro.serving.paged_cache import (PagedCacheConfig, init_paged_cache,
                                       paged_cache_nbytes, per_block_nbytes)
from repro.serving.scheduler import SchedulerConfig

CFG = ModelConfig(name="t", vocab_size=128, d_model=64, n_layers=2, n_heads=4,
                  n_kv_heads=2, d_ff=128, attn_chunk=16)
PARAMS = init_params(CFG, jax.random.PRNGKey(0))
PROMPT48 = (np.arange(48, dtype=np.int32) * 5) % 128


def _engine(params=PARAMS, cfg=CFG, **kw):
    defaults = dict(block_size=16, num_blocks=24, max_batch=4,
                    max_blocks_per_req=8, prefill_chunk=16, token_budget=128,
                    partial_prefix=False)
    defaults.update(kw)
    return PagedServeEngine(params, cfg, SchedulerConfig(**defaults))


# ---------------------------------------------------------------------------
# Codec registry + primitives
# ---------------------------------------------------------------------------

def test_codec_registry():
    assert get_codec("int8").pack == 1 and get_codec("int8").bits == 8
    cd = get_codec("int4")
    assert cd.pack == 2 and cd.packed_dim(64) == 32
    assert get_codec(cd) is cd                       # idempotent
    with pytest.raises(ValueError, match="not divisible"):
        cd.packed_dim(7)
    with pytest.raises(ValueError, match="unknown cache codec"):
        get_codec("int3")
    assert sorted(CODECS) == ["int4", "int8"]


def test_nibble_pack_roundtrip_exact():
    codes = jnp.arange(-8, 8, dtype=jnp.int8).reshape(2, 8)
    packed = pack_nibbles(codes)
    assert packed.shape == (2, 4) and packed.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(unpack_nibbles(packed)),
                                  np.asarray(codes))


def test_ladder_codes_bounded_and_endpoint_exact():
    """demote -> promote moves any int8 code by at most 8 positions, and the
    range endpoints (which pin the frozen affine) roundtrip exactly."""
    c8 = jnp.arange(-128, 128, dtype=jnp.int8).reshape(16, 16)
    back = promote_codes_full(demote_codes(c8))
    err = np.abs(np.asarray(back, np.int32) - np.asarray(c8, np.int32))
    assert err.max() <= 8
    flat = np.asarray(back).ravel()
    assert flat[0] == -128 and flat[-1] == 127       # 255 == 15 * 17 exact
    # the halved promote path picks the same codes out of a packed pair
    paired = jnp.concatenate([demote_codes(c8), demote_codes(c8 ^ 1)], -1)
    np.testing.assert_array_equal(
        np.asarray(promote_codes(paired, jnp.int32(0))), np.asarray(back))


def test_bf16_pair_carrier_roundtrip():
    rs = np.random.RandomState(0)
    a = jnp.asarray(rs.uniform(1e-4, 4.0, size=(8, 8)), jnp.float32)
    b = jnp.asarray(rs.uniform(-3.0, 3.0, size=(8, 8)), jnp.float32)
    p = pack_f32_pair(a, b)
    assert p.dtype == jnp.float32 and not np.isnan(np.asarray(p)).any()
    ra = np.asarray(unpack_f32_pair(p, jnp.int32(0)))
    rb = np.asarray(unpack_f32_pair(p, jnp.int32(1)))
    np.testing.assert_allclose(ra, np.asarray(a), rtol=0.01, atol=0.02)
    np.testing.assert_allclose(rb, np.asarray(b), rtol=0.01, atol=0.02)


# ---------------------------------------------------------------------------
# Packed pool layout + ladder device ops
# ---------------------------------------------------------------------------

def test_int4_pool_halves_value_leaves():
    pcfg = PagedCacheConfig(block_size=8, num_blocks=6, max_batch=3,
                            max_blocks_per_req=4)
    p8 = init_paged_cache(CFG, pcfg, codec="int8")
    p4 = init_paged_cache(CFG, pcfg, codec="int4")
    assert p4["p0"]["k_vals"].shape[-1] * 2 == p8["p0"]["k_vals"].shape[-1]
    assert p4["p0"]["k_scale"].shape == p8["p0"]["k_scale"].shape
    assert per_block_nbytes(p4) < per_block_nbytes(p8)
    assert paged_cache_nbytes(p4) < paged_cache_nbytes(p8)


def test_demote_promote_device_ops_roundtrip():
    """The jitted ladder ops fold blocks 1+2 into block 1 and lift half 0
    back out onto block 3: codes within the 8-code bound, bf16 scale rows
    within 1%."""
    pcfg = PagedCacheConfig(block_size=4, num_blocks=4, max_batch=2,
                            max_blocks_per_req=2)
    pool = init_paged_cache(CFG, pcfg)
    rs = np.random.RandomState(1)
    ent = dict(pool["p0"])
    shape1 = ent["k_vals"].shape[0:1] + ent["k_vals"].shape[2:]
    k1 = rs.randint(-128, 128, size=shape1).astype(np.int8)
    v1 = rs.randint(-128, 128, size=shape1).astype(np.int8)
    vs_shape = ent["v_scale"].shape[:-1]               # blocks are lanes
    vs1 = rs.uniform(0.01, 2.0, size=vs_shape).astype(np.float32)
    ent["k_vals"] = ent["k_vals"].at[:, 1].set(k1)
    ent["v_vals"] = ent["v_vals"].at[:, 1].set(v1)
    ent["v_scale"] = ent["v_scale"].at[..., 1].set(vs1)
    pool["p0"] = ent
    pool = demote_pair_blocks(pool, jnp.int32(1), jnp.int32(2), jnp.int32(1))
    pool = promote_block(pool, jnp.int32(1), jnp.int32(0), jnp.int32(3))
    got_k = np.asarray(pool["p0"]["k_vals"][:, 3], np.int32)
    got_v = np.asarray(pool["p0"]["v_vals"][:, 3], np.int32)
    assert np.abs(got_k - k1.astype(np.int32)).max() <= 8
    assert np.abs(got_v - v1.astype(np.int32)).max() <= 8
    np.testing.assert_allclose(np.asarray(pool["p0"]["v_scale"][..., 3]), vs1,
                               rtol=0.01, atol=1e-4)


# ---------------------------------------------------------------------------
# int4 codec end-to-end
# ---------------------------------------------------------------------------

def test_int4_engine_serves_with_warm_golden():
    """An int4-codec engine completes generation, allocates roughly half the
    pool bytes, and its warm prefix hit is bit-identical to its own cold run
    (the golden contract holds per-codec)."""
    e8 = _engine()
    e4 = _engine(codec="int4")
    assert e4.cache_nbytes() < e8.cache_nbytes()
    e4.add_request(Request(uid=0, prompt=PROMPT48.copy(), max_new_tokens=8))
    e4.run()
    cold = e4.finished[0].generated
    assert len(cold) == 8
    e4.add_request(Request(uid=1, prompt=PROMPT48.copy(), max_new_tokens=8))
    e4.run()
    m = e4.metrics()
    assert m["prefix_hit_tokens"] == 32
    warm = next(r for r in e4.finished if r.uid == 1)
    assert warm.generated == cold
    e4.scheduler.alloc.check()


def test_ladder_requires_int8_codec():
    with pytest.raises(ValueError, match="ladder"):
        _engine(codec="int4", ladder=True)


# ---------------------------------------------------------------------------
# Bit ladder
# ---------------------------------------------------------------------------

def test_ladder_inert_without_pressure():
    """Big pool, ladder on: zero demotions and output streams bit-identical
    to the ladder-off engine."""
    off = _engine()
    on = _engine(ladder=True)
    for eng in (off, on):
        for uid in range(2):
            eng.add_request(Request(uid=uid, prompt=PROMPT48.copy(),
                                    max_new_tokens=8))
            eng.run()
    assert on.metrics()["demotions"] == 0
    assert on.metrics()["promotions"] == 0
    a = {r.uid: r.generated for r in off.finished}
    b = {r.uid: r.generated for r in on.finished}
    assert a == b


def test_ladder_demotes_and_promotes_under_pressure():
    """Tiny pool + high watermark: cold prefixes get folded to int4 halves
    (capacity: >num_blocks logical blocks resident), and resubmitting the
    first prompt promotes its entries back and completes."""
    kw = dict(num_blocks=10, max_blocks_per_req=4, max_batch=2,
              token_budget=64)
    eng = _engine(ladder=True, ladder_watermark=0.75, **kw)
    sched = eng.scheduler
    p_b = (PROMPT48 + 17) % 128
    eng.add_request(Request(uid=0, prompt=PROMPT48.copy(), max_new_tokens=6))
    eng.run()
    eng.add_request(Request(uid=1, prompt=p_b.copy(), max_new_tokens=6))
    eng.run()
    m = eng.metrics()
    assert m["demotions"] >= 2             # a CACHED pair was folded
    assert m["int4_blocks"] >= 1
    assert m["effective_cache_bytes"] > 0
    # resubmit prompt A: its demoted chain promotes back on the hit
    eng.add_request(Request(uid=2, prompt=PROMPT48.copy(), max_new_tokens=6))
    eng.run()
    m = eng.metrics()
    assert m["promotions"] >= 1
    assert m["prefix_hit_tokens"] >= 16
    assert all(len(r.generated) == 6 for r in eng.finished)
    sched.alloc.check()


def test_ladder_capacity_exceeds_physical_blocks():
    """Keep publishing distinct prompts: demoted halves let the logical
    resident block count climb past the physical pool size."""
    eng = _engine(ladder=True, ladder_watermark=0.9, num_blocks=8,
                  max_blocks_per_req=4, max_batch=1, token_budget=64)
    sched = eng.scheduler
    for uid in range(4):
        p = (PROMPT48 + 31 * uid) % 128
        eng.add_request(Request(uid=uid, prompt=p, max_new_tokens=4))
        eng.run()
    m = eng.metrics()
    assert m["demotions"] >= 2
    assert m["effective_cache_blocks_peak"] > 0
    a = sched.alloc
    logical = a.num_used + a.num_cached + a.int4_blocks
    physical = a.num_used + a.num_cached + a.num_packed
    assert logical > physical              # two halves in one block somewhere
    a.check()


# ---------------------------------------------------------------------------
# Hybrid state-aware prefix sharing (satellite)
# ---------------------------------------------------------------------------

HYB_CFG = ModelConfig(name="hyb", vocab_size=128, d_model=64, n_layers=2,
                      n_heads=4, n_kv_heads=2, d_ff=128, ssm_state=16,
                      ssm_head_dim=32, ssm_chunk=16, attn_chunk=16,
                      layer_pattern=(LayerSpec("ssm", "dense"),
                                     LayerSpec("attn", "dense")))
HYB_PARAMS = init_params(HYB_CFG, jax.random.PRNGKey(1))


def test_hybrid_state_aware_prefix_hit_golden():
    """SSM+attention: a resubmitted prompt matches the snapshotted chain,
    restores the donor's SSM state, and emits the cold run's tokens."""
    eng = _engine(params=HYB_PARAMS, cfg=HYB_CFG, num_blocks=16,
                  max_blocks_per_req=4, max_batch=2, token_budget=64)
    eng.add_request(Request(uid=0, prompt=PROMPT48.copy(), max_new_tokens=8))
    eng.run()
    cold = eng.finished[0].generated
    assert eng.metrics()["prefix_hit_tokens"] == 0
    eng.add_request(Request(uid=1, prompt=PROMPT48.copy(), max_new_tokens=8))
    eng.run()
    m = eng.metrics()
    assert m["state_prefix_hits"] >= 1
    assert m["prefix_hit_tokens"] == 32
    warm = next(r for r in eng.finished if r.uid == 1)
    assert warm.generated == cold
    eng.scheduler.alloc.check()


def test_hybrid_match_trimmed_to_snapshot_boundary():
    """A prefix whose later blocks were published without a state snapshot
    (snapshot LRU evicted) must only match up to the last snapshotted
    boundary — never adopt KV blocks whose paired state is gone."""
    eng = _engine(params=HYB_PARAMS, cfg=HYB_CFG, num_blocks=16,
                  max_blocks_per_req=4, max_batch=2, token_budget=64)
    sched = eng.scheduler
    eng.add_request(Request(uid=0, prompt=PROMPT48.copy(), max_new_tokens=6))
    eng.run()
    # forget every snapshot: the warm request must fall back to a cold run
    sched._state_snaps.clear()
    eng.add_request(Request(uid=1, prompt=PROMPT48.copy(), max_new_tokens=6))
    eng.run()
    assert eng.metrics()["prefix_hit_tokens"] == 0
    assert all(len(r.generated) == 6 for r in eng.finished)
    sched.alloc.check()


# ---------------------------------------------------------------------------
# Per-layer weight bitwidths under a byte budget (satellite)
# ---------------------------------------------------------------------------

def test_assign_weight_bitwidths_meets_budget():
    fp_bytes = sum(l.nbytes for l in jax.tree_util.tree_leaves(PARAMS)
                   if hasattr(l, "nbytes"))
    qparams, res = assign_weight_bitwidths(PARAMS, fp_bytes // 6)
    assert res is not None
    assert res.bytes_total <= fp_bytes // 6
    bits = set(res.assignment.values())
    assert bits <= {4, 8} and len(res.assignment) > 0
    q_leaves = [l for l in jax.tree_util.tree_leaves(
        qparams, is_leaf=lambda x: isinstance(x, QTensor))
        if isinstance(l, QTensor)]
    assert q_leaves                          # modules really quantized
    with pytest.raises(ValueError, match="budget"):
        assign_weight_bitwidths(PARAMS, 1)   # below the all-min floor


def test_weight_budget_engine_builds_and_serves():
    fp_bytes = sum(l.nbytes for l in jax.tree_util.tree_leaves(PARAMS)
                   if hasattr(l, "nbytes"))
    eng = _engine(weight_budget_mb=(fp_bytes / 5) / 2 ** 20)
    m = eng.metrics()
    assert 4 <= m["weight_bits_min"] <= m["weight_bits_avg"] \
        <= m["weight_bits_max"] <= 8
    eng.add_request(Request(uid=0, prompt=PROMPT48.copy(), max_new_tokens=6))
    eng.run()
    assert len(eng.finished[0].generated) == 6
