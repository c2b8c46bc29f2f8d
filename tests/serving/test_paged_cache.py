"""Paged KV cache: allocator, pool layout, and quantization-parity units."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import ModelConfig
from repro.models.config import LayerSpec
from repro.serving.kv_cache import gqa_cache_entry
from repro.serving.paged_cache import (BlockAllocator, BlockPoolError,
                                       PagedCacheConfig, copy_pool_block,
                                       gqa_chunk_write, gqa_gather_prefix,
                                       gqa_paged_append, init_paged_cache,
                                       paged_cache_nbytes)

KEY = jax.random.PRNGKey(0)
CFG = ModelConfig(name="t", vocab_size=128, d_model=64, n_layers=2, n_heads=4,
                  n_kv_heads=2, d_ff=128)


# ---------------------------------------------------------------------------
# BlockAllocator: refcounted pool + prefix index
# ---------------------------------------------------------------------------

def test_allocator_alloc_free_reuse():
    a = BlockAllocator(4)
    got = a.alloc(3)
    assert sorted(got) == [0, 1, 2]
    assert a.num_free == 1 and a.num_used == 3
    a.free([1])
    # LIFO recycling: the just-freed block is handed out first
    assert a.alloc(1) == [1]
    a.free([0, 1, 2])
    assert a.num_free == 4
    assert a.utilization == 0.0
    a.check()


def test_allocator_all_or_nothing_oom():
    a = BlockAllocator(2)
    assert a.alloc(3) is None          # refused outright, nothing leaked
    assert a.num_free == 2
    first = a.alloc(2)
    assert a.alloc(1) is None
    a.free(first)
    assert a.alloc(2) is not None


def test_allocator_double_free_rejected():
    """Double free / negative refcount raises in O(1) (no free-list scan)."""
    a = BlockAllocator(2)
    blk = a.alloc(1)
    a.free(blk)
    with pytest.raises(BlockPoolError, match="double free"):
        a.free(blk)
    with pytest.raises(BlockPoolError):
        a.decref(99)                    # out of range


def test_allocator_refcount_sharing():
    a = BlockAllocator(4)
    [b] = a.alloc(1)
    a.incref(b)
    assert a.refcount(b) == 2 and a.is_shared(b)
    a.decref(b)
    assert a.refcount(b) == 1 and not a.is_shared(b)
    assert a.num_free == 3              # still held by the last reference
    a.decref(b)
    assert a.num_free == 4
    with pytest.raises(BlockPoolError):
        a.incref(b)                     # incref of a free block
    a.check()


def test_allocator_publish_cache_acquire():
    """A published block survives its last decref as a CACHED prefix entry,
    is revived by acquire(), and only then counts as used again."""
    a = BlockAllocator(4)
    [b] = a.alloc(1)
    assert a.publish(b, b"k1", tag=1, meta="snap")
    assert a.is_published(b)
    a.decref(b)
    assert a.num_cached == 1 and a.num_free == 3
    assert a.num_available == 4         # cached blocks are reclaimable
    assert a.num_used == 0
    e = a.lookup(b"k1")
    assert e.block == b and e.tag == 1 and e.meta == "snap"
    got = a.acquire(b"k1")
    assert got == b and a.refcount(b) == 1 and a.num_cached == 0
    assert a.acquire(b"k1") == b and a.refcount(b) == 2   # active incref
    assert a.acquire(b"missing") is None
    a.check()


def test_allocator_publish_first_wins():
    a = BlockAllocator(4)
    b1, b2 = a.alloc(2)
    assert a.publish(b1, b"k", tag=1)
    assert not a.publish(b2, b"k", tag=2)    # key taken: no-op
    assert a.lookup(b"k").block == b1
    a.free([b1, b2])
    assert a.num_cached == 1 and a.num_free == 3   # b2 was never indexed
    a.check()


def test_allocator_lru_eviction_under_pressure():
    """alloc() reclaims the least-recently-cached block (and its index
    entry) when the free list runs dry."""
    a = BlockAllocator(3)
    blocks = a.alloc(3)
    for i, b in enumerate(blocks):
        a.publish(b, bytes([i]), tag=0)
    a.free(blocks)                      # all cached, LRU order 0,1,2
    assert (a.num_free, a.num_cached) == (0, 3)
    got = a.alloc(2)                    # evicts the two oldest entries
    assert got == [blocks[0], blocks[1]]
    assert a.lookup(bytes([0])) is None and a.lookup(bytes([1])) is None
    assert a.lookup(bytes([2])).block == blocks[2]
    assert a.cache_evictions == 2
    a.check()


# ---------------------------------------------------------------------------
# Allocator invariant property tests
# ---------------------------------------------------------------------------

def _apply_ops(num_blocks: int, ops):
    """Drive an allocator through an op stream, mirroring scheduler usage:
    tables = writable views (refs), published = index lifecycle, demote/
    promote = the bit ladder.  After every op the conservation invariant
    ``free + cached + active + packed == num_blocks`` and all internal
    bookkeeping must hold (allocator.check()), and no block may be writable
    (ref == 1, unpublished) from two tables at once."""
    a = BlockAllocator(num_blocks)
    tables = []                          # list of lists: refs held per table
    next_key = 0
    for kind, arg in ops:
        if kind == "alloc":
            got = a.alloc(arg % 3 + 1)
            if got is not None:
                tables.append(got)
        elif kind == "share" and tables:
            src = tables[arg % len(tables)]
            if src:
                b = src[arg % len(src)]
                a.incref(b)
                tables.append([b])
        elif kind == "publish" and tables:
            src = tables[arg % len(tables)]
            if src:
                a.publish(src[arg % len(src)], bytes([next_key % 256, 7]),
                          tag=next_key)
                next_key += 1
        elif kind == "acquire" and next_key:
            key = bytes([arg % max(next_key, 1) % 256, 7])
            e = a.lookup(key)
            if e is not None and e.bits != 8:
                # acquire of a demoted entry must refuse loudly, never
                # hand out a block of packed nibbles
                with pytest.raises(BlockPoolError, match="promote"):
                    a.acquire(key)
            else:
                b = a.acquire(key)
                if b is not None:
                    tables.append([b])
        elif kind == "demote":
            before = a.int4_blocks
            pair = a.demote_oldest_pair()
            if pair is not None:
                key_a, key_b, src_a, src_b, dst = pair
                assert dst == src_a and src_b != src_a
                assert a.int4_blocks == before + 2
                assert a.lookup(key_a).bits == 4
                assert a.lookup(key_b).bits == 4
        elif kind == "promote" and next_key:
            demoted = [bytes([i % 256, 7]) for i in range(next_key)
                       if (e := a.lookup(bytes([i % 256, 7]))) is not None
                       and e.bits == 4]
            if demoted:
                key = demoted[arg % len(demoted)]
                e = a.lookup(key)
                got = a.alloc(1, exclude=(e.block,))
                if got is not None:
                    phys, half = a.promote(key, got[0])
                    assert phys != got[0] and half in (0, 1)
                    assert a.lookup(key).bits == 8
                    assert a.refcount(got[0]) == 1
                    tables.append([got[0]])  # promote() hands over the ref
        elif kind == "cow" and tables:
            # copy-on-write: a table holding a shared/published block swaps
            # it for a fresh private copy
            ti = arg % len(tables)
            if tables[ti]:
                bi = arg % len(tables[ti])
                old = tables[ti][bi]
                if a.is_shared(old) or a.is_published(old):
                    got = a.alloc(1)
                    if got is not None:
                        a.decref(old)
                        tables[ti][bi] = got[0]
        elif kind == "free" and tables:
            for b in tables.pop(arg % len(tables)):
                a.decref(b)
        a.check()
        # every block reachable from >1 table must be refcounted accordingly,
        # so no two tables ever see the same *writable* (ref==1) block
        seen = {}
        for t in tables:
            for b in t:
                seen[b] = seen.get(b, 0) + 1
        for b, n in seen.items():
            assert a.refcount(b) == n, (b, n, a.refcount(b))
            assert n == 1 or a.is_shared(b)
    for t in tables:
        for b in t:
            a.decref(b)
    a.check()
    # nothing leaked: every block free, cached, or holding packed halves
    assert a.num_free + a.num_cached + a.num_packed == num_blocks
    # byte accounting: demoted logical blocks live two to a physical block
    assert a.int4_blocks <= 2 * a.num_packed
    assert a.promotions <= a.demotions    # each promote consumed a demotion


_WALK_KINDS = ["alloc", "share", "publish", "acquire", "cow", "free",
               "demote", "promote"]


def test_allocator_property_seeded_walk():
    """Deterministic random-walk version of the hypothesis property (runs
    even without hypothesis installed)."""
    rng = np.random.default_rng(0)
    for _ in range(25):
        ops = [(_WALK_KINDS[int(rng.integers(len(_WALK_KINDS)))],
                int(rng.integers(1000))) for _ in range(60)]
        _apply_ops(int(rng.integers(2, 12)), ops)


try:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(num_blocks=st.integers(2, 12),
           ops=st.lists(st.tuples(
               st.sampled_from(_WALK_KINDS),
               st.integers(0, 999)), max_size=80))
    def test_allocator_property_hypothesis(num_blocks, ops):
        _apply_ops(num_blocks, ops)
except ImportError:                      # pragma: no cover - optional dep
    pass


# ---------------------------------------------------------------------------
# Copy-on-write device copy
# ---------------------------------------------------------------------------

def test_copy_pool_block_copies_codes_not_scales():
    pcfg = PagedCacheConfig(block_size=4, num_blocks=4, max_batch=2,
                            max_blocks_per_req=2)
    pool = init_paged_cache(CFG, pcfg)
    ent = dict(pool["p0"])
    ent["k_vals"] = ent["k_vals"].at[:, 1].set(7)
    ent["v_scale"] = ent["v_scale"].at[..., 1].set(0.5)   # blocks are lanes
    ent["k_scale"] = ent["k_scale"].at[:, 1].set(3.0)   # slot row, not block
    pool["p0"] = ent
    out = copy_pool_block(pool, 1, 2)
    assert int(jnp.sum(out["p0"]["k_vals"][:, 2] != 7)) == 0
    assert float(jnp.min(out["p0"]["v_scale"][..., 2])) == 0.5
    # slot-scale rows untouched by a block copy
    np.testing.assert_array_equal(np.asarray(out["p0"]["k_scale"]),
                                  np.asarray(pool["p0"]["k_scale"]))
    # source block unchanged
    assert int(jnp.sum(out["p0"]["k_vals"][:, 1] != 7)) == 0


# ---------------------------------------------------------------------------
# Pool layout
# ---------------------------------------------------------------------------

def test_pool_shapes_and_trash_block():
    pcfg = PagedCacheConfig(block_size=8, num_blocks=6, max_batch=3,
                            max_blocks_per_req=4)
    pool = init_paged_cache(CFG, pcfg)
    ent = pool["p0"]
    r = CFG.n_repeats
    assert ent["k_vals"].shape == (r, 7, 8, 2 * 16)    # num_blocks + trash
    assert ent["k_vals"].dtype == jnp.int8
    assert ent["v_scale"].shape == (r, 2, 8, 7)        # blocks are lanes
    assert ent["k_scale"].shape == (r, 3, 2, 16)       # per-slot frozen affine
    assert pcfg.trash_block == 6
    assert pcfg.tokens_per_req == 32


def test_pool_skips_ssm_positions():
    """SSM positions have no sequence axis to page: their fixed-size state
    lives in the state pool (serving/state_pool.py), so the KV block pool
    simply omits them — pure-SSM patterns get an empty pool."""
    cfg = ModelConfig(name="s", vocab_size=64, d_model=64, n_layers=2,
                      n_heads=4, n_kv_heads=2, d_ff=128, ssm_state=16,
                      ssm_head_dim=32,
                      layer_pattern=(LayerSpec("ssm", "none"),
                                     LayerSpec("attn", "dense")))
    pool = init_paged_cache(cfg, PagedCacheConfig())
    assert set(pool) == {"p1"}                     # attention position only
    pure = ModelConfig(name="m", vocab_size=64, d_model=64, n_layers=1,
                       n_heads=1, d_ff=0, ssm_state=16, ssm_head_dim=32,
                       tie_embeddings=True,
                       layer_pattern=(LayerSpec("ssm", "none"),))
    assert init_paged_cache(pure, PagedCacheConfig()) == {}


def test_pool_scales_with_blocks_not_slots():
    """The dense layout pays max_slots * smax regardless of load; the pool
    pays num_blocks * block_size."""
    small = init_paged_cache(CFG, PagedCacheConfig(block_size=8, num_blocks=4,
                                                   max_batch=8))
    big = init_paged_cache(CFG, PagedCacheConfig(block_size=8, num_blocks=32,
                                                 max_batch=8))
    assert paged_cache_nbytes(small) < paged_cache_nbytes(big) / 4


# ---------------------------------------------------------------------------
# Quantization parity with the dense cache
# ---------------------------------------------------------------------------

def _entry0(pool):
    """Strip the repeat axis of pattern position 0 (as lax.scan does)."""
    return jax.tree_util.tree_map(lambda a: a[0], pool["p0"])


def test_chunk_write_matches_dense_prefill_codes():
    """A single full-prompt chunk must produce bit-identical int8 codes and
    scales to the dense gqa_cache_entry path (golden-parity contract)."""
    s, kh, d, t = 16, 2, 16, 8
    k = jax.random.normal(KEY, (1, s, kh, d), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(1), (1, s, kh, d), jnp.bfloat16)
    dense = gqa_cache_entry(k, v, smax=s)

    pcfg = PagedCacheConfig(block_size=t, num_blocks=4, max_batch=2,
                            max_blocks_per_req=2)
    entry = _entry0(init_paged_cache(CFG, pcfg))
    block_row = jnp.asarray([0, 1], jnp.int32)
    entry = gqa_chunk_write(entry, k[0], v[0], slot=jnp.int32(0),
                            block_row=block_row, ctx=jnp.int32(0),
                            chunk_len=jnp.int32(s), block_size=t,
                            is_first=True)
    got_k = np.asarray(entry["k_vals"][block_row]).reshape(s, kh, d)
    got_v = np.asarray(entry["v_vals"][block_row]).reshape(s, kh, d)
    np.testing.assert_array_equal(got_k, np.asarray(dense["k_vals"][0]))
    np.testing.assert_array_equal(got_v, np.asarray(dense["v_vals"][0]))
    np.testing.assert_array_equal(np.asarray(entry["k_scale"][0]),
                                  np.asarray(dense["k_scale"][0, 0]))
    np.testing.assert_array_equal(np.asarray(entry["k_zero"][0]),
                                  np.asarray(dense["k_zero"][0, 0]))
    got_vs = np.asarray(entry["v_scale"][..., block_row]).transpose(
        2, 1, 0).reshape(s, kh, 1)
    np.testing.assert_array_equal(got_vs, np.asarray(dense["v_scale"][0]))


def test_chunk_write_pad_lanes_go_to_trash():
    """Padding lanes of a short chunk land in the trash block, and the
    frozen K range is computed over valid tokens only."""
    s, c, kh, d, t = 5, 8, 2, 16, 4
    k = jax.random.normal(KEY, (c, kh, d))
    v = jax.random.normal(jax.random.PRNGKey(1), (c, kh, d))
    # plant a huge outlier in a padding lane: must NOT blow up the K range
    k = k.at[s + 1].set(1000.0)
    pcfg = PagedCacheConfig(block_size=t, num_blocks=3, max_batch=1,
                            max_blocks_per_req=3)
    entry = _entry0(init_paged_cache(CFG, pcfg))
    row = jnp.asarray([0, 1, pcfg.trash_block], jnp.int32)
    entry = gqa_chunk_write(entry, k, v, slot=jnp.int32(0), block_row=row,
                            ctx=jnp.int32(0), chunk_len=jnp.int32(s),
                            block_size=t, is_first=True)
    assert float(jnp.max(entry["k_scale"][0])) < 1.0   # outlier excluded
    # valid tokens 0..4 occupy block 0 fully + block 1 token 0
    assert int(jnp.sum(jnp.abs(entry["k_vals"][1, 1:]))) == 0


def test_append_then_gather_roundtrip():
    """Decode-append a token, gather the prefix back, check dequantization."""
    kh, d, t = 2, 16, 4
    pcfg = PagedCacheConfig(block_size=t, num_blocks=4, max_batch=2,
                            max_blocks_per_req=2)
    entry = _entry0(init_paged_cache(CFG, pcfg))
    # freeze scales with a first chunk of 3 tokens
    k0 = jax.random.normal(KEY, (4, kh, d))
    v0 = jax.random.normal(jax.random.PRNGKey(1), (4, kh, d))
    row = jnp.asarray([0, 1], jnp.int32)
    entry = gqa_chunk_write(entry, k0, v0, slot=jnp.int32(0), block_row=row,
                            ctx=jnp.int32(0), chunk_len=jnp.int32(3),
                            block_size=t, is_first=True)
    # append token 3, clamped into the frozen per-channel range (out-of-range
    # values clip by design — paper Eq. 1, same contract as the dense cache)
    tables = jnp.asarray([[0, 1], [2, pcfg.trash_block]], jnp.int32)
    lengths = jnp.asarray([3, 0], jnp.int32)
    kmin = (-128.0 - entry["k_zero"][0]) * entry["k_scale"][0]
    kmax = (127.0 - entry["k_zero"][0]) * entry["k_scale"][0]
    k_t = jnp.clip(jax.random.normal(jax.random.PRNGKey(2), (2, kh, d)),
                   kmin, kmax)
    v_t = jax.random.normal(jax.random.PRNGKey(3), (2, kh, d))
    entry = gqa_paged_append(entry, k_t, v_t, tables, lengths, block_size=t)
    k_re, v_re = gqa_gather_prefix(entry, row, jnp.int32(0), jnp.float32)
    np.testing.assert_allclose(np.asarray(v_re[3]), np.asarray(v_t[0]),
                               atol=0.02)
    np.testing.assert_allclose(np.asarray(k_re[3]), np.asarray(k_t[0]),
                               atol=0.1)
