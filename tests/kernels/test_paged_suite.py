"""Paged kernel suite (verify + chunk prefill) vs ref.py oracles — exact.

The suite's kernels buffer the dequantized prefix in VMEM and run a one-shot
softmax, which is the *same float path* as the dense-gather oracles — so
interpret-mode parity is asserted bitwise (assert_array_equal), not approx.
Covered: GQA + MLA, ragged per-lane lengths, a lane exactly at a block
boundary, gamma spanning a block edge, vlens-masked (trash) lanes, and a
1-token verify lane equal to plain decode.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.methods.simquant import quantize_kv
from repro.core.qtensor import pack_nibbles
from repro.kernels import ref
from repro.kernels.kv_decode_attention import paged_kv_decode_attention
from repro.kernels.paged_attention import (mla_paged_prefix_chunk_attention,
                                           mla_paged_verify_attention,
                                           paged_kv_verify_attention,
                                           paged_prefix_chunk_attention)

KEY = jax.random.PRNGKey(0)


def _stored(k_codes, k_scale, k_zero, v_codes, v_scale, v_zero):
    """Token-major pool leaves (N, T, KH, Dp) / (N, T, KH, 1) in the stored
    layout: code rows (N, T, KH*Dp), V affine (KH, T, N) with blocks as
    lanes."""
    n, t = k_codes.shape[:2]
    return (k_codes.reshape(n, t, -1), k_scale, k_zero,
            v_codes.reshape(n, t, -1), v_scale.reshape(n, t, -1).T,
            v_zero.reshape(n, t, -1).T)


def _gqa_pool(b, kh, d, n, t, seed=1):
    k_pool = jax.random.normal(jax.random.PRNGKey(seed), (1, n * t, kh, d))
    v_pool = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, n * t, kh, d))
    qk, qv = quantize_kv(k_pool, v_pool)
    k_scale = (jnp.broadcast_to(qk.scale[0], (b, kh, d))
               * jnp.linspace(0.9, 1.1, b)[:, None, None])
    k_zero = jnp.broadcast_to(qk.zero[0], (b, kh, d))
    return _stored(qk.values.reshape(n, t, kh, d), k_scale, k_zero,
                   qv.values.reshape(n, t, kh, d), qv.scale, qv.zero)


def _mla_pool(b, rkv, dr, n, t, seed=3):
    rs = np.random.RandomState(seed)
    c_vals = jnp.asarray(rs.randint(-128, 128, size=(n, t, rkv)), jnp.int8)
    kr_vals = jnp.asarray(rs.randint(-128, 128, size=(n, t, dr)), jnp.int8)
    return c_vals, _token_scales(rs, n, t), kr_vals, _token_scales(rs, n, t)


def _token_scales(rs, n, t):
    """Per-token latent scales (T, N), blocks as lanes, one value each."""
    return jnp.asarray(rs.uniform(0.01, 0.05, size=(t, n)), jnp.float32)


# lengths exercise: lane 0 exactly at a block boundary (gamma spans the block
# edge mid-verify), lane 1 ragged mid-block, lane 2 short; the last lane is a
# vlens-masked decoy whose table row points at the trash block with length 0.
def _tables_and_lengths(b, n, m, t, rs):
    tables = rs.randint(0, n - 1, size=(b, m)).astype(np.int32)
    lengths = rs.randint(1, (m - 1) * t, size=(b,)).astype(np.int32)
    lengths[0] = t                      # block boundary; verify crosses edge
    if b > 1:
        lengths[1] = t + t // 2
    tables[-1, :] = n - 1               # trash lane
    lengths[-1] = 0
    return jnp.asarray(tables), jnp.asarray(lengths)


@pytest.mark.parametrize("b,h,kh,d,n,t,m,g", [(3, 8, 4, 32, 10, 16, 4, 3),
                                              (4, 4, 1, 64, 6, 8, 3, 5),
                                              (2, 6, 2, 16, 5, 4, 5, 2)])
def test_paged_verify_attention_exact(b, h, kh, d, n, t, m, g):
    q = jax.random.normal(KEY, (b, g, h, d))
    kv = _gqa_pool(b, kh, d, n, t)
    rs = np.random.RandomState(0)
    tables, lengths = _tables_and_lengths(b, n, m, t, rs)
    out = paged_kv_verify_attention(q, *kv, tables, lengths, interpret=True)
    outr = ref.paged_kv_verify_attention_ref(q, *kv, tables, lengths)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(outr))


def test_paged_verify_one_token_equals_plain_decode():
    """A G=1 verify is exactly a plain decode launch at lengths+1."""
    b, h, kh, d, n, t, m = 2, 8, 4, 32, 10, 16, 4
    q = jax.random.normal(KEY, (b, 1, h, d))
    kv = _gqa_pool(b, kh, d, n, t)
    rs = np.random.RandomState(1)
    tables = jnp.asarray(rs.randint(0, n, size=(b, m)), jnp.int32)
    lengths = jnp.asarray([t - 1, 2 * t], jnp.int32)
    out = paged_kv_verify_attention(q, *kv, tables, lengths, interpret=True)
    plain = ref.paged_kv_decode_attention_ref(q[:, 0], *kv, tables,
                                              lengths + 1)
    np.testing.assert_array_equal(np.asarray(out[:, 0]), np.asarray(plain))
    outr = ref.paged_kv_verify_attention_ref(q, *kv, tables, lengths)
    np.testing.assert_array_equal(np.asarray(outr[:, 0]), np.asarray(plain))


@pytest.mark.parametrize("b,h,rkv,dn,dr,n,t,m,g", [(3, 4, 16, 16, 8, 8, 16, 3, 3),
                                                   (2, 2, 8, 8, 4, 5, 4, 4, 2)])
def test_mla_paged_verify_attention_exact(b, h, rkv, dn, dr, n, t, m, g):
    dv = dn
    q_nope = jax.random.normal(KEY, (b, g, h, dn))
    q_rope = jax.random.normal(jax.random.PRNGKey(7), (b, g, h, dr))
    w_uk = jax.random.normal(jax.random.PRNGKey(8), (rkv, h, dn))
    w_uv = jax.random.normal(jax.random.PRNGKey(9), (rkv, h, dv))
    pool = _mla_pool(b, rkv, dr, n, t)
    rs = np.random.RandomState(2)
    tables, lengths = _tables_and_lengths(b, n, m, t, rs)
    # kernel path: fold W_uk / W_uv per position exactly like ops dispatch
    f32 = jnp.float32
    q_lat = jnp.stack([jnp.einsum("bhd,rhd->bhr", q_nope[:, j].astype(f32),
                                  w_uk.astype(f32)) for j in range(g)], axis=1)
    o_lat = mla_paged_verify_attention(q_lat, q_rope, *pool, tables, lengths,
                                       qk_nope_dim=dn, interpret=True)
    out = jnp.stack([jnp.einsum("bhr,rhd->bhd", o_lat[:, j],
                                w_uv.astype(f32)) for j in range(g)], axis=1)
    outr = ref.mla_paged_verify_attention_ref(q_nope, q_rope, w_uk, w_uv,
                                              *pool, tables, lengths)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(outr))


@pytest.mark.parametrize("ctx_kind", ["mid_block", "block_edge", "full"])
@pytest.mark.parametrize("b_unused,h,kh,d,n,t,m,c", [(1, 8, 4, 32, 10, 16, 4, 16),
                                                     (1, 6, 2, 16, 5, 4, 3, 8)])
def test_paged_prefix_chunk_attention_exact(ctx_kind, b_unused, h, kh, d, n,
                                            t, m, c):
    kv = _gqa_pool(1, kh, d, n, t)
    k_vals, k_scale, k_zero, v_vals, v_scale, v_zero = kv
    k_scale, k_zero = k_scale[0], k_zero[0]               # slot rows (KH, D)
    q = jax.random.normal(KEY, (1, c, h, d))
    k_chunk = jax.random.normal(jax.random.PRNGKey(11), (1, c, kh, d))
    v_chunk = jax.random.normal(jax.random.PRNGKey(12), (1, c, kh, d))
    rs = np.random.RandomState(3)
    block_row = jnp.asarray(rs.randint(0, n, size=(m,)), jnp.int32)
    ctx = {"mid_block": t + 3, "block_edge": 2 * t, "full": m * t}[ctx_kind]
    ctx = jnp.asarray(min(ctx, m * t), jnp.int32)
    args = (q, k_vals, k_scale, k_zero, v_vals, v_scale, v_zero,
            k_chunk, v_chunk, block_row, ctx)
    out = paged_prefix_chunk_attention(*args, interpret=True)
    outr = ref.paged_prefix_chunk_attention_ref(*args)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(outr))


def _relocate(kv, ids, n_wide, seed=10):
    """The pool ``kv`` with its blocks moved to ids ``ids`` of an
    ``n_wide``-block pool; every other block holds random codes and NaN V
    affine, which no kernel may read."""
    k_vals, k_scale, k_zero, v_vals, v_scale, v_zero = kv
    rs = np.random.RandomState(seed)
    codes = lambda x: jnp.asarray(
        rs.randint(-128, 128, size=(n_wide,) + x.shape[1:]), x.dtype
    ).at[ids].set(x)
    lanes = lambda x: jnp.full(x.shape[:-1] + (n_wide,), jnp.nan,
                               x.dtype).at[..., ids].set(x)
    return (codes(k_vals), k_scale, k_zero, codes(v_vals), lanes(v_scale),
            lanes(v_zero))


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("kernel", ["decode", "verify", "chunk"])
def test_paged_kernels_read_blocks_past_first_scale_tile(kernel, bits):
    """The V scale/zero leaves keep blocks as lanes, read 128 to a tile: a
    pool whose blocks sit past the first tile, one of them in a partial last
    tile, gives bitwise the output of the same blocks at low ids."""
    b, h, kh, d, n, t, m, g, c = 3, 4, 2, 16, 6, 4, 4, 2, 8
    kv = (_gqa_pool if bits == 8 else _gqa_pool_int4)(b, kh, d, n, t)
    ids = jnp.asarray([1, 127, 128, 200, 256, 299], jnp.int32)
    wide = _relocate(kv, ids, 300)
    rs = np.random.RandomState(11)
    tables = jnp.asarray(rs.randint(0, n, size=(b, m)), jnp.int32)
    lengths = jnp.asarray([t, t + t // 2, (m - 1) * t], jnp.int32)
    if kernel == "decode":
        run = lambda pool, tb: paged_kv_decode_attention(
            jax.random.normal(KEY, (b, h, d)), *pool, tb, lengths,
            interpret=True)
    elif kernel == "verify":
        run = lambda pool, tb: paged_kv_verify_attention(
            jax.random.normal(KEY, (b, g, h, d)), *pool, tb, lengths,
            interpret=True)
    else:
        def run(pool, tb):
            k_vals, k_scale, k_zero, v_vals, v_scale, v_zero = pool
            return paged_prefix_chunk_attention(
                jax.random.normal(KEY, (1, c, h, d)), k_vals, k_scale[0],
                k_zero[0], v_vals, v_scale, v_zero,
                jax.random.normal(jax.random.PRNGKey(11), (1, c, kh, d)),
                jax.random.normal(jax.random.PRNGKey(12), (1, c, kh, d)),
                tb[0], jnp.asarray(t + 3, jnp.int32), interpret=True)
    out = np.asarray(run(wide, ids[tables]))
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out, np.asarray(run(kv, tables)))


@pytest.mark.parametrize("ctx_val", [5, 16, 44])
def test_mla_paged_prefix_chunk_attention_exact(ctx_val):
    h, rkv, dn, dr, n, t, m, c = 4, 16, 16, 8, 8, 16, 3, 12
    pool = _mla_pool(1, rkv, dr, n, t)
    q_lat = jax.random.normal(KEY, (1, c, h, rkv))
    q_rope = jax.random.normal(jax.random.PRNGKey(13), (1, c, h, dr))
    c_chunk = jax.random.normal(jax.random.PRNGKey(14), (1, c, rkv))
    kr_chunk = jax.random.normal(jax.random.PRNGKey(15), (1, c, dr))
    rs = np.random.RandomState(4)
    block_row = jnp.asarray(rs.randint(0, n, size=(m,)), jnp.int32)
    ctx = jnp.asarray(ctx_val, jnp.int32)
    args = (q_lat, q_rope, *pool, c_chunk, kr_chunk, block_row, ctx)
    out = mla_paged_prefix_chunk_attention(*args, qk_nope_dim=dn,
                                           interpret=True)
    outr = ref.mla_paged_prefix_chunk_attention_ref(*args, qk_nope_dim=dn)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(outr))


# ---------------------------------------------------------------------------
# Packed-int4 pools (cache codec): kernels infer the codec from the pool's
# halved last dim and must stay bitwise equal to the unpacking oracles.
# ---------------------------------------------------------------------------

def _gqa_pool_int4(b, kh, d, n, t, seed=5):
    rs = np.random.RandomState(seed)
    k_codes = jnp.asarray(rs.randint(-8, 8, size=(n, t, kh, d)), jnp.int8)
    v_codes = jnp.asarray(rs.randint(-8, 8, size=(n, t, kh, d)), jnp.int8)
    k_scale = jnp.asarray(rs.uniform(0.02, 0.06, size=(b, kh, d)), jnp.float32)
    k_zero = jnp.asarray(rs.uniform(-2, 2, size=(b, kh, d)), jnp.float32)
    v_scale = jnp.asarray(rs.uniform(0.02, 0.06, size=(n, t, kh, 1)),
                          jnp.float32)
    v_zero = jnp.asarray(rs.uniform(-2, 2, size=(n, t, kh, 1)), jnp.float32)
    return _stored(pack_nibbles(k_codes), k_scale, k_zero,
                   pack_nibbles(v_codes), v_scale, v_zero)


def _mla_pool_int4(b, rkv, dr, n, t, seed=6):
    rs = np.random.RandomState(seed)
    c_vals = pack_nibbles(jnp.asarray(rs.randint(-8, 8, size=(n, t, rkv)),
                                      jnp.int8))
    kr_vals = pack_nibbles(jnp.asarray(rs.randint(-8, 8, size=(n, t, dr)),
                                       jnp.int8))
    return c_vals, _token_scales(rs, n, t), kr_vals, _token_scales(rs, n, t)


@pytest.mark.parametrize("b,h,kh,d,n,t,m", [(3, 8, 4, 32, 10, 16, 4),
                                            (2, 6, 2, 16, 5, 4, 5)])
def test_paged_decode_attention_int4_exact(b, h, kh, d, n, t, m):
    q = jax.random.normal(KEY, (b, h, d))
    kv = _gqa_pool_int4(b, kh, d, n, t)
    assert kv[0].shape[-1] == kh * d // 2       # really packed
    rs = np.random.RandomState(5)
    tables = jnp.asarray(rs.randint(0, n, size=(b, m)), jnp.int32)
    lengths = jnp.asarray(rs.randint(1, m * t + 1, size=(b,)), jnp.int32)
    out = paged_kv_decode_attention(q, *kv, tables, lengths, interpret=True)
    outr = ref.paged_kv_decode_attention_ref(q, *kv, tables, lengths)
    # decode streams an online softmax (different accumulation order from the
    # one-shot oracle), so parity is allclose like the int8 sweep — the
    # nibble unpack itself is exact (the verify/chunk tests assert bitwise)
    np.testing.assert_allclose(np.asarray(out), np.asarray(outr),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("b,h,kh,d,n,t,m,g", [(3, 8, 4, 32, 10, 16, 4, 3),
                                              (2, 6, 2, 16, 5, 4, 5, 2)])
def test_paged_verify_attention_int4_exact(b, h, kh, d, n, t, m, g):
    q = jax.random.normal(KEY, (b, g, h, d))
    kv = _gqa_pool_int4(b, kh, d, n, t)
    rs = np.random.RandomState(6)
    tables, lengths = _tables_and_lengths(b, n, m, t, rs)
    out = paged_kv_verify_attention(q, *kv, tables, lengths, interpret=True)
    outr = ref.paged_kv_verify_attention_ref(q, *kv, tables, lengths)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(outr))


@pytest.mark.parametrize("ctx_val", [3, 16, 40])
def test_paged_prefix_chunk_attention_int4_exact(ctx_val):
    h, kh, d, n, t, m, c = 8, 4, 32, 10, 16, 4, 16
    kv = _gqa_pool_int4(1, kh, d, n, t)
    k_vals, k_scale, k_zero, v_vals, v_scale, v_zero = kv
    k_scale, k_zero = k_scale[0], k_zero[0]               # slot rows (KH, D)
    q = jax.random.normal(KEY, (1, c, h, d))
    k_chunk = jax.random.normal(jax.random.PRNGKey(21), (1, c, kh, d))
    v_chunk = jax.random.normal(jax.random.PRNGKey(22), (1, c, kh, d))
    rs = np.random.RandomState(7)
    block_row = jnp.asarray(rs.randint(0, n, size=(m,)), jnp.int32)
    ctx = jnp.asarray(min(ctx_val, m * t), jnp.int32)
    args = (q, k_vals, k_scale, k_zero, v_vals, v_scale, v_zero,
            k_chunk, v_chunk, block_row, ctx)
    out = paged_prefix_chunk_attention(*args, interpret=True)
    outr = ref.paged_prefix_chunk_attention_ref(*args)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(outr))


def test_mla_paged_verify_attention_int4_exact():
    b, h, rkv, dn, dr, n, t, m, g = 3, 4, 16, 16, 8, 8, 16, 3, 3
    q_nope = jax.random.normal(KEY, (b, g, h, dn))
    q_rope = jax.random.normal(jax.random.PRNGKey(7), (b, g, h, dr))
    w_uk = jax.random.normal(jax.random.PRNGKey(8), (rkv, h, dn))
    w_uv = jax.random.normal(jax.random.PRNGKey(9), (rkv, h, dn))
    pool = _mla_pool_int4(b, rkv, dr, n, t)
    rs = np.random.RandomState(8)
    tables, lengths = _tables_and_lengths(b, n, m, t, rs)
    f32 = jnp.float32
    q_lat = jnp.stack([jnp.einsum("bhd,rhd->bhr", q_nope[:, j].astype(f32),
                                  w_uk.astype(f32)) for j in range(g)], axis=1)
    o_lat = mla_paged_verify_attention(q_lat, q_rope, *pool, tables, lengths,
                                       qk_nope_dim=dn, interpret=True)
    out = jnp.stack([jnp.einsum("bhr,rhd->bhd", o_lat[:, j],
                                w_uv.astype(f32)) for j in range(g)], axis=1)
    outr = ref.mla_paged_verify_attention_ref(q_nope, q_rope, w_uk, w_uv,
                                              *pool, tables, lengths)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(outr))


def test_mla_paged_prefix_chunk_attention_int4_exact():
    h, rkv, dn, dr, n, t, m, c = 4, 16, 16, 8, 8, 16, 3, 12
    pool = _mla_pool_int4(1, rkv, dr, n, t)
    q_lat = jax.random.normal(KEY, (1, c, h, rkv))
    q_rope = jax.random.normal(jax.random.PRNGKey(13), (1, c, h, dr))
    c_chunk = jax.random.normal(jax.random.PRNGKey(14), (1, c, rkv))
    kr_chunk = jax.random.normal(jax.random.PRNGKey(15), (1, c, dr))
    rs = np.random.RandomState(9)
    block_row = jnp.asarray(rs.randint(0, n, size=(m,)), jnp.int32)
    ctx = jnp.asarray(16, jnp.int32)
    args = (q_lat, q_rope, *pool, c_chunk, kr_chunk, block_row, ctx)
    out = mla_paged_prefix_chunk_attention(*args, qk_nope_dim=dn,
                                           interpret=True)
    outr = ref.mla_paged_prefix_chunk_attention_ref(*args, qk_nope_dim=dn)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(outr))
