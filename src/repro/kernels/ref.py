"""Pure-jnp oracles for every Pallas kernel (the contract each kernel must
match under assert_allclose in tests/kernels/)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.qtensor import storage_dtype, unpack_nibbles

NEG_INF = -2.0e38


def _unpack_pool(pool: jax.Array, full_dim: int) -> jax.Array:
    """Nibble-unpack a paged-pool code leaf when the codec packed it.

    The pool stores codes in its last dim; a packed-INT4 pool halves that
    dim while the matching scale/query keeps ``full_dim``.  Unpacking is
    elementwise per byte, so doing it before the block-table gather is
    exact — the same integer ops the Pallas kernels run in-register."""
    if pool.shape[-1] == full_dim:
        return pool
    return unpack_nibbles(pool)


def _pool_heads(pool: jax.Array, kh: int, full_dim: int) -> jax.Array:
    """(N, T, KH*Dp) code rows -> (N, T, KH, D) codes, nibble-unpacked when
    the codec packed them."""
    return _unpack_pool(pool.reshape(*pool.shape[:-1], kh, -1), full_dim)


def _lane_rows(pool: jax.Array, ids: jax.Array) -> jax.Array:
    """Blocks ``ids`` of a (KH, T, N) V scale/zero pool, whose blocks are its
    lanes, as (*ids.shape, T, KH, 1): the layout of the gathered codes."""
    x = pool[:, :, ids]                                   # (KH, T, *ids)
    return jnp.moveaxis(x, (0, 1), (-1, -2))[..., None]


def fused_quant_ref(x: jax.Array, eps: float = 1e-8):
    """Row-wise dynamic symmetric INT8 quantization (paper Alg. 1 lines 2+5).

    x: (M, K) -> (q int8 (M,K), scale f32 (M,1))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, eps) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                 -128, 127).astype(storage_dtype(8))
    return q, scale


def w8a8_matmul_ref(q_x: jax.Array, x_scale: jax.Array,
                    q_w: jax.Array, w_scale: jax.Array,
                    out_dtype=jnp.float32) -> jax.Array:
    """INT8 x INT8 -> INT32 GEMM with affine rescale (paper Alg. 2 QuantGEMMFused).

    q_x: (M,K) int8; x_scale: (M,1) f32; q_w: (K,N) int8; w_scale: (1,N) f32.

    Uses a native int8 dot with int32 accumulation (no widened operand
    materialization — the roofline found 70 GB/step of s32 weight converts
    with the astype formulation).
    """
    acc = jax.lax.dot_general(q_x, q_w, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * x_scale * w_scale).astype(out_dtype)


def expert_gemm_ref(x: jax.Array, x_scale: jax.Array, w: jax.Array,
                    w_scale: jax.Array, tile_expert: jax.Array,
                    used: jax.Array, *, tm: int,
                    out_dtype=jnp.float32) -> jax.Array:
    """Grouped INT8 GEMM over rows sorted by expert (``expert_gemm``'s
    contract): row tile i (``tm`` rows) of ``x`` (R, K) int8 times expert
    ``tile_expert[i]``'s (K, N) codes of ``w`` (E, K, N) into int32, then
    the row scale and the expert's (1, N) scale; rows past ``used * tm``
    are zero."""
    e = w.shape[0]
    live = jnp.arange(tile_expert.shape[0]) < used[0]
    sizes = tm * jnp.sum((tile_expert[None, :] == jnp.arange(e)[:, None])
                         & live[None, :], axis=1).astype(jnp.int32)
    acc = jax.lax.ragged_dot(x, w, sizes, preferred_element_type=jnp.int32)
    rows = jnp.repeat(tile_expert, tm)
    out = acc.astype(jnp.float32) * x_scale * w_scale[rows, 0]
    keep = jnp.repeat(live, tm)[:, None]
    return jnp.where(keep, out, 0.0).astype(out_dtype)


def quant_gemm_fused_ref(x: jax.Array, q_w: jax.Array, w_scale: jax.Array,
                         out_dtype=jnp.float32) -> jax.Array:
    """End-to-end fused path: dynamic act quant + INT8 GEMM (Alg. 1 + Alg. 2)."""
    q_x, x_scale = fused_quant_ref(x)
    return w8a8_matmul_ref(q_x, x_scale, q_w, w_scale, out_dtype)


def kv_decode_attention_ref(q: jax.Array,
                            k_vals: jax.Array, k_scale: jax.Array, k_zero: jax.Array,
                            v_vals: jax.Array, v_scale: jax.Array, v_zero: jax.Array,
                            length: jax.Array) -> jax.Array:
    """SimQuant INT8-cache decode attention (oracle shared with the model).

    q: (B,H,D); k_vals/v_vals: (B,S,KH,D) int8; k_scale/k_zero: (B,1,KH,D);
    v_scale/v_zero: (B,S,KH,1); length: (B,) -> (B,H,D).
    """
    from repro.models.attention import decode_attention_ref
    return decode_attention_ref(q, k_vals, k_scale, k_zero,
                                v_vals, v_scale, v_zero, length)


def paged_kv_decode_attention_ref(q: jax.Array,
                                  k_vals: jax.Array, k_scale: jax.Array,
                                  k_zero: jax.Array, v_vals: jax.Array,
                                  v_scale: jax.Array, v_zero: jax.Array,
                                  block_tables: jax.Array,
                                  lengths: jax.Array) -> jax.Array:
    """Paged-pool oracle: gather blocks into the dense layout, then reuse the
    dense oracle (identical float path — the scheduler's golden-parity tests
    rely on this).

    q: (B,H,D); k_vals/v_vals: (N,T,KH*Dp) code pool (Dp == D for INT8,
    D // 2 nibble-packed for INT4); v_scale/v_zero: (KH,T,N); k_scale/
    k_zero: (B,KH,D) per-slot; block_tables: (B,M); lengths: (B,) -> (B,H,D).
    """
    b, m = block_tables.shape
    t = k_vals.shape[1]
    kh, d = k_scale.shape[-2], q.shape[-1]
    k_vals = _pool_heads(k_vals, kh, d)
    v_vals = _pool_heads(v_vals, kh, d)
    gather = lambda pool: pool[block_tables].reshape(b, m * t, *pool.shape[2:])
    scales = lambda pool: _lane_rows(pool, block_tables).reshape(
        b, m * t, kh, 1)
    return kv_decode_attention_ref(
        q, gather(k_vals), k_scale[:, None], k_zero[:, None],
        gather(v_vals), scales(v_scale), scales(v_zero), lengths)


def paged_kv_verify_attention_ref(q: jax.Array,
                                  k_vals: jax.Array, k_scale: jax.Array,
                                  k_zero: jax.Array, v_vals: jax.Array,
                                  v_scale: jax.Array, v_zero: jax.Array,
                                  block_tables: jax.Array,
                                  lengths: jax.Array) -> jax.Array:
    """Multi-token spec-decode verify oracle: one pool gather shared by all
    G positions, each scored at its own causal length ``lengths + j + 1``.
    Position j's attention is op-for-op the decode oracle at that length, so
    verify stays bit-identical to G sequential decode steps (the greedy
    spec-decode golden contract); hoisting the gather out of the j loop is
    exact because every position reads the same post-append pool.

    q: (B,G,H,D); pool leaves as in ``paged_kv_decode_attention_ref``;
    lengths: (B,) pre-verify context lengths -> (B,G,H,D).
    """
    b, m = block_tables.shape
    t = k_vals.shape[1]
    g = q.shape[1]
    kh, d = k_scale.shape[-2], q.shape[-1]
    k_vals = _pool_heads(k_vals, kh, d)
    v_vals = _pool_heads(v_vals, kh, d)
    gather = lambda pool: pool[block_tables].reshape(b, m * t, *pool.shape[2:])
    scales = lambda pool: _lane_rows(pool, block_tables).reshape(
        b, m * t, kh, 1)
    kg, vg = gather(k_vals), gather(v_vals)
    vsg, vzg = scales(v_scale), scales(v_zero)
    ks, kz = k_scale[:, None], k_zero[:, None]
    outs = [kv_decode_attention_ref(q[:, j], kg, ks, kz, vg, vsg, vzg,
                                    lengths + j + 1)
            for j in range(g)]
    return jnp.stack(outs, axis=1)


def _token_rows(pool: jax.Array, ids: jax.Array) -> jax.Array:
    """Blocks ``ids`` of a (T, N) MLA per-token scale pool, whose blocks are
    its lanes, as (*ids.shape, T, 1): the layout of the gathered codes."""
    return jnp.moveaxis(pool[:, ids], 0, -1)[..., None]


def _gather_latent(c_vals, c_scale, kr_vals, kr_scale, block_tables,
                   rkv: int, dr: int):
    """Every lane's block table gathered into dense rows: codes (B, M*T,
    width) and their per-token scales (B, M*T, 1)."""
    b, m = block_tables.shape
    t = c_vals.shape[1]
    codes = lambda pool, w: _unpack_pool(pool, w)[block_tables].reshape(
        b, m * t, w)
    scales = lambda pool: _token_rows(pool, block_tables).reshape(b, m * t, 1)
    return (codes(c_vals, rkv), scales(c_scale), codes(kr_vals, dr),
            scales(kr_scale))


def mla_paged_verify_attention_ref(q_nope: jax.Array, q_rope: jax.Array,
                                   w_uk: jax.Array, w_uv: jax.Array,
                                   c_vals: jax.Array, c_scale: jax.Array,
                                   kr_vals: jax.Array, kr_scale: jax.Array,
                                   block_tables: jax.Array,
                                   lengths: jax.Array) -> jax.Array:
    """MLA multi-token verify oracle (absorbed latent-space attention).

    q_nope: (B,G,H,dn); q_rope: (B,G,H,dr); c_vals: (N,T,rkv) int8 latent
    pool with per-token scales c_scale: (T,N); kr_vals: (N,T,dr) int8 rope
    keys with kr_scale: (T,N); block_tables: (B,M); lengths: (B,) ->
    (B,G,H,dv).  Same hoisted-gather construction as the GQA verify oracle,
    delegating per position to ``mla_decode_ref``.
    """
    from repro.models.mla import mla_decode_ref
    g = q_nope.shape[1]
    cg, csg, krg, krsg = _gather_latent(c_vals, c_scale, kr_vals, kr_scale,
                                        block_tables, w_uk.shape[0],
                                        q_rope.shape[-1])
    outs = [mla_decode_ref(q_nope[:, j], q_rope[:, j], cg, csg, krg, krsg,
                           w_uk, w_uv, lengths + j + 1, None)
            for j in range(g)]
    return jnp.stack(outs, axis=1)


def mla_paged_decode_attention_ref(q_nope: jax.Array, q_rope: jax.Array,
                                   w_uk: jax.Array, w_uv: jax.Array,
                                   c_vals: jax.Array, c_scale: jax.Array,
                                   kr_vals: jax.Array, kr_scale: jax.Array,
                                   block_tables: jax.Array,
                                   lengths: jax.Array) -> jax.Array:
    """MLA paged decode oracle: gather every lane's block table into dense
    rows, then ``mla_decode_ref``.  q_nope: (B,H,dn); q_rope: (B,H,dr);
    lengths: (B,) tokens attended -> (B,H,dv)."""
    from repro.models.mla import mla_decode_ref
    cg, csg, krg, krsg = _gather_latent(c_vals, c_scale, kr_vals, kr_scale,
                                        block_tables, w_uk.shape[0],
                                        q_rope.shape[-1])
    return mla_decode_ref(q_nope, q_rope, cg, csg, krg, krsg, w_uk, w_uv,
                          lengths, None)


def paged_prefix_chunk_attention_ref(q: jax.Array,
                                     k_vals: jax.Array, k_scale: jax.Array,
                                     k_zero: jax.Array, v_vals: jax.Array,
                                     v_scale: jax.Array, v_zero: jax.Array,
                                     k_chunk: jax.Array, v_chunk: jax.Array,
                                     block_row: jax.Array,
                                     ctx: jax.Array) -> jax.Array:
    """Chunk-prefill attention against the INT8 block pool (one request).

    The chunk's C queries attend to (a) the request's cached prefix, read
    straight from the pool through its block-table row and dequantized with
    the slot's frozen K affine / per-token V affine, and (b) the chunk's own
    fresh fp K/V under a causal mask.  Pool positions >= ctx are masked (the
    chunk was already written into the pool before attention runs), padding
    query lanes just see their causal prefix and are never read.

    q: (1,C,H,D); k_vals/v_vals: (N,T,KH*D) int8 pool; k_scale/k_zero:
    (KH,D) the slot's frozen affine; v_scale/v_zero: (KH,T,N);
    k_chunk/v_chunk: (1,C,KH,D) fp; block_row: (M,); ctx: () int32
    -> (1,C,H,D) f32.
    """
    c, h, d = q.shape[1], q.shape[2], q.shape[3]
    kh = k_chunk.shape[2]
    g = h // kh
    m, t = block_row.shape[0], k_vals.shape[1]
    k_vals = _pool_heads(k_vals, kh, d)
    v_vals = _pool_heads(v_vals, kh, d)
    f32 = jnp.float32
    k_pre = ((k_vals[block_row].astype(f32) - k_zero.astype(f32))
             * k_scale.astype(f32)).reshape(m * t, kh, d)
    v_pre = ((v_vals[block_row].astype(f32) - _lane_rows(v_zero, block_row))
             * _lane_rows(v_scale, block_row)).reshape(m * t, kh, d)
    k_all = jnp.concatenate([k_pre, k_chunk[0].astype(f32)], axis=0)
    v_all = jnp.concatenate([v_pre, v_chunk[0].astype(f32)], axis=0)
    qg = q[0].reshape(c, kh, g, d).astype(f32) / jnp.sqrt(d).astype(f32)
    s = jnp.einsum("chgd,shd->hgcs", qg, k_all,
                   preferred_element_type=jnp.float32)
    col = jnp.arange(m * t + c)
    keep = jnp.where(col[None, :] < m * t, col[None, :] < ctx,
                     col[None, :] - m * t <= jnp.arange(c)[:, None])
    s = jnp.where(keep[None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("hgcs,shd->chgd", w, v_all,
                     preferred_element_type=jnp.float32)
    return out.reshape(1, c, h, d)


def mla_paged_prefix_chunk_attention_ref(q_lat: jax.Array, q_rope: jax.Array,
                                         c_vals: jax.Array, c_scale: jax.Array,
                                         kr_vals: jax.Array, kr_scale: jax.Array,
                                         c_chunk: jax.Array, kr_chunk: jax.Array,
                                         block_row: jax.Array, ctx: jax.Array,
                                         *, qk_nope_dim: int) -> jax.Array:
    """MLA chunk-prefill attention in absorbed latent space.

    q_lat: (1,C,H,rkv) absorbed queries (q_nope @ W_uk); q_rope: (1,C,H,dr);
    c_vals: (N,T,rkv) int8 latent pool with per-token scales c_scale:
    (T,N); kr_vals: (N,T,dr) with kr_scale: (T,N); c_chunk:
    (1,C,rkv) / kr_chunk: (1,C,dr) the chunk's fresh fp latent; block_row:
    (M,); ctx: () -> o_lat (1,C,H,rkv) f32 (caller applies W_uv).  Same
    masking rules as the GQA chunk oracle; the softmax scale is the expanded
    head dim's ``1/sqrt(dn+dr)`` exactly as in ``mla_decode_ref``.
    """
    c, hh = q_lat.shape[1], q_lat.shape[2]
    rkv, dr = q_lat.shape[3], q_rope.shape[3]
    m, t = block_row.shape[0], c_vals.shape[1]
    c_vals = _unpack_pool(c_vals, rkv)
    kr_vals = _unpack_pool(kr_vals, dr)
    f32 = jnp.float32
    scale = 1.0 / jnp.sqrt(qk_nope_dim + dr)
    c_pre = (c_vals[block_row].astype(f32) * _token_rows(c_scale, block_row)
             ).reshape(m * t, rkv)
    kr_pre = (kr_vals[block_row].astype(f32) * _token_rows(kr_scale, block_row)
              ).reshape(m * t, dr)
    c_all = jnp.concatenate([c_pre, c_chunk[0].astype(f32)], axis=0)
    kr_all = jnp.concatenate([kr_pre, kr_chunk[0].astype(f32)], axis=0)
    s_lat = jnp.einsum("chr,sr->hcs", q_lat[0].astype(f32), c_all)
    s_rope = jnp.einsum("chd,sd->hcs", q_rope[0].astype(f32), kr_all)
    s = (s_lat + s_rope) * scale
    col = jnp.arange(m * t + c)
    keep = jnp.where(col[None, :] < m * t, col[None, :] < ctx,
                     col[None, :] - m * t <= jnp.arange(c)[:, None])
    s = jnp.where(keep[None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("hcs,sr->chr", w, c_all)
    return o_lat[None]
