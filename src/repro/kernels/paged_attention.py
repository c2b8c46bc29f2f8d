"""Pallas TPU kernels: single-launch spec-decode verify + block-table chunk
prefill over the paged KV pool (int8 or nibble-packed int4 codecs — the
wrappers infer the codec from the pool-leaf carrier widths).

Both kernels extend the ``paged_kv_decode_attention`` pattern — the grid's
last dimension walks a request's block table, delivered to the index maps via
``PrefetchScalarGridSpec`` scalar prefetch — but serve *many* query rows per
launch instead of one token:

  * ``paged_kv_verify_attention`` scores all G spec-decode verify positions
    of every lane in ONE launch.  Each (B, KH) program streams the lane's M
    INT8 K/V blocks HBM->VMEM exactly once (the per-position decode loop it
    replaces streamed them G times), dequantizes in-register into a VMEM
    f32 buffer, and finishes with a one-shot softmax over all G*G_q rows —
    row r belongs to verify position ``r // group`` and is masked at its own
    causal length ``lengths[b] + r//group + 1``.  Trash-table lanes need no
    special casing: every masked column contributes an exact 0 after the
    softmax (same as the dense-gather oracle), so garbage blocks are
    score-invisible.
  * ``paged_prefix_chunk_attention`` lets a prefill chunk's C queries attend
    to the request's cached prefix directly from the pool (block_row scalar
    prefetch) plus the chunk's own fresh fp K/V — replacing the XLA-side
    dense gather.  Masking: pool columns are live iff ``col < ctx``; chunk
    columns are causal within the chunk (``col - M*T <= row // group``).

The one-shot softmax (buffer scores' inputs, then max/exp/normalize once) is
deliberate: it is the exact float path of the jnp oracles in ``ref.py``, so
interpret-mode parity is bitwise, which is what lets the serving goldens
(spec-decode == plain decode, warm prefix hit == cold run) hold on every
backend.  MLA variants run in absorbed latent space; the caller folds
``W_uk`` into the queries and applies ``W_uv`` to the returned o_lat.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kv_decode_attention import codes_f32, pick_lane, scale_lanes

NEG_INF = -2.0e38


def _head(rows: jax.Array, hh, d: int) -> jax.Array:
    """Head ``hh``'s ``d`` lanes of a (T, KH*d) block of pool rows, ``hh``
    a grid index: a select over the static head slices, which is exact."""
    out = rows[:, :d]
    for i in range(1, rows.shape[1] // d):
        out = jnp.where(hh == i, rows[:, i * d:(i + 1) * d], out)
    return out


def _dequant_block(k_ref, v_ref, vs_ref, vz_ref, ks, kz, hh, blk, *,
                   d: int, lanes: int, bits: int):
    """Head ``hh`` of one pool block, dequantized: K with the slot's frozen
    per-channel affine, V with its per-token affine picked from the block's
    lane of the scale tiles.  Nibble unpacking uses the same integer ops as
    the jnp oracles, so the floats — and the whole attention output — stay
    bitwise equal to the dense-gather reference for either codec."""
    lane = blk % lanes
    k = (_head(codes_f32(k_ref[0], bits), hh, d) - kz) * ks
    v = ((_head(codes_f32(v_ref[0], bits), hh, d) - pick_lane(vz_ref[0], lane))
         * pick_lane(vs_ref[0], lane))
    return k, v


def _latent_block(c_ref, kr_ref, cs_ref, krs_ref, blk, *, lanes: int,
                  bits: int):
    """One pool block's latent (T, rkv) and rope key (T, dr), dequantized
    with their per-token scales picked from the block's lane of the scale
    tiles (the same float path as the oracles' gathered scale columns)."""
    lane = blk % lanes
    c = codes_f32(c_ref[0], bits) * pick_lane(cs_ref[...], lane)
    kr = codes_f32(kr_ref[0], bits) * pick_lane(krs_ref[...], lane)
    return c, kr


def _softmax_rows(s: jax.Array) -> jax.Array:
    """One-shot softmax over the last axis, op-for-op ``jax.nn.softmax``."""
    mx = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - mx)
    return p / jnp.sum(p, axis=-1, keepdims=True)


def _prescale_q(q: jax.Array, d: int) -> jax.Array:
    """Materialize q/sqrt(d) with a true division, behind barriers.

    The oracles divide q eagerly, so their score dot consumes the exact
    quotient.  Inside one jitted program XLA constant-folds sqrt(d) and
    rewrites the division into a reciprocal multiply — bit-identical only
    when sqrt(d) is a power of two (d = 16, 64, ...), off by last ulps
    otherwise (d = 32, ...).  Hiding the divisor behind an optimization
    barrier keeps the real division; the outer barrier stops the scalar
    from being hoisted out of the score dot.
    """
    rsqrt = jax.lax.optimization_barrier(jnp.sqrt(d).astype(jnp.float32))
    return jax.lax.optimization_barrier(q.astype(jnp.float32) / rsqrt)


def _chunk_live(shape, mt: int, ctx, group: int, row0=0) -> jax.Array:
    """Live score columns of a chunk-prefill launch: pool columns below
    ``ctx``, and chunk columns causal within the chunk (row ``r`` is chunk
    position ``(row0 + r) // group``).  Written as and/or of comparisons:
    Mosaic cannot lower a select between two boolean vectors."""
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + row0
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (((col < mt) & (col < ctx))
            | ((col >= mt) & (col - mt <= row // group)))


# The TPU compiler's default scoped-VMEM limit for one kernel on a v5e.
VMEM_LIMIT_BYTES = 16 * 2 ** 20


def _tile_bytes(rows: int, cols: int) -> int:
    """Bytes of a (rows, cols) f32 value in VMEM, padded to (8, 128) tiles."""
    return 4 * (-(-rows // 8) * 8) * (-(-cols // 128) * 128)


def one_shot_vmem_bytes(rows: int, cols: int, scratch_widths,
                        io_widths) -> int:
    """VMEM working set of one program of the verify / chunk kernels.

    ``cols`` dequantized key positions are buffered per scratch width (K
    and V, or latent and rope), one (rows, cols) score value is live, and
    the (rows, width) query and output blocks are double-buffered.  Unlike
    the streaming decode kernel this grows with the block table, so the
    engine checks it at build time (``Scheduler``) instead of failing to
    compile mid-serve; tests/kernels/test_tpu_compile.py holds the estimate
    to the compiler's verdict on either side of the line."""
    return (sum(_tile_bytes(cols, w) for w in scratch_widths)
            + _tile_bytes(rows, cols)
            + 2 * sum(_tile_bytes(rows, w) for w in io_widths))


# ---------------------------------------------------------------------------
# Multi-token spec-decode verify
# ---------------------------------------------------------------------------

def _verify_kernel(bt_ref, len_ref, q_ref, ks_ref, kz_ref, k_ref, v_ref,
                   vs_ref, vz_ref, o_ref, kf_ref, vf_ref, *, n_blk: int,
                   t: int, group: int, d: int, lanes: int, bits: int):
    b_idx = pl.program_id(0)
    hh = pl.program_id(1)
    m_idx = pl.program_id(2)

    # stream + dequantize this block once, shared by all G*group query rows
    k, v = _dequant_block(k_ref, v_ref, vs_ref, vz_ref, ks_ref[0, 0],
                          kz_ref[0, 0], hh, bt_ref[b_idx, m_idx], d=d,
                          lanes=lanes, bits=bits)
    kf_ref[pl.ds(m_idx * t, t), :] = k
    vf_ref[pl.ds(m_idx * t, t), :] = v

    @pl.when(m_idx == n_blk - 1)
    def _finish():
        qg = q_ref[0, 0]                      # pre-scaled by _prescale_q
        kf, vf = kf_ref[...], vf_ref[...]
        s = jax.lax.dot_general(qg, kf, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        live = pos < len_ref[b_idx] + row // group + 1
        w = _softmax_rows(jnp.where(live, s, NEG_INF))
        o_ref[0, 0] = jax.lax.dot_general(w, vf, (((1,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_kv_verify_attention(q: jax.Array,
                              k_vals: jax.Array, k_scale: jax.Array,
                              k_zero: jax.Array, v_vals: jax.Array,
                              v_scale: jax.Array, v_zero: jax.Array,
                              block_tables: jax.Array, lengths: jax.Array, *,
                              interpret: bool = False) -> jax.Array:
    """All G verify positions against the paged pool in one launch.

    q: (B, G, H, D); pool leaves as in ``paged_kv_decode_attention``
    (k_vals/v_vals (N, T, KH*D/pack) codes, v_scale/v_zero (KH, T, N),
    k_scale/k_zero (B, KH, D) per-slot); block_tables: (B, M);
    lengths: (B,) pre-verify context lengths -> (B, G, H, D) f32.
    """
    b, gq, h, d = q.shape
    kh = k_scale.shape[-2]
    t = k_vals.shape[1]
    dp = k_vals.shape[2] // kh                            # carrier width
    bits = 8 if dp == d else 4
    m = block_tables.shape[1]
    g = h // kh
    rows = gq * g
    lanes = scale_lanes(v_scale.shape[-1])

    # row r = j * group + gi  <->  verify position j, grouped query head gi
    q_r = q.reshape(b, gq, kh, g, d).transpose(0, 2, 1, 3, 4)
    q_r = _prescale_q(q_r.reshape(b, kh, rows, d), d)
    ks_r = k_scale[:, :, None, :]                         # (B, KH, 1, D)
    kz_r = k_zero[:, :, None, :]

    kernel = functools.partial(_verify_kernel, n_blk=m, t=t, group=g, d=d,
                               lanes=lanes, bits=bits)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # block_tables, lengths
        grid=(b, kh, m),
        in_specs=[
            pl.BlockSpec((1, 1, rows, d), lambda bb, hh, mm, bt, ln: (bb, hh, 0, 0)),
            pl.BlockSpec((1, 1, 1, d), lambda bb, hh, mm, bt, ln: (bb, hh, 0, 0)),
            pl.BlockSpec((1, 1, 1, d), lambda bb, hh, mm, bt, ln: (bb, hh, 0, 0)),
            pl.BlockSpec((1, t, kh * dp),
                         lambda bb, hh, mm, bt, ln: (bt[bb, mm], 0, 0)),
            pl.BlockSpec((1, t, kh * dp),
                         lambda bb, hh, mm, bt, ln: (bt[bb, mm], 0, 0)),
            pl.BlockSpec((1, t, lanes), lambda bb, hh, mm, bt, ln: (
                hh, 0, bt[bb, mm] // lanes)),
            pl.BlockSpec((1, t, lanes), lambda bb, hh, mm, bt, ln: (
                hh, 0, bt[bb, mm] // lanes)),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, d),
                               lambda bb, hh, mm, bt, ln: (bb, hh, 0, 0)),
        scratch_shapes=[pltpu.VMEM((m * t, d), jnp.float32),
                        pltpu.VMEM((m * t, d), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, rows, d), jnp.float32),
        interpret=interpret,
    )(block_tables, lengths, q_r, ks_r, kz_r, k_vals, v_vals, v_scale, v_zero)
    return out.reshape(b, kh, gq, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, gq, h, d)


def _mla_verify_kernel(bt_ref, len_ref, ql_ref, qr_ref, cs_ref, krs_ref,
                       c_ref, kr_ref, o_ref, cf_ref, krf_ref, *, n_blk: int,
                       t: int, heads: int, dn: int, dr: int, lanes: int,
                       bits: int):
    b_idx = pl.program_id(0)
    m_idx = pl.program_id(1)

    c, kr = _latent_block(c_ref, kr_ref, cs_ref, krs_ref,
                          bt_ref[b_idx, m_idx], lanes=lanes, bits=bits)
    cf_ref[pl.ds(m_idx * t, t), :] = c
    krf_ref[pl.ds(m_idx * t, t), :] = kr

    @pl.when(m_idx == n_blk - 1)
    def _finish():
        scale = 1.0 / jnp.sqrt(dn + dr)
        cf, krf = cf_ref[...], krf_ref[...]
        s_lat = jax.lax.dot_general(ql_ref[0], cf, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        s_rope = jax.lax.dot_general(qr_ref[0], krf, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        s = (s_lat + s_rope) * scale
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        live = pos < len_ref[b_idx] + row // heads + 1
        w = _softmax_rows(jnp.where(live, s, NEG_INF))
        o_ref[0] = jax.lax.dot_general(w, cf, (((1,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("qk_nope_dim", "interpret"))
def mla_paged_verify_attention(q_lat: jax.Array, q_rope: jax.Array,
                               c_vals: jax.Array, c_scale: jax.Array,
                               kr_vals: jax.Array, kr_scale: jax.Array,
                               block_tables: jax.Array, lengths: jax.Array, *,
                               qk_nope_dim: int,
                               interpret: bool = False) -> jax.Array:
    """MLA verify in absorbed latent space, one launch for all G positions.

    q_lat: (B, G, H, rkv) absorbed queries (q_nope @ W_uk); q_rope:
    (B, G, H, dr); c_vals: (N, T, rkv) int8 latent pool with per-token
    scales c_scale (T, N), blocks as lanes; kr_vals: (N, T, dr) with
    kr_scale (T, N); -> o_lat (B, G, H, rkv) f32 (caller applies W_uv).
    """
    b, gq, h, rkv = q_lat.shape
    dr = q_rope.shape[-1]
    t = c_vals.shape[1]
    rkv_p, dr_p = c_vals.shape[-1], kr_vals.shape[-1]     # carrier widths
    bits = 8 if rkv_p == rkv else 4
    m = block_tables.shape[1]
    rows = gq * h
    lanes = scale_lanes(c_scale.shape[-1])

    ql_r = q_lat.astype(jnp.float32).reshape(b, rows, rkv)
    qr_r = q_rope.astype(jnp.float32).reshape(b, rows, dr)

    kernel = functools.partial(_mla_verify_kernel, n_blk=m, t=t, heads=h,
                               dn=qk_nope_dim, dr=dr, lanes=lanes, bits=bits)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # block_tables, lengths
        grid=(b, m),
        in_specs=[
            pl.BlockSpec((1, rows, rkv), lambda bb, mm, bt, ln: (bb, 0, 0)),
            pl.BlockSpec((1, rows, dr), lambda bb, mm, bt, ln: (bb, 0, 0)),
            pl.BlockSpec((t, lanes),
                         lambda bb, mm, bt, ln: (0, bt[bb, mm] // lanes)),
            pl.BlockSpec((t, lanes),
                         lambda bb, mm, bt, ln: (0, bt[bb, mm] // lanes)),
            pl.BlockSpec((1, t, rkv_p), lambda bb, mm, bt, ln: (bt[bb, mm], 0, 0)),
            pl.BlockSpec((1, t, dr_p), lambda bb, mm, bt, ln: (bt[bb, mm], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, rkv), lambda bb, mm, bt, ln: (bb, 0, 0)),
        scratch_shapes=[pltpu.VMEM((m * t, rkv), jnp.float32),
                        pltpu.VMEM((m * t, dr), jnp.float32)],
    )
    o_lat = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, rkv), jnp.float32),
        interpret=interpret,
    )(block_tables, lengths, ql_r, qr_r, c_scale, kr_scale, c_vals, kr_vals)
    return o_lat.reshape(b, gq, h, rkv)


# ---------------------------------------------------------------------------
# MLA decode: one query per lane, streamed over the lane's live blocks
# ---------------------------------------------------------------------------

def _last_block(length, t: int):
    """Index of a lane's last live block (0 for an empty lane)."""
    return jnp.maximum(length - 1, 0) // t


def _mla_decode_kernel(bt_ref, len_ref, ql_ref, qr_ref, cs_ref, krs_ref,
                       c_ref, kr_ref, o_ref, m_ref, l_ref, acc_ref, *,
                       n_blk: int, t: int, dn: int, dr: int, lanes: int,
                       bits: int):
    b_idx = pl.program_id(0)
    m_idx = pl.program_id(1)
    length = len_ref[b_idx]
    scale = 1.0 / jnp.sqrt(dn + dr)

    @pl.when(m_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(m_idx <= _last_block(length, t))
    def _block():
        c, kr = _latent_block(c_ref, kr_ref, cs_ref, krs_ref,
                              bt_ref[b_idx, m_idx], lanes=lanes, bits=bits)
        s_lat = jax.lax.dot_general(ql_ref[0], c, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        s_rope = jax.lax.dot_general(qr_ref[0], kr, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        s = (s_lat + s_rope) * scale                                # (H, T)
        pos = m_idx * t + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, c, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new

    @pl.when(m_idx == n_blk - 1)
    def _finish():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


# VMEM of one MLA decode program: it streams one block at a time, so the
# working set does not grow with the block table (scheduler's guard)
def mla_decode_vmem_bytes(heads: int, t: int, rkv: int, dr: int) -> int:
    """Double-buffered query, scale and code blocks, the (H, rkv) output,
    and the online-softmax scratch of one ``mla_paged_decode_attention``
    program (the scale tiles at their widest, 128 blocks of lanes)."""
    io = (_tile_bytes(heads, rkv) + _tile_bytes(heads, dr)
          + 2 * _tile_bytes(t, 128)
          + _tile_bytes(t, rkv) + _tile_bytes(t, dr)
          + _tile_bytes(heads, rkv))
    scratch = 2 * _tile_bytes(heads, 1) + _tile_bytes(heads, rkv)
    live = 2 * _tile_bytes(t, rkv) + 3 * _tile_bytes(heads, t)
    return 2 * io + scratch + live


@functools.partial(jax.jit, static_argnames=("qk_nope_dim", "interpret"))
def mla_paged_decode_attention(q_lat: jax.Array, q_rope: jax.Array,
                               c_vals: jax.Array, c_scale: jax.Array,
                               kr_vals: jax.Array, kr_scale: jax.Array,
                               block_tables: jax.Array, lengths: jax.Array, *,
                               qk_nope_dim: int,
                               interpret: bool = False) -> jax.Array:
    """MLA decode in absorbed latent space: lane b's one query attends its
    first ``lengths[b]`` pool tokens, read block by block through its
    table row with an online softmax.  Grid steps past a lane's last live
    block fetch nothing (the block index repeats) and compute nothing.

    q_lat: (B, H, rkv) absorbed queries; q_rope: (B, H, dr); pool and
    per-token scales as in :func:`mla_paged_verify_attention` -> o_lat
    (B, H, rkv) f32 (caller applies W_uv)."""
    b, h, rkv = q_lat.shape
    dr = q_rope.shape[-1]
    t = c_vals.shape[1]
    rkv_p, dr_p = c_vals.shape[-1], kr_vals.shape[-1]
    bits = 8 if rkv_p == rkv else 4
    m = block_tables.shape[1]
    lanes = scale_lanes(c_scale.shape[-1])

    def blk(bb, mm, bt, ln):
        return bt[bb, jnp.minimum(mm, _last_block(ln[bb], t))]

    kernel = functools.partial(_mla_decode_kernel, n_blk=m, t=t,
                               dn=qk_nope_dim, dr=dr, lanes=lanes, bits=bits)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # block_tables, lengths
        grid=(b, m),
        in_specs=[
            pl.BlockSpec((1, h, rkv), lambda bb, mm, bt, ln: (bb, 0, 0)),
            pl.BlockSpec((1, h, dr), lambda bb, mm, bt, ln: (bb, 0, 0)),
            pl.BlockSpec((t, lanes), lambda bb, mm, bt, ln: (
                0, blk(bb, mm, bt, ln) // lanes)),
            pl.BlockSpec((t, lanes), lambda bb, mm, bt, ln: (
                0, blk(bb, mm, bt, ln) // lanes)),
            pl.BlockSpec((1, t, rkv_p),
                         lambda bb, mm, bt, ln: (blk(bb, mm, bt, ln), 0, 0)),
            pl.BlockSpec((1, t, dr_p),
                         lambda bb, mm, bt, ln: (blk(bb, mm, bt, ln), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, rkv), lambda bb, mm, bt, ln: (bb, 0, 0)),
        scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, rkv), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, rkv), jnp.float32),
        interpret=interpret,
    )(block_tables, lengths, q_lat.astype(jnp.float32),
      q_rope.astype(jnp.float32), c_scale, kr_scale, c_vals, kr_vals)


# ---------------------------------------------------------------------------
# Chunk-prefill attention: chunk queries vs pool prefix + fresh chunk K/V
# ---------------------------------------------------------------------------

def _chunk_kernel(br_ref, ctx_ref, q_ref, ks_ref, kz_ref, k_ref, v_ref,
                  vs_ref, vz_ref, kc_ref, vc_ref, o_ref, kf_ref, vf_ref, *,
                  n_blk: int, t: int, group: int, d: int, lanes: int,
                  bits: int):
    hh = pl.program_id(0)
    m_idx = pl.program_id(1)

    k, v = _dequant_block(k_ref, v_ref, vs_ref, vz_ref, ks_ref[0], kz_ref[0],
                          hh, br_ref[m_idx], d=d, lanes=lanes, bits=bits)
    kf_ref[pl.ds(m_idx * t, t), :] = k
    vf_ref[pl.ds(m_idx * t, t), :] = v

    @pl.when(m_idx == n_blk - 1)
    def _finish():
        mt = n_blk * t
        # append the chunk's fresh fp K/V after the dequantized prefix
        kf_ref[pl.ds(mt, kc_ref.shape[1]), :] = kc_ref[0].astype(jnp.float32)
        vf_ref[pl.ds(mt, vc_ref.shape[1]), :] = vc_ref[0].astype(jnp.float32)
        qg = q_ref[0]                         # pre-scaled by _prescale_q
        kf, vf = kf_ref[...], vf_ref[...]
        s = jax.lax.dot_general(qg, kf, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        live = _chunk_live(s.shape, mt, ctx_ref[0], group)
        w = _softmax_rows(jnp.where(live, s, NEG_INF))
        o_ref[0] = jax.lax.dot_general(w, vf, (((1,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_prefix_chunk_attention(q: jax.Array,
                                 k_vals: jax.Array, k_scale: jax.Array,
                                 k_zero: jax.Array, v_vals: jax.Array,
                                 v_scale: jax.Array, v_zero: jax.Array,
                                 k_chunk: jax.Array, v_chunk: jax.Array,
                                 block_row: jax.Array, ctx: jax.Array, *,
                                 interpret: bool = False) -> jax.Array:
    """Chunk-prefill attention reading the prefix straight from the pool.

    q: (1, C, H, D); pool leaves as in ``paged_kv_decode_attention`` with
    k_scale/k_zero (KH, D) the slot's frozen affine; k_chunk/v_chunk:
    (1, C, KH, D) the chunk's fresh fp K/V; block_row: (M,) int32 (entries
    past the prefix may be trash — masked by ctx); ctx: () int32 cached
    prefix length -> (1, C, H, D) f32.
    """
    c, h, d = q.shape[1], q.shape[2], q.shape[3]
    kh = k_scale.shape[-2]
    t = k_vals.shape[1]
    dp = k_vals.shape[2] // kh                            # carrier width
    bits = 8 if dp == d else 4
    m = block_row.shape[0]
    g = h // kh
    rows = c * g
    lanes = scale_lanes(v_scale.shape[-1])

    # row r = ci * group + gi  <->  chunk position ci, grouped head gi
    q_r = q[0].reshape(c, kh, g, d).transpose(1, 0, 2, 3).reshape(kh, rows, d)
    q_r = _prescale_q(q_r, d)
    kc_r = k_chunk[0].transpose(1, 0, 2)                  # (KH, C, D)
    vc_r = v_chunk[0].transpose(1, 0, 2)
    ctx_arr = jnp.asarray(ctx, jnp.int32).reshape(1)

    kernel = functools.partial(_chunk_kernel, n_blk=m, t=t, group=g, d=d,
                               lanes=lanes, bits=bits)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # block_row, ctx
        grid=(kh, m),
        in_specs=[
            pl.BlockSpec((1, rows, d), lambda hh, mm, br, cx: (hh, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda hh, mm, br, cx: (hh, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda hh, mm, br, cx: (hh, 0, 0)),
            pl.BlockSpec((1, t, kh * dp), lambda hh, mm, br, cx: (br[mm], 0, 0)),
            pl.BlockSpec((1, t, kh * dp), lambda hh, mm, br, cx: (br[mm], 0, 0)),
            pl.BlockSpec((1, t, lanes),
                         lambda hh, mm, br, cx: (hh, 0, br[mm] // lanes)),
            pl.BlockSpec((1, t, lanes),
                         lambda hh, mm, br, cx: (hh, 0, br[mm] // lanes)),
            pl.BlockSpec((1, c, d), lambda hh, mm, br, cx: (hh, 0, 0)),
            pl.BlockSpec((1, c, d), lambda hh, mm, br, cx: (hh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, d), lambda hh, mm, br, cx: (hh, 0, 0)),
        scratch_shapes=[pltpu.VMEM((m * t + c, d), jnp.float32),
                        pltpu.VMEM((m * t + c, d), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((kh, rows, d), jnp.float32),
        interpret=interpret,
    )(block_row, ctx_arr, q_r, k_scale[:, None], k_zero[:, None], k_vals,
      v_vals, v_scale, v_zero, kc_r, vc_r)
    return out.reshape(kh, c, g, d).transpose(1, 0, 2, 3).reshape(1, c, h, d)


def _mla_chunk_kernel(br_ref, ctx_ref, ql_ref, qr_ref, cs_ref, krs_ref,
                      c_ref, kr_ref, cc_ref, krc_ref, o_ref, cf_ref, krf_ref,
                      *, n_blk: int, t: int, heads: int, dn: int, dr: int,
                      lanes: int, bits: int):
    m_idx = pl.program_id(1)
    row0 = pl.program_id(0) * ql_ref.shape[0]

    c, kr = _latent_block(c_ref, kr_ref, cs_ref, krs_ref, br_ref[m_idx],
                          lanes=lanes, bits=bits)
    cf_ref[pl.ds(m_idx * t, t), :] = c
    krf_ref[pl.ds(m_idx * t, t), :] = kr

    @pl.when(m_idx == n_blk - 1)
    def _finish():
        mt = n_blk * t
        cf_ref[pl.ds(mt, cc_ref.shape[0]), :] = cc_ref[...].astype(jnp.float32)
        krf_ref[pl.ds(mt, krc_ref.shape[0]), :] = krc_ref[...].astype(jnp.float32)
        scale = 1.0 / jnp.sqrt(dn + dr)
        cf, krf = cf_ref[...], krf_ref[...]
        s_lat = jax.lax.dot_general(ql_ref[...], cf, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        s_rope = jax.lax.dot_general(qr_ref[...], krf, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        s = (s_lat + s_rope) * scale
        live = _chunk_live(s.shape, mt, ctx_ref[0], heads, row0)
        w = _softmax_rows(jnp.where(live, s, NEG_INF))
        o_ref[...] = jax.lax.dot_general(w, cf, (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)


def mla_chunk_vmem_bytes(tokens: int, heads: int, cols: int, rkv: int,
                         dr: int) -> int:
    """VMEM of one MLA chunk-prefill program serving ``tokens`` chunk
    positions (``tokens * heads`` query rows) over ``cols`` key
    positions: the one-shot working set, with the latent and rope score
    parts and the weights live beside the scores (three (rows, cols)
    values; the compiler's verdict, tests/kernels/test_tpu_compile.py)."""
    rows = tokens * heads
    return (one_shot_vmem_bytes(rows, cols, (rkv, dr), (rkv, dr, rkv))
            + 2 * _tile_bytes(rows, cols))


def mla_chunk_tile(c: int, heads: int, cols: int, rkv: int, dr: int) -> int:
    """Chunk positions per MLA chunk-prefill program: all ``c`` where they
    fit VMEM, else the largest power-of-two share of them that does (each
    program buffers the whole prefix again), else ``c`` (which the
    compiler refuses, and ``Scheduler`` at build)."""
    tile = c
    while tile > 1 and (c % tile or mla_chunk_vmem_bytes(
            tile, heads, cols, rkv, dr) > VMEM_LIMIT_BYTES):
        tile //= 2
    fits = mla_chunk_vmem_bytes(tile, heads, cols, rkv, dr) \
        <= VMEM_LIMIT_BYTES
    return tile if fits and c % tile == 0 else c


@functools.partial(jax.jit, static_argnames=("qk_nope_dim", "interpret"))
def mla_paged_prefix_chunk_attention(q_lat: jax.Array, q_rope: jax.Array,
                                     c_vals: jax.Array, c_scale: jax.Array,
                                     kr_vals: jax.Array, kr_scale: jax.Array,
                                     c_chunk: jax.Array, kr_chunk: jax.Array,
                                     block_row: jax.Array, ctx: jax.Array, *,
                                     qk_nope_dim: int,
                                     interpret: bool = False) -> jax.Array:
    """MLA chunk-prefill attention in absorbed latent space.

    q_lat: (1, C, H, rkv); q_rope: (1, C, H, dr); c_vals: (N, T, rkv) int8
    latent pool with per-token scales c_scale (T, N), blocks as lanes;
    kr_vals: (N, T, dr) with kr_scale (T, N); c_chunk: (1, C, rkv) /
    kr_chunk: (1, C, dr) fresh fp chunk latent; block_row: (M,); ctx: ()
    -> o_lat (1, C, H, rkv) f32 (caller applies W_uv).

    The grid's first axis splits the chunk's query rows into the tiles
    ``mla_chunk_tile`` sizes to VMEM; each row's softmax stays one-shot,
    so the tiling changes no float.
    """
    c, h, rkv = q_lat.shape[1], q_lat.shape[2], q_lat.shape[3]
    dr = q_rope.shape[-1]
    t = c_vals.shape[1]
    rkv_p, dr_p = c_vals.shape[-1], kr_vals.shape[-1]     # carrier widths
    bits = 8 if rkv_p == rkv else 4
    m = block_row.shape[0]
    rows = mla_chunk_tile(c, h, m * t + c, rkv, dr) * h
    lanes = scale_lanes(c_scale.shape[-1])

    ql_r = q_lat[0].astype(jnp.float32).reshape(c * h, rkv)
    qr_r = q_rope[0].astype(jnp.float32).reshape(c * h, dr)
    ctx_arr = jnp.asarray(ctx, jnp.int32).reshape(1)

    kernel = functools.partial(_mla_chunk_kernel, n_blk=m, t=t, heads=h,
                               dn=qk_nope_dim, dr=dr, lanes=lanes, bits=bits)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # block_row, ctx
        grid=(c * h // rows, m),
        in_specs=[
            pl.BlockSpec((rows, rkv), lambda i, mm, br, cx: (i, 0)),
            pl.BlockSpec((rows, dr), lambda i, mm, br, cx: (i, 0)),
            pl.BlockSpec((t, lanes),
                         lambda i, mm, br, cx: (0, br[mm] // lanes)),
            pl.BlockSpec((t, lanes),
                         lambda i, mm, br, cx: (0, br[mm] // lanes)),
            pl.BlockSpec((1, t, rkv_p),
                         lambda i, mm, br, cx: (br[mm], 0, 0)),
            pl.BlockSpec((1, t, dr_p), lambda i, mm, br, cx: (br[mm], 0, 0)),
            pl.BlockSpec((c, rkv), lambda i, mm, br, cx: (0, 0)),
            pl.BlockSpec((c, dr), lambda i, mm, br, cx: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rows, rkv), lambda i, mm, br, cx: (i, 0)),
        scratch_shapes=[pltpu.VMEM((m * t + c, rkv), jnp.float32),
                        pltpu.VMEM((m * t + c, dr), jnp.float32)],
    )
    o_lat = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((c * h, rkv), jnp.float32),
        interpret=interpret,
    )(block_row, ctx_arr, ql_r, qr_r, c_scale, kr_scale, c_vals, kr_vals,
      c_chunk[0], kr_chunk[0])
    return o_lat.reshape(1, c, h, rkv)
