"""Pallas TPU kernel: flash-decode attention over the SimQuant INT8 KV cache.

One new query token attends to an S-long quantized cache (paper §4.7
"SimQuant is particularly effective for KV cache quantization in
long-sequence inference").  Design:

  * grid = (B, KH, S/chunk): each step streams one (chunk, D) INT8 K tile and
    V tile HBM->VMEM — the INT8 stream is the point: half the T_load bytes of
    a bf16 cache (paper Table 5's Load column).
  * dequantization runs in-register right before the MXU dot (the paper's
    fused dequant in SMEM), with per-channel K affine and per-token V affine.
  * online softmax state (m, l, acc) lives in VMEM scratch across the S grid
    dim (flash-decode); the final chunk writes acc / l.
  * `length` masking: positions >= length contribute NEG_INF scores.

Group dimension (H/KH query heads per KV head) rides inside the block: the
score matmul is (G, D) x (D, chunk).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.qtensor import unpack_nibbles

NEG_INF = -2.0e38


def codes_f32(raw, bits: int):
    """Pool codes -> f32: nibble-unpack first when the pool is packed INT4.
    The bits==8 path is byte-identical to the pre-codec kernels."""
    if bits == 4:
        return unpack_nibbles(raw).astype(jnp.float32)
    return raw.astype(jnp.float32)


def _kernel(len_ref, q_ref, ks_ref, kz_ref, k_ref, v_ref, vs_ref, vz_ref,
            o_ref, m_ref, l_ref, acc_ref, *, n_chunks: int, chunk: int,
            scale: float):
    b_idx = pl.program_id(0)
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale           # (G, D)
    k_q = k_ref[0, 0].astype(jnp.float32)                 # (C, D)
    k = (k_q - kz_ref[0, 0]) * ks_ref[0, 0]               # per-channel affine
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, C)

    length = len_ref[b_idx]
    pos = s_idx * chunk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < length, s, NEG_INF)

    m_prev = m_ref[...]                                   # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                                # (G, C)
    alpha = jnp.exp(m_prev - m_new)                       # (G, 1)

    v_q = v_ref[0, 0].astype(jnp.float32)                 # (C, D)
    v = (v_q - vz_ref[0, 0]) * vs_ref[0, 0]               # per-token affine
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new

    @pl.when(s_idx == n_chunks - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def kv_decode_attention(q: jax.Array,
                        k_vals: jax.Array, k_scale: jax.Array, k_zero: jax.Array,
                        v_vals: jax.Array, v_scale: jax.Array, v_zero: jax.Array,
                        length: jax.Array, *, chunk: int = 512,
                        interpret: bool = False) -> jax.Array:
    """q: (B, H, D); k_vals/v_vals: (B, S, KH, D) int8;
    k_scale/k_zero: (B, 1, KH, D) f32; v_scale/v_zero: (B, S, KH, 1) f32;
    length: (B,) int32 -> (B, H, D) f32.
    """
    b, h, d = q.shape
    s, kh = k_vals.shape[1], k_vals.shape[2]
    g = h // kh
    chunk = min(chunk, s)
    pad_s = (-s) % chunk
    if pad_s:
        k_vals = jnp.pad(k_vals, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
        v_vals = jnp.pad(v_vals, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
        v_scale = jnp.pad(v_scale, ((0, 0), (0, pad_s), (0, 0), (0, 0)),
                          constant_values=1.0)
        v_zero = jnp.pad(v_zero, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
    s_p = s + pad_s
    n_chunks = s_p // chunk

    # Layout: (B, KH, S_or_G, D) so the last two dims form the VMEM tile.
    q_r = q.reshape(b, kh, g, d)
    k_r = k_vals.transpose(0, 2, 1, 3)                    # (B, KH, S, D)
    v_r = v_vals.transpose(0, 2, 1, 3)
    ks_r = k_scale.transpose(0, 2, 1, 3)                  # (B, KH, 1, D)
    kz_r = k_zero.transpose(0, 2, 1, 3)
    vs_r = v_scale.transpose(0, 2, 1, 3)                  # (B, KH, S, 1)
    vz_r = v_zero.transpose(0, 2, 1, 3)

    kernel = functools.partial(_kernel, n_chunks=n_chunks, chunk=chunk,
                               scale=1.0 / (d ** 0.5))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,          # length (B,) -> SMEM
        grid=(b, kh, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda bb, hh, ss, ln: (bb, hh, 0, 0)),      # q
            pl.BlockSpec((1, 1, 1, d), lambda bb, hh, ss, ln: (bb, hh, 0, 0)),      # ks
            pl.BlockSpec((1, 1, 1, d), lambda bb, hh, ss, ln: (bb, hh, 0, 0)),      # kz
            pl.BlockSpec((1, 1, chunk, d), lambda bb, hh, ss, ln: (bb, hh, ss, 0)), # k
            pl.BlockSpec((1, 1, chunk, d), lambda bb, hh, ss, ln: (bb, hh, ss, 0)), # v
            pl.BlockSpec((1, 1, chunk, 1), lambda bb, hh, ss, ln: (bb, hh, ss, 0)), # vs
            pl.BlockSpec((1, 1, chunk, 1), lambda bb, hh, ss, ln: (bb, hh, ss, 0)), # vz
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda bb, hh, ss, ln: (bb, hh, 0, 0)),
        scratch_shapes=[pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, d), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, g, d), jnp.float32),
        interpret=interpret,
    )(length, q_r, ks_r, kz_r, k_r, v_r, vs_r, vz_r)
    return out.reshape(b, h, d)


# ---------------------------------------------------------------------------
# Paged variant: gather-by-block-table (serving/paged_cache.py pool layout)
# ---------------------------------------------------------------------------

def scale_lanes(n_blocks: int) -> int:
    """Lane width of one V scale/zero tile: the pool's block axis is the lane
    axis of those leaves, so a block's column sits in the 128-lane tile
    ``blk // lanes`` at lane ``blk % lanes``."""
    return min(128, n_blocks)


def pick_lane(tile: jax.Array, lane) -> jax.Array:
    """Column ``lane`` of a (T, lanes) tile as (T, 1): a masked row-sum, which
    is exact (one value plus zeros), so the dequant after it keeps the float
    path of a gathered (T, 1) column."""
    hit = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1) == lane
    return jnp.sum(jnp.where(hit, tile, 0.0), axis=1, keepdims=True)


def _paged_kernel(bt_ref, len_ref, q_ref, ks_ref, kz_ref, k_ref, v_ref,
                  vs_ref, vz_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  n_blk: int, t: int, d: int, lanes: int, scale: float,
                  bits: int):
    """Same online-softmax body as ``_kernel``, for every kv head of a block
    in one grid step; the grid's second dim walks a request's *block table*
    instead of a contiguous sequence.  Dead table lanes (m*T >= length) skip
    the compute entirely, and the index maps clamp them to the last live
    block so the pipeline revisits an already-resident tile instead of
    streaming trash blocks."""
    b_idx = pl.program_id(0)
    m_idx = pl.program_id(1)
    length = len_ref[b_idx]

    @pl.when(m_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(m_idx * t < length)
    def _compute():
        lane = bt_ref[b_idx, m_idx] % lanes
        k_all = codes_f32(k_ref[0], bits)                    # (T, KH*D)
        v_all = codes_f32(v_ref[0], bits)
        for hh in range(q_ref.shape[1]):
            cols = slice(hh * d, (hh + 1) * d)
            q = q_ref[0, hh].astype(jnp.float32) * scale      # (G, D)
            k = (k_all[:, cols] - kz_ref[0, hh:hh + 1]) * ks_ref[0, hh:hh + 1]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)

            pos = m_idx * t + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos < length, s, NEG_INF)

            m_prev = m_ref[hh]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)

            v = ((v_all[:, cols] - pick_lane(vz_ref[hh], lane))
                 * pick_lane(vs_ref[hh], lane))               # per-tok affine
            acc_ref[hh] = acc_ref[hh] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            l_ref[hh] = l_ref[hh] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            m_ref[hh] = m_new

    @pl.when(m_idx == n_blk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_kv_decode_attention(q: jax.Array,
                              k_vals: jax.Array, k_scale: jax.Array,
                              k_zero: jax.Array, v_vals: jax.Array,
                              v_scale: jax.Array, v_zero: jax.Array,
                              block_tables: jax.Array, lengths: jax.Array, *,
                              interpret: bool = False) -> jax.Array:
    """Flash-decode over the paged quantized pool, read in place.

    q: (B, H, D); k_vals/v_vals: (N, T, KH*Dp) code block pool, each token
    row holding its KH heads side by side, where Dp == D for INT8 codes and
    D // 2 for nibble-packed INT4 (the codec bitwidth is inferred from that
    shape); v_scale/v_zero: (KH, T, N) f32, blocks as lanes; k_scale/k_zero:
    (B, KH, D) f32 per-slot frozen affine; block_tables: (B, M) int32 pool
    block ids (dead table slots may point anywhere — masked by
    ``lengths``); lengths: (B,) int32 -> (B, H, D) f32.

    A grid step fetches one pool block for all its heads: the (T, KH*Dp)
    code rows and the (KH, T, 128) scale tiles that hold the block's lane
    (consecutive blocks of one 128-lane group revisit those tiles).
    """
    b, h, d = q.shape
    kh = k_scale.shape[-2]
    t = k_vals.shape[1]
    dp = k_vals.shape[2] // kh
    bits = 8 if dp == d else 4
    m = block_tables.shape[1]
    g = h // kh
    lanes = scale_lanes(v_scale.shape[-1])

    q_r = q.reshape(b, kh, g, d)
    kernel = functools.partial(_paged_kernel, n_blk=m, t=t, d=d, lanes=lanes,
                               scale=1.0 / (d ** 0.5), bits=bits)

    def _blk(bb, mm, ln, bt):
        # clamp dead table lanes to the last live block: consecutive grid
        # steps then ask for the same tile and the pipeline skips the fetch
        last = jnp.maximum(ln[bb] - 1, 0) // t
        return bt[bb, jnp.minimum(mm, last)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # block_tables, lengths
        grid=(b, m),
        in_specs=[
            pl.BlockSpec((1, kh, g, d), lambda bb, mm, bt, ln: (bb, 0, 0, 0)),
            pl.BlockSpec((1, kh, d), lambda bb, mm, bt, ln: (bb, 0, 0)),
            pl.BlockSpec((1, kh, d), lambda bb, mm, bt, ln: (bb, 0, 0)),
            pl.BlockSpec((1, t, kh * dp),
                         lambda bb, mm, bt, ln: (_blk(bb, mm, ln, bt), 0, 0)),
            pl.BlockSpec((1, t, kh * dp),
                         lambda bb, mm, bt, ln: (_blk(bb, mm, ln, bt), 0, 0)),
            pl.BlockSpec((kh, t, lanes), lambda bb, mm, bt, ln: (
                0, 0, _blk(bb, mm, ln, bt) // lanes)),
            pl.BlockSpec((kh, t, lanes), lambda bb, mm, bt, ln: (
                0, 0, _blk(bb, mm, ln, bt) // lanes)),
        ],
        out_specs=pl.BlockSpec((1, kh, g, d),
                               lambda bb, mm, bt, ln: (bb, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((kh, g, 1), jnp.float32),
                        pltpu.VMEM((kh, g, 1), jnp.float32),
                        pltpu.VMEM((kh, g, d), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, g, d), jnp.float32),
        interpret=interpret,
    )(block_tables, lengths, q_r, k_scale, k_zero, k_vals, v_vals, v_scale,
      v_zero)
    return out.reshape(b, h, d)
