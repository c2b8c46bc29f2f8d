"""Pallas TPU kernels: flash-decode attention over the SimQuant INT8 KV cache.

One new query token attends to an S-long quantized cache (paper §4.7
"SimQuant is particularly effective for KV cache quantization in
long-sequence inference").  Both kernels dequantize in-register right
before the MXU dot (the paper's fused dequant), with a per-channel K
affine and a per-token V affine, keep the online-softmax state (m, l,
acc) in VMEM scratch (flash-decode), and mask positions >= length with
NEG_INF scores.  The group dimension (H/KH query heads per KV head) rides
inside the block: the score matmul is (G, D) x (D, tokens).

  * ``kv_decode_attention``, over a dense (B, S, KH, D) cache: grid
    (B, KH, S/chunk), each step streaming one (chunk, D) INT8 K tile and V
    tile HBM->VMEM — the INT8 stream is the point: half the T_load bytes
    of a bf16 cache (paper Table 5's Load column).
  * ``paged_kv_decode_attention``, over the paged block pool
    (serving/paged_cache.py): grid (B,), one lane a step, whose loop visits
    only the lane's live blocks, P at a time (``decode_blocks_per_step``),
    their code rows fetched by async copies double-buffered across groups
    and lanes, one online-softmax update per group and KV head.  Code rows
    that are not whole 128-lane tiles keep a (B, M) grid that walks the
    block table one block a step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.qtensor import unpack_nibbles

NEG_INF = -2.0e38

# Scoped VMEM the paged decode kernel may take: it holds a layer's V affine
# leaves whole, and they grow with the pool (a v5e has 128 MiB of VMEM; the
# compiler's default of 16 MiB would cap a qwen2-0.5b pool near 62k blocks).
DECODE_VMEM_LIMIT_BYTES = 64 * 2 ** 20


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
    """Column ``lane`` of a (..., T, lanes) tile as (..., T, 1): a masked
    row-sum, which is exact (one value plus zeros), so the dequant after it
    keeps the float path of a gathered (T, 1) column."""
    hit = jax.lax.broadcasted_iota(jnp.int32, tile.shape,
                                   tile.ndim - 1) == lane
    return jnp.sum(jnp.where(hit, tile, 0.0), axis=-1, keepdims=True)


def decode_blocks_per_step(t: int, row_bytes: int, m: int) -> int:
    """Pool blocks the paged decode kernel fetches and scores together, P:
    about 256 tokens, fewer where a group's (tokens, ``row_bytes``) codes
    would pass 256 KiB, never more than a table row of ``m`` blocks.  A
    code row whose bytes are not a multiple of the 128-lane tile (one kv
    head of 64 in int8, two in int4) cannot be copied one block at a time
    by hand, so it keeps the pipeline's one-block walk: P = 1."""
    if row_bytes % 128:
        return 1
    tokens = max(t, min(256, 2 ** 18 // row_bytes))
    return max(1, min(m, tokens // t))


def _paged_kernel(bt_ref, len_ref, q_ref, ks_ref, kz_ref, vs_ref, vz_ref,
                  k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, kf_ref, vf_ref,
                  m_ref, l_ref, acc_ref, slot_ref, *, p: int, t: int, d: int,
                  scale: float, bits: int):
    """Same online-softmax body as ``_kernel``, one update per group of P
    table entries and kv head; a grid step is one lane, and its loop runs
    over the lane's live groups only.  Group j's code rows arrive by async
    copies started while group j-1 was scored (a lane's first group by the
    previous lane's last), into the other half of a two-slot buffer;
    ``slot_ref`` carries, across grid steps, the slot of the next group
    and whether its copies are in flight."""
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    pt = p * t
    # a lane attends at most its table
    live_len = lambda lane: jnp.minimum(len_ref[lane], bt_ref.shape[1] * t)
    length = live_len(b)
    last = jnp.maximum(length - 1, 0) // t            # last live block
    n_groups = (length + pt - 1) // pt
    # the next lane with a live block, or nb
    succ = jax.lax.while_loop(
        lambda c: (c < nb) & (len_ref[jnp.minimum(c, nb - 1)] == 0),
        lambda c: c + 1, b + 1)

    def copies(lane, j, slot, act):
        """``act`` each copy of lane's group j (its live blocks only) into
        buffer ``slot``."""
        n_live = jnp.minimum(p, (live_len(lane) - 1) // t - j * p + 1)

        def one(i, carry):
            blk = bt_ref[lane, j * p + i]
            for src, dst in ((k_hbm, k_buf), (v_hbm, v_buf)):
                act(pltpu.make_async_copy(src.at[blk], dst.at[slot, i],
                                          sem.at[slot]))
            return carry
        jax.lax.fori_loop(0, n_live, one, 0)

    @pl.when(b == 0)
    def _start():
        slot_ref[0] = 0                 # slot of the next group
        slot_ref[1] = 0                 # its copies already started

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((n_groups > 0) & (slot_ref[1] == 0))
    def _first():
        copies(b, 0, slot_ref[0], lambda c: c.start())

    def group(j, carry):
        slot = (slot_ref[0] + j) % 2

        @pl.when(j + 1 < n_groups)
        def _next_group():
            copies(b, j + 1, 1 - slot, lambda c: c.start())

        @pl.when((j + 1 == n_groups) & (succ < nb))
        def _next_lane():
            copies(succ, 0, 1 - slot, lambda c: c.start())

        copies(b, j, slot, lambda c: c.wait())

        def unpack(i, carry):
            rows = pl.ds(pl.multiple_of(i * t, t), t)
            kf_ref[rows, :] = codes_f32(k_buf[slot, i], bits)
            vf_ref[rows, :] = codes_f32(v_buf[slot, i], bits)
            return carry
        jax.lax.fori_loop(0, p, unpack, 0)
        k_all, v_all = kf_ref[...], vf_ref[...]               # (P*T, KH*D)
        # each block's V affine column, all heads at once; entries past the
        # last live block read the group's first (live) column, and their
        # buffer rows hold earlier codes: both meet a zero probability
        vs, vz = [], []
        for i in range(p):
            blk = bt_ref[b, j * p + jnp.where(j * p + i <= last, i, 0)]
            off = pl.multiple_of((blk // 128) * 128, 128)
            vs.append(pick_lane(vs_ref[:, :, pl.ds(off, 128)], blk % 128))
            vz.append(pick_lane(vz_ref[:, :, pl.ds(off, 128)], blk % 128))
        vs = jnp.concatenate(vs, axis=1)                       # (KH, P*T, 1)
        vz = jnp.concatenate(vz, axis=1)
        for hh in range(q_ref.shape[1]):
            cols = slice(hh * d, (hh + 1) * d)
            q = q_ref[0, hh].astype(jnp.float32) * scale      # (G, D)
            k = (k_all[:, cols] - kz_ref[0, hh:hh + 1]) * ks_ref[0, hh:hh + 1]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)

            pos = j * pt + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos < length, s, NEG_INF)

            m_prev = m_ref[hh]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            pr = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)

            v = (v_all[:, cols] - vz[hh]) * vs[hh]            # per-tok affine
            acc_ref[hh] = acc_ref[hh] * alpha + jax.lax.dot_general(
                pr, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            l_ref[hh] = l_ref[hh] * alpha + jnp.sum(pr, axis=-1, keepdims=True)
            m_ref[hh] = m_new
        return carry

    jax.lax.fori_loop(0, n_groups, group, 0)

    @pl.when(n_groups > 0)
    def _advance():
        slot_ref[0] = (slot_ref[0] + n_groups) % 2
        slot_ref[1] = 1

    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                ).astype(o_ref.dtype)


def _paged_block_kernel(bt_ref, len_ref, q_ref, ks_ref, kz_ref, k_ref, v_ref,
                        vs_ref, vz_ref, o_ref, m_ref, l_ref, acc_ref, *,
                        n_blk: int, t: int, d: int, lanes: int, scale: float,
                        bits: int):
    """The one-block walk of narrow code rows: a (B, M) grid over the block
    table, the pipeline fetching one block a step.  Dead table lanes
    (m*T >= length) skip the compute, and the index maps clamp them to the
    last live block so the pipeline revisits an already-resident tile."""
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


def _block_walk(q_r, k_scale, k_zero, k_vals, v_vals, v_scale, v_zero,
                block_tables, lengths, *, bits: int, interpret: bool):
    """``paged_kv_decode_attention`` by ``_paged_block_kernel``."""
    b, kh, g, d = q_r.shape
    t, row = k_vals.shape[1:]
    m = block_tables.shape[1]
    lanes = scale_lanes(v_scale.shape[-1])
    kernel = functools.partial(_paged_block_kernel, n_blk=m, t=t, d=d,
                               lanes=lanes, scale=1.0 / (d ** 0.5), bits=bits)

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
            pl.BlockSpec((1, t, row),
                         lambda bb, mm, bt, ln: (_blk(bb, mm, ln, bt), 0, 0)),
            pl.BlockSpec((1, t, row),
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
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, g, d), jnp.float32),
        interpret=interpret,
    )(block_tables, lengths, q_r, k_scale, k_zero, k_vals, v_vals, v_scale,
      v_zero)


def _group_walk(q_r, k_scale, k_zero, k_vals, v_vals, v_scale, v_zero,
                block_tables, lengths, *, bits: int, interpret: bool):
    """``paged_kv_decode_attention`` by ``_paged_kernel``."""
    b, kh, g, d = q_r.shape
    n, t, row = k_vals.shape
    p = decode_blocks_per_step(t, row, block_tables.shape[1])
    kernel = functools.partial(_paged_kernel, p=p, t=t, d=d,
                               scale=1.0 / (d ** 0.5), bits=bits)
    lane_spec = lambda shape: pl.BlockSpec(
        shape, lambda bb, *_: (bb,) + (0,) * (len(shape) - 1))
    # the V affine leaves whole, once a launch, their lanes padded to whole
    # 128-lane tiles so every block's tile is an aligned slice
    whole = pl.BlockSpec((kh, t, pl.cdiv(n, 128) * 128),
                         lambda bb, *_: (0, 0, 0),
                         pipeline_mode=pl.Buffered(1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # block_tables, lengths
        grid=(b,),
        in_specs=[lane_spec((1, kh, g, d)), lane_spec((1, kh, d)),
                  lane_spec((1, kh, d)), whole, whole,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=lane_spec((1, kh, g, d)),
        scratch_shapes=[pltpu.VMEM((2, p, t, row), k_vals.dtype),
                        pltpu.VMEM((2, p, t, row), v_vals.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.VMEM((p * t, kh * d), jnp.float32),
                        pltpu.VMEM((p * t, kh * d), jnp.float32),
                        pltpu.VMEM((kh, g, 1), jnp.float32),
                        pltpu.VMEM((kh, g, 1), jnp.float32),
                        pltpu.VMEM((kh, g, d), jnp.float32),
                        pltpu.SMEM((2,), jnp.int32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, g, d), jnp.float32),
        # copies run ahead across grid steps: the lanes go in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=DECODE_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(block_tables, lengths, q_r, k_scale, k_zero, v_scale, v_zero, k_vals,
      v_vals)


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
    block ids (entries past a lane's last live block may point anywhere:
    they are never read); lengths: (B,) int32 -> (B, H, D) f32 (a lane of
    length 0 reads zeros).

    The grid walks the lanes; each lane's loop visits only its live blocks,
    P at a time (``decode_blocks_per_step``), copying their (T, KH*Dp) code
    rows from the pool by async copies double-buffered across groups and
    lanes.  The layer's V scale and zero leaves sit whole in VMEM, fetched
    once a launch (``paged_attention.decode_vmem_bytes`` sizes them for the
    engine's guard).  Code rows whose bytes are not a multiple of 128 take
    the one-block walk of ``_paged_block_kernel`` instead.
    """
    b, h, d = q.shape
    kh = k_scale.shape[-2]
    dp = k_vals.shape[2] // kh
    walk = _block_walk if (kh * dp) % 128 else _group_walk
    out = walk(q.reshape(b, kh, h // kh, d), k_scale, k_zero, k_vals, v_vals,
               v_scale, v_zero, block_tables, lengths,
               bits=8 if dp == d else 4, interpret=interpret)
    return out.reshape(b, h, d)
