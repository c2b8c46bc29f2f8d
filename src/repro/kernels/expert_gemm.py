"""Pallas TPU kernel: the held experts' grouped INT8 GEMM (dropless MoE).

Rows arrive sorted by expert, each expert's group padded to a multiple of
the row tile ``tm`` (``models/moe.py`` lays them out), so every row tile
belongs to one expert: ``tile_expert[i]`` names it and ``used`` counts the
tiles that hold rows.  The grid walks (output-column tile, row tile); a
tile's weight block is its expert's ``(K, tn)`` int8 codes, fetched from
HBM only when the expert changes from one row tile to the next (the block
index repeats otherwise), so each held expert's codes are read once per
column tile however many of its tiles there are.  Tiles past ``used`` reuse
the last used tile's blocks (no DMA) and write zeros.

The product runs on the MXU's int8 path into int32 and is rescaled once
by the row's activation scale and the expert's per-output-channel weight
scale: W8A8, the weights never leave int8.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# weight bytes per (K, tn) block: double-buffered, it stays well inside the
# 16 MiB of scoped VMEM beside the row tiles
WEIGHT_BLOCK_BYTES = 4 * 2 ** 20


def column_tile(k: int, n: int) -> int:
    """The widest multiple of 128 dividing ``n`` whose int8 ``(k, tn)``
    block fits ``WEIGHT_BLOCK_BYTES`` (all of ``n`` when it fits or when
    ``n`` is not a multiple of 128)."""
    if k * n <= WEIGHT_BLOCK_BYTES or n % 128:
        return n
    best = 128
    for tn in range(128, n + 1, 128):
        if n % tn == 0 and k * tn <= WEIGHT_BLOCK_BYTES:
            best = tn
    return best


def _kernel(te_ref, used_ref, x_ref, w_ref, xs_ref, ws_ref, o_ref):
    live = pl.program_id(1) < used_ref[0]

    @pl.when(live)
    def _gemm():
        acc = jax.lax.dot_general(x_ref[...], w_ref[0],
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        o_ref[...] = (acc.astype(jnp.float32) * xs_ref[...]
                      * ws_ref[0]).astype(o_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _pad():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("tm", "out_dtype", "interpret"))
def expert_gemm(x: jax.Array, x_scale: jax.Array, w: jax.Array,
                w_scale: jax.Array, tile_expert: jax.Array, used: jax.Array,
                *, tm: int, out_dtype=jnp.float32,
                interpret: bool = False) -> jax.Array:
    """x (R, K) int8 rows sorted by expert, R a multiple of ``tm``; x_scale
    (R, 1) f32; w (E, K, N) int8; w_scale (E, 1, N) f32; tile_expert
    (R // tm,) int32; used (1,) int32 -> (R, N) ``out_dtype``, zero past
    ``used * tm``."""
    r, k = x.shape
    e, k2, n = w.shape
    assert k == k2 and r % tm == 0, (x.shape, w.shape, tm)
    tn = column_tile(k, n)

    def row(j, i, te, used):
        return jnp.maximum(jnp.minimum(i, used[0] - 1), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # tile_expert, used
        grid=(n // tn, r // tm),
        in_specs=[
            pl.BlockSpec((tm, k), lambda j, i, te, u: (row(j, i, te, u), 0)),
            pl.BlockSpec((1, k, tn),
                         lambda j, i, te, u: (te[row(j, i, te, u)], 0, j)),
            pl.BlockSpec((tm, 1), lambda j, i, te, u: (row(j, i, te, u), 0)),
            pl.BlockSpec((1, 1, tn),
                         lambda j, i, te, u: (te[row(j, i, te, u)], 0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda j, i, te, u: (i, j)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, n), out_dtype),
        interpret=interpret,
    )(tile_expert, used, x, w, x_scale, w_scale)
