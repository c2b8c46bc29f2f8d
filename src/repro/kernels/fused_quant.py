"""Pallas TPU kernel: fused row-wise dynamic INT8 quantization (paper Alg. 1).

The GPU version stages tiles HBM->SMEM with cudaMemcpyAsync and reduces with
warp shuffles (paper §3.2).  TPU mapping (DESIGN.md §2): the grid pipeline
streams (bm, K) tiles HBM->VMEM (double-buffered by Pallas), the absmax
reduction runs on the VPU across the 128-wide lane dim, and the quantized
tile is written back alongside its per-row scale — one pass over the data,
so T_quant rides on T_load exactly like the paper's fused stage.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .paged_attention import VMEM_LIMIT_BYTES


def _kernel(x_ref, q_ref, s_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                    # (bm, K)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)    # (bm, 1) VPU reduce
    scale = jnp.maximum(amax, eps) / 127.0
    q = jnp.clip(jnp.round(x / scale), -128, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _block_rows(m: int, k: int, itemsize: int, block_m: int) -> int:
    """Rows per grid step so that the (bm, K) working set fits VMEM.

    Per element: the double-buffered input (2 * itemsize) and int8 output
    (2 * 1) blocks plus ~8 bytes of f32 temporaries.  A power of two (so a
    multiple of the 8-row tile) from 8 up to ``block_m``, or all of ``m``
    when that is smaller.  On a v5e this keeps f32 K=4864 at 128 rows, where
    256 rows exhaust VMEM."""
    fit = VMEM_LIMIT_BYTES // (k * (2 * itemsize + 2 + 8))
    bm = 8
    while bm * 2 <= min(block_m, fit):
        bm *= 2
    return m if m <= bm else bm


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def fused_quant(x: jax.Array, *, block_m: int = 256, eps: float = 1e-8,
                interpret: bool = False):
    """x: (M, K) -> (q int8 (M, K), scale f32 (M, 1)).

    At most ``block_m`` rows per grid step, fewer where K is wide: K stays
    whole in VMEM (see ``_block_rows``).
    """
    m, k = x.shape
    bm = _block_rows(m, k, x.dtype.itemsize, block_m)
    grid = (-(-m // bm),)
    return pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((m, k), jnp.int8),
                   jax.ShapeDtypeStruct((m, 1), jnp.float32)],
        interpret=interpret,
    )(x)
