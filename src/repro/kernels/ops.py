"""Dispatch layer: model code calls these; they pick Pallas kernel vs oracle.

``qdot(x, w)`` is the single integration point for the paper's Execution
Runtime Layer: a weight leaf may be a raw array (fp path), or a QTensor from
core.quantize_tree; dispatch covers

  * W8A8 per-channel symmetric  -> fused dynamic act-quant + INT8 GEMM
    (paper Alg. 1 + Alg. 2 — Pallas on TPU, int-matmul oracle elsewhere)
  * W8A8 asymmetric / grouped   -> dequant-then-GEMM oracle
  * weight-only INT4/INT3/INT2 (AWQ/GPTQ/search) -> dequant-then-GEMM (W4A16)

Pallas execution is enabled when running on real TPU (or forced with
REPRO_FORCE_PALLAS=1, interpret mode — used by integration tests).

Under a multi-device mesh every kernel launch runs inside ``shard_map``: the
TPU compiler cannot partition a Mosaic kernel on its own.  GQA attention
splits over kv heads and the W8A8 GEMM over its output columns where the
tensor-parallel axis divides them; any other launch runs replicated.
"""
from __future__ import annotations

import os
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.qtensor import QTensor
from . import ref
from .fused_quant import fused_quant
from .w8a8_matmul import w8a8_matmul
from .expert_gemm import expert_gemm as _expert_gemm
from .kv_decode_attention import kv_decode_attention, paged_kv_decode_attention
from . import paged_attention as pa


def _use_pallas() -> Optional[dict]:
    """None = jnp oracle; {"interpret": bool} = pallas_call kwargs."""
    if os.environ.get("REPRO_FORCE_PALLAS") == "1":
        return {"interpret": jax.default_backend() != "tpu"}
    if jax.default_backend() == "tpu":
        return {"interpret": False}
    return None


def _split_axis(logical: str, *dims: int):
    """Mesh axis for splitting a kernel launch along ``logical`` when the
    active rules bind it to live mesh axes of product ``n > 1`` and every
    dim in ``dims`` divides by ``n``; else None.

    For the GQA paged kernels (``kv_heads``): attention is head-local, and
    the q head block [i*H/n, (i+1)*H/n) attends exactly kv heads
    [i*KH/n, (i+1)*KH/n) (heads are grouped kv-major), so the per-shard
    launches compute the same floats as one wide launch."""
    from repro.distributed import sharding as shd
    mesh = shd.active_mesh()
    if mesh is None:
        return None
    axes = shd.resolve(logical)
    n = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    if n <= 1 or any(d % n for d in dims):
        return None
    return axes[0] if len(axes) == 1 else tuple(axes)


def _on_mesh(fn, in_specs, out_specs):
    """``fn`` under ``shard_map`` over the active mesh, or ``fn`` itself
    when no multi-device mesh is bound.  ``in_specs=None`` runs the launch
    replicated: every operand whole on every device."""
    from repro.distributed import sharding as shd
    mesh = shd.active_mesh()
    if mesh is None or mesh.size == 1:
        return fn
    if in_specs is None:
        in_specs, out_specs = P(), P()
    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


def _w8a8(x2d: jax.Array, qw: QTensor, out_dtype):
    w_scale = qw.scale.reshape(1, -1)
    pk = _use_pallas()
    if pk is None:
        q_x, x_scale = ref.fused_quant_ref(x2d)
        return ref.w8a8_matmul_ref(q_x, x_scale, qw.values, w_scale,
                                   out_dtype)

    def launch(x, w, ws):
        q_x, x_scale = fused_quant(x, **pk)
        return w8a8_matmul(q_x, x_scale, w, ws, out_dtype=out_dtype, **pk)
    # each device quantizes the whole activation and multiplies its slice of
    # the output columns: full contraction locally, so the columns are the
    # floats of one wide launch
    ax = _split_axis("ffn", qw.values.shape[1])
    specs = None if ax is None else (P(), P(None, ax), P(None, ax))
    return _on_mesh(launch, specs, P(None, ax))(x2d, qw.values, w_scale)


def quantize_rows(x2d: jax.Array):
    """Dynamic per-row symmetric INT8 activations: (M, K) -> (int8 (M, K),
    f32 scale (M, 1)) — the fused-quant kernel on TPU, its oracle
    elsewhere (the same quantization ``qdot``'s W8A8 path applies)."""
    pk = _use_pallas()
    if pk is None:
        return ref.fused_quant_ref(x2d)
    return _on_mesh(partial(fused_quant, **pk), None, None)(x2d)


def expert_gemm(x_q: jax.Array, x_scale: jax.Array, qw: QTensor,
                tile_expert: jax.Array, used: jax.Array, *, tm: int,
                out_dtype=jnp.float32) -> jax.Array:
    """The held experts' grouped W8A8 GEMM over rows sorted by expert
    (``kernels/expert_gemm.py`` on TPU, ``ref.expert_gemm_ref`` elsewhere).
    ``qw``: per-output-channel int8 expert codes, values (E, K, N), scale
    (E, 1, N)."""
    pk = _use_pallas()
    if pk is None:
        return ref.expert_gemm_ref(x_q, x_scale, qw.values, qw.scale,
                                   tile_expert, used, tm=tm,
                                   out_dtype=out_dtype)
    fn = partial(_expert_gemm, tm=tm, out_dtype=out_dtype, **pk)
    return _on_mesh(fn, None, None)(x_q, x_scale, qw.values, qw.scale,
                                    tile_expert, used)


def qdot(x: jax.Array, w, out_dtype=None) -> jax.Array:
    """Matmul against a maybe-quantized weight.  x: (..., K); w: (K, N) array
    or QTensor.  Returns (..., N) in ``out_dtype`` (default x.dtype)."""
    out_dtype = out_dtype or x.dtype
    if not isinstance(w, QTensor):
        return jnp.matmul(x, w.astype(x.dtype)).astype(out_dtype)

    lead = x.shape[:-1]
    k = x.shape[-1]
    x2d = x.reshape(-1, k)

    fast_w8a8 = (w.bits == 8 and w.zero is None and w.values.ndim == 2
                 and w.axis == (0,))
    if fast_w8a8:
        out = _w8a8(x2d, w, jnp.float32)
    else:
        deq = w.dequantize(jnp.float32)
        if deq.ndim == 3 and w.axis == (1,):              # ZeroQuant grouped
            deq = deq.reshape(-1, deq.shape[-1])
        out = x2d.astype(jnp.float32) @ deq               # weight-only path
    return out.reshape(*lead, -1).astype(out_dtype)


def decode_attention(q, k_vals, k_scale, k_zero, v_vals, v_scale, v_zero,
                     length, *, chunk: int = 512):
    """SimQuant cache decode attention: Pallas on TPU, oracle elsewhere.

    REPRO_FLASH_DECODE=1 selects the chunk-scanned jnp formulation: the
    INT8 cache is dequantized per chunk inside a scan (XLA fuses the
    dequant into the chunk matmul) instead of materializing the full fp32
    cache — the XLA-level mirror of the Pallas kernel's memory behaviour.
    """
    pk = _use_pallas()
    if pk is not None:
        fn = partial(kv_decode_attention, chunk=chunk, **pk)
        return _on_mesh(fn, None, None)(q, k_vals, k_scale, k_zero, v_vals,
                                        v_scale, v_zero, length)
    if os.environ.get("REPRO_FLASH_DECODE") == "1":
        return flash_decode_ref(q, k_vals, k_scale, k_zero,
                                v_vals, v_scale, v_zero, length, chunk=2048)
    return ref.kv_decode_attention_ref(q, k_vals, k_scale, k_zero,
                                       v_vals, v_scale, v_zero, length)


def paged_decode_attention(q, k_vals, k_scale, k_zero, v_vals, v_scale, v_zero,
                           block_tables, lengths):
    """Paged-pool decode attention: Pallas gather-by-block-table kernel on
    TPU, dense-gather oracle elsewhere (bit-identical float path to the
    dense engine's oracle — golden-parity contract)."""
    pk = _use_pallas()
    if pk is not None:
        ax = _split_axis("kv_heads", k_scale.shape[-2], q.shape[-2])
        specs = None if ax is None else (
            P(None, ax, None),          # q (B,H,D)
            P(None, None, ax),          # k_vals (N,T,KH*D): whole heads
            P(None, ax, None),          # k_scale (B,KH,D)
            P(None, ax, None),          # k_zero
            P(None, None, ax),          # v_vals
            P(ax, None, None),          # v_scale (KH,T,N)
            P(ax, None, None),          # v_zero
            P(None, None), P(None))     # block_tables, lengths
        fn = _on_mesh(partial(paged_kv_decode_attention, **pk), specs,
                      P(None, ax, None))
        return fn(q, k_vals, k_scale, k_zero, v_vals, v_scale, v_zero,
                  block_tables, lengths)
    return ref.paged_kv_decode_attention_ref(q, k_vals, k_scale, k_zero,
                                             v_vals, v_scale, v_zero,
                                             block_tables, lengths)


def paged_verify_attention(q, k_vals, k_scale, k_zero, v_vals, v_scale,
                           v_zero, block_tables, lengths):
    """Multi-token spec-decode verify: one launch scores all G positions
    (Pallas on TPU); the oracle hoists the pool gather out of the position
    loop — both are bit-identical to G sequential decode-attention calls,
    the greedy spec-decode golden contract.  q: (B,G,H,D) -> (B,G,H,D)."""
    pk = _use_pallas()
    if pk is not None:
        ax = _split_axis("kv_heads", k_scale.shape[-2], q.shape[-2])
        specs = None if ax is None else (
            P(None, None, ax, None),    # q (B,G,H,D)
            P(None, None, ax),          # k_vals (N,T,KH*D)
            P(None, ax, None),          # k_scale (B,KH,D)
            P(None, ax, None),          # k_zero
            P(None, None, ax),          # v_vals
            P(ax, None, None),          # v_scale (KH,T,N)
            P(ax, None, None),          # v_zero
            P(None, None), P(None))     # block_tables, lengths
        fn = _on_mesh(partial(pa.paged_kv_verify_attention, **pk), specs,
                      P(None, None, ax, None))
        return fn(q, k_vals, k_scale, k_zero, v_vals, v_scale, v_zero,
                  block_tables, lengths)
    return ref.paged_kv_verify_attention_ref(q, k_vals, k_scale, k_zero,
                                             v_vals, v_scale, v_zero,
                                             block_tables, lengths)


def mla_paged_verify_attention(q_nope, q_rope, w_uk, w_uv, c_vals, c_scale,
                               kr_vals, kr_scale, block_tables, lengths):
    """MLA multi-token verify (absorbed).  q_nope: (B,G,H,dn), q_rope:
    (B,G,H,dr) -> (B,G,H,dv).  The kernel path folds W_uk/W_uv per position
    with the exact per-j einsums of ``mla_decode_ref`` so its float path
    stays bitwise comparable to the oracle."""
    pk = _use_pallas()
    if pk is not None:
        g = q_nope.shape[1]
        f32 = jnp.float32
        q_lat = jnp.stack(
            [jnp.einsum("bhd,rhd->bhr", q_nope[:, j].astype(f32),
                        w_uk.astype(f32)) for j in range(g)], axis=1)
        fn = partial(pa.mla_paged_verify_attention,
                     qk_nope_dim=q_nope.shape[-1], **pk)
        o_lat = _on_mesh(fn, None, None)(
            q_lat, q_rope, c_vals, c_scale, kr_vals, kr_scale, block_tables,
            lengths)
        return jnp.stack(
            [jnp.einsum("bhr,rhd->bhd", o_lat[:, j], w_uv.astype(f32))
             for j in range(g)], axis=1)
    return ref.mla_paged_verify_attention_ref(q_nope, q_rope, w_uk, w_uv,
                                              c_vals, c_scale, kr_vals,
                                              kr_scale, block_tables, lengths)


def mla_paged_decode_attention(q_nope, q_rope, w_uk, w_uv, c_vals, c_scale,
                               kr_vals, kr_scale, block_tables, lengths):
    """MLA paged decode (absorbed): each lane's query attends its first
    ``lengths[b]`` pool tokens through its block table.  q_nope: (B,H,dn),
    q_rope: (B,H,dr) -> (B,H,dv).  The kernel streams the live latent
    blocks (Pallas on TPU); the oracle gathers whole tables."""
    pk = _use_pallas()
    if pk is not None:
        f32 = jnp.float32
        q_lat = jnp.einsum("bhd,rhd->bhr", q_nope.astype(f32),
                           w_uk.astype(f32))
        fn = partial(pa.mla_paged_decode_attention,
                     qk_nope_dim=q_nope.shape[-1], **pk)
        o_lat = _on_mesh(fn, None, None)(
            q_lat, q_rope, c_vals, c_scale, kr_vals, kr_scale, block_tables,
            lengths)
        return jnp.einsum("bhr,rhd->bhd", o_lat, w_uv.astype(f32))
    return ref.mla_paged_decode_attention_ref(q_nope, q_rope, w_uk, w_uv,
                                              c_vals, c_scale, kr_vals,
                                              kr_scale, block_tables, lengths)


def paged_prefix_chunk_attention(q, k_vals, k_scale, k_zero, v_vals, v_scale,
                                 v_zero, k_chunk, v_chunk, block_row, ctx):
    """Chunk-prefill attention: chunk queries read the cached prefix straight
    from the INT8 pool via the block-table row (Pallas on TPU, dense-gather
    oracle elsewhere).  q: (1,C,H,D) -> (1,C,H,D) f32."""
    pk = _use_pallas()
    if pk is not None:
        ax = _split_axis("kv_heads", k_scale.shape[-2], q.shape[-2])
        specs = None if ax is None else (
            P(None, None, ax, None),    # q (1,C,H,D)
            P(None, None, ax),          # k_vals (N,T,KH*D)
            P(ax, None),                # k_scale[slot] (KH,D)
            P(ax, None),                # k_zero[slot]
            P(None, None, ax),          # v_vals
            P(ax, None, None),          # v_scale (KH,T,N)
            P(ax, None, None),          # v_zero
            P(None, None, ax, None),    # k_chunk (1,C,KH,D)
            P(None, None, ax, None),    # v_chunk
            P(None), P())               # block_row, ctx
        fn = _on_mesh(partial(pa.paged_prefix_chunk_attention, **pk), specs,
                      P(None, None, ax, None))
        return fn(q, k_vals, k_scale, k_zero, v_vals, v_scale, v_zero,
                  k_chunk, v_chunk, block_row, ctx)
    return ref.paged_prefix_chunk_attention_ref(q, k_vals, k_scale, k_zero,
                                                v_vals, v_scale, v_zero,
                                                k_chunk, v_chunk, block_row,
                                                ctx)


def mla_paged_prefix_chunk_attention(q_lat, q_rope, c_vals, c_scale,
                                     kr_vals, kr_scale, c_chunk, kr_chunk,
                                     block_row, ctx, *, qk_nope_dim: int):
    """MLA chunk-prefill attention in absorbed latent space.
    q_lat: (1,C,H,rkv) -> o_lat (1,C,H,rkv) f32 (caller applies W_uv)."""
    pk = _use_pallas()
    if pk is not None:
        fn = partial(pa.mla_paged_prefix_chunk_attention,
                     qk_nope_dim=qk_nope_dim, **pk)
        return _on_mesh(fn, None, None)(
            q_lat, q_rope, c_vals, c_scale, kr_vals, kr_scale, c_chunk,
            kr_chunk, block_row, ctx)
    return ref.mla_paged_prefix_chunk_attention_ref(
        q_lat, q_rope, c_vals, c_scale, kr_vals, kr_scale, c_chunk, kr_chunk,
        block_row, ctx, qk_nope_dim=qk_nope_dim)


def flash_decode_ref(q, k_vals, k_scale, k_zero, v_vals, v_scale, v_zero,
                     length, *, chunk: int = 2048):
    """Chunk-scanned INT8-cache decode attention (online softmax)."""
    b, h, d = q.shape
    s, kh = k_vals.shape[1], k_vals.shape[2]
    g = h // kh
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        padv = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_vals, v_vals = padv(k_vals), padv(v_vals)
        v_scale = jnp.pad(v_scale, ((0, 0), (0, pad), (0, 0), (0, 0)),
                          constant_values=1.0)
        v_zero = jnp.pad(v_zero, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (s + pad) // chunk
    qg = (q.reshape(b, kh, g, d).astype(jnp.float32) / (d ** 0.5))
    kc = k_vals.reshape(b, nc, chunk, kh, d).transpose(1, 0, 2, 3, 4)
    vc = v_vals.reshape(b, nc, chunk, kh, d).transpose(1, 0, 2, 3, 4)
    vs = v_scale.reshape(b, nc, chunk, kh, 1).transpose(1, 0, 2, 3, 4)
    vz = v_zero.reshape(b, nc, chunk, kh, 1).transpose(1, 0, 2, 3, 4)
    ks32, kz32 = k_scale.astype(jnp.float32), k_zero.astype(jnp.float32)
    neg = -2.0e38

    def step(carry, inp):
        m, l, acc = carry
        idx, k_j, v_j, vs_j, vz_j = inp
        kf = (k_j.astype(jnp.float32) - kz32) * ks32          # (B,C,KH,D)
        sc = jnp.einsum("bhgd,bchd->bhgc", qg, kf)
        pos = idx * chunk + jnp.arange(chunk)
        sc = jnp.where((pos[None, :] < length[:, None])[:, None, None], sc, neg)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        pexp = jnp.exp(sc - m_new)
        alpha = jnp.exp(m - m_new)
        vf = (v_j.astype(jnp.float32) - vz_j) * vs_j
        acc = acc * alpha + jnp.einsum("bhgc,bchd->bhgd", pexp, vf)
        l = l * alpha + jnp.sum(pexp, axis=-1, keepdims=True)
        return (m_new, l, acc), None

    m0 = jnp.full((b, kh, g, 1), neg, jnp.float32)
    l0 = jnp.zeros((b, kh, g, 1), jnp.float32)
    a0 = jnp.zeros((b, kh, g, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                  (jnp.arange(nc), kc, vc, vs, vz))
    return (acc / jnp.maximum(l, 1e-30)).reshape(b, h, d)
