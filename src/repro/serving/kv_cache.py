"""SimQuant INT8 KV cache (paper §2 SimQuant + §3.4 runtime adaptation).

Layouts (per pattern-position, stacked over scan repeats on a leading axis):

  GQA:  k_vals  int8 (R, B, Smax, KH, D)   per-channel affine K
        k_scale f32  (R, B, 1,    KH, D)   (frozen at prefill — KVQuant-style
        k_zero  f32  (R, B, 1,    KH, D)    offline per-channel calibration)
        v_vals  int8 (R, B, Smax, KH, D)   per-token affine V
        v_scale f32  (R, B, Smax, KH, 1)   (computed online per appended token)
        v_zero  f32  (R, B, Smax, KH, 1)
  MLA:  c_vals  int8 (R, B, Smax, rkv)  + per-token scale (R,B,Smax,1)
        kr_vals int8 (R, B, Smax, dr)   + per-token scale (R,B,Smax,1)
  SSM:  conv      bf16 (R, B, K-1, conv_dim)   causal-conv tail (x|B|C fused)
        ssd_vals  int8 (R, B, H, P, N)         quantized SSD state
        ssd_scale f32  (R, B, H)               per-slot per-head absmax scale

SSM entries are built/consumed by ``models.ssm.ssm_state_entry`` /
``ssm_state_from_entry``: the SSD state is stored symmetric-absmax INT8
(4x smaller than the old f32 leaf) and round-trips through the *same*
quantize/dequantize ops the paged state pool (``serving/state_pool.py``)
uses, so dense and paged hybrid serving emit identical greedy tokens.

Decode appends K with the *frozen* per-channel scales (clipping handled by
the affine clip — paper Eq. 1) and V/token scales computed on the fly
(paper's online quantization path).  Batch shards over (pod, data); the
sequence axis can shard over `data` for the long-context cells ("kv_seq"
logical axis — DESIGN.md §4 SP).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.qtensor import int_range, storage_dtype
from repro.core.methods.simquant import quantize_keys, quantize_values
from repro.distributed.sharding import constrain
from repro.models.config import ModelConfig
from repro.serving.codec import latent_rows


# ---------------------------------------------------------------------------
# GQA cache
# ---------------------------------------------------------------------------

def gqa_cache_entry(k: jax.Array, v: jax.Array, smax: int) -> Dict[str, jax.Array]:
    """Quantize prefill K/V (B, S, KH, D) and embed into an Smax-long cache."""
    b, s, kh, d = k.shape
    qk = quantize_keys(k)                       # per-channel (reduce over seq)
    qv = quantize_values(v)                     # per-token
    pad = [(0, 0), (0, smax - s), (0, 0), (0, 0)]
    entry = {
        "k_vals": jnp.pad(qk.values, pad),
        "k_scale": qk.scale,
        "k_zero": qk.zero,
        "v_vals": jnp.pad(qv.values, pad),
        "v_scale": jnp.pad(qv.scale, pad, constant_values=1.0),
        "v_zero": jnp.pad(qv.zero, pad),
    }
    return {n: constrain_cache(n, a) for n, a in entry.items()}


def constrain_cache(name: str, a: jax.Array) -> jax.Array:
    """Apply logical sharding to one cache tensor (no leading repeat dim)."""
    if a.ndim == 4:
        seq_ax = "kv_seq" if a.shape[1] > 1 else None
        return constrain(a, "batch", seq_ax, "kv_heads", None)
    if a.ndim == 3:
        seq_ax = "kv_seq" if a.shape[1] > 1 else None
        return constrain(a, "batch", seq_ax, None)
    return a


def gqa_cache_append(entry: Dict[str, jax.Array], k_t: jax.Array, v_t: jax.Array,
                     pos: jax.Array) -> Dict[str, jax.Array]:
    """Append one token's K/V (B, KH, D) at position ``pos`` (B,).

    K uses the frozen per-channel scales; V computes fresh per-token scales
    (paper Alg. 1 online path with alpha=0 — instantaneous range).
    """
    b, kh, d = k_t.shape
    qmin, qmax = int_range(8)
    k_scale = entry["k_scale"][:, 0]            # (B,KH,D)
    k_zero = entry["k_zero"][:, 0]
    k_q = jnp.clip(jnp.round(k_t.astype(jnp.float32) / k_scale) + k_zero,
                   qmin, qmax).astype(storage_dtype(8))

    vmin = jnp.min(v_t, axis=-1, keepdims=True).astype(jnp.float32)
    vmax = jnp.max(v_t, axis=-1, keepdims=True).astype(jnp.float32)
    v_scale = jnp.maximum((vmax - vmin) / (qmax - qmin), 1e-8)
    v_zero = qmin - jnp.round(vmin / v_scale)
    v_q = jnp.clip(jnp.round(v_t.astype(jnp.float32) / v_scale) + v_zero,
                   qmin, qmax).astype(storage_dtype(8))

    bidx = jnp.arange(b)
    new = dict(entry)
    new["k_vals"] = entry["k_vals"].at[bidx, pos].set(k_q)
    new["v_vals"] = entry["v_vals"].at[bidx, pos].set(v_q)
    new["v_scale"] = entry["v_scale"].at[bidx, pos].set(v_scale)
    new["v_zero"] = entry["v_zero"].at[bidx, pos].set(v_zero)
    return new


# ---------------------------------------------------------------------------
# MLA latent cache
# ---------------------------------------------------------------------------

def mla_cache_entry(c_kv: jax.Array, k_rope: jax.Array, smax: int) -> Dict[str, jax.Array]:
    """Quantize the latent (B,S,rkv) + rope key (B,S,dr) per token."""
    out = {}
    for name, x in (("c", c_kv), ("kr", k_rope)):
        q, scale = latent_rows(x, 8)
        pad = [(0, 0), (0, smax - x.shape[1]), (0, 0)]
        out[f"{name}_vals"] = constrain_cache(
            "", jnp.pad(q.astype(storage_dtype(8)), pad))
        out[f"{name}_scale"] = jnp.pad(scale, pad, constant_values=1.0)
    return out


def mla_cache_append(entry: Dict[str, jax.Array], c_t: jax.Array, kr_t: jax.Array,
                     pos: jax.Array) -> Dict[str, jax.Array]:
    """Append one token's latent (B,rkv) + rope key (B,dr) at ``pos``."""
    new = dict(entry)
    bidx = jnp.arange(c_t.shape[0])
    for name, x_t in (("c", c_t), ("kr", kr_t)):
        q, scale = latent_rows(x_t, 8)
        new[f"{name}_vals"] = entry[f"{name}_vals"].at[bidx, pos].set(
            q.astype(storage_dtype(8)))
        new[f"{name}_scale"] = entry[f"{name}_scale"].at[bidx, pos].set(scale)
    return new


def cache_nbytes(cache) -> int:
    """Packed size of a cache pytree (memory accounting for benches)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(cache):
        if hasattr(leaf, "nbytes"):
            total += leaf.nbytes
    return total
