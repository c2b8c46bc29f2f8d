"""Multi-head Latent Attention (MiniCPM3 / DeepSeek-V2 family).

Queries and KV are low-rank compressed; the KV cache stores only the shared
latent ``c_kv`` (kv_lora_rank) plus a small shared RoPE key — ~4.5x smaller
than a GQA cache at this width *before* quantization.  SimQuant is applied to
the latent (per-channel asymmetric INT8): quantization and MLA compression
compound (DESIGN.md §5).

Decode uses the *absorbed* formulation: W_uk is folded into the query and
W_uv into the output so attention runs directly in latent space — O(S * r)
per token instead of re-expanding the full K/V (the production trick from
DeepSeek-V2; essential for the 32K decode dry-run cells).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.kernels.ops import qdot
from .config import ModelConfig
from .layers import apply_rope, dense_init, rms_norm
from .attention import NEG_INF, flash_attention


def mla_init(key, cfg: ModelConfig):
    d, h = cfg.d_model, cfg.n_heads
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 5)
    dt = jnp.dtype(cfg.param_dtype)
    if rq:
        q = {"q_a": dense_init(ks[0], (d, rq), dt),
             "q_a_norm": jnp.ones((rq,), dt),
             "q_b": dense_init(ks[1], (rq, h * (dn + dr)), dt)}
    else:                                   # DeepSeek-V3 without q-LoRA
        q = {"q_proj": dense_init(ks[0], (d, h * (dn + dr)), dt)}
    return {
        **q,
        "kv_a": dense_init(ks[2], (d, rkv + dr), dt),
        "kv_a_norm": jnp.ones((rkv,), dt),
        "kv_b": dense_init(ks[3], (rkv, h * (dn + dv)), dt),
        "wo": dense_init(ks[4], (h * dv, d), dt),
    }


def mla_queries(p, x: jax.Array, cfg: ModelConfig, positions: jax.Array):
    """-> q_nope (B,S,H,dn), q_rope (B,S,H,dr)."""
    b, s, _ = x.shape
    h = cfg.n_heads
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dt = x.dtype
    if "q_proj" in p:
        q = qdot(x, p["q_proj"])
    else:
        q = qdot(rms_norm(qdot(x, p["q_a"]), p["q_a_norm"], cfg.norm_eps),
                 p["q_b"])
    q = q.reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    # TP boundary: q_b is column-parallel over `heads`, so the paged MLA
    # kernels (and the absorbed einsums) see head-sharded queries while the
    # latent cache stays replicated
    q_nope = constrain(q_nope, "batch", None, "heads", None)
    q_rope = constrain(q_rope, "batch", None, "heads", None)
    return q_nope, q_rope


def mla_latent(p, x: jax.Array, cfg: ModelConfig, positions: jax.Array):
    """-> c_kv (B,S,rkv) normed latent, k_rope (B,S,dr) shared rope key."""
    rkv, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dt = x.dtype
    kv = qdot(x, p["kv_a"])
    c_kv = rms_norm(kv[..., :rkv], p["kv_a_norm"], cfg.norm_eps)
    k_rope = kv[..., rkv:]
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]
    c_kv = constrain(c_kv, "batch", "seq", "latent")
    return c_kv, k_rope


def mla_apply(p, x: jax.Array, cfg: ModelConfig, *, positions: jax.Array,
              prefix_len: int = 0) -> jax.Array:
    """Full-sequence MLA (train / prefill): expand K,V then flash-attend."""
    b, s, _ = x.shape
    h = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    dt = x.dtype
    q_nope, q_rope = mla_queries(p, x, cfg, positions)
    c_kv, k_rope = mla_latent(p, x, cfg, positions)

    kv = qdot(c_kv, p["kv_b"]).reshape(b, s, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, dr))],
                        axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    pos1d = positions[0] if positions.ndim > 1 else positions
    out = flash_attention(q, k, v, q_positions=pos1d, kv_positions=pos1d,
                          chunk=cfg.attn_chunk, prefix_len=prefix_len)
    out = constrain(out, "batch", "seq", "heads", None)
    return qdot(out.reshape(b, s, h * dv), p["wo"])


def mla_absorbed_weights(p, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """Split kv_b into W_uk (rkv,H,dn) and W_uv (rkv,H,dv) for absorption."""
    from repro.core.qtensor import QTensor
    h = cfg.n_heads
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    kv_b = p["kv_b"]
    if isinstance(kv_b, QTensor):
        kv_b = kv_b.dequantize(jnp.float32)
    kv_b = kv_b.reshape(cfg.kv_lora_rank, h, dn + dv)
    return kv_b[..., :dn], kv_b[..., dn:]


def mla_decode_ref(q_nope: jax.Array, q_rope: jax.Array,
                   c_vals: jax.Array, c_scale: jax.Array,
                   kr_vals: jax.Array, kr_scale: jax.Array,
                   w_uk: jax.Array, w_uv: jax.Array,
                   length: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Absorbed MLA decode over the quantized latent cache (jnp oracle).

    q_nope: (B,H,dn), q_rope: (B,H,dr); c_vals: (B,Smax,rkv) int8 latent
    codes with per-token scales c_scale: (B,Smax,1); kr_vals: (B,Smax,dr)
    rope-key codes with kr_scale: (B,Smax,1).  Returns (B, H, dv) pre-wo
    attention output.
    """
    b, h, dn = q_nope.shape
    dr = q_rope.shape[-1]
    smax = c_vals.shape[1]
    scale = 1.0 / jnp.sqrt(dn + dr)
    c = c_vals.astype(jnp.float32) * c_scale                     # (B,S,rkv)
    kr = kr_vals.astype(jnp.float32) * kr_scale                  # (B,S,dr)
    # absorb: q_lat = q_nope @ W_uk  -> (B,H,rkv)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    s_lat = jnp.einsum("bhr,bsr->bhs", q_lat, c)
    s_rope = jnp.einsum("bhd,bsd->bhs", q_rope.astype(jnp.float32), kr)
    s = (s_lat + s_rope) * scale
    mask = jnp.arange(smax)[None, :] < length[:, None]
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", w, c)                     # (B,H,rkv)
    return jnp.einsum("bhr,rhd->bhd", o_lat, w_uv.astype(jnp.float32))
