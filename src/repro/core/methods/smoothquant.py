"""SmoothQuant backend (Xiao et al., 2023) — activation-difficulty migration.

Per-channel smoothing factor (paper Appendix A.1, Lemma 1):

    s_j = max(|X_j|)^alpha / max(|W_j|)^(1-alpha)        (alpha = 0.5 default)

Activations are divided by ``s`` and weights multiplied by ``s`` — an exact
algebraic identity pre-quantization (Thm 1: (X/s)(sW) = XW), that moves
outlier mass from activations (hard to quantize per-tensor) into weights
(easy, per-channel).  The division by ``s`` is *folded into the preceding
normalization layer's gamma*, so the runtime sees zero extra ops — this is
why SmoothQuant wins the paper's latency breakdown (Table 5).
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from ..qtensor import QTensor, absmax_scale, quantize_affine
from .base import QuantMethod, register


def smoothing_factors(act_absmax: jnp.ndarray, w: jnp.ndarray, alpha: float = 0.5,
                      eps: float = 1e-5) -> jnp.ndarray:
    """Per-input-channel s_j from calibration absmax stats and the weight.

    act_absmax: (d_in,) channel-wise absmax of the layer input from
    calibration.  w: (d_in, d_out).
    """
    a = jnp.maximum(jnp.asarray(act_absmax, jnp.float32), eps)
    wmax = jnp.maximum(jnp.max(jnp.abs(w.astype(jnp.float32)), axis=1), eps)
    s = (a ** alpha) / (wmax ** (1.0 - alpha))
    # Guard degenerate channels (dead inputs): identity scaling.
    return jnp.maximum(s, eps)


def fold(w: jnp.ndarray, norm_gamma: jnp.ndarray, act_absmax: jnp.ndarray,
         alpha: float = 0.5) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Migrate difficulty: returns (w*s, gamma/s, s).

    ``gamma/s`` replaces the preceding RMSNorm/LayerNorm gain so the smoothed
    activation X/s is produced for free; ``w*s`` restores exactness.
    """
    s = smoothing_factors(act_absmax, w, alpha)
    return w * s[:, None], norm_gamma / s, s


def quantize_weight(w, *, stats=None, bits: int = 8, alpha: float = 0.5) -> QTensor:
    """Quantize a (possibly pre-folded) weight per output channel.

    When ``stats`` (activation absmax) is provided and folding was not done
    at the graph level, the scaling is applied here (out-of-place).
    """
    if stats is not None:
        s = smoothing_factors(stats, w, alpha)
        w = w * s[:, None]
    scale = absmax_scale(w, bits=bits, axis=(0,))
    return quantize_affine(w, scale, None, bits=bits, axis=(0,))


def quantize_activation(a, *, bits: int = 8) -> QTensor:
    # Post-smoothing activations are tame: per-token symmetric is enough.
    scale = absmax_scale(a, bits=bits, axis=(-1,))
    return quantize_affine(a, scale, None, bits=bits, axis=(-1,))


METHOD = register(QuantMethod(
    name="smoothquant",
    bits_weight=8,
    bits_act=8,
    needs_calibration=True,
    weight_only=False,
    quantize_weight=quantize_weight,
    description="SmoothQuant alpha-migration folded into the preceding norm; W8A8 per-channel/per-token.",
))

def apply_fold_to_model(params, taps_stats: dict, alpha: float = 0.5):
    """Graph-level SmoothQuant fold over our transformer layout.

    For each pattern position pX: migrate difficulty from the norm outputs
    into the consuming projections —
      norm_mix  -> (wq, wk, wv)   with one shared s (max over the fused QKV)
      norm_ffn  -> (w_gate, w_up) likewise.
    Stacked (R, d, f) leaves use per-repeat smoothing factors (taps are
    stacked over scan repeats).  Leading layers (``params["lead"]``, taps
    ``lead/...``) fold the same way.  MLA projections and MoE layers are
    not folded: their norm outputs feed q / ``kv_a`` and the router, the
    experts and the shared experts together, and the router's choice must
    not move.  Returns a new params pytree; the runtime then quantizes it
    with the plain symmetric W8A8 backend — zero extra ops at inference
    (the paper's Table-5 argument).
    """
    import jax

    params = jax.tree_util.tree_map(lambda x: x, params)      # shallow copy
    layers = dict(params["layers"])
    if "lead" in params:
        layers["lead"] = params["lead"]
    for pos_name, blk in layers.items():
        blk = jax.tree_util.tree_map(lambda x: x, blk)
        attn_tag = f"{pos_name}/attn_in"
        ffn_tag = f"{pos_name}/ffn_in"
        if attn_tag in taps_stats and "attn" in blk and "wq" in blk.get("attn", {}):
            a_max = taps_stats[attn_tag]                      # (R, d) or (d,)
            attn = dict(blk["attn"])
            fused = jnp.concatenate([attn["wq"], attn["wk"], attn["wv"]], axis=-1)

            def fold_pos(a_vec, w_fused, wq, wk, wv, gamma):
                s = smoothing_factors(a_vec, w_fused, alpha)
                return wq * s[:, None], wk * s[:, None], wv * s[:, None], gamma / s

            if fused.ndim == 3:                               # stacked repeats
                wq, wk, wv, gamma = jax.vmap(fold_pos)(
                    jnp.broadcast_to(a_max, (fused.shape[0], a_max.shape[-1]))
                    if a_max.ndim == 1 else a_max,
                    fused, attn["wq"], attn["wk"], attn["wv"], blk["norm_mix"])
            else:
                a_vec = a_max if a_max.ndim == 1 else jnp.max(a_max, axis=0)
                wq, wk, wv, gamma = fold_pos(a_vec, fused, attn["wq"],
                                             attn["wk"], attn["wv"],
                                             blk["norm_mix"])
            attn.update(wq=wq, wk=wk, wv=wv)
            blk["attn"] = attn
            blk["norm_mix"] = gamma
        if ffn_tag in taps_stats and "ffn" in blk:
            a_max = taps_stats[ffn_tag]
            ffn = dict(blk["ffn"])
            fused = jnp.concatenate([ffn["w_gate"], ffn["w_up"]], axis=-1)

            def fold_ffn(a_vec, w_fused, wg, wu, gamma):
                s = smoothing_factors(a_vec, w_fused, alpha)
                return wg * s[:, None], wu * s[:, None], gamma / s

            if fused.ndim == 3:
                wg, wu, gamma = jax.vmap(fold_ffn)(
                    jnp.broadcast_to(a_max, (fused.shape[0], a_max.shape[-1]))
                    if a_max.ndim == 1 else a_max,
                    fused, ffn["w_gate"], ffn["w_up"], blk["norm_ffn"])
            else:
                a_vec = a_max if a_max.ndim == 1 else jnp.max(a_max, axis=0)
                wg, wu, gamma = fold_ffn(a_vec, fused, ffn["w_gate"],
                                         ffn["w_up"], blk["norm_ffn"])
            ffn.update(w_gate=wg, w_up=wu)
            blk["ffn"] = ffn
            blk["norm_ffn"] = gamma
        layers[pos_name] = blk
    if "lead" in params:
        params["lead"] = layers.pop("lead")
    params["layers"] = layers
    return params
