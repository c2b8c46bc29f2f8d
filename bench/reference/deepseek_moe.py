"""Plain reference for DeepSeek-V3-block decoders (Moonlight-16B-A3B).

Follows the published description (DeepSeek-V3 technical report,
arXiv:2412.19437, and the Hugging Face ``DeepseekV3ForCausalLM``
modelling code that Moonlight's ``config.json`` names): token embedding;
per layer RMSNorm -> multi-head latent attention without a query LoRA
(one q projection to heads of ``qk_nope_head_dim + qk_rope_head_dim``;
``kv_a`` to a ``kv_lora_rank`` latent, RMSNorm'd, and a shared rope key;
``kv_b`` from the latent to the heads' nope keys and values) -> rotary
embedding on the rope parts, which the modelling code stores as
interleaved pairs and permutes to halves before ``rotate_half`` -> causal
softmax attention scaled by 1/sqrt(nope + rope) -> output projection ->
residual; RMSNorm -> the FFN -> residual; final RMSNorm; logits against an
untied head.  The first ``first_k_dense_replace`` layers have a dense
SwiGLU FFN; the others a mixture of experts routed as ``MoEGate`` with
``topk_method`` noaux_tc: sigmoid scores of float32 router logits, the
top ``num_experts_per_tok`` chosen on score plus the correction bias,
weighted by their scores without it, normalised over the chosen and scaled
by ``routed_scaling_factor``, plus the shared experts (one SwiGLU of
``n_shared_experts * moe_intermediate_size``).

The expert share: the configuration holds ``n_routed_experts`` experts,
ids ``first_held_expert`` onwards, of a router ``router_experts`` wide.
Routing runs over the whole router; only the held experts' part of the
routed sum is computed, as the program does on one chip of the stated
deployment.  Moonlight has one expert group (``n_group`` = ``topk_group``
= 1), so group-limited routing is the identity and is not written.

Everything is float32 at ``highest`` matmul precision, layer by layer,
with no kernel, cache, batching or quantization.  It imports nothing of
the system under test; it shares with it only the layout of the weight
dictionary, which :func:`make_weights` builds from a seed.  That layout is
the program's: the program rotates the two halves of a rope vector, so
loading permutes the rope columns of the q projection and of ``kv_a`` from
pairs to halves, and :func:`logits` restores the published layout before
it runs the modelling code's rotary embedding.

``control`` computes the logits one precision step below what the
configuration serves: ``"int4"`` rounds every projection matrix (the
router excepted, which the program keeps in float32) to int4 per output
channel, every matmul input to int4 per row, and the cached latent and
rope key to int4 per token; ``"int4-weights"`` rounds the matrices alone.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

ATTN = ("q_proj", "kv_a", "kv_b", "wo")
FFN = ("w_gate", "w_up", "w_out")


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    held = cfg["n_routed_experts"]
    return {"d": d, "h": h, "hd": cfg["qk_nope_head_dim"]
            + cfg["qk_rope_head_dim"],
            "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "r": cfg["kv_lora_rank"],
            "f": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"],
            "fs": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
            "e": held, "er": cfg["router_experts"],
            "e0": cfg["first_held_expert"], "k": cfg["num_experts_per_tok"],
            "scale": float(cfg["routed_scaling_factor"]),
            "v": cfg["vocab_size"], "layers": cfg["num_hidden_layers"],
            "lead": cfg["first_k_dense_replace"],
            "theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"])}


def _supported(cfg: dict) -> None:
    want = {"q_lora_rank": None, "scoring_func": "sigmoid",
            "topk_method": "noaux_tc", "norm_topk_prob": True, "n_group": 1,
            "topk_group": 1, "moe_layer_freq": 1, "tie_word_embeddings": False,
            "hidden_act": "silu"}
    for key, value in want.items():
        if cfg.get(key) != value:
            raise ValueError(f"this reference serves {key}={value!r}, "
                             f"not {cfg.get(key)!r}")
    if cfg.get("rope_scaling"):
        raise ValueError("this reference has no rope scaling")


def program_config(cfg: dict) -> dict:
    """Keyword arguments of the serving program's model configuration."""
    _supported(cfg)
    m = dims(cfg)
    return {"vocab_size": m["v"], "d_model": m["d"], "n_layers": m["layers"],
            "n_heads": m["h"], "d_ff": m["f"], "q_lora_rank": 0,
            "kv_lora_rank": m["r"], "qk_nope_head_dim": m["dn"],
            "qk_rope_head_dim": m["dr"], "v_head_dim": m["dv"],
            "n_experts": m["e"], "n_routed_experts": m["er"],
            "expert_offset": m["e0"], "n_experts_active": m["k"],
            "moe_d_ff": m["fe"],
            "n_shared_experts": cfg["n_shared_experts"],
            "moe_score": "sigmoid", "moe_score_bias": True,
            "moe_routed_scale": m["scale"], "capacity_factor": 0.0,
            "first_k_dense_replace": m["lead"], "rope_theta": m["theta"],
            "norm_eps": m["eps"], "tie_embeddings": False,
            "layer_pattern": [["mla", "moe"]]}


def _gamma(key, shape):
    # norm gains near 1 but not 1, so that a dropped norm shows
    return 1.0 + 0.2 * jax.random.truncated_normal(key, -2.0, 2.0, shape)


def _matrix(key, shape):
    # truncated normal with fan-in scaling: unit-size activations per layer;
    # cast at once so that no float32 copy of the whole stack stays live
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape)
            / np.sqrt(shape[-2])).astype(jnp.bfloat16)


def _halves(dr: int) -> np.ndarray:
    """Column order that takes a rope vector stored as interleaved pairs
    (x0, x1), (x2, x3), ... to halves (x0, x2, ..., x1, x3, ...)."""
    return np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])


def _rope_cols(w, h: int, lo: int, dr: int, order):
    """Reorder the ``dr`` rope columns at offset ``lo`` of each of ``h``
    heads of a (..., K, h * width) projection."""
    shape = w.shape
    w = w.reshape(*shape[:-1], h, shape[-1] // h)
    w = jnp.concatenate([w[..., :lo], w[..., lo + order]], axis=-1)
    return w.reshape(shape)


def make_weights(cfg: dict, seed: int):
    """The weight dictionary, bfloat16 (the router's correction bias
    float32, as the checkpoint keeps it), on the default device."""
    m = dims(cfg)
    return _make(tuple(sorted(m.items())), jnp.uint32(seed % 2**32))


@partial(jax.jit, static_argnums=(0,))
def _make(items, seed):
    m = dict(items)
    d, h, r, dr, dv = m["d"], m["h"], m["r"], m["dr"], m["dv"]
    n, lead = m["layers"] - m["lead"], m["lead"]
    ks = iter(jax.random.split(jax.random.key(seed), 32))
    halves = _halves(dr)

    def attn(n):
        # drawn in the published layout, then loaded: rope columns to halves
        q = _matrix(next(ks), (n, d, h * m["hd"]))
        kv_a = _matrix(next(ks), (n, d, r + dr))
        return {"q_proj": _rope_cols(q, h, m["dn"], dr, halves),
                "kv_a": _rope_cols(kv_a, 1, r, dr, halves),
                "kv_a_norm": _gamma(next(ks), (n, r)).astype(jnp.bfloat16),
                "kv_b": _matrix(next(ks), (n, r, h * (m["dn"] + dv))),
                "wo": _matrix(next(ks), (n, h * dv, d))}

    def swiglu(shape, f):
        return {"w_gate": _matrix(next(ks), (*shape, d, f)),
                "w_up": _matrix(next(ks), (*shape, d, f)),
                "w_out": _matrix(next(ks), (*shape, f, d))}

    def norms(n):
        return {"norm_mix": _gamma(next(ks), (n, d)).astype(jnp.bfloat16),
                "norm_ffn": _gamma(next(ks), (n, d)).astype(jnp.bfloat16)}

    moe = {"gate_w": _matrix(next(ks), (n, d, m["er"])),
           # the correction bias, drawn from the seed (assumed: a trained
           # model's is learnt), of a size that changes the selection
           "gate_bias": 0.1 * jax.random.normal(next(ks), (n, m["er"])),
           "experts": swiglu((n, m["e"]), m["fe"]),
           "shared": swiglu((n,), m["fs"])}
    return {"embed": {"tok": (0.02 * jax.random.normal(
                next(ks), (m["v"], d))).astype(jnp.bfloat16)},
            "lm_head": _matrix(next(ks), (d, m["v"])),
            "lead": {**norms(lead), "attn": attn(lead),
                     "ffn": swiglu((lead,), m["f"])},
            "layers": {"p0": {**norms(n), "attn": attn(n), "moe": moe}},
            "final_norm": _gamma(next(ks), (d,)).astype(jnp.bfloat16)}


CONTROLS = {None: (16, 16, 16), "int4": (4, 4, 4),
            "int4-weights": (4, 16, 16)}


def _int4(x, axis):
    """Round ``x`` to int4 codes with one symmetric scale per slice along
    ``axis`` (the reduced axis), and back."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-12) / 7.0
    return jnp.clip(jnp.round(x / s), -8, 7) * s


def _mm(x, w, abits):
    return (_int4(x, -1) if abits == 4 else x) @ w


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """The modelling code's ``apply_rotary_pos_emb``: x (S, heads, dr) at
    positions 0..S-1, stored as interleaved pairs, permuted to halves,
    then ``x * cos + rotate_half(x) * sin``."""
    s, heads, dr = x.shape
    x = x.reshape(s, heads, dr // 2, 2).swapaxes(-1, -2).reshape(s, heads, dr)
    inv = 1.0 / theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    freqs = jnp.arange(s, dtype=jnp.float32)[:, None] * inv        # (S, dr/2)
    emb = jnp.concatenate([freqs, freqs], -1)[:, None]              # (S,1,dr)
    a, b = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(emb) + jnp.concatenate([-b, a], -1) * jnp.sin(emb)


def _swiglu(y, p, abits):
    g = jax.nn.silu(_mm(y, p["w_gate"], abits)) * _mm(y, p["w_up"], abits)
    return _mm(g, p["w_out"], abits)


def _attention(x, p, m, valid, bits):
    """MLA over the whole sequence, in the published rope layout."""
    _, abits, kvbits = bits
    h, dn, dr, dv, r = m["h"], m["dn"], m["dr"], m["dv"], m["r"]
    a = p["attn"]
    # undo the program's loading permutation: back to interleaved pairs
    pairs = np.argsort(_halves(dr))
    q_proj = _rope_cols(a["q_proj"], h, dn, dr, pairs)
    kv_a = _rope_cols(a["kv_a"], 1, r, dr, pairs)
    s = x.shape[0]
    y = _rms(x, p["norm_mix"], m["eps"])
    q = _mm(y, q_proj, abits).reshape(s, h, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    ckv = _mm(y, kv_a, abits)
    c, k_pe = ckv[:, :r], ckv[:, r:]
    c = _rms(c, a["kv_a_norm"], m["eps"])
    if kvbits == 4:                       # the cached latent and rope key
        c, k_pe = _int4(c, -1), _int4(k_pe, -1)
    kv = _mm(c, a["kv_b"], abits).reshape(s, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q_pe = _rope(q_pe, m["theta"])
    k_pe = _rope(k_pe[:, None, :], m["theta"])                      # (S,1,dr)
    att = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
           + jnp.einsum("qhd,kd->hqk", q_pe, k_pe[:, 0])) / np.sqrt(dn + dr)
    pos = jnp.arange(s)
    live = (pos[None, :] <= pos[:, None]) & (pos[None, :] < valid)
    att = jax.nn.softmax(jnp.where(live[None], att, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", att, v).reshape(s, h * dv)
    return x + _mm(o, a["wo"], abits)


def _quantized(p, bits, names):
    return {k: _int4(w, -2) if k in names else w for k, w in p.items()} \
        if bits[0] == 4 else p


@partial(jax.jit, static_argnums=(2, 4))
def _dense_layer(x, p, m, valid, bits):
    m = dict(m)
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    p = dict(p, attn=_quantized(p["attn"], bits, ATTN),
             ffn=_quantized(p["ffn"], bits, FFN))
    x = _attention(x, p, m, valid, bits)
    return x + _swiglu(_rms(x, p["norm_ffn"], m["eps"]), p["ffn"], bits[1])


def route(y, gate_w, gate_bias, k: int, scale: float):
    """``MoEGate`` (noaux_tc, one group): -> (ids (S, k), weights (S, k))."""
    scores = jax.nn.sigmoid(y @ gate_w)
    _, idx = jax.lax.top_k(scores + gate_bias, k)
    w = jnp.take_along_axis(scores, idx, -1)
    return idx, w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * scale


def moe(y, p, m, abits):
    """The held experts' share of the routed sum plus the shared experts,
    for normed inputs y (S, d) in float32."""
    idx, w = route(y, p["gate_w"], p["gate_bias"], m["k"], m["scale"])
    out = _swiglu(y, p["shared"], abits)
    for j in range(m["e"]):
        gate = jnp.sum(jnp.where(idx == m["e0"] + j, w, 0.0), -1)   # (S,)
        ex = jax.tree_util.tree_map(lambda a: a[j], p["experts"])
        out = out + gate[:, None] * _swiglu(y, ex, abits)
    return out


@partial(jax.jit, static_argnums=(2, 4))
def _moe_layer(x, p, m, valid, bits):
    m = dict(m)
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    mp = p["moe"]
    mp = dict(mp, experts=_quantized(mp["experts"], bits, FFN),
              shared=_quantized(mp["shared"], bits, FFN))
    p = dict(p, attn=_quantized(p["attn"], bits, ATTN), moe=mp)
    x = _attention(x, p, m, valid, bits)
    return x + moe(_rms(x, p["norm_ffn"], m["eps"]), mp, m, bits[1])


@partial(jax.jit, static_argnums=(2, 4))
def _head(x, w, eps, start, rows):
    g, head = (a.astype(jnp.float32) for a in w)
    x = jax.lax.dynamic_slice_in_dim(x, start, rows)
    return _rms(x, g, eps) @ head


ROWS = 512


def logits(weights, cfg: dict, tokens: np.ndarray, first: int, *,
           control=None, bucket: int = 1024):
    """Float32 logits of rows ``first .. len(tokens) - 1`` of ``tokens``
    (S,), on the device: row j of the result is position ``first + j`` and
    predicts token ``first + j + 1``.  Rows are padded to a multiple of
    ``ROWS``; those past ``len(tokens) - first`` are padding.

    The sequence is padded to a multiple of ``bucket`` so that a few
    programs serve every length; padding sits after the last real token and
    is masked, so it changes no real row (each token's routing is its
    own)."""
    m = dims(cfg)
    s = len(tokens)
    n = s - first
    rows = -(-n // ROWS) * ROWS
    padded = -(-(first + rows) // bucket) * bucket
    toks = np.zeros((padded,), np.int32)
    toks[:s] = tokens
    static = tuple(sorted(m.items()))
    bits = CONTROLS[control]
    with jax.default_matmul_precision("highest"):
        x = _embed(weights["embed"]["tok"], jnp.asarray(toks))
        for i in range(m["lead"]):
            x = _dense_layer(x, _at(weights["lead"], i), static,
                             jnp.int32(s), bits)
        layers = weights["layers"]["p0"]
        for i in range(m["layers"] - m["lead"]):
            x = _moe_layer(x, _at(layers, i), static, jnp.int32(s), bits)
        z = _head(x, (weights["final_norm"], weights["lm_head"]),
                  m["eps"], jnp.int32(first), rows)
    return z


@jax.jit
def _embed(table, toks):
    return table[toks].astype(jnp.float32)


@jax.jit
def _at(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)
