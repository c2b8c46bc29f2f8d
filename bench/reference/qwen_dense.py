"""Plain reference for dense GQA decoders of the Qwen2 / Qwen3 families.

Follows the published description (Qwen2 technical report, arXiv:2407.10671,
and the Hugging Face ``Qwen2ForCausalLM`` / ``Qwen3ForCausalLM`` modelling
code): token embedding; per layer RMSNorm -> q/k/v projections (bias on
q/k/v for Qwen2, per-head RMSNorm on q and k for Qwen3) -> rotary embedding
on the two halves of each head -> causal softmax attention with grouped kv
heads -> output projection -> residual; RMSNorm -> SwiGLU MLP -> residual;
final RMSNorm; logits against the tied embedding.

Everything is float32 at ``highest`` matmul precision, computed layer by
layer, with no kernel, cache, batching or quantization.  It imports nothing
of the system under test; it shares with it only the layout of the weight
dictionary, which :func:`make_weights` builds from a seed.

Three things belong to a configuration and live here:
  * :func:`program_config` maps the published ``config.json`` keys to the
    serving program's model settings;
  * :func:`make_weights` draws the weights, in bfloat16 (the published
    checkpoints' dtype), on the device in one jitted call;
  * :func:`logits` is the reference forward pass.  ``control`` computes it
    one precision step below what the configuration serves (int8 weights,
    activations and KV cache): ``"int4"`` rounds every projection matrix to
    int4 per output channel, every matmul input to int4 per row, and every
    cached key and value to int4 per token and head; ``"int4-weights"``
    rounds the matrices alone.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_out")


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "h": h, "kh": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // h, "f": cfg["intermediate_size"],
            "v": cfg["vocab_size"], "layers": cfg["num_hidden_layers"],
            "bias": bool(cfg.get("qkv_bias", False)),
            "qk_norm": bool(cfg.get("qk_norm", False)),
            "theta": float(cfg["rope_theta"]), "eps": float(cfg["rms_norm_eps"])}


def program_config(cfg: dict) -> dict:
    """Keyword arguments of the serving program's model configuration."""
    m = dims(cfg)
    if not cfg.get("tie_word_embeddings", False):
        raise ValueError("this reference serves tied embeddings only")
    return {"vocab_size": m["v"], "d_model": m["d"], "n_layers": m["layers"],
            "n_heads": m["h"], "n_kv_heads": m["kh"], "head_dim": m["hd"],
            "d_ff": m["f"], "qkv_bias": m["bias"], "qk_norm": m["qk_norm"],
            "rope_theta": m["theta"], "norm_eps": m["eps"],
            "tie_embeddings": True, "layer_pattern": [["attn", "dense"]]}


def _gamma(key, shape):
    # norm gains near 1 but not 1, so that a dropped norm shows
    return 1.0 + 0.2 * jax.random.truncated_normal(key, -2.0, 2.0, shape)


def _matrix(key, shape):
    # truncated normal with fan-in scaling: unit-size activations per layer
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape)
            / np.sqrt(shape[-2]))


def make_weights(cfg: dict, seed: int):
    """The weight dictionary, bfloat16, on the default device."""
    m = dims(cfg)
    return _make(m["d"], m["h"], m["kh"], m["hd"], m["f"], m["v"],
                 m["layers"], m["bias"], m["qk_norm"],
                 jnp.uint32(seed % 2**32))


@partial(jax.jit, static_argnums=tuple(range(9)))
def _make(d, h, kh, hd, f, v, n, bias, qk_norm, seed):
    ks = iter(jax.random.split(jax.random.key(seed), 16))
    bf = jnp.bfloat16
    attn = {"wq": _matrix(next(ks), (n, d, h * hd)),
            "wk": _matrix(next(ks), (n, d, kh * hd)),
            "wv": _matrix(next(ks), (n, d, kh * hd)),
            "wo": _matrix(next(ks), (n, h * hd, d))}
    if bias:
        for name, width in (("b_q", h * hd), ("b_k", kh * hd),
                            ("b_v", kh * hd)):
            attn[name] = 0.1 * jax.random.normal(next(ks), (n, width))
    if qk_norm:
        attn["q_norm"] = _gamma(next(ks), (n, hd))
        attn["k_norm"] = _gamma(next(ks), (n, hd))
    layer = {"norm_mix": _gamma(next(ks), (n, d)), "attn": attn,
             "norm_ffn": _gamma(next(ks), (n, d)),
             "ffn": {"w_gate": _matrix(next(ks), (n, d, f)),
                     "w_up": _matrix(next(ks), (n, d, f)),
                     "w_out": _matrix(next(ks), (n, f, d))}}
    tree = {"embed": {"tok": 0.02 * jax.random.normal(next(ks), (v, d))},
            "layers": {"p0": layer},
            "final_norm": _gamma(next(ks), (d,))}
    return jax.tree_util.tree_map(lambda x: x.astype(bf), tree)


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
    """x: (S, heads, hd) at positions 0..S-1; rotates the two halves."""
    s, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv      # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


@partial(jax.jit, static_argnums=(2,))
def _layer(x, p, m, valid):
    """One decoder layer over the whole sequence, float32.  ``m`` is the
    static tuple of sizes; rows at or past ``valid`` are padding and are
    never attended to (the mask is causal, so they change nothing before)."""
    h, kh, hd, bias, qk_norm, theta, eps, (wbits, abits, kvbits) = m
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    if wbits == 4:
        p = dict(p, attn={k: _int4(w, 0) if k in MATRICES else w
                          for k, w in p["attn"].items()},
                 ffn={k: _int4(w, 0) for k, w in p["ffn"].items()})
    s = x.shape[0]
    a = p["attn"]
    y = _rms(x, p["norm_mix"], eps)
    q, k, v = (_mm(y, a["wq"], abits), _mm(y, a["wk"], abits),
               _mm(y, a["wv"], abits))
    if bias:
        q, k, v = q + a["b_q"], k + a["b_k"], v + a["b_v"]
    q, k, v = (q.reshape(s, h, hd), k.reshape(s, kh, hd),
               v.reshape(s, kh, hd))
    if qk_norm:
        q, k = _rms(q, a["q_norm"], eps), _rms(k, a["k_norm"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    if kvbits == 4:
        k, v = _int4(k, -1), _int4(v, -1)
    k, v = jnp.repeat(k, h // kh, axis=1), jnp.repeat(v, h // kh, axis=1)
    att = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
    pos = jnp.arange(s)
    live = (pos[None, :] <= pos[:, None]) & (pos[None, :] < valid)
    att = jax.nn.softmax(jnp.where(live[None], att, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", att, v).reshape(s, h * hd)
    x = x + _mm(o, a["wo"], abits)
    f = p["ffn"]
    y = _rms(x, p["norm_ffn"], eps)
    g = jax.nn.silu(_mm(y, f["w_gate"], abits)) * _mm(y, f["w_up"], abits)
    return x + _mm(g, f["w_out"], abits)


@partial(jax.jit, static_argnums=(2, 4))
def _head(x, w, eps, start, rows):
    g, emb = (a.astype(jnp.float32) for a in w)
    x = jax.lax.dynamic_slice_in_dim(x, start, rows)
    return _rms(x, g, eps) @ emb.T


ROWS = 512


def logits(weights, cfg: dict, tokens: np.ndarray, first: int, *,
           control=None, bucket: int = 1024):
    """Float32 logits of rows ``first .. len(tokens) - 1`` of ``tokens``
    (S,), on the device: row j of the result is position ``first + j`` and
    predicts token ``first + j + 1``.  Rows are padded to a multiple of
    ``ROWS``; those past ``len(tokens) - first`` are padding.

    The sequence is padded to a multiple of ``bucket`` so that a few
    programs serve every length; padding sits after the last real token and
    is masked, so it changes no real row."""
    m = dims(cfg)
    s = len(tokens)
    n = s - first
    rows = -(-n // ROWS) * ROWS
    padded = -(-(first + rows) // bucket) * bucket
    toks = np.zeros((padded,), np.int32)
    toks[:s] = tokens
    static = (m["h"], m["kh"], m["hd"], m["bias"], m["qk_norm"],
              m["theta"], m["eps"], CONTROLS[control])
    with jax.default_matmul_precision("highest"):
        x = _embed(weights["embed"]["tok"], jnp.asarray(toks))
        layers = weights["layers"]["p0"]
        for i in range(m["layers"]):
            x = _layer(x, _at(layers, i), static, jnp.int32(s))
        z = _head(x, (weights["final_norm"], weights["embed"]["tok"]),
                  m["eps"], jnp.int32(first), rows)
    return z


@jax.jit
def _embed(table, toks):
    return table[toks].astype(jnp.float32)


@jax.jit
def _at(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)
