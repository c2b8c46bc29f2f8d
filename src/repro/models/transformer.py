"""Unified decoder LM over heterogeneous layer patterns.

One model covers all 10 assigned architectures: the layer stack is
``lax.scan`` over ``n_repeats`` of the config's ``layer_pattern`` (pattern
positions unrolled inside the scanned block).  Modes:

  * ``forward_train``   — full-sequence, optional calibration taps
  * ``forward_prefill`` — full-sequence + builds the SimQuant INT8 cache
  * ``forward_decode``  — one token against the quantized cache / SSM state

Multimodal stubs: MusicGen consumes (B, K, S) codebook tokens (summed
embeddings, per-codebook heads); PaliGemma consumes precomputed patch
embeddings concatenated before the text tokens with a bidirectional prefix
mask (frontends are stubs per the assignment).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.calibration import record_activation
from repro.distributed.sharding import constrain
from repro.kernels import ops
from repro.kernels.ops import qdot
from repro.serving import kv_cache as kvc
from repro.serving import paged_cache as pgc
from repro.serving import state_pool as spl
from .attention import attn_apply, attn_init, decode_attention_ref, flash_attention, qkv_project
from .config import LayerSpec, ModelConfig
from .layers import apply_rope, dense_init, embed_init, rms_norm, rms_norm_init, swiglu_apply, swiglu_init
from .mla import (mla_absorbed_weights, mla_apply, mla_decode_ref, mla_init,
                  mla_latent, mla_queries)
from .moe import moe_apply, moe_dropless, moe_init
from .ssm import (ssm_apply, ssm_decode_step, ssm_init, ssm_prefill_chunk,
                  ssm_state_entry, ssm_state_from_entry)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _layer_init(key, cfg: ModelConfig, spec: LayerSpec):
    k_mix, k_ffn = jax.random.split(key)
    dt = jnp.dtype(cfg.param_dtype)
    p: Dict[str, Any] = {"norm_mix": rms_norm_init(cfg.d_model, dt)}
    if spec.mixer == "attn":
        p["attn"] = attn_init(k_mix, cfg)
    elif spec.mixer == "mla":
        p["attn"] = mla_init(k_mix, cfg)
    else:
        p["ssm"] = ssm_init(k_mix, cfg)
    if spec.ffn != "none":
        p["norm_ffn"] = rms_norm_init(cfg.d_model, dt)
        if spec.ffn == "dense":
            p["ffn"] = swiglu_init(k_ffn, cfg.d_model, cfg.d_ff, dt)
        else:
            p["moe"] = moe_init(k_ffn, cfg)
    return p


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    keys = jax.random.split(key, cfg.pattern_len + 3)
    dt = jnp.dtype(cfg.param_dtype)
    params: Dict[str, Any] = {}

    if cfg.n_codebooks:
        emb_keys = jax.random.split(keys[-1], cfg.n_codebooks)
        params["embed"] = {f"cb{i}": embed_init(emb_keys[i], (cfg.vocab_size, cfg.d_model), dt)
                           for i in range(cfg.n_codebooks)}
        head_keys = jax.random.split(keys[-2], cfg.n_codebooks)
        params["heads"] = {f"head_cb{i}": dense_init(head_keys[i], (cfg.d_model, cfg.vocab_size), dt)
                           for i in range(cfg.n_codebooks)}
    else:
        params["embed"] = {"tok": embed_init(keys[-1], (cfg.vocab_size, cfg.d_model), dt)}
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(keys[-2], (cfg.d_model, cfg.vocab_size), dt)

    # Stacked layer params: one sub-tree per pattern position, each leaf
    # stacked over n_repeats (scan axis).
    layers = {}
    for i, spec in enumerate(cfg.layer_pattern):
        rep_keys = jax.random.split(keys[i], cfg.n_repeats)
        stacked = jax.vmap(lambda k: _layer_init(k, cfg, spec))(rep_keys)
        layers[f"p{i}"] = stacked
    params["layers"] = layers
    if cfg.first_k_dense_replace:
        # leading layers: their own subtree, stacked over first_k, run one by
        # one ahead of the scan (a key of their own leaves the pattern's
        # draws as they were)
        lead_keys = jax.random.split(jax.random.fold_in(key, 1 << 20),
                                     cfg.first_k_dense_replace)
        params["lead"] = jax.vmap(
            lambda k: _layer_init(k, cfg, cfg.lead_spec))(lead_keys)
    params["final_norm"] = rms_norm_init(cfg.d_model, dt)
    return params


# ---------------------------------------------------------------------------
# Leading layers and MoE helpers shared by every path
# ---------------------------------------------------------------------------

def _pattern(cfg: ModelConfig):
    """(key, spec) of each scanned pattern position."""
    return tuple((f"p{i}", s) for i, s in enumerate(cfg.layer_pattern))


def _lead_specs(cfg: ModelConfig):
    return ((pgc.LEAD, cfg.lead_spec),)


def _at(tree, i: int):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def _stack(trees):
    return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *trees)


def _moe(p, y, cfg: ModelConfig, *, valid, gather: bool):
    """Dropless MoE over y (..., D) -> (out (..., D), counts int32 in
    ``moe.COUNTERS`` order);
    ``valid`` (y's leading shape) marks real tokens."""
    f, counts = moe_dropless(p, y.reshape(-1, y.shape[-1]), cfg,
                             valid=valid.reshape(-1), gather=gather)
    return f.reshape(y.shape), counts


def _add_counts(acc, counts):
    return counts if acc is None else acc + counts


def _with_lead(new_pool, new_lead):
    """The scanned entries plus the leading layers' entry, restacked."""
    if new_lead:
        new_pool = dict(new_pool)
        new_pool[pgc.LEAD] = _stack(new_lead)
    return new_pool


def _with_stats(new_pool, pool, counts):
    """Fold one step's MoE counters (stacked over the scanned repeats) into
    the pool's running totals (uint32, wrapping: the scheduler reads
    differences)."""
    if pgc.STATS in pool:
        new_pool = dict(new_pool)
        new_pool[pgc.STATS] = {"moe": pool[pgc.STATS]["moe"]
                               + jnp.sum(counts, axis=0).astype(jnp.uint32)}
    return new_pool


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def _embed_lookup(table, tokens, cfg: ModelConfig) -> jax.Array:
    """Embedding lookup.  Under a mesh with a vocab-sharded table and a
    long token axis, use a chunked one-hot matmul: the backward becomes a
    sharded GEMM instead of a full-table f32 scatter-add (dry-run finding:
    6x 3.85 GiB replicated scatter operands on the 200K-vocab cell).
    """
    from repro.distributed.sharding import active_mesh
    dt = cfg.compute_dtype
    v = table.shape[0]
    if active_mesh() is None or tokens.ndim != 2 or tokens.shape[1] < 512:
        return table[tokens].astype(dt)
    b, s = tokens.shape
    nc = 8
    while s % nc != 0:
        nc -= 1
    c = s // nc
    tc = tokens.reshape(b, nc, c).transpose(1, 0, 2)              # (nc,B,c)

    def step(_, tk):
        oh = jax.nn.one_hot(tk, v, dtype=table.dtype)             # (B,c,V)
        oh = constrain(oh, "batch", None, "vocab")
        return None, (oh @ table).astype(dt)                      # (B,c,D)

    _, hs = jax.lax.scan(jax.checkpoint(step), None, tc)
    return hs.transpose(1, 0, 2, 3).reshape(b, s, -1)


def embed_tokens(params, batch, cfg: ModelConfig) -> Tuple[jax.Array, int]:
    """-> (h (B,S,D) in compute dtype, prefix_len)."""
    dt = cfg.compute_dtype
    if cfg.n_codebooks:
        tokens = batch["tokens"] if isinstance(batch, dict) else batch   # (B,K,S)
        h = sum(_embed_lookup(params["embed"][f"cb{i}"], tokens[:, i], cfg)
                for i in range(cfg.n_codebooks))
        return h.astype(dt), 0
    if cfg.n_img_patches:
        tokens = batch["tokens"]                                          # (B, S_text)
        patches = batch["patches"].astype(dt)                             # (B, P, D)
        h_txt = _embed_lookup(params["embed"]["tok"], tokens, cfg)
        h = jnp.concatenate([patches, h_txt], axis=1)
        return h, cfg.n_img_patches
    tokens = batch["tokens"] if isinstance(batch, dict) else batch
    return _embed_lookup(params["embed"]["tok"], tokens, cfg), 0


def logits_head(params, h: jax.Array, cfg: ModelConfig) -> jax.Array:
    dt = h.dtype
    if cfg.n_codebooks:
        logits = jnp.stack([qdot(h, params["heads"][f"head_cb{i}"])
                            for i in range(cfg.n_codebooks)], axis=-2)    # (...,K,V)
    elif cfg.tie_embeddings:
        logits = h @ params["embed"]["tok"].T.astype(dt)
    else:
        logits = qdot(h, params["lm_head"])
    logits = logits.astype(jnp.float32)
    if cfg.logits_softcap > 0:
        logits = cfg.logits_softcap * jnp.tanh(logits / cfg.logits_softcap)
    # Vocab-shard the fp32 logits: at 150K+ vocab an unsharded (B,S,V) fp32
    # tensor is the single biggest temp in the train step (dry-run finding).
    if logits.ndim == 4:
        logits = constrain(logits, "batch", "seq", None, "vocab")
    elif logits.ndim == 3:
        logits = constrain(logits, "batch", "seq", "vocab")
    elif logits.ndim == 2:
        logits = constrain(logits, "batch", "vocab")
    return logits


# ---------------------------------------------------------------------------
# Block (one pattern repeat: P layers)
# ---------------------------------------------------------------------------

def _block_full(p_blk, h, cfg: ModelConfig, *, positions, prefix_len: int,
                mode: str, smax: int, capture: bool, specs=None):
    """Full-sequence pass over one pattern repeat.

    Returns (h, aux, cache_entries, taps).  ``cache_entries``/{taps} are {}
    unless mode=="prefill"/capture.
    """
    aux = jnp.zeros((), jnp.float32)
    cache_entries: Dict[str, Any] = {}
    taps: Dict[str, Any] = {} if capture else None
    pos1d = positions[0] if positions.ndim > 1 else positions

    for key, spec in specs or _pattern(cfg):
        p = p_blk[key]
        x = rms_norm(h, p["norm_mix"], cfg.norm_eps)
        if capture:
            record_activation(taps, f"{key}/attn_in", x)
        if spec.mixer == "attn":
            if mode == "prefill":
                q, k, v = qkv_project(p["attn"], x, cfg, positions)
                out = flash_attention(q, k, v, q_positions=pos1d, kv_positions=pos1d,
                                      chunk=cfg.attn_chunk, prefix_len=prefix_len)
                b, s, _, _ = q.shape
                dtc = x.dtype
                mix = qdot(out.reshape(b, s, -1), p["attn"]["wo"])
                cache_entries[key] = kvc.gqa_cache_entry(k, v, smax)
            else:
                mix = attn_apply(p["attn"], x, cfg, positions=positions,
                                 prefix_len=prefix_len)
        elif spec.mixer == "mla":
            if mode == "prefill":
                c_kv, k_rope = mla_latent(p["attn"], x, cfg, positions)
                cache_entries[key] = kvc.mla_cache_entry(c_kv, k_rope, smax)
            mix = mla_apply(p["attn"], x, cfg, positions=positions,
                            prefix_len=prefix_len)
        else:  # ssm
            if mode == "prefill":
                mix, state = ssm_apply(p["ssm"], x, cfg, return_state=True)
                # stored quantized (INT8 SSD codes + per-slot scales) — the
                # same round-trip the paged state pool applies, so dense and
                # paged hybrid serving stay token-for-token identical
                cache_entries[key] = ssm_state_entry(state)
            else:
                mix = ssm_apply(p["ssm"], x, cfg)
        # constrain the mixer output to the residual's seq-sharding BEFORE the
        # add: the row-parallel psum then lowers to a reduce-scatter instead
        # of a full all-reduce + slice (dry-run: 2x wire on every layer)
        mix = constrain(mix, "batch", "seq", "embed")
        h = h + mix
        h = constrain(h, "batch", "seq", "embed")

        if spec.ffn != "none":
            y = rms_norm(h, p["norm_ffn"], cfg.norm_eps)
            if capture:
                record_activation(taps, f"{key}/ffn_in", y)
            if spec.ffn == "dense":
                f = swiglu_apply(p["ffn"], y, cfg.act_fn)
            elif mode == "train" and cfg.capacity_factor > 0:
                f, aux_i = moe_apply(p["moe"], y, cfg)
                aux = aux + aux_i
            else:
                f, _ = _moe(p["moe"], y, cfg, valid=jnp.ones(y.shape[:-1], bool),
                            gather=False)
            f = constrain(f, "batch", "seq", "embed")
            h = h + f
            h = constrain(h, "batch", "seq", "embed")
    return h, aux, cache_entries, (taps if capture else {})


def _block_decode(p_blk, h, cache_blk, cfg: ModelConfig, *, length,
                  specs=None):
    """One-token pass over one pattern repeat.  h: (B, D)."""
    new_cache: Dict[str, Any] = {}
    b = h.shape[0]
    positions = length[:, None]                           # (B,1)

    for key, spec in specs or _pattern(cfg):
        p = p_blk[key]
        entry = cache_blk[key]
        x = rms_norm(h, p["norm_mix"], cfg.norm_eps)
        if spec.mixer == "attn":
            q, k, v = qkv_project(p["attn"], x[:, None, :], cfg, positions)
            entry = kvc.gqa_cache_append(entry, k[:, 0], v[:, 0], length)
            out = ops.decode_attention(
                q[:, 0], entry["k_vals"], entry["k_scale"], entry["k_zero"],
                entry["v_vals"], entry["v_scale"], entry["v_zero"],
                length + 1)
            mix = qdot(out.astype(x.dtype).reshape(b, -1), p["attn"]["wo"])
        elif spec.mixer == "mla":
            q_nope, q_rope = mla_queries(p["attn"], x[:, None, :], cfg, positions)
            c_t, kr_t = mla_latent(p["attn"], x[:, None, :], cfg, positions)
            entry = kvc.mla_cache_append(entry, c_t[:, 0], kr_t[:, 0], length)
            w_uk, w_uv = mla_absorbed_weights(p["attn"], cfg)
            out = mla_decode_ref(q_nope[:, 0], q_rope[:, 0],
                                 entry["c_vals"], entry["c_scale"],
                                 entry["kr_vals"], entry["kr_scale"],
                                 w_uk, w_uv, length + 1, cfg)
            mix = qdot(out.astype(x.dtype).reshape(b, -1), p["attn"]["wo"])
        else:
            work = ssm_state_from_entry(entry)
            mix, work = ssm_decode_step(p["ssm"], x, work, cfg)
            entry = ssm_state_entry(work)
        new_cache[key] = entry
        h = h + mix.astype(h.dtype)

        if spec.ffn != "none":
            y = rms_norm(h, p["norm_ffn"], cfg.norm_eps)
            if spec.ffn == "dense":
                f = swiglu_apply(p["ffn"], y[:, None, :], cfg.act_fn)[:, 0]
            else:
                f, _ = _moe(p["moe"], y, cfg, valid=jnp.ones((b,), bool),
                            gather=False)
            h = h + f.astype(h.dtype)
    return h, new_cache


# ---------------------------------------------------------------------------
# Full model entry points
# ---------------------------------------------------------------------------

def _scan_full(params, h, cfg: ModelConfig, *, positions, prefix_len, mode,
               smax, capture):
    block = partial(_block_full, cfg=cfg, positions=positions,
                    prefix_len=prefix_len, mode=mode, smax=smax, capture=capture)

    def body(carry, p_blk):
        h, aux = carry
        h_new, aux_i, cache_i, taps_i = block(p_blk, h)
        if mode == "train":
            # carry sharded over (batch, seq->model): shrinks the saved
            # residual stacks by the TP degree (see sharding.seq_carry)
            h_new = constrain(h_new, "batch", "seq_carry", "embed")
        return (h_new, aux + aux_i), (cache_i, taps_i)

    aux = jnp.zeros((), jnp.float32)
    lead_cache, lead_taps = [], []
    for l in range(cfg.first_k_dense_replace):
        h, aux_l, cache_l, taps_l = block({pgc.LEAD: _at(params["lead"], l)},
                                          h, specs=_lead_specs(cfg))
        if mode == "train":
            h = constrain(h, "batch", "seq_carry", "embed")
        aux = aux + aux_l
        lead_cache.append(cache_l)
        lead_taps.append(taps_l)
    if cfg.remat and mode == "train":
        policy = {
            "dots_nobatch": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            "nothing": jax.checkpoint_policies.nothing_saveable,
            "everything": jax.checkpoint_policies.everything_saveable,
        }[cfg.remat_policy]
        body = jax.checkpoint(body, policy=policy)
    (h, aux_s), (cache, taps) = jax.lax.scan(body, (h, aux), params["layers"])
    if lead_cache:
        # stacked over the leading layers, as the scan stacks its repeats
        cache = dict(cache, **_stack(lead_cache))
        taps = dict(taps, **_stack(lead_taps))
    return h, aux_s, cache, taps


def forward_train(params, batch, cfg: ModelConfig, *, capture: bool = False):
    """-> (logits, aux_loss, taps).  batch: tokens or dict (see embed_tokens)."""
    h, prefix_len = embed_tokens(params, batch, cfg)
    b, s, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    h = constrain(h, "batch", "seq", "embed")
    h, aux, _, taps = _scan_full(params, h, cfg, positions=positions,
                                 prefix_len=prefix_len, mode="train",
                                 smax=0, capture=capture)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = logits_head(params, h, cfg)
    return logits, aux, taps


def forward_prefill(params, batch, cfg: ModelConfig, *, smax: int):
    """-> (last-position logits, cache).  Builds the quantized cache."""
    h, prefix_len = embed_tokens(params, batch, cfg)
    b, s, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    h = constrain(h, "batch", "seq", "embed")
    h, _, cache, _ = _scan_full(params, h, cfg, positions=positions,
                                prefix_len=prefix_len, mode="prefill",
                                smax=smax, capture=False)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = logits_head(params, h[:, -1:, :], cfg)[:, 0]
    cache = {"entries": cache, "length": jnp.full((b,), s, jnp.int32)}
    return logits, cache


def forward_decode(params, tokens_t, cache, cfg: ModelConfig):
    """One decode step.  tokens_t: (B,) int32 (or (B,K) MusicGen).

    -> (logits (B, V) / (B, K, V), new cache).
    """
    dt = cfg.compute_dtype
    if cfg.n_codebooks:
        h = sum(params["embed"][f"cb{i}"][tokens_t[:, i]] for i in range(cfg.n_codebooks))
    else:
        h = params["embed"]["tok"][tokens_t]
    h = h.astype(dt)                                       # (B, D)
    length = cache["length"]
    entries = dict(cache["entries"])
    lead = entries.pop(pgc.LEAD, None)
    new_lead = []
    for l in range(cfg.first_k_dense_replace):
        h, new_l = _block_decode({pgc.LEAD: _at(params["lead"], l)}, h,
                                 {pgc.LEAD: _at(lead, l)}, cfg,
                                 length=length, specs=_lead_specs(cfg))
        new_lead.append(new_l[pgc.LEAD])

    def body(h, xs):
        p_blk, cache_blk = xs
        h_new, cache_new = _block_decode(p_blk, h, cache_blk, cfg, length=length)
        return h_new, cache_new

    h, new_entries = jax.lax.scan(body, h, (params["layers"], entries))
    if new_lead:
        new_entries = dict(new_entries, **{pgc.LEAD: _stack(new_lead)})
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = logits_head(params, h[:, None, :], cfg)[:, 0]
    return logits, {"entries": new_entries, "length": length + 1}


# ---------------------------------------------------------------------------
# Paged-cache entry points (block-table path — serving/scheduler.py)
# ---------------------------------------------------------------------------

def _gather_heads(out):
    """All-gather a head-sharded attention output before the (replicated)
    ``wo`` matmul.  Serving TP is gather-based: the projection's reduction
    stays device-local, so sharded paged decode is bit-identical to the
    unsharded engine (a partial-sum psum would reassociate fp adds and
    cross int8 round() boundaries in the pool quantizers).  No-op when no
    mesh is bound."""
    return constrain(out, "batch", *([None] * (out.ndim - 1)))


def _block_prefill_chunk(p_blk, h, pool_blk, spool_blk, cfg: ModelConfig, *,
                         positions, slot, block_row, ctx, chunk_len,
                         block_size: int, is_first: bool, state_slot,
                         specs=None):
    """One pattern repeat of a prefill *chunk* (B=1) against the block pool.

    The chunk's queries attend to the request's cached prefix (read straight
    from the INT8 pool through the block-table row —
    ``ops.paged_prefix_chunk_attention``) plus the chunk itself —
    position-exact right-aligned handling, no left-pad.  ``is_first``
    (static) skips the prefix read and freezes the per-channel K scales.  SSM layers carry
    conv/SSD state across chunk boundaries through the state pool
    (``state_slot``): read -> chunk-exact scan -> write back quantized.
    Returns (h, new pool, new state pool, MoE counts or None).
    """
    new_pool: Dict[str, Any] = {}
    new_spool: Dict[str, Any] = {}
    counts = None
    pos1d = positions[0] if positions.ndim > 1 else positions
    c = h.shape[1]

    for key, spec in specs or _pattern(cfg):
        p = p_blk[key]
        x = rms_norm(h, p["norm_mix"], cfg.norm_eps)
        if spec.mixer == "attn":
            entry = pool_blk[key]
            q, k, v = qkv_project(p["attn"], x, cfg, positions)
            entry = pgc.gqa_chunk_write(
                entry, k[0], v[0], slot=slot, block_row=block_row, ctx=ctx,
                chunk_len=chunk_len, block_size=block_size, is_first=is_first)
            if is_first:
                out = flash_attention(q, k, v, q_positions=pos1d,
                                      kv_positions=pos1d, chunk=cfg.attn_chunk)
            else:
                # prefix read straight from the INT8 pool by block table —
                # no dense gather (kernels/paged_attention.py chunk kernel)
                out = ops.paged_prefix_chunk_attention(
                    q, entry["k_vals"], entry["k_scale"][slot],
                    entry["k_zero"][slot], entry["v_vals"], entry["v_scale"],
                    entry["v_zero"], k, v, block_row, ctx)
            mix = qdot(_gather_heads(out.astype(x.dtype).reshape(1, c, -1)),
                       p["attn"]["wo"])
            new_pool[key] = entry
        elif spec.mixer == "mla":
            entry = pool_blk[key]
            q_nope, q_rope = mla_queries(p["attn"], x, cfg, positions)
            c_kv, k_rope = mla_latent(p["attn"], x, cfg, positions)
            entry = pgc.mla_chunk_write(
                entry, c_kv[0], k_rope[0], block_row=block_row, ctx=ctx,
                chunk_len=chunk_len, block_size=block_size)
            h_heads = cfg.n_heads
            dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
            dv = cfg.v_head_dim
            if is_first:
                s_all = c_kv.shape[1]
                kv = qdot(c_kv, p["attn"]["kv_b"]).reshape(1, s_all, h_heads,
                                                           dn + dv)
                k_nope, v_full = kv[..., :dn], kv[..., dn:]
                k_cat = jnp.concatenate(
                    [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                              (1, s_all, h_heads, dr))],
                    axis=-1)
                q_cat = jnp.concatenate([q_nope, q_rope], axis=-1)
                out = flash_attention(q_cat, k_cat, v_full, q_positions=pos1d,
                                      kv_positions=pos1d, chunk=cfg.attn_chunk)
            else:
                # absorbed latent-space attention against the pool prefix —
                # no dense gather, no K/V re-expansion of cached tokens
                w_uk, w_uv = mla_absorbed_weights(p["attn"], cfg)
                q_lat = jnp.einsum("bchd,rhd->bchr",
                                   q_nope.astype(jnp.float32),
                                   w_uk.astype(jnp.float32))
                o_lat = ops.mla_paged_prefix_chunk_attention(
                    q_lat, q_rope, entry["c_vals"], entry["c_scale"],
                    entry["kr_vals"], entry["kr_scale"], c_kv, k_rope,
                    block_row, ctx, qk_nope_dim=dn)
                out = jnp.einsum("bchr,rhd->bchd", o_lat,
                                 w_uv.astype(jnp.float32))
            mix = qdot(
                _gather_heads(out.astype(x.dtype).reshape(1, c, h_heads * dv)),
                p["attn"]["wo"])
            new_pool[key] = entry
        else:  # ssm: state pool carry across chunk boundaries
            sentry = spool_blk[key]
            carried = None if is_first else spl.read_state(sentry, state_slot)
            mix, work = ssm_prefill_chunk(p["ssm"], x, cfg, state=carried,
                                          chunk_len=chunk_len,
                                          is_first=is_first)
            new_spool[key] = spl.write_state(sentry, state_slot, work)
        # chunk/verify activations keep seq unsharded (the chunk is small;
        # sharding C over `model` would fight the TP head sharding) — the
        # constraint marks the row-parallel wo/w_out reduce-scatter boundary
        h = h + constrain(mix, "batch", None, "embed")
        if spec.ffn != "none":
            y = rms_norm(h, p["norm_ffn"], cfg.norm_eps)
            if spec.ffn == "dense":
                f = swiglu_apply(p["ffn"], y, cfg.act_fn, gather=True)
            else:
                f, cnt = _moe(p["moe"], y, cfg,
                              valid=(jnp.arange(c) < chunk_len)[None],
                              gather=True)
                counts = _add_counts(counts, cnt)
            h = h + constrain(f, "batch", None, "embed")
    return h, new_pool, new_spool, counts


def forward_prefill_chunk(params, tokens, pool, cfg: ModelConfig, *,
                          slot, block_row, ctx, chunk_len, block_size: int,
                          is_first: bool, state_pool=None, state_slot=0,
                          chunk_logits: bool = False):
    """One prefill chunk of a single request against the block pool.

    tokens: (1, C) right-padded (or (1, K, C) MusicGen); positions are
    ``ctx + arange(C)`` — position-exact, no left-pad.  ``state_pool`` /
    ``state_slot`` carry SSM layer state across chunks for hybrid patterns
    (``{}`` / ignored for pure-attention configs).  Returns
    (last-valid-token logits (1, V), new pool, new state pool).

    ``chunk_logits`` (static) returns the *full* per-position logits
    ``(1, C, V)`` instead of the last row — the serving-path scoring mode
    (teacher-forced NLL through the paged engine) needs every position's
    distribution, not just the sampling row.  Rows past ``chunk_len`` are
    pad garbage the caller must slice off; the valid rows are bitwise
    identical to the default path's last-row logits (same ``h``, same
    head).
    """
    spool = {} if state_pool is None else state_pool
    h, _ = embed_tokens(params, tokens, cfg)
    h = constrain(h, "batch", None, "embed")
    b, s, _ = h.shape
    positions = jnp.broadcast_to(ctx + jnp.arange(s)[None, :], (b, s))

    block = partial(_block_prefill_chunk, cfg=cfg, positions=positions,
                    slot=slot, block_row=block_row, ctx=ctx,
                    chunk_len=chunk_len, block_size=block_size,
                    is_first=is_first,
                    state_slot=jnp.asarray(state_slot, jnp.int32).reshape(1))
    new_lead = []
    for l in range(cfg.first_k_dense_replace):
        h, new_l, _, _ = block({pgc.LEAD: _at(params["lead"], l)}, h,
                               {pgc.LEAD: _at(pool[pgc.LEAD], l)}, {},
                               specs=_lead_specs(cfg))
        new_lead.append(new_l[pgc.LEAD])

    def body(h, xs):
        p_blk, pool_blk, spool_blk = xs
        h, new_pool, new_spool, counts = block(p_blk, h, pool_blk, spool_blk)
        return h, (new_pool, new_spool, counts)

    h, (new_pool, new_spool, counts) = jax.lax.scan(
        body, h, (params["layers"], pgc.scanned(pool), spool))
    new_pool = _with_stats(_with_lead(new_pool, new_lead), pool, counts)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    if chunk_logits:
        return logits_head(params, h, cfg), new_pool, new_spool
    last = jax.lax.dynamic_slice_in_dim(h, chunk_len - 1, 1, axis=1)
    logits = logits_head(params, last, cfg)[:, 0]
    return logits, new_pool, new_spool


def _block_decode_paged(p_blk, h, pool_blk, spool_blk, cfg: ModelConfig, *,
                        block_tables, lengths, block_size: int, state_slots,
                        specs=None):
    """One-token pass over one pattern repeat against the block pool.

    SSM layers step their recurrent state through the slot pool instead:
    gather + dequantize by ``state_slots``, one recurrent update, quantize +
    scatter back (inactive lanes read/write the trash slot).  Lanes with no
    context (``lengths`` 0) are idle: their MoE rows route nowhere.
    Returns (h, new pool, new state pool, MoE counts or None)."""
    new_pool: Dict[str, Any] = {}
    new_spool: Dict[str, Any] = {}
    counts = None
    b = h.shape[0]
    positions = lengths[:, None]

    for key, spec in specs or _pattern(cfg):
        p = p_blk[key]
        x = rms_norm(h, p["norm_mix"], cfg.norm_eps)
        if spec.mixer == "attn":
            entry = pool_blk[key]
            q, k, v = qkv_project(p["attn"], x[:, None, :], cfg, positions)
            entry = pgc.gqa_paged_append(entry, k[:, 0], v[:, 0],
                                         block_tables, lengths,
                                         block_size=block_size)
            out = ops.paged_decode_attention(
                q[:, 0], entry["k_vals"], entry["k_scale"], entry["k_zero"],
                entry["v_vals"], entry["v_scale"], entry["v_zero"],
                block_tables, lengths + 1)
            mix = qdot(_gather_heads(out.astype(x.dtype).reshape(b, -1)),
                       p["attn"]["wo"])
            new_pool[key] = entry
        elif spec.mixer == "mla":
            entry = pool_blk[key]
            q_nope, q_rope = mla_queries(p["attn"], x[:, None, :], cfg, positions)
            c_t, kr_t = mla_latent(p["attn"], x[:, None, :], cfg, positions)
            entry = pgc.mla_paged_append(entry, c_t[:, 0], kr_t[:, 0],
                                         block_tables, lengths,
                                         block_size=block_size)
            w_uk, w_uv = mla_absorbed_weights(p["attn"], cfg)
            out = ops.mla_paged_decode_attention(
                q_nope[:, 0], q_rope[:, 0], w_uk, w_uv,
                entry["c_vals"], entry["c_scale"], entry["kr_vals"],
                entry["kr_scale"], block_tables, lengths + 1)
            mix = qdot(_gather_heads(out.astype(x.dtype).reshape(b, -1)),
                       p["attn"]["wo"])
            new_pool[key] = entry
        else:  # ssm: O(1) recurrent update through the state slot pool
            sentry = spool_blk[key]
            work = spl.read_state(sentry, state_slots)
            mix, work = ssm_decode_step(p["ssm"], x, work, cfg)
            new_spool[key] = spl.write_state(sentry, state_slots, work)
        h = h + constrain(mix.astype(h.dtype), "batch", "embed")

        if spec.ffn != "none":
            y = rms_norm(h, p["norm_ffn"], cfg.norm_eps)
            if spec.ffn == "dense":
                f = swiglu_apply(p["ffn"], y[:, None, :], cfg.act_fn,
                                 gather=True)[:, 0]
            else:
                f, cnt = _moe(p["moe"], y, cfg, valid=lengths > 0,
                              gather=True)
                counts = _add_counts(counts, cnt)
            h = h + constrain(f.astype(h.dtype), "batch", "embed")
    return h, new_pool, new_spool, counts


def forward_decode_paged(params, tokens_t, pool, block_tables, lengths,
                         cfg: ModelConfig, *, block_size: int,
                         state_pool=None, state_slots=None):
    """One decode step over the block pool.  tokens_t: (B,) int32 (or (B,K));
    block_tables: (B, M) int32 pool block ids; lengths: (B,) live token
    counts (the new token is appended at position ``lengths[b]``);
    state_slots: (B,) int32 state-pool slot per lane for hybrid patterns
    (trash slot for inactive lanes; ignored for pure-attention configs).

    -> (logits (B, V) / (B, K, V), new pool, new state pool).
    """
    spool = {} if state_pool is None else state_pool
    if state_slots is None:
        state_slots = jnp.zeros((tokens_t.shape[0],), jnp.int32)
    dt = cfg.compute_dtype
    if cfg.n_codebooks:
        h = sum(params["embed"][f"cb{i}"][tokens_t[:, i]]
                for i in range(cfg.n_codebooks))
    else:
        h = params["embed"]["tok"][tokens_t]
    h = constrain(h.astype(dt), "batch", "embed")          # (B, D)
    block = partial(_block_decode_paged, cfg=cfg, block_tables=block_tables,
                    lengths=lengths, block_size=block_size,
                    state_slots=state_slots)
    new_lead = []
    for l in range(cfg.first_k_dense_replace):
        h, new_l, _, _ = block({pgc.LEAD: _at(params["lead"], l)}, h,
                               {pgc.LEAD: _at(pool[pgc.LEAD], l)}, {},
                               specs=_lead_specs(cfg))
        new_lead.append(new_l[pgc.LEAD])

    def body(h, xs):
        p_blk, pool_blk, spool_blk = xs
        h, new_pool, new_spool, counts = block(p_blk, h, pool_blk, spool_blk)
        return h, (new_pool, new_spool, counts)

    h, (new_pool, new_spool, counts) = jax.lax.scan(
        body, h, (params["layers"], pgc.scanned(pool), spool))
    new_pool = _with_stats(_with_lead(new_pool, new_lead), pool, counts)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = logits_head(params, h[:, None, :], cfg)[:, 0]
    return logits, new_pool, new_spool


def _block_verify_paged(p_blk, h, pool_blk, cfg: ModelConfig, *,
                        block_tables, lengths, vlens, block_size: int,
                        specs=None):
    """Multi-token verify pass over one pattern repeat (speculative decoding).

    h: (B, G, D) — position j of lane b sits at sequence position
    ``lengths[b] + j``.  Per layer the pass appends all G tokens' KV into the
    block pool with the *decode* quantization ops (frozen per-slot K affine,
    fresh per-token V scales), then scores all G positions in a single
    verify-attention launch (``ops.paged_verify_attention``) — each position
    masked at its own causal length, so the result is op-for-op identical to
    G sequential ``_block_decode_paged`` steps, which is what makes greedy
    spec-decode output bit-identical to plain paged decode.  Positions
    ``j >= vlens[b]`` write to the trash block (their logits are ignored by
    the host); entries past each query's causal length are masked by the
    attention's length argument, so the pre-written "future" tokens are
    invisible to earlier positions.
    """
    new_pool: Dict[str, Any] = {}
    counts = None
    b, g = h.shape[0], h.shape[1]
    positions = lengths[:, None] + jnp.arange(g)[None, :]          # (B, G)

    for key, spec in specs or _pattern(cfg):
        p = p_blk[key]
        x = rms_norm(h, p["norm_mix"], cfg.norm_eps)
        if spec.mixer == "attn":
            entry = pool_blk[key]
            q, k, v = qkv_project(p["attn"], x, cfg, positions)
            trash = entry["k_vals"].shape[0] - 1       # (N+1, T, KH*D)
            for j in range(g):
                bt_j = jnp.where((j < vlens)[:, None], block_tables, trash)
                entry = pgc.gqa_paged_append(entry, k[:, j], v[:, j],
                                             bt_j, lengths + j,
                                             block_size=block_size)
            out = ops.paged_verify_attention(
                q, entry["k_vals"], entry["k_scale"], entry["k_zero"],
                entry["v_vals"], entry["v_scale"], entry["v_zero"],
                block_tables, lengths)                             # (B,G,H,D)
            mix = qdot(_gather_heads(out.astype(x.dtype).reshape(b, g, -1)),
                       p["attn"]["wo"])
            new_pool[key] = entry
        elif spec.mixer == "mla":
            entry = pool_blk[key]
            q_nope, q_rope = mla_queries(p["attn"], x, cfg, positions)
            c_t, kr_t = mla_latent(p["attn"], x, cfg, positions)
            trash = entry["c_vals"].shape[0] - 1       # (N+1, T, rkv)
            for j in range(g):
                bt_j = jnp.where((j < vlens)[:, None], block_tables, trash)
                entry = pgc.mla_paged_append(entry, c_t[:, j], kr_t[:, j],
                                             bt_j, lengths + j,
                                             block_size=block_size)
            w_uk, w_uv = mla_absorbed_weights(p["attn"], cfg)
            out = ops.mla_paged_verify_attention(
                q_nope, q_rope, w_uk, w_uv,
                entry["c_vals"], entry["c_scale"], entry["kr_vals"],
                entry["kr_scale"], block_tables, lengths)          # (B,G,H,dv)
            mix = qdot(_gather_heads(out.astype(x.dtype).reshape(b, g, -1)),
                       p["attn"]["wo"])
            new_pool[key] = entry
        else:
            raise NotImplementedError(
                "spec-decode verify has no SSM rewind path; gate via "
                "spec_decode.ensure_spec_supported before building the step")
        h = h + constrain(mix.astype(h.dtype), "batch", None, "embed")

        if spec.ffn != "none":
            y = rms_norm(h, p["norm_ffn"], cfg.norm_eps)
            if spec.ffn == "dense":
                f = swiglu_apply(p["ffn"], y, cfg.act_fn, gather=True)
            else:
                f, cnt = _moe(p["moe"], y, cfg,
                              valid=jnp.arange(g)[None, :] < vlens[:, None],
                              gather=True)
                counts = _add_counts(counts, cnt)
            h = h + constrain(f.astype(h.dtype), "batch", None, "embed")
    return h, new_pool, counts


def forward_verify_paged(params, tokens, pool, block_tables, lengths, vlens,
                         cfg: ModelConfig, *, block_size: int):
    """Batched multi-token verify over the block pool (speculative decoding).

    tokens: (B, G) int32 — column 0 is each lane's pending token, columns
    1..G-1 the draft proposals; block_tables: (B, M); lengths: (B,) live
    token counts (token j is appended at ``lengths[b] + j``); vlens: (B,)
    per-lane verify span — positions ``j >= vlens[b]`` write to the trash
    block (lanes near their output budget, hot-sampled lanes, inactive
    lanes with vlen 0).

    Writes KV for every in-span position, then computes each position's
    logits against its exact causal prefix — the caller accepts the longest
    matching draft prefix and rewinds ``lengths`` / block-table tails past
    it (``paged_cache.rewind_tail``).  Pure-attention patterns only (see
    ``spec_decode.spec_unsupported_reason``).

    -> (logits (B, G, V), new pool).
    """
    dt = cfg.compute_dtype
    h = params["embed"]["tok"][tokens].astype(dt)          # (B, G, D)
    h = constrain(h, "batch", None, "embed")
    block = partial(_block_verify_paged, cfg=cfg, block_tables=block_tables,
                    lengths=lengths, vlens=vlens, block_size=block_size)
    new_lead = []
    for l in range(cfg.first_k_dense_replace):
        h, new_l, _ = block({pgc.LEAD: _at(params["lead"], l)}, h,
                            {pgc.LEAD: _at(pool[pgc.LEAD], l)},
                            specs=_lead_specs(cfg))
        new_lead.append(new_l[pgc.LEAD])

    def body(h, xs):
        p_blk, pool_blk = xs
        h, new_pool, counts = block(p_blk, h, pool_blk)
        return h, (new_pool, counts)

    h, (new_pool, counts) = jax.lax.scan(body, h,
                                         (params["layers"], pgc.scanned(pool)))
    new_pool = _with_stats(_with_lead(new_pool, new_lead), pool, counts)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = logits_head(params, h, cfg)                   # (B, G, V)
    return logits, new_pool


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def lm_loss(logits: jax.Array, labels: jax.Array, mask: Optional[jax.Array] = None,
            z_coef: float = 1e-4) -> jax.Array:
    """Causal LM cross-entropy in fp32 with z-loss.

    logits: (B,S,V) or (B,S,K,V); labels: (B,S) or (B,K,S).
    """
    if logits.ndim == 4:                                   # MusicGen codebooks
        labels = labels.transpose(0, 2, 1)                 # (B,S,K)
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    # Fused one-hot contraction instead of take_along_axis: stays sharded
    # over the vocab axis (a vocab gather would force an all-gather of the
    # (B,S,V) logits under SPMD).
    v = logits.shape[-1]
    onehot = jax.nn.one_hot(labels, v, dtype=logits.dtype)
    gold = jnp.sum(logits * onehot, axis=-1)
    nll = lse - gold
    z = z_coef * lse ** 2
    per_tok = nll + z
    if mask is not None:
        while mask.ndim < per_tok.ndim:
            mask = mask[..., None]
        per_tok = per_tok * mask
        return jnp.sum(per_tok) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(per_tok)

def _head_weights(params, cfg: ModelConfig):
    """List of (D, V) head weights (1 normally, K for MusicGen codebooks)."""
    if cfg.n_codebooks:
        return [params["heads"][f"head_cb{i}"] for i in range(cfg.n_codebooks)]
    if cfg.tie_embeddings:
        return [params["embed"]["tok"].T]
    return [params["lm_head"]]


def chunked_ce(h: jax.Array, w_head, labels: jax.Array, cfg: ModelConfig,
               *, mask: Optional[jax.Array] = None, loss_chunks: int = 8,
               z_coef: float = 1e-4) -> jax.Array:
    """Cross-entropy without materializing (B, S, V) logits.

    Sequence is processed in ``loss_chunks`` slices; each slice computes its
    (B, c, V) logits (vocab-sharded), reduces to per-token nll, and is
    remat'd — peak logits memory drops by the chunk factor.  Dry-run finding:
    at 150K vocab the fp32 logits were the largest train-step temp.

    h: (B, S, D); w_head: (D, V); labels: (B, S); mask: (B, S) or None.
    """
    b, s, d = h.shape
    nc = loss_chunks
    while s % nc != 0:
        nc -= 1
    c = s // nc
    hc = h.reshape(b, nc, c, d).transpose(1, 0, 2, 3)           # (nc,B,c,D)
    lc = labels.reshape(b, nc, c).transpose(1, 0, 2)            # (nc,B,c)
    mc = (mask.reshape(b, nc, c).transpose(1, 0, 2).astype(jnp.float32)
          if mask is not None else jnp.ones((nc, b, c), jnp.float32))

    def step(acc, inp):
        hh, ll, mm = inp                                        # (B,c,D)...
        logits = qdot(hh, w_head, out_dtype=jnp.float32)        # (B,c,V)
        logits = constrain(logits, "batch", None, "vocab")
        if cfg.logits_softcap > 0:
            logits = cfg.logits_softcap * jnp.tanh(logits / cfg.logits_softcap)
        lse = jax.nn.logsumexp(logits, axis=-1)                 # (B,c)
        onehot = jax.nn.one_hot(ll, logits.shape[-1], dtype=logits.dtype)
        # match the logits' vocab sharding: an unconstrained one-hot makes
        # SPMD gather the full-V logits chunk instead (26 GB/dev on mamba2)
        onehot = constrain(onehot, "batch", None, "vocab")
        gold = jnp.sum(logits * onehot, axis=-1)
        per_tok = (lse - gold + z_coef * lse * lse) * mm
        nll_sum, msum = acc
        return (nll_sum + jnp.sum(per_tok), msum + jnp.sum(mm)), None

    (nll_sum, msum), _ = jax.lax.scan(
        jax.checkpoint(step), (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hc, lc, mc))
    return nll_sum / jnp.maximum(msum, 1.0)


def train_loss(params, batch, cfg: ModelConfig, *, loss_chunks: int = 8):
    """Full train-mode loss with chunked CE (the train_step entry point)."""
    inputs = {k: v for k, v in batch.items() if k != "labels"}
    if set(inputs) == {"tokens"}:
        inputs = inputs["tokens"]
    labels = batch["labels"]
    mask = batch.get("loss_mask")

    h, prefix_len = embed_tokens(params, inputs, cfg)
    b, s, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    # initial carry matches the per-layer carry sharding (seq over model):
    # a replicated step-0 input would force the whole saved stack replicated
    h = constrain(h, "batch", "seq_carry", "embed")
    h, aux, _, _ = _scan_full(params, h, cfg, positions=positions,
                              prefix_len=prefix_len, mode="train",
                              smax=0, capture=False)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)

    heads = _head_weights(params, cfg)
    if cfg.n_codebooks:                                    # labels (B,K,S)
        losses = [chunked_ce(h, heads[i], labels[:, i], cfg,
                             loss_chunks=loss_chunks)
                  for i in range(cfg.n_codebooks)]
        loss = sum(losses) / len(losses)
    else:
        if cfg.n_img_patches and labels.shape[1] == s and mask is None:
            # patch-prefix positions carry no LM target
            mask = (positions >= cfg.n_img_patches).astype(jnp.float32)
        loss = chunked_ce(h, heads[0], labels, cfg, mask=mask,
                          loss_chunks=loss_chunks)
    return loss + aux
