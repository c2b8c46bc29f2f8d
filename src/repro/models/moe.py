"""Mixture-of-Experts FFN: top-k router, then either a capacity-factor
einsum dispatch (training) or a dropless grouped GEMM over the held experts
(serving, and training where ``capacity_factor`` is 0).

**Dropless, with an expert share** (``moe_dropless``): a chip holds experts
``[expert_offset, expert_offset + n_experts)`` of a router ``router_width``
wide.  Every token is routed over all router outputs (softmax, or
DeepSeek-V3's sigmoid scores with top-k chosen on score plus a correction
bias, the chosen scores normalised and scaled by ``moe_routed_scale``); the
(token, expert) pairs that land on held experts are sorted by expert into
row groups padded to the GEMM's row tile, and every pair is computed, so a
token's output never depends on which tokens share its batch.  The routed
part is this chip's share: the shares of all chips sum to the whole layer,
the shared experts are added once per chip.  Quantized expert weights run
the grouped W8A8 kernel (``kernels/expert_gemm.py``); float weights run
``lax.ragged_dot`` on the same row layout.

Switch/Mesh-style dropping implementation: tokens are dispatched to experts
through one-hot einsum tensors, so under pjit the expert dimension shards
cleanly (EP over the `data` axis, TP over `model` inside each expert) and
SPMD emits the dispatch collectives — no gather/scatter custom ops.

Supports top-1 (Llama-4 Maverick, with a shared expert that always runs) and
top-2 (Phi-3.5-MoE, Jamba).  Router runs in fp32 and is excluded from
quantization (core/apply.py DEFAULT_EXCLUDE) — range-sensitive softmax.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.qtensor import QTensor
from repro.distributed.sharding import constrain
from repro.kernels import ops
from .config import ModelConfig
from .layers import act, dense_init, swiglu_apply, swiglu_init


# rows per tile of the grouped INT8 GEMM: an int8 sublane tile holds 32
ROW_TILE = 32
# the counters one MoE layer call adds (``moe_dropless``), in this order
COUNTERS = ("moe_pairs_routed", "moe_pairs_held", "moe_rows_peak",
            "moe_rows_held_steps", "moe_experts_hit")


def moe_init(key, cfg: ModelConfig):
    d, e, f = cfg.d_model, cfg.n_experts, cfg.expert_d_ff
    k_r, k_g, k_u, k_o, k_s = jax.random.split(key, 5)
    dt = jnp.dtype(cfg.param_dtype)
    p = {
        "gate_w": dense_init(k_r, (d, cfg.router_width),
                             jnp.float32),                # router stays fp32
        "experts": {
            "w_gate": dense_init(k_g, (e, d, f), dt),
            "w_up": dense_init(k_u, (e, d, f), dt),
            "w_out": dense_init(k_o, (e, f, d), dt),
        },
    }
    if cfg.n_shared_experts:
        p["shared"] = swiglu_init(k_s, d, f * cfg.n_shared_experts, dt)
    if cfg.moe_score_bias:
        p["gate_bias"] = 0.1 * jax.random.normal(
            jax.random.fold_in(key, 1), (cfg.router_width,), jnp.float32)
    return p


def route(p, x2d: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """Top-k routing of tokens x2d (T, D) over all ``router_width``
    experts -> (weights (T, k) f32, expert ids (T, k) int32).

    Scores are a softmax or a sigmoid of the f32 router logits; with
    ``moe_score_bias`` the k experts are chosen on score plus the
    correction bias, and weighted by their scores without it.  The chosen
    scores are normalised over the k and scaled by ``moe_routed_scale``."""
    logits = x2d.astype(jnp.float32) @ p["gate_w"].astype(jnp.float32)
    if cfg.moe_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    choice = scores + p["gate_bias"] if cfg.moe_score_bias else scores
    _, idx = jax.lax.top_k(choice, cfg.n_experts_active)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg.moe_routed_scale, idx.astype(jnp.int32)


def expert_rows(idx: jax.Array, w: jax.Array, valid: jax.Array,
                cfg: ModelConfig, tm: int):
    """Lay the (token, expert) pairs that land on held experts out as rows
    sorted by expert, each expert's group padded to a multiple of ``tm``.

    idx, w: (T, k) routing; valid: (T,) bool (pad rows route nowhere).
    -> dict of ``token`` (R,) int32 and ``weight`` (R,) f32 per row (0 on
    padding rows), ``sizes`` (E,) padded group sizes, ``tile_expert``
    (R // tm,) int32 and ``used`` (1,) int32 tiles holding rows, and
    ``counts`` (5,) int32 (``COUNTERS``).  R = tm * ceil((T k + E (tm -
    1)) / tm): every pair can land on a held expert, so none is dropped."""
    t, k = idx.shape
    e = cfg.n_experts
    loc = idx - cfg.expert_offset
    held = (loc >= 0) & (loc < e) & valid[:, None]
    key = jnp.where(held, loc, e).reshape(-1)                   # (T*k,)
    order = jnp.argsort(key, stable=True)
    skey = key[order]
    sizes = jnp.zeros((e + 1,), jnp.int32).at[key].add(1)[:e]
    tiles = (sizes + tm - 1) // tm
    start = (jnp.cumsum(tiles) - tiles) * tm                    # padded
    first = jnp.cumsum(sizes) - sizes                           # unpadded
    grp = jnp.minimum(skey, e - 1)
    r = tm * (-(-(t * k + e * (tm - 1)) // tm))
    dest = jnp.where(skey < e,
                     start[grp] + jnp.arange(t * k) - first[grp], r)
    token = jnp.zeros((r,), jnp.int32).at[dest].set(
        (order // k).astype(jnp.int32), mode="drop")
    weight = jnp.zeros((r,), jnp.float32).at[dest].set(
        w.reshape(-1)[order].astype(jnp.float32), mode="drop")
    ends = jnp.cumsum(tiles)
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(r // tm), side="right"),
        e - 1).astype(jnp.int32)
    counts = jnp.stack([jnp.sum(valid).astype(jnp.int32) * k,
                        jnp.sum(sizes), jnp.max(sizes),
                        jnp.ones((), jnp.int32),
                        jnp.sum(sizes > 0).astype(jnp.int32)])
    return {"token": token, "weight": weight, "sizes": tiles * tm,
            "tile_expert": tile_expert, "used": ends[-1:].astype(jnp.int32),
            "counts": counts}


def _w8a8_experts(ws) -> bool:
    return all(isinstance(w, QTensor) and w.bits == 8 and w.zero is None
               and w.values.ndim == 3 and w.scale.ndim == 3
               and w.scale.shape[1] == 1 for w in ws)


def moe_dropless(p, x2d: jax.Array, cfg: ModelConfig, *,
                 valid: Optional[jax.Array] = None,
                 gather: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Dropless MoE over tokens x2d (T, D): this chip's routed share plus
    the shared experts -> (out (T, D) in x's dtype, counts (5,) int32 in
    ``COUNTERS`` order).  ``valid`` (T,) marks the real tokens: pad rows
    route to no expert and are not counted."""
    t, d = x2d.shape
    dt = x2d.dtype
    if valid is None:
        valid = jnp.ones((t,), bool)
    w, idx = route(p, x2d, cfg)
    ew = p["experts"]
    mats = (ew["w_gate"], ew["w_up"], ew["w_out"])
    quant = _w8a8_experts(mats)
    rows = expert_rows(idx, w, valid, cfg, ROW_TILE if quant else 1)
    if quant:
        # W8A8: each token's activations quantized once, rows gathered as
        # int8; the expert codes stay int8 through the grouped kernel
        te, used = rows["tile_expert"], rows["used"]
        xq, xs = ops.quantize_rows(x2d)
        xq, xs = xq[rows["token"]], xs[rows["token"]]
        g = ops.expert_gemm(xq, xs, ew["w_gate"], te, used, tm=ROW_TILE)
        u = ops.expert_gemm(xq, xs, ew["w_up"], te, used, tm=ROW_TILE)
        hq, hs = ops.quantize_rows(act(cfg.act_fn)(g) * u)
        y = ops.expert_gemm(hq, hs, ew["w_out"], te, used, tm=ROW_TILE)
    else:
        def mat(m):
            return (m.dequantize(jnp.float32) if isinstance(m, QTensor)
                    else m).astype(dt)
        xr = x2d[rows["token"]]
        gs = rows["sizes"]
        h = act(cfg.act_fn)(jax.lax.ragged_dot(xr, mat(mats[0]), gs)) \
            * jax.lax.ragged_dot(xr, mat(mats[1]), gs)
        y = jax.lax.ragged_dot(h.astype(dt), mat(mats[2]), gs)
    y = y.astype(jnp.float32) * rows["weight"][:, None]
    out = jnp.zeros((t, d), jnp.float32).at[rows["token"]].add(y)
    out = out.astype(dt)
    if cfg.n_shared_experts:
        out = out + swiglu_apply(p["shared"], x2d, cfg.act_fn, gather=gather)
    return out, rows["counts"]


def _route(logits: jax.Array, k: int, capacity: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """-> dispatch (T,E,C) bool-ish, combine (T,E,C) fp32, aux_loss scalar.

    T tokens, E experts, C capacity.  Over-capacity tokens are dropped
    (standard capacity-factor semantics); probs renormalized over top-k.
    """
    t, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)      # (T,E)
    top_p, top_i = jax.lax.top_k(probs, k)                           # (T,k)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)

    # Load-balancing aux loss (Switch): E * sum_e f_e * P_e
    me = jnp.mean(probs, axis=0)                                     # (E,)
    one_hot_any = jax.nn.one_hot(top_i[:, 0], e, dtype=jnp.float32)
    ce = jnp.mean(one_hot_any, axis=0)
    aux = e * jnp.sum(me * ce)

    dispatch = jnp.zeros((t, e, capacity), jnp.float32)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    counts = jnp.zeros((e,), jnp.float32)              # queue fill across slots
    for slot in range(k):                              # k is 1 or 2: unrolled
        idx = top_i[:, slot]                           # (T,)
        gate = top_p[:, slot]
        oh = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # (T,E)
        # position within the expert queue, offset by earlier slots' totals
        pos = (jnp.cumsum(oh, axis=0) - 1.0 + counts[None, :]) * oh  # (T,E)
        pos_tok = jnp.sum(pos, axis=-1)                # (T,)
        keep = pos_tok < capacity
        pos_oh = jax.nn.one_hot(pos_tok.astype(jnp.int32), capacity, dtype=jnp.float32)
        d_slot = oh[:, :, None] * pos_oh[:, None, :] * keep[:, None, None]
        dispatch = jnp.maximum(dispatch, d_slot)
        combine = combine + d_slot * gate[:, None, None]
        counts = counts + jnp.sum(oh, axis=0)
    return dispatch, combine, aux


def moe_apply(p, x: jax.Array, cfg: ModelConfig, *,
              gather: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out, aux_loss).

    GShard-style grouped dispatch: tokens are split into routing groups of
    ``moe_group_size``; dispatch/combine one-hots are (G, S_g, E, C_g) with
    per-group capacity — O(T * E * C_g) memory instead of O(T * E * C_T)
    (dry-run finding: the ungrouped form was 1.3 TiB/device on the 400B
    MoE train cell).  Group dim shards over (pod, data); the dispatched
    activations re-shard to expert-parallel (E over data) — GSPMD inserts
    the all-to-all, exactly GShard's schedule.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.n_experts_active
    # gather any seq-sharding first: group reshape must not straddle shards
    # (SPMD otherwise falls back to replicate-then-repartition)
    x = constrain(x, "batch", None, None)
    t = b * s
    gs = min(cfg.moe_group_size, t)
    while t % gs != 0:
        gs //= 2
    ng = t // gs
    xg = x.reshape(ng, gs, d)
    capacity = max(int(cfg.capacity_factor * k * gs / e), 4)
    capacity = -(-capacity // 4) * 4               # lane-friendly multiple

    logits = xg.astype(jnp.float32) @ p["gate_w"]                 # (G,Sg,E)
    dispatch, combine, aux = jax.vmap(_route, in_axes=(0, None, None)
                                      )(logits, k, capacity)
    aux = jnp.mean(aux)

    dt = x.dtype
    xg = constrain(xg, "moe_groups", None, None)
    dispatch = constrain(dispatch, "moe_groups", None, None, None)
    # keep g leading + g-sharded through the dispatch einsum (purely local),
    # THEN reshard g->e: SPMD emits an all-to-all.  A single fused einsum
    # with an e-sharded output makes SPMD all-gather xg to full (dry-run:
    # 3x 20 GiB buffers on the 400B cell).
    dispatched = jnp.einsum("gsd,gsec->gecd", xg, dispatch.astype(dt))
    dispatched = constrain(dispatched, "moe_groups", None, None, None)
    dispatched = constrain(dispatched, None, "experts", None, None)   # a2a
    dispatched = dispatched.transpose(1, 0, 2, 3)                 # (E,G,C,D)
    # 2D: experts over data, surviving group sharding over pod (dedup drops
    # axes already used) — keeps multi-pod expert work per-device constant
    dispatched = constrain(dispatched, "experts", "moe_groups", None, None)

    def _ew(w):                                # expert weights may be QTensors
        if isinstance(w, QTensor):
            return w.dequantize(jnp.float32).astype(dt)
        return w.astype(dt)

    ew = p["experts"]
    h = act(cfg.act_fn)(jnp.einsum("egcd,edf->egcf", dispatched, _ew(ew["w_gate"])))
    h = h * jnp.einsum("egcd,edf->egcf", dispatched, _ew(ew["w_up"]))
    # gather=True (paged serving): all-gather the f-sharded hidden so the
    # (replicated) w_out contraction stays device-local — bit-stable TP
    h = constrain(h, "experts", "moe_groups", None,
                  None if gather else "expert_ffn")
    expert_out = jnp.einsum("egcf,efd->egcd", h, _ew(ew["w_out"]))
    expert_out = constrain(expert_out, "experts", "moe_groups", None, None)
    # reshard e->g (all-to-all) BEFORE the combine einsum so it stays local
    expert_out = expert_out.transpose(1, 0, 2, 3)                 # (G,E,C,D)
    expert_out = constrain(expert_out, "moe_groups", None, None, None)

    out = jnp.einsum("gecd,gsec->gsd", expert_out, combine.astype(dt))
    out = constrain(out, "moe_groups", None, None)
    out = out.reshape(b * s, d)
    if cfg.n_shared_experts:
        out = out + swiglu_apply(p["shared"], x.reshape(b * s, d), cfg.act_fn,
                                 gather=gather)
    return out.reshape(b, s, d), aux * cfg.router_aux_coef
