"""Continuous-batching scheduler: chunked prefill, prefix caching, priorities.

The paper's Distributed Controller Layer serves batched traffic; this module
is its single-controller scheduling core, replacing the dense engine's
synchronous slot loop:

  * **continuous batching** — a fixed decode-batch width B; requests stream
    through slots, a finishing request frees its slot (and block references)
    at once.
  * **chunked prefill** — waiting prompts are split into fixed-size chunks
    and co-scheduled with decode in one jitted step, so a long prompt never
    stalls in-flight decodes for more than one chunk's latency (Sarathi-style
    stall-free batching).  Chunks are position-exact and right-aligned: the
    dense engine's left-pad RoPE shift is gone.
  * **prefix caching** — full prompt blocks are published into the
    allocator's content-hash index as they complete; admission matches a new
    prompt's block chain against the index and maps hits straight into the
    request's block table, skipping those prefill chunks entirely (``ctx``
    starts at the matched boundary).  Matched blocks are refcount-shared;
    the donor's frozen K scales are restored into the matcher's slot so the
    shared int8 codes dequantize bit-identically (see paged_cache docstring).
    Writes into a shared or published block copy-on-write to a fresh block.
  * **admission / preemption under a token budget** — each step spends at
    most ``token_budget`` tokens (decodes first, prefill fills the rest).
    Admission is priority-aware (higher ``Request.priority`` first, FCFS
    within a priority, optional aging: ``priority_age_steps`` grows a
    waiting request's effective priority with queue age so sustained
    high-priority load cannot starve anyone); when the block pool runs dry
    the lowest-priority — then youngest — running request is preempted
    (references dropped, request re-queued for recompute), vLLM-style.  A
    preempted request's published blocks survive as cached entries, so its
    recompute usually re-matches them instead of re-prefilling.
  * **hybrid SSM state pool** — Jamba/Mamba-pattern layers have fixed-size
    recurrent state instead of a growing KV; each admitted request holds one
    slot of the quantized state pool (``serving/state_pool.py``: conv tail
    bf16, SSD state INT8 + per-slot scales) from admission to finish, freed
    at preemption (recompute-on-resume, like KV).  Prefix matching is
    *state-aware*: publishing a block boundary whose prefill chunk landed
    exactly on it also snapshots the request's state-slot rows keyed by the
    chain digest (bounded LRU, ``state_snap_cap``), and a match is trimmed
    to the longest chain key holding a snapshot so the donor's exact
    quantized SSM state is restored alongside the KV blocks.  Sub-block
    partial matches stay disabled for hybrid configs (no state exists at a
    mid-block boundary).
  * **cache codec + pressure bit ladder** — the pool's storage layout comes
    from ``serving/codec.py``: ``codec="int8"`` is today's bit-identical
    layout, ``codec="int4"`` packs two codes per byte (capacity doubles,
    divergence-gated).  With ``ladder=True`` (int8 pools only) the scheduler
    demotes pairs of LRU-cold CACHED prefix blocks into single packed-int4
    blocks whenever the free list drops below ``ladder_watermark`` of the
    pool, promotes them back to int8 blocks on a prefix hit (packed blocks
    are never kernel-read), and demotes cold hybrid state snapshots the
    same way.  Ladder off means no demotion ever happens and serving stays
    bit-identical to the pre-codec engine.
  * **speculative decoding** — with ``SchedulerConfig.spec`` set, a low-bit
    draft of the same checkpoint (``serving/spec_decode.py``) proposes
    ``gamma`` tokens per decoding request; the target verifies all
    ``gamma + 1`` positions in one batched pass over the block pool and the
    scheduler accepts the longest matching prefix, rewinding ``ctx`` and the
    block-table tail past the rejections (``paged_cache.rewind_tail``).
    Greedy verification emits exactly the tokens plain decode would —
    spec-decode is a throughput knob, never a correctness knob.
  * **TTFT-aware prefill scheduling** — with ``ttft_target_steps`` set, a
    prefilling request whose queue age crosses the target takes the prefill
    turn (shortest-remaining-first among the overdue, so the late request
    closest to its first token wins), and the chunk budget shrinks to
    ``ttft_chunk`` while *other* requests are overdue, bounding how long one
    big chunk can delay the next scheduling decision.

The jitted step has three static shapes: decode width B, prefill-chunk
bucket C, and the block-table width M — bounded recompilation, same
philosophy as the dense engine's bucketed prefill.  Spec decoding adds one
more: the verify width ``gamma + 1``.
"""
from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict, deque
from functools import partial
from typing import Any, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.online import EmaScaleState
from repro.obs import NULL_TRACER, SERVING_HISTS, MetricsRegistry, clock
from repro.obs import compiles
from repro.distributed import sharding as shd
from repro.models.config import ModelConfig
from repro.models.transformer import (forward_decode_paged,
                                      forward_prefill_chunk,
                                      forward_verify_paged)
from repro.serving.codec import (demote_codes, demote_pair_blocks,
                                 get_codec, promote_block, promote_codes_full)
from repro.models.moe import COUNTERS as MOE_COUNTERS
from repro.serving.paged_cache import (STATS, BlockAllocator, PagedCacheConfig,
                                       copy_pool_block, init_paged_cache,
                                       paged_cache_nbytes, per_block_nbytes,
                                       per_device_nbytes, restore_slot_scales,
                                       rewind_tail, snapshot_slot_scales)
from repro.serving.spec_decode import (DraftProposer, SpecConfig,
                                       ensure_spec_supported)
from repro.serving.state_pool import (StateAllocator, init_state_pool,
                                      restore_state_slot, snapshot_state_slot,
                                      state_pool_nbytes)


def paged_unsupported_reason(cfg: ModelConfig) -> Optional[str]:
    """Why ``cfg`` cannot serve through the paged stack, or None.

    Shared capability detection for ``Scheduler`` / ``PagedServeEngine`` /
    ``ReplicatedServeEngine`` — only genuinely unsupported layouts are
    rejected.  SSM and hybrid attention+SSM patterns are served (block pool
    for attention KV, state pool for conv/SSD state)."""
    if cfg.n_img_patches:
        return ("prefix-LM image prefixes (n_img_patches="
                f"{cfg.n_img_patches}) need the bidirectional prefix mask "
                "only the dense ServeEngine implements")
    return None


def ensure_paged_supported(cfg: ModelConfig) -> None:
    reason = paged_unsupported_reason(cfg)
    if reason is not None:
        raise NotImplementedError(
            f"paged serving does not support {cfg.name}: {reason}")


def _kernel_vmem_bytes(cfg: ModelConfig, scfg: "SchedulerConfig",
                       blocks: int) -> Dict[str, int]:
    """VMEM per program of each one-shot paged kernel this engine launches
    (prefix-chunk prefill, plus verify under spec decoding) at a block-table
    width of ``blocks``."""
    from repro.kernels.paged_attention import (mla_chunk_tile,
                                               mla_chunk_vmem_bytes,
                                               mla_decode_vmem_bytes,
                                               one_shot_vmem_bytes)
    kinds = {s.mixer for s in cfg.layer_pattern}
    cols = blocks * scfg.block_size
    c = scfg.prefill_chunk
    g1 = scfg.spec.gamma + 1 if scfg.spec is not None else 0
    need: Dict[str, int] = {}
    if "attn" in kinds:
        d, grp = cfg.hd, cfg.n_heads // cfg.kv_heads
        need["prefix-chunk"] = one_shot_vmem_bytes(c * grp, cols + c,
                                                   (d, d), (d, d))
        if g1:
            need["verify"] = one_shot_vmem_bytes(g1 * grp, cols,
                                                 (d, d), (d, d))
    if "mla" in kinds:
        rkv, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        h = cfg.n_heads
        need["mla prefix-chunk"] = mla_chunk_vmem_bytes(
            mla_chunk_tile(c, h, cols + c, rkv, dr), h, cols + c, rkv, dr)
        need["mla decode"] = mla_decode_vmem_bytes(h, scfg.block_size, rkv,
                                                   dr)
        if g1:
            need["mla verify"] = one_shot_vmem_bytes(
                g1 * h, cols, (rkv, dr), (rkv, dr, rkv))
    return need


def _decode_vmem_bytes(cfg: ModelConfig, scfg: "SchedulerConfig",
                       num_blocks: int) -> int:
    """VMEM of the GQA paged decode launch over a pool of ``num_blocks``
    blocks and the trash block (0 without GQA layers): it holds a layer's
    V scale and zero leaves whole, so it grows with the pool."""
    if "attn" not in {s.mixer for s in cfg.layer_pattern}:
        return 0
    from repro.kernels.paged_attention import decode_vmem_bytes
    kh, d = cfg.kv_heads, cfg.hd
    return decode_vmem_bytes(kh, cfg.n_heads // kh, d, scfg.block_size,
                             kh * get_codec(scfg.codec).packed_dim(d),
                             scfg.max_blocks_per_req, num_blocks + 1)


def _decode_blocks_per_step(cfg: ModelConfig, scfg: "SchedulerConfig",
                            mesh, rules) -> int:
    """Blocks P that a decode step's attention launch scores together: the
    GQA kernel's own choice for the model's shapes, per device under a
    mesh; 1 for the MLA decode kernel, which walks one block a step."""
    if "attn" not in {s.mixer for s in cfg.layer_pattern}:
        return 1
    from repro.kernels.ops import paged_decode_blocks_per_step
    args = (cfg.n_heads, cfg.kv_heads,
            get_codec(scfg.codec).packed_dim(cfg.hd), scfg.block_size,
            scfg.max_blocks_per_req)
    if mesh is None:
        return paged_decode_blocks_per_step(*args)
    with shd.axis_rules(mesh, rules):
        return paged_decode_blocks_per_step(*args)


def ensure_kernels_fit(cfg: ModelConfig, scfg: "SchedulerConfig") -> None:
    """Refuse, at engine build, a pool the decode kernel or a block table
    the verify / prefix-chunk kernels cannot buffer in VMEM — the TPU
    compiler would otherwise reject the first step that launches them,
    mid-serve."""
    from repro.kernels.kv_decode_attention import DECODE_VMEM_LIMIT_BYTES
    from repro.kernels.paged_attention import VMEM_LIMIT_BYTES
    nbytes = _decode_vmem_bytes(cfg, scfg, scfg.num_blocks)
    if nbytes > DECODE_VMEM_LIMIT_BYTES:
        lo, hi = 0, scfg.num_blocks
        while lo < hi:
            mid = (lo + hi + 1) // 2
            if _decode_vmem_bytes(cfg, scfg, mid) <= DECODE_VMEM_LIMIT_BYTES:
                lo = mid
            else:
                hi = mid - 1
        raise ValueError(
            f"{cfg.name}: num_blocks={scfg.num_blocks} needs "
            f"{nbytes / 2**20:.1f} MiB of VMEM in the decode attention "
            f"kernel, which holds a layer's V scale and zero leaves whole, "
            f"over the {DECODE_VMEM_LIMIT_BYTES / 2**20:.0f} MiB it may "
            f"use; at most num_blocks={lo} fits — lower num_blocks")
    need = _kernel_vmem_bytes(cfg, scfg, scfg.max_blocks_per_req)
    over = {k: v for k, v in need.items() if v > VMEM_LIMIT_BYTES}
    if not over:
        return
    fit = scfg.max_blocks_per_req
    while fit > 0 and max(_kernel_vmem_bytes(cfg, scfg, fit).values()) \
            > VMEM_LIMIT_BYTES:
        fit -= 1
    kern, nbytes = max(over.items(), key=lambda kv: kv[1])
    raise ValueError(
        f"{cfg.name}: max_blocks_per_req * block_size = "
        f"{scfg.max_blocks_per_req * scfg.block_size} tokens needs "
        f"{nbytes / 2**20:.1f} MiB of VMEM in the {kern} attention kernel "
        f"(prefill_chunk={scfg.prefill_chunk}), over the "
        f"{VMEM_LIMIT_BYTES / 2**20:.0f} MiB a TPU kernel may use; at most "
        f"max_blocks_per_req={fit} ({fit * scfg.block_size} tokens) fits — "
        f"lower max_blocks_per_req or prefill_chunk")


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    block_size: int = 16                 # tokens per KV block
    num_blocks: int = 64                 # shared pool size
    max_batch: int = 8                   # decode-batch width (slots)
    max_blocks_per_req: int = 16         # block-table row width
    prefill_chunk: int = 64              # max tokens prefilled per step
    token_budget: int = 128              # decode + prefill tokens per step
    eos_id: int = -1                     # -1 = never stop early
    ema_alpha: float = 0.9
    seed: int = 0
    prefix_cache: bool = True            # publish/match full prompt blocks
    partial_prefix: bool = True          # sub-block sharing: after the full-
                                         # block chain match, device-copy the
                                         # longest matching partial tail of a
                                         # published block into the request's
                                         # first private block
    partial_min_tokens: int = 4          # shortest common run worth a partial
                                         # hit: shorter runs trade a full
                                         # block copy + the donor's frozen K
                                         # affine (computed on an unrelated
                                         # prompt) for skipping a token or
                                         # two of prefill — a bad perf trade
                                         # that also perturbs warm-request
                                         # quantization scales
    num_state_slots: int = 0             # SSM state-pool slots (0 = max_batch)
    priority_age_steps: int = 0          # waiting requests gain +1 effective
                                         # priority every N steps (0 = off) —
                                         # anti-starvation under sustained
                                         # high-priority load
    spec: Optional[SpecConfig] = None    # speculative decoding: low-bit draft
                                         # + multi-token verify (None = off)
    ttft_target_steps: int = 0           # TTFT-aware prefill scheduling: a
                                         # request older than this many steps
                                         # takes the prefill turn (SRJF among
                                         # the overdue); 0 = off
    ttft_chunk: int = 16                 # shrunken chunk budget while other
                                         # requests are past the TTFT target
    codec: str = "int8"                  # block/state pool storage codec
                                         # ("int8" = bit-identical legacy
                                         # layout, "int4" = packed nibbles,
                                         # double capacity, divergence-gated)
    ladder: bool = False                 # pressure-driven bit ladder: demote
                                         # LRU-cold CACHED blocks (and cold
                                         # hybrid state snapshots) to packed
                                         # int4, promote on prefix hit; int8
                                         # pools only
    ladder_watermark: float = 0.25       # demote while num_free falls below
                                         # this fraction of the pool
    state_snap_cap: int = 32             # hybrid prefix snapshots kept (LRU)
    state_snap_hot: int = 8              # newest snapshots kept int8 when the
                                         # ladder demotes the cold shelf
    weight_budget_mb: float = 0.0        # >0: per-layer weight bitwidths are
                                         # re-assigned at engine build via
                                         # core.bitwidth_search under this
                                         # byte budget (0 = params untouched)
    weight_bits_method: str = "symmetric"  # core.methods scheme the budget
                                         # re-quantization uses

    @property
    def paged(self) -> PagedCacheConfig:
        return PagedCacheConfig(block_size=self.block_size,
                                num_blocks=self.num_blocks,
                                max_batch=self.max_batch,
                                max_blocks_per_req=self.max_blocks_per_req)

    @property
    def state_slots(self) -> int:
        return self.num_state_slots or self.max_batch


def _prefix_keys(target: np.ndarray, block_size: int) -> List[bytes]:
    """Chain digests for every *full* block of ``target``: key j commits to
    tokens [0, (j+1)*block_size), so equal keys imply equal full prefixes.
    Exact token bytes feed the chain — no truncation collisions.  Tokens are
    canonicalized to int32 (the device dtype) first, so the same sequence
    submitted as a list / int64 array still matches."""
    target = np.asarray(target, dtype=np.int32)
    n = target.shape[-1] // block_size
    keys: List[bytes] = []
    d = b""
    for j in range(n):
        blk = np.ascontiguousarray(target[..., j * block_size:(j + 1) * block_size])
        d = hashlib.blake2b(d + blk.tobytes(), digest_size=16).digest()
        keys.append(d)
    return keys


class _Run:
    """One admitted request's scheduling state."""

    __slots__ = ("req", "slot", "ctx", "target", "pending", "resume_pending",
                 "state", "order", "priority", "t_add", "t_last_tok", "chain",
                 "published_upto", "scale_tag", "snapshot", "state_slot",
                 "step_enqueued", "step_added", "score_from", "score_lps")

    def __init__(self, req, order: int):
        self.req = req
        self.slot = -1
        self.ctx = 0                       # tokens currently in the cache
        self.target = np.asarray(req.prompt)   # tokens to prefill
        st = getattr(req, "score_tokens", None)
        if st is not None:
            # scoring mode: teacher-force prompt ++ score_tokens through
            # prefill; every chunk's full logits score the target tokens it
            # predicts and the request finishes without sampling anything
            self.target = np.concatenate(
                [self.target, np.asarray(st, self.target.dtype)], axis=-1)
            self.score_from = int(np.asarray(req.prompt).shape[-1])
            self.score_lps: Optional[Dict[int, float]] = {}
        else:
            self.score_from = -1           # not scoring
            self.score_lps = None
        self.pending = None                # sampled token awaiting decode
        self.resume_pending = None         # pending token across a preemption
        self.state = "prefill"
        self.order = order                 # arrival sequence (FCFS tiebreak)
        self.priority = int(getattr(req, "priority", 0))
        self.t_add = clock()               # for TTFT / queue-wait accounting
        self.t_last_tok = None             # last emit time (TPOT histogram)
        self.chain: List[bytes] = []       # prefix keys over target's blocks
        self.published_upto = 0            # blocks of target already indexed
        self.scale_tag: Optional[int] = None   # scale-freeze epoch id
        self.snapshot = None               # slot-scale rows for publishing
        self.state_slot = -1               # SSM state-pool slot (hybrid only)
        self.step_enqueued = 0             # scheduler step at enqueue (aging)
        self.step_added = 0                # step at add_request — never reset
                                           # (TTFT pressure measures total age)


def _step_impl(params, pool, spool, dec_tokens, dec_bt, dec_lens, dec_sslots,
               pf_tokens, pf_slot, pf_row, pf_ctx, pf_len, pf_sslot, *,
               cfg: ModelConfig, block_size: int,
               do_prefill: bool, do_decode: bool, pf_first: bool,
               pf_score: bool = False):
    """One engine iteration: prefill chunk + decode batch, fused in one jit.

    The prefill request and the decode slots are disjoint, so ordering inside
    the step is arbitrary; both write the (donated) KV block pool and — for
    hybrid patterns — the (donated) SSM state slot pool.  ``pf_score``
    (static, scoring mode) keeps every chunk position's logits instead of
    just the last row, so the consumer can read teacher-forced logprobs.
    """
    pf_logits: Any = ()
    dec_logits: Any = ()
    if do_prefill:
        pf_logits, pool, spool = forward_prefill_chunk(
            params, pf_tokens, pool, cfg, slot=pf_slot, block_row=pf_row,
            ctx=pf_ctx, chunk_len=pf_len, block_size=block_size,
            is_first=pf_first, state_pool=spool, state_slot=pf_sslot,
            chunk_logits=pf_score)
    if do_decode:
        dec_logits, pool, spool = forward_decode_paged(
            params, dec_tokens, pool, dec_bt, dec_lens, cfg,
            block_size=block_size, state_pool=spool, state_slots=dec_sslots)
    return pf_logits, dec_logits, pool, spool


def _spec_step_impl(params, pool, spool, dec_tokens, dec_bt, dec_lens,
                    dec_vlens, pf_tokens, pf_slot, pf_row, pf_ctx, pf_len,
                    pf_sslot, *, cfg: ModelConfig, block_size: int,
                    do_prefill: bool, do_decode: bool, pf_first: bool,
                    pf_score: bool = False):
    """Speculative-decoding variant of the fused step: the decode half is a
    batched multi-token verify (``forward_verify_paged``) over the drafts in
    ``dec_tokens`` columns 1.., with column 0 each lane's pending token."""
    pf_logits: Any = ()
    ver_logits: Any = ()
    if do_prefill:
        pf_logits, pool, spool = forward_prefill_chunk(
            params, pf_tokens, pool, cfg, slot=pf_slot, block_row=pf_row,
            ctx=pf_ctx, chunk_len=pf_len, block_size=block_size,
            is_first=pf_first, state_pool=spool, state_slot=pf_sslot,
            chunk_logits=pf_score)
    if do_decode:
        ver_logits, pool = forward_verify_paged(
            params, dec_tokens, pool, dec_bt, dec_lens, dec_vlens, cfg,
            block_size=block_size)
    return pf_logits, ver_logits, pool, spool


def _chunk_bucket(c: int, cap: int) -> int:
    """Pad a chunk length to a power-of-two bucket (bounded recompilation)."""
    b = 16
    while b < c:
        b *= 2
    return min(b, max(cap, c))


# one jitted fused step per (cfg, block_size, mesh fingerprint) and one CoW
# copy, shared by every Scheduler instance: N replicas of the same model over
# the same (sub)mesh reuse a single compilation cache instead of paying the
# identical compile per engine.  The fingerprint keeps sharded and unsharded
# engines — or engines on different meshes — from colliding on one
# executable whose baked-in shardings only fit one of them.
_STEP_FN_CACHE: Dict[Any, Any] = {}
_COW_FN: Any = None


def _mesh_traced(impl, mesh, rules):
    """Close ``impl`` over an ``axis_rules`` binding so the sharding
    constraints inside the model code are active *at trace time* (the rules
    live in a thread-local read while jit traces, not at call time)."""
    if mesh is None:
        return impl

    def traced(*args, do_prefill, do_decode, pf_first, pf_score=False):
        with shd.axis_rules(mesh, rules):
            return impl(*args, do_prefill=do_prefill, do_decode=do_decode,
                        pf_first=pf_first, pf_score=pf_score)
    return traced


def _step_fn_for(cfg: ModelConfig, block_size: int, mesh=None, rules=None,
                 codec: str = "int8"):
    # codec is in the key even though jit would re-specialize on the packed
    # pool shapes anyway: two codecs must never race one cache entry's
    # in-flight compilation or donation bookkeeping
    key = (cfg, block_size, codec, shd.mesh_fingerprint(mesh, rules))
    fn = _STEP_FN_CACHE.get(key)
    if fn is None:
        base = partial(_step_impl, cfg=cfg, block_size=block_size)
        fn = jax.jit(_mesh_traced(base, mesh, rules),
                     static_argnames=("do_prefill", "do_decode", "pf_first",
                                      "pf_score"),
                     donate_argnums=(1, 2))
        _STEP_FN_CACHE[key] = fn
    return fn


def _spec_fn_for(cfg: ModelConfig, block_size: int, mesh=None, rules=None,
                 codec: str = "int8"):
    key = (cfg, block_size, codec, "spec", shd.mesh_fingerprint(mesh, rules))
    fn = _STEP_FN_CACHE.get(key)
    if fn is None:
        base = partial(_spec_step_impl, cfg=cfg, block_size=block_size)
        fn = jax.jit(_mesh_traced(base, mesh, rules),
                     static_argnames=("do_prefill", "do_decode", "pf_first",
                                      "pf_score"),
                     donate_argnums=(1, 2))
        _STEP_FN_CACHE[key] = fn
    return fn


def _shared_cow_fn():
    global _COW_FN
    if _COW_FN is None:
        _COW_FN = jax.jit(copy_pool_block, donate_argnums=(0,))
    return _COW_FN


class Scheduler:
    """Paged continuous-batching scheduler (host-side control plane)."""

    def __init__(self, params, cfg: ModelConfig, scfg: SchedulerConfig, *,
                 draft_built=None, mesh=None, rules=None, tracer=None,
                 trace_track: int = 0):
        """``draft_built``: optional pre-built draft ``(params, cfg)`` pair
        handed to the proposer so replica fleets quantize the draft once
        (see ``ReplicatedServeEngine``); ignored when ``scfg.spec`` is
        unset.

        ``tracer``: optional :class:`repro.obs.Tracer` recording scheduler
        phase spans and request lifecycle events (None = the no-op
        singleton: the hot path pays one branch).  ``trace_track`` is this
        scheduler's track id in the trace — the replica index when driven by
        ``ReplicatedServeEngine``, so each replica exports as its own
        Chrome-trace process.

        ``mesh``/``rules``: optional ``jax.sharding.Mesh`` (+ logical-axis
        rule overrides) for tensor/expert-parallel serving *inside* this
        scheduler.  Params are committed to ``param_spec`` shardings
        (``heads``/``kv_heads``/``ffn``/``vocab`` over ``model``, experts
        over ``data``) and the KV block pool / SSM state pool to
        kv-head-sharded layouts pinned to the mesh's devices; the fused step
        is traced under ``axis_rules(mesh, rules)`` so activation
        constraints in the model code become real collective boundaries."""
        ensure_paged_supported(cfg)
        ensure_kernels_fit(cfg, scfg)
        if scfg.ladder and scfg.codec != "int8":
            raise ValueError(
                "the bit ladder demotes int8 blocks to packed int4; it "
                f"requires codec='int8' (got codec={scfg.codec!r})")
        self.mesh = mesh
        self.rules = rules
        # per-layer weight bitwidths under a byte budget (engine-build hook):
        # the policy-eligible matrices are re-quantized with the widths
        # core.bitwidth_search assigns, before any sharding commit
        self.weight_bits: Optional[Dict[str, int]] = None
        if scfg.weight_budget_mb > 0:
            from repro.core.bitwidth_search import assign_weight_bitwidths
            params, wres = assign_weight_bitwidths(
                params, int(scfg.weight_budget_mb * 2 ** 20),
                method=scfg.weight_bits_method)
            if wres is not None:
                self.weight_bits = dict(wres.assignment)
        self.params = params
        self.cfg = cfg
        self.scfg = scfg
        self.pcfg = scfg.paged
        self.trash = self.pcfg.trash_block
        self.pool = init_paged_cache(cfg, self.pcfg, codec=scfg.codec)
        self.alloc = BlockAllocator(scfg.num_blocks)
        # hybrid (attention+SSM) patterns: fixed-size conv/SSD state lives in
        # a slot pool beside the KV block pool; a request holds one slot from
        # admission to finish (freed at preemption — recompute-on-resume).
        self._has_ssm = any(s.mixer == "ssm" for s in cfg.layer_pattern)
        self.state_trash = scfg.state_slots if self._has_ssm else 0
        self.spool = init_state_pool(cfg, scfg.state_slots, codec=scfg.codec) \
            if self._has_ssm else {}
        self.state_alloc = StateAllocator(scfg.state_slots) \
            if self._has_ssm else None
        self._prefix_on = scfg.prefix_cache
        # hybrid prefix sharing: exact quantized state-slot rows captured at
        # published block boundaries, keyed by the boundary's chain digest
        # (a KV match is only usable up to a key whose state we can restore)
        self._state_snaps: "OrderedDict[bytes, Dict[str, Any]]" = OrderedDict()
        if mesh is not None:
            # commit params + pools to their mesh placements now: jit infers
            # in_shardings from committed inputs, so the traced constraints
            # and the actual layouts agree from the first step (no silent
            # full-replication resharding on entry)
            with shd.axis_rules(mesh, rules):
                self.params = jax.device_put(
                    params,
                    shd.tree_param_shardings(mesh, params, serving=True))
                self.pool = jax.device_put(
                    self.pool, shd.tree_pool_shardings(mesh, self.pool))
                if self.spool:
                    self.spool = jax.device_put(
                        self.spool, shd.tree_pool_shardings(mesh, self.spool))
        self.block_tables = np.full(
            (scfg.max_batch, scfg.max_blocks_per_req), self.trash, np.int32)
        self.slots: List[Optional[_Run]] = [None] * scfg.max_batch
        self.waiting: Deque[_Run] = deque()
        self.finished: List[Any] = []
        self._order = 0
        self._scale_tag = 0                # scale-freeze epoch counter
        self._rng = jax.random.PRNGKey(scfg.seed)
        self.scale_state = EmaScaleState.init()
        self._step_fn = _step_fn_for(cfg, scfg.block_size, mesh, rules,
                                     codec=scfg.codec)
        self._decode_p = _decode_blocks_per_step(cfg, scfg, mesh, rules)
        self._cow_fn = _shared_cow_fn()
        if self._prefix_on:
            # build the prefix-hit programs now (a no-op copy of the trash
            # block, a slot's scales written back over themselves), not at
            # the first hit mid-serve
            self.pool = self._cow_fn(self.pool, jnp.int32(self.trash),
                                     jnp.int32(self.trash))
            self.pool = restore_slot_scales(
                self.pool, 0, snapshot_slot_scales(self.pool, 0))
        # observability: tracer (no-op singleton unless injected) + the
        # always-on latency histograms metrics() summarizes
        self.trace = tracer if tracer is not None else NULL_TRACER
        compiles.install()
        self.track = int(trace_track)
        self.mreg = MetricsRegistry()
        # speculative decoding: the draft proposer holds one dense-cache lane
        # per decode slot; the verify step replaces the one-token decode
        self.spec = scfg.spec
        if self.spec is not None:
            ensure_spec_supported(cfg)
            cap = min(self.pcfg.tokens_per_req,
                      scfg.num_blocks * scfg.block_size)
            self.draft = DraftProposer(params, cfg, self.spec,
                                       max_batch=scfg.max_batch, capacity=cap,
                                       built=draft_built, tracer=self.trace,
                                       trace_track=self.track)
            self._spec_fn = _spec_fn_for(cfg, scfg.block_size, mesh, rules,
                                         codec=scfg.codec)
        else:
            self.draft = None
            self._spec_fn = None
        self.stats = {"prefill_tokens": 0, "prefill_chunks": 0,
                      "decode_steps": 0, "decode_tokens": 0, "first_tokens": 0,
                      "preemptions": 0, "steps": 0, "failed_alloc": 0,
                      "prefix_hits": 0, "prefix_hit_tokens": 0,
                      "prefix_partial_tokens": 0,
                      "prefix_query_tokens": 0, "cow_copies": 0,
                      "spec_rounds": 0, "spec_lane_rounds": 0,
                      "spec_proposed": 0, "spec_accepted": 0,
                      "spec_emitted": 0, "snap_demotions": 0,
                      "snap_promotions": 0, "state_prefix_hits": 0,
                      "score_requests": 0, "score_tokens": 0,
                      # per decode or verify step: block-table entries that
                      # hold KV the step attends to, entries handed to the
                      # paged kernel, and the kernel steps that score the
                      # live ones, P blocks a step
                      "decode_kv_blocks_live": 0,
                      "decode_kv_blocks_passed": 0,
                      "decode_kv_steps_live": 0,
                      # executables built or loaded by this scheduler's
                      # steps (repro.obs.compiles)
                      "compiles": 0}
        if STATS in self.pool:
            # MoE layers, summed on the device over every layer call of
            # every step (models/moe.py COUNTERS): (token, expert) pairs
            # routed over the whole router, pairs on held experts, the
            # busiest held expert's rows, the layer calls counted, and the
            # held experts that had rows
            self.stats.update({k: 0 for k in MOE_COUNTERS})
            self._moe_read = np.zeros((len(MOE_COUNTERS),), np.uint32)
        self._consuming = 0             # ordinal of the step being consumed
        self._score_lat_sum = 0.0       # summed per-request scoring latency
        self._util_sum = 0.0
        self._util_peak = 0.0
        self._cached_sum = 0.0
        self._logical_peak = 0          # peak logical-resident blocks
        self._cache_peak = 0            # peak reusable prefix blocks (cached
                                        # + int4 halves): the ladder's
                                        # capacity-ratio numerator
        self._t_start: Optional[float] = None
        self._t_last = 0.0

    # -- public API -----------------------------------------------------------
    def add_request(self, req) -> None:
        scoring = getattr(req, "score_tokens", None) is not None
        if scoring:
            if self.cfg.n_codebooks:
                raise ValueError(
                    f"request {req.uid}: teacher-forced scoring is not "
                    f"supported for multi-codebook (MusicGen) models")
            if int(np.asarray(req.score_tokens).shape[-1]) < 1:
                raise ValueError(
                    f"request {req.uid}: score_tokens is empty — nothing "
                    f"to score")
            if int(np.asarray(req.prompt).shape[-1]) < 1:
                raise ValueError(
                    f"request {req.uid}: scoring needs a non-empty prompt "
                    f"(the first score token's logprob is conditioned on "
                    f"at least one context token)")
        run = _Run(req, self._order)
        s = int(run.target.shape[-1])
        # the final sampled token is never appended to the cache, so a
        # generating request occupies at most s + max_new - 1 slots (same
        # contract as the dense engine); a scoring request prefills its
        # whole target and decodes nothing
        need = s if scoring else s + req.max_new_tokens - 1
        cap = min(self.pcfg.tokens_per_req,
                  self.scfg.num_blocks * self.scfg.block_size)
        if need > cap:
            raise ValueError(
                f"request {req.uid}: prompt ({s}) + max_new_tokens "
                f"({req.max_new_tokens}) needs {need} cache slots, exceeding "
                f"the paged cache capacity per request ({cap} = "
                f"min(max_blocks_per_req * block_size, num_blocks * "
                f"block_size)); shorten the prompt or grow the pool")
        if req.generated is None:
            req.generated = []
        run.step_enqueued = self.stats["steps"]
        run.step_added = self.stats["steps"]
        if hasattr(req, "t_add"):
            req.t_add = run.t_add
        self._order += 1
        self.waiting.append(run)
        self.trace.event("enqueue", track=self.track, uid=req.uid,
                         prompt=s, max_new=req.max_new_tokens)

    def step(self) -> bool:
        """One iteration: admit -> schedule decode (or a speculative verify
        round) + one prefill chunk -> run the fused jitted step ->
        sample/retire."""
        return self.step_consume(self.step_launch())

    def step_launch(self) -> Optional[Dict[str, Any]]:
        """Admit/schedule and *dispatch* the fused device step, without
        blocking on its results.  jax dispatch is async: the returned context
        holds logits futures that ``step_consume`` materializes.  Splitting
        the step here lets ``ReplicatedServeEngine`` launch every replica's
        step before consuming any of them, so replicas (each pinned to its
        own ``data``-axis device slice) genuinely compute concurrently
        instead of serializing through the host control loop.  Returns None
        when there is no work this step."""
        t0 = clock()
        c0 = compiles.count()
        if self._t_start is None:
            self._t_start = t0
        if self.scfg.ladder:
            self._maybe_demote()        # before admission: freed blocks and
                                        # promote headroom help the matcher
        self._admit()
        dec_slots = self._live_decode(self._schedule_decode())
        vlens = (self._schedule_spec(dec_slots)
                 if self.spec is not None and dec_slots else None)
        n_dec = sum(vlens.values()) if vlens else len(dec_slots)
        pf = self._schedule_prefill(n_dec)
        # prefill scheduling can also preempt (CoW allocation), so re-filter
        dec_slots = self._live_decode(dec_slots)
        if vlens is not None:
            vlens = {s: v for s, v in vlens.items() if s in set(dec_slots)}
            if vlens and max(vlens.values()) == 1:
                # every span degenerated (all-hot lanes, last tokens, pool
                # dry): a 1-token verify IS plain decode — skip the draft
                # proposal and the wide verify entirely
                vlens = None
        if not dec_slots and pf is None:
            self.stats["compiles"] += compiles.count() - c0
            return None
        self.stats["steps"] += 1
        self._util_sum += self.alloc.utilization
        self._util_peak = max(self._util_peak, self.alloc.utilization)
        self._cached_sum += self.alloc.cached_frac
        self._logical_peak = max(self._logical_peak, self._logical_blocks())
        self._cache_peak = max(self._cache_peak,
                               self.alloc.num_cached + self.alloc.int4_blocks)

        # scoring chunks keep every position's logits (static flag: the
        # chunk-logits head is a different — larger — jit specialization)
        pf_score = (pf is not None
                    and self.slots[pf[0]].score_from >= 0)
        tr, step = self.trace, self.stats["steps"]
        if dec_slots and vlens:
            drafts = self._propose_drafts(dec_slots, vlens)
            fn, name, sargs = self._spec_fn, "paged_spec_step", {"spec": True}
        else:
            drafts = vlens = None
            fn, name, sargs = self._step_fn, "paged_step", \
                {"prefill": pf is not None}
        with tr.span("stage", track=self.track, step=step):
            args = (self._build_spec_args(dec_slots, vlens, drafts, pf)
                    if vlens else self._build_args(dec_slots, pf))
        t1 = clock()
        if tr.enabled:
            held = compiles.count() - c0
            if held:
                sargs["compiles"] = held
            tr.add_span("schedule", t0, t1 - t0, track=self.track, step=step,
                        decode=len(dec_slots), **sargs)
        with tr.span("device_step", track=self.track, annotation=name,
                     step=step):
            pf_logits, dec_logits, self.pool, self.spool = fn(
                self.params, self.pool, self.spool, *args["device"],
                do_prefill=pf is not None, do_decode=bool(dec_slots),
                pf_first=(pf is None or pf[1] == 0), pf_score=pf_score)
        self.stats["compiles"] += compiles.count() - c0
        return {"dec_slots": dec_slots, "vlens": vlens, "drafts": drafts,
                "pf": pf, "pf_logits": pf_logits, "dec_logits": dec_logits,
                "t0": t0, "t1": t1, "step": step, "ctx": args.get("ctx", 0)}

    def step_consume(self, launched: Optional[Dict[str, Any]]) -> bool:
        """Block on a ``step_launch`` context's logits and sample/retire."""
        if launched is None:
            return False
        c0 = compiles.count()
        dec_slots, pf = launched["dec_slots"], launched["pf"]
        tr, step = self.trace, launched["step"]
        self._consuming = step
        with tr.span("consume", track=self.track, rest="retire", step=step):
            if launched["vlens"] is not None:
                self._consume_spec(dec_slots, launched["vlens"],
                                   launched["drafts"], launched["dec_logits"])
            elif dec_slots:
                self._consume_decode(dec_slots, launched["dec_logits"])
            if pf is not None:
                self._consume_prefill(pf, launched["pf_logits"])
        t3 = clock()
        self.stats["compiles"] += compiles.count() - c0
        if tr.enabled:
            t1 = launched["t1"]
            if launched["vlens"] is not None:
                tr.add_span("spec_round", t1, t3 - t1, track=self.track,
                            step=step, lanes=len(launched["vlens"]))
            elif dec_slots:
                tr.add_span("decode_step", t1, t3 - t1, track=self.track,
                            step=step, batch=len(dec_slots),
                            ctx=launched["ctx"])
            if pf is not None:
                run = self.slots[pf[0]]
                tr.add_span("prefill_chunk", t1, t3 - t1, track=self.track,
                            lane=pf[0], step=step, ctx=pf[1], tokens=pf[2],
                            uid=run.req.uid if run is not None else -1)
        self.mreg.observe("step_wall", t3 - launched["t0"])
        self._t_last = t3
        return True

    def _fetch(self, x) -> np.ndarray:
        """Bring a device result to the host: the only points at which
        ``consume`` blocks on the device.  A MoE pool's running counters
        ride in the same transfer."""
        with self.trace.span("device_wait", track=self.track,
                             step=self._consuming):
            if STATS not in self.pool:
                return np.asarray(x)
            x, moe = jax.device_get((x, self.pool[STATS]["moe"]))
        # uint32 totals wrap: add the difference since the last read
        delta = (np.asarray(moe, np.uint32) - self._moe_read).astype(np.int64)
        self._moe_read = np.asarray(moe, np.uint32)
        for k, d in zip(MOE_COUNTERS, delta.tolist()):
            self.stats[k] += d
        return np.asarray(x)

    def run(self, max_steps: int = 10_000):
        steps = 0
        while (self.waiting or any(self.slots)) and steps < max_steps:
            self.step()
            steps += 1
        return self.finished

    def _live_decode(self, dec_slots: List[int]) -> List[int]:
        """Drop slots that were preempted after being scheduled: victim
        selection is a global min over ``(priority, -order)``, so a later
        slot's multi-eviction loop can vacate an earlier-scheduled slot;
        ``_build_args`` must never dereference the ``None`` left behind."""
        return [s for s in dec_slots
                if self.slots[s] is not None
                and self.slots[s].state == "decode"]

    @property
    def has_work(self) -> bool:
        return bool(self.waiting or any(self.slots))

    @property
    def num_running(self) -> int:
        """Occupied decode-batch slots (prefilling or decoding)."""
        return sum(1 for r in self.slots if r is not None)

    @property
    def num_waiting(self) -> int:
        return len(self.waiting)

    @property
    def live_tokens(self) -> int:
        """Tokens this engine is responsible for right now: cached context of
        every running request plus the not-yet-prefilled prompt tokens of the
        queue — the load signal ``least_loaded`` routing balances on."""
        live = sum(max(int(r.ctx), int(r.target.shape[-1]))
                   for r in self.slots if r is not None)
        live += sum(int(r.target.shape[-1]) for r in self.waiting)
        return int(live)

    @property
    def occupancy(self) -> float:
        """Fraction of pool blocks holding live (referenced) data."""
        return self.alloc.utilization

    def drain(self, max_steps: int = 10_000) -> List[Any]:
        """Quiesce hook for the replica router: hand back every *pristine*
        queued request (the caller re-routes them elsewhere) and run the
        in-flight work to completion.  A preempted request awaiting recompute
        already has emitted tokens and a resume state that only this engine
        holds, so it stays and finishes locally."""
        keep = deque(r for r in self.waiting if r.req.generated)
        handed = [r.req for r in self.waiting if not r.req.generated]
        self.waiting = keep
        self.run(max_steps)
        return handed

    def metrics(self) -> Dict[str, float]:
        done = [r for r in self.finished]
        # wall clock covers first launch -> last consume; before any step
        # ran there is no wall at all — report explicit zeros instead of a
        # near-epoch `_t_last - 0.0` difference masquerading as throughput
        if self._t_start is None:
            wall = 0.0
        else:
            wall = max(self._t_last - self._t_start, 1e-9)
        # prefill-sampled first tokens are counted as they are emitted, so
        # in-flight requests contribute theirs too (counting finished
        # requests instead dropped them and dipped mid-flight throughput)
        gen = self.stats["decode_tokens"] + self.stats["first_tokens"]
        steps = max(self.stats["steps"], 1)
        out = {
            "requests_finished": len(done),
            "ttft_avg_s": (float(np.mean([r.ttft_s for r in done]))
                           if done else 0.0),
            "ttft_max_s": (float(np.max([r.ttft_s for r in done]))
                           if done else 0.0),
            "tokens_per_s": gen / wall if wall else 0.0,
            "wall_s": wall,
            "cache_util_avg": self._util_sum / steps,
            "cache_util_peak": self._util_peak,
            "cache_nbytes": paged_cache_nbytes(self.pool),
            # what one device actually holds: shrinks with the `model` axis
            # for kv-head-sharded pools, == cache_nbytes when unsharded
            "cache_nbytes_per_device": per_device_nbytes(self.pool),
            "preemptions": self.stats["preemptions"],
            "failed_alloc": self.stats["failed_alloc"],
            "decode_steps": self.stats["decode_steps"],
            "prefill_chunks": self.stats["prefill_chunks"],
            # prefix cache: tokens whose prefill was skipped via the index,
            # the fraction of admitted prompt tokens they cover, and how much
            # of the pool holds reclaimable cached blocks
            "prefix_hits": self.stats["prefix_hits"],
            "prefix_hit_tokens": self.stats["prefix_hit_tokens"],
            "prefix_hit_rate": (self.stats["prefix_hit_tokens"] /
                                max(self.stats["prefix_query_tokens"], 1)),
            "cached_blocks": self.alloc.num_cached,
            "cached_frac_avg": self._cached_sum / steps,
            "cow_copies": self.stats["cow_copies"],
            # cache codec + bit ladder: logical blocks demoted/promoted
            # (including hybrid state snapshots), packed residents right now,
            # and the *logical* cache footprint — what an int8-only pool
            # would need in bytes to hold the same resident blocks; its peak
            # over the run is the ladder's capacity-ratio numerator
            "demotions": self.alloc.demotions + self.stats["snap_demotions"],
            "promotions": (self.alloc.promotions
                           + self.stats["snap_promotions"]),
            "int4_blocks": self.alloc.int4_blocks,
            "effective_cache_bytes": (self._logical_blocks()
                                      * per_block_nbytes(self.pool)),
            "effective_cache_blocks_peak": self._logical_peak,
            "prefix_cache_blocks_peak": self._cache_peak,
            "state_prefix_hits": self.stats["state_prefix_hits"],
            # teacher-forced scoring (eval subsystem): requests/tokens scored
            # through the serving path, summed and mean per-request latency,
            # and scored-token throughput (scoring emits no decode tokens, so
            # tokens_per_s above stays a generation metric)
            "score_requests": self.stats["score_requests"],
            "score_tokens": self.stats["score_tokens"],
            "score_latency_s": self._score_lat_sum,
            "score_latency_avg_s": (self._score_lat_sum /
                                    max(self.stats["score_requests"], 1)),
            "score_tokens_per_s": (self.stats["score_tokens"] / wall
                                   if wall else 0.0),
            # per-layer weight bitwidths from the build-time budget search
            # (zeros when weight_budget_mb == 0)
            "weight_bits_min": (min(self.weight_bits.values())
                                if self.weight_bits else 0),
            "weight_bits_max": (max(self.weight_bits.values())
                                if self.weight_bits else 0),
            "weight_bits_avg": (sum(self.weight_bits.values())
                                / len(self.weight_bits)
                                if self.weight_bits else 0.0),
            # speculative decoding (zeros with spec=None): acceptance rate
            # over proposed draft tokens, mean emitted tokens per verified
            # lane-round (the >1 decode-speedup signal), and the draft's
            # weight+cache memory bill
            "spec_rounds": self.stats["spec_rounds"],
            "spec_accept_rate": (self.stats["spec_accepted"] /
                                 max(self.stats["spec_proposed"], 1)),
            "spec_tokens_per_step": (self.stats["spec_emitted"] /
                                     max(self.stats["spec_lane_rounds"], 1)),
            "spec_draft_nbytes": (self.draft.nbytes()
                                  if self.draft is not None else 0),
            # lane rebuild split: pool-gather bootstraps (self-drafts) vs
            # dense prefills (re-quantized / truncated drafts, fallback)
            "spec_draft_prefills": (self.draft.prefills
                                    if self.draft is not None else 0),
            "spec_draft_bootstraps": (self.draft.bootstraps
                                      if self.draft is not None else 0),
            # SSM state pool (hybrid patterns; zeros otherwise): slot
            # occupancy and the INT8 pool's allocated bytes
            "state_slots": (self.state_alloc.num_slots
                            if self.state_alloc else 0),
            "state_slots_active": (self.state_alloc.num_active
                                   if self.state_alloc else 0),
            "state_slot_util": (self.state_alloc.utilization
                                if self.state_alloc else 0.0),
            "state_pool_nbytes": state_pool_nbytes(self.spool),
        }
        # latency percentiles from the always-on histograms: TTFT / TPOT /
        # queue wait / step wall / scoring latency p50/p90/p99 (+ counts).
        # The legacy ttft_avg_s / ttft_max_s keys above keep their
        # finished-request definitions; these add the distribution view
        out.update(self.mreg.summary(SERVING_HISTS))
        return out

    def debug_snapshot(self) -> Dict[str, Any]:
        """JSON-serializable postmortem dump of the scheduler's resident
        state: the block allocator (per-block state/refcount/key, free-list
        depth, prefix-index chains), the per-slot runs and block tables,
        and the SSM state pool.  Read-only; see docs/OBSERVABILITY.md."""
        slots = []
        for s, run in enumerate(self.slots):
            if run is None:
                slots.append(None)
                continue
            row = self.block_tables[s]
            slots.append({
                "slot": s, "uid": run.req.uid, "state": run.state,
                "ctx": int(run.ctx), "priority": int(run.priority),
                "published_upto": int(run.published_upto),
                "generated": len(run.req.generated or ()),
                "blocks": [int(b) for b in row if b != self.trash],
            })
        snap = {
            "alloc": self.alloc.debug_snapshot(),
            "slots": slots,
            "waiting": [{"uid": r.req.uid,
                         "prompt": int(r.target.shape[-1]),
                         "priority": int(r.priority)} for r in self.waiting],
            "stats": dict(self.stats),
        }
        if self.state_alloc is not None:
            snap["state_pool"] = self.state_alloc.debug_snapshot()
            snap["state_snaps"] = [k.hex() for k in self._state_snaps]
        return snap

    # -- admission / scheduling ----------------------------------------------
    def _eff_priority(self, run: _Run) -> int:
        """Effective priority of a waiting request: the submitted priority
        plus one point per ``priority_age_steps`` scheduler steps spent in
        the queue, so sustained high-priority load cannot starve low-priority
        requests forever (an SLA-style aging ramp; 0 disables it)."""
        age = self.scfg.priority_age_steps
        if not age:
            return run.priority
        return run.priority + (self.stats["steps"] - run.step_enqueued) // age

    def _admit(self) -> None:
        free = [s for s in range(self.scfg.max_batch) if self.slots[s] is None]
        if not free or not self.waiting:
            return
        # priority-aware: highest effective priority first, FCFS (arrival
        # order) within; aging (see _eff_priority) lifts long-waiting
        # low-priority requests above fresher high-priority arrivals
        self.waiting = deque(sorted(self.waiting,
                                    key=lambda r: (-self._eff_priority(r),
                                                   r.order)))
        while free and self.waiting:
            run = self.waiting[0]
            if self.state_alloc is not None:
                got = self.state_alloc.alloc()
                if got is None:
                    return               # state pool dry: stop admitting
                run.state_slot = got
            self.waiting.popleft()
            # the aged priority sticks: once admitted, preemption-victim
            # selection must not see the stale submitted value, or the aged
            # request would be evicted right back out.  The absorbed age is
            # consumed — step_enqueued resets so a preempt/re-admit cycle
            # cannot re-add the same wait twice and ratchet the request
            # above genuinely higher-priority traffic.
            run.priority = self._eff_priority(run)
            run.step_enqueued = self.stats["steps"]
            slot = free.pop(0)
            run.slot = slot
            self.block_tables[slot, :] = self.trash
            self.slots[slot] = run
            waited = clock() - run.t_add
            self.mreg.observe("queue_wait", waited)
            self.trace.event("admit", track=self.track, lane=slot,
                             uid=run.req.uid, waited_ms=1e3 * waited)
            self._match_prefix(slot, run)

    def _match_cap(self, run: _Run) -> int:
        """Most prefix tokens a cache match may cover.  Generating requests
        stop one short of the target (the final chunk's logits seed the
        first sampled token); scoring requests stop one short of
        ``score_from`` — every score token's predecessor row must actually
        be *computed* by a chunk, or its logprob would never materialize."""
        if run.score_from >= 0:
            return run.score_from - 1
        return int(run.target.shape[-1]) - 1

    def _match_prefix(self, slot: int, run: _Run) -> None:
        """Map the longest indexed chain of ``run.target``'s full blocks into
        the block table and start ``ctx`` past them.  The match is capped one
        token short of the target so the final chunk always runs (its logits
        seed the first sampled token), and stays within one scale tag so
        every shared block dequantizes with the restored donor scales."""
        run.ctx = 0
        run.published_upto = 0
        run.scale_tag = None
        run.snapshot = None
        run.chain = []
        self.stats["prefix_query_tokens"] += int(run.target.shape[-1])
        if not self._prefix_on:
            return
        bs = self.scfg.block_size
        run.chain = _prefix_keys(run.target, bs)
        limit = min(len(run.chain), self._match_cap(run) // bs,
                    self.scfg.max_blocks_per_req)
        matched: List[int] = []
        tag, meta = None, None
        for j in range(limit):
            e = self.alloc.lookup(run.chain[j])
            if e is None or (tag is not None and e.tag != tag):
                break
            if tag is None:
                tag, meta = e.tag, e.meta
            if e.bits != 8:
                b = self._promote_entry(run.chain[j], e)
                if b is None:
                    break              # pool too tight to lift the demoted
                matched.append(b)      # promote() hands over the reference
            else:
                matched.append(self.alloc.acquire(run.chain[j]))
        if self._has_ssm and matched:
            # state-aware: the match must end at a boundary whose SSM state
            # was snapshotted, or the restored KV would pair with a state
            # computed over a different prefix
            keep = 0
            for j in range(len(matched)):
                if run.chain[j] in self._state_snaps:
                    keep = j + 1
            for b in matched[keep:]:
                self.alloc.decref(b)
            matched = matched[:keep]
        if matched:
            for j, b in enumerate(matched):
                self.block_tables[slot, j] = b
            run.ctx = len(matched) * bs
            run.published_upto = len(matched)
            run.scale_tag = tag
            run.snapshot = meta
            if meta is not None:
                self.pool = restore_slot_scales(self.pool, slot, meta)
            if self._has_ssm:
                self._restore_state_snap(run, run.chain[len(matched) - 1])
        # sub-block reuse needs no state (attention-only): no SSM state
        # exists at a mid-block boundary, so hybrid configs skip it
        part = (self._match_partial(slot, run, tag)
                if self.scfg.partial_prefix and not self._has_ssm else 0)
        if not matched and not part:
            return
        self.stats["prefix_hits"] += 1
        self.stats["prefix_hit_tokens"] += run.ctx
        self.trace.event("partial_hit" if part else "prefix_hit",
                         track=self.track, lane=slot, uid=run.req.uid,
                         tokens=int(run.ctx))

    def _match_partial(self, slot: int, run: _Run, tag) -> int:
        """Sub-block prefix reuse after the full-block chain match.

        The first unmatched block position is checked against every published
        block with the same chain parent; the donor with the longest common
        token run is device-copied into a fresh *private* block (the donor
        stays immutable and shared), the copy becomes the request's first
        writable block, and ``ctx`` starts mid-block past the copied tokens.
        The donor's frozen scales are adopted when no full block matched (the
        copied int8 codes only dequantize correctly under the donor's
        affine); with a full-chain match the donor must carry the same scale
        tag.  Returns the number of partially-matched tokens."""
        bs = self.scfg.block_size
        j = run.ctx // bs                      # first unmatched block index
        if j >= self.scfg.max_blocks_per_req:
            return 0
        # cap one token short of the target (or of score_from, in scoring
        # mode) so the chunks that must produce logits always run
        avail = min(self._match_cap(run) - j * bs, bs)
        if avail <= 0:
            return 0
        parent = run.chain[j - 1] if j else b""
        blk = np.asarray(run.target[..., j * bs:(j + 1) * bs], np.int32)
        got = self.alloc.alloc(1)              # before scanning: alloc may
        if got is None:                        # LRU-evict a candidate donor
            return 0
        best, best_r = None, 0
        for e in self.alloc.children_of(parent):
            if e.tokens is None or (tag is not None and e.tag != tag):
                continue
            if e.bits != 8:
                continue          # demoted donor: its block holds packed
                                  # nibbles a plain CoW copy cannot read
            width = min(e.tokens.shape[-1], blk.shape[-1], avail)
            neq = (e.tokens[..., :width] != blk[..., :width])
            neq = neq.reshape(-1, width).any(axis=0)
            r = int(np.argmax(neq)) if neq.any() else width
            if r > best_r:
                best, best_r = e, r
        if best is None or best_r < max(1, self.scfg.partial_min_tokens):
            self.alloc.decref(got[0])          # unpublished active -> FREE
            return 0
        self.pool = self._cow_fn(self.pool, jnp.int32(best.block),
                                 jnp.int32(got[0]))
        self.block_tables[slot, j] = got[0]
        run.ctx = j * bs + best_r
        if run.scale_tag is None:              # no full match: adopt donor
            run.scale_tag = best.tag
            run.snapshot = best.meta
            if best.meta is not None:
                self.pool = restore_slot_scales(self.pool, slot, best.meta)
        self.stats["prefix_partial_tokens"] += best_r
        return best_r

    # -- bit ladder / state snapshots -----------------------------------------
    def _logical_blocks(self) -> int:
        """Logical blocks resident right now: live + cached int8 blocks plus
        demoted entries surviving as packed halves.  With the ladder on this
        can exceed ``num_blocks`` — that surplus is the capacity win."""
        a = self.alloc
        return a.num_used + a.num_cached + a.int4_blocks

    def _maybe_demote(self) -> None:
        """Pressure valve: while the free list sits below the watermark, fold
        the two LRU-oldest CACHED prefix blocks into one packed-int4 block
        (freeing the other).  Host bookkeeping and the device rewrite move
        together; packed blocks never enter a block table, so no kernel ever
        reads nibbles the promote path hasn't unpacked first."""
        floor = self.scfg.ladder_watermark * self.scfg.num_blocks
        while self.alloc.num_free < floor:
            pair = self.alloc.demote_oldest_pair()
            if pair is None:
                break                 # < 2 cached blocks: nothing demotable
            _key_a, _key_b, src_a, src_b, dst = pair
            self.pool = demote_pair_blocks(self.pool, jnp.int32(src_a),
                                           jnp.int32(src_b), jnp.int32(dst))
            self.trace.event("demote", track=self.track,
                             src_a=int(src_a), src_b=int(src_b),
                             dst=int(dst))
        if self._has_ssm:
            self._demote_old_snaps()

    def _promote_entry(self, key: bytes, e) -> Optional[int]:
        """Lift a ladder-demoted prefix entry back onto a fresh int8 block
        before the matcher maps it.  The packed source is excluded from the
        allocation so eviction cannot recycle the bytes being read.  Returns
        the promoted block — ACTIVE at ref 1, the caller now holds that
        reference (do NOT acquire again) — or None when the pool has no
        block to give (the match just ends here)."""
        got = self.alloc.alloc(1, exclude=(e.block,))
        if got is None:
            return None
        src, half = self.alloc.promote(key, got[0])
        self.pool = promote_block(self.pool, jnp.int32(src), jnp.int32(half),
                                  jnp.int32(got[0]))
        self.trace.event("promote", track=self.track, src=int(src),
                         dst=int(got[0]))
        return got[0]

    def _store_state_snap(self, key: bytes, slot: int) -> None:
        """Capture the state-slot rows at a published block boundary (hybrid
        prefix sharing), bounded by an LRU cap."""
        if key in self._state_snaps:
            self._state_snaps.move_to_end(key)
            return
        self._state_snaps[key] = snapshot_state_slot(self.spool, slot)
        while len(self._state_snaps) > max(self.scfg.state_snap_cap, 1):
            self._state_snaps.popitem(last=False)
        if self.scfg.ladder:
            self._demote_old_snaps()

    def _demote_old_snaps(self) -> None:
        """Ladder the snapshot shelf: every snapshot older than the
        ``state_snap_hot`` newest gets its SSD codes demoted to packed int4
        (same code-space requant as the block ladder; conv/scales stay)."""
        if not self.scfg.ladder:
            return
        hot = max(self.scfg.state_snap_hot, 0)
        cold = list(self._state_snaps)[:max(len(self._state_snaps) - hot, 0)]
        for key in cold:
            snap = self._state_snaps[key]
            if not any("ssd_vals" in lv for lv in snap.values()):
                continue              # already demoted
            self._state_snaps[key] = {
                pk: self._demote_snap_entry(lv) for pk, lv in snap.items()}
            self.stats["snap_demotions"] += 1

    @staticmethod
    def _demote_snap_entry(leaves: Dict[str, Any]) -> Dict[str, Any]:
        if "ssd_vals" not in leaves:
            return leaves
        out = {n: l for n, l in leaves.items() if n != "ssd_vals"}
        out["ssd_vals4"] = demote_codes(leaves["ssd_vals"])
        return out

    def _restore_state_snap(self, run: _Run, key: bytes) -> None:
        """Adopt the donor's exact quantized SSM state for a hybrid prefix
        hit.  A ladder-demoted snapshot is promoted back to the int8 pool
        layout first (bounded code-space error, divergence-gated)."""
        snap = self._state_snaps[key]
        self._state_snaps.move_to_end(key)
        restored: Dict[str, Any] = {}
        promoted = False
        for pkey, leaves in snap.items():
            if "ssd_vals4" in leaves and "ssd_vals4" not in self.spool[pkey]:
                leaves = {n: l for n, l in leaves.items() if n != "ssd_vals4"}
                leaves["ssd_vals"] = promote_codes_full(snap[pkey]["ssd_vals4"])
                promoted = True
            restored[pkey] = leaves
        if promoted:
            self.stats["snap_promotions"] += 1
        self.spool = restore_state_slot(self.spool, run.state_slot, restored)
        self.stats["state_prefix_hits"] += 1

    def _schedule_decode(self) -> List[int]:
        """Ensure every decoding slot has a writable block for its next
        token, preempting the lowest-priority/youngest request when the pool
        is dry and copy-on-writing shared tail blocks."""
        order = sorted((s for s, r in enumerate(self.slots)
                        if r is not None and r.state == "decode"),
                       key=lambda s: (-self.slots[s].priority,
                                      self.slots[s].order))
        out = []
        for s in order:
            run = self.slots[s]
            if run is None or run.state != "decode":
                continue                    # preempted by an earlier lap
            bi = run.ctx // self.scfg.block_size
            if run.ctx % self.scfg.block_size == 0 and \
                    self.block_tables[s, bi] == self.trash:
                got = self._alloc_or_preempt(1, protect=s)
                if got is None:             # s itself was the victim
                    continue
                self.block_tables[s, bi] = got[0]
            elif not self._ensure_writable(s, bi):
                continue                    # CoW failed: s was preempted
            out.append(s)
        return out

    def _queue_age(self, run: _Run) -> int:
        """Scheduler steps since ``add_request`` — the TTFT-pressure clock.
        Unlike the aging clock (``step_enqueued``) this is never reset: a
        preempted request is still late from the caller's point of view."""
        return self.stats["steps"] - run.step_added

    def _schedule_prefill(self, n_decode: int):
        """Pick the prefilling request for this step's chunk and size the
        chunk under the token budget and block availability.

        Default pick: highest priority, then oldest (FCFS).  With
        ``ttft_target_steps`` set, a request whose queue age crossed the
        target takes the turn instead — shortest-remaining-prefill-first
        among the overdue, so the late request closest to emitting its first
        token wins, then yields back.  While *other* requests (prefilling or
        still queued) are overdue, the chunk budget shrinks to ``ttft_chunk``
        so one big chunk cannot delay the next scheduling decision by a full
        ``prefill_chunk`` of compute.  -> (slot, ctx, c, c_pad)"""
        cand = sorted((s for s, r in enumerate(self.slots)
                       if r is not None and r.state == "prefill"),
                      key=lambda s: (-self.slots[s].priority,
                                     self.slots[s].order))
        if not cand:
            return None
        s = cand[0]
        shrink = False
        tgt = self.scfg.ttft_target_steps
        if tgt:
            overdue = [c_ for c_ in cand
                       if self._queue_age(self.slots[c_]) >= tgt]
            if overdue:
                s = min(overdue, key=lambda c_: (
                    int(self.slots[c_].target.shape[-1]) - self.slots[c_].ctx,
                    -self.slots[c_].priority, self.slots[c_].order))
            shrink = (any(c_ != s for c_ in overdue) or
                      any(self._queue_age(r) >= tgt for r in self.waiting))
        run = self.slots[s]
        remaining = run.target.shape[-1] - run.ctx
        budget = self.scfg.token_budget - n_decode
        if n_decode and budget <= 0:
            return None                     # decodes ate the whole budget
        # honor the budget even on prefill-only steps (clamped to >= 1 so a
        # degenerate token_budget cannot deadlock the queue)
        c = min(remaining, self.scfg.prefill_chunk, max(budget, 1))
        if shrink:
            c = min(c, max(self.scfg.ttft_chunk, 1))
        c = self._fit_chunk_blocks(s, run, c, allow_preempt=(n_decode == 0))
        if c <= 0:
            return None
        c_pad = _chunk_bucket(c, self.scfg.prefill_chunk)
        return (s, run.ctx, c, c_pad)

    # -- speculative decoding -------------------------------------------------
    def _schedule_spec(self, dec_slots: List[int]) -> Dict[int, int]:
        """Size each decode lane's verify span: 1..gamma+1 tokens.

        ``_schedule_decode`` already guaranteed a writable block for each
        lane's next token; the extra speculative positions are opportunistic
        — backed by plain allocation, *never* by preemption (evicting live
        work to speculate would be a net loss), and the span shrinks to what
        the pool can cover.  Hot-sampled lanes verify exactly one token
        (greedy acceptance is only lossless for greedy lanes), which makes
        their round identical to plain decode."""
        g1 = self.spec.gamma + 1
        t = self.scfg.block_size
        vlens: Dict[int, int] = {}
        for s in dec_slots:
            run = self.slots[s]
            remaining = run.req.max_new_tokens - len(run.req.generated)
            want = 1 if run.req.temperature > 0 else \
                max(1, min(g1, remaining))
            lo, hi = run.ctx // t, (run.ctx + want - 1) // t
            for bi in range(lo + 1, hi + 1):
                if bi >= self.scfg.max_blocks_per_req:
                    want = min(want, bi * t - run.ctx)     # row exhausted
                    break
                if self.block_tables[s, bi] != self.trash:
                    continue                               # already backed
                got = self.alloc.alloc(1)
                if got is None:
                    want = min(want, bi * t - run.ctx)     # pool dry: shrink
                    break
                self.block_tables[s, bi] = got[0]
            vlens[s] = max(want, 1)
        return vlens

    def _propose_drafts(self, dec_slots: List[int],
                        vlens: Dict[int, int]) -> np.ndarray:
        """Align each speculating lane's draft cache with the target context
        and run one batched gamma-token proposal.  Lanes pinned to a 1-token
        span (hot-sampled) never consume their proposals, so they get no
        draft lane at all — no sequence rebuild, no dense draft prefill."""
        spec_slots = [s for s in dec_slots if vlens[s] > 1]
        pending: Dict[int, int] = {}
        for s in spec_slots:
            run = self.slots[s]
            if not self.draft.aligned(s, run.ctx):
                # misaligned lanes (fresh admission, preemption resume):
                # self-drafts rebuild by dequantizing the slot's pool blocks
                # (one gather); everything else pays the O(ctx) sequence
                # rebuild + dense prefill
                if not self.draft.ensure_from_pool(
                        s, self.pool, self.block_tables[s], run.ctx):
                    seq = _with_generated(np.asarray(run.req.prompt),
                                          run.req.generated)
                    self.draft.ensure(s, seq, run.ctx)
            pending[s] = run.pending
        return self.draft.propose(spec_slots, pending)

    def _consume_spec(self, dec_slots: List[int], vlens: Dict[int, int],
                      drafts: np.ndarray, ver_logits) -> None:
        """Accept the longest matching draft prefix per lane and emit.

        Position 0's logits are what plain decode would have produced for
        the pending token, so its argmax (or temperature sample, for hot
        lanes) is always emitted; draft token j is accepted iff it equals
        the target's choice at position j, unlocking position j+1's logits.
        Rejected tail positions are rolled back: ``ctx`` simply stops at the
        accepted boundary and ``rewind_tail`` releases block-table tail
        blocks past it (CoW-safe decref; conservation property-tested)."""
        temps = np.zeros((self.scfg.max_batch,), np.float32)
        for s in dec_slots:
            temps[s] = self.slots[s].req.temperature
        first = self._fetch(self._sample(ver_logits[:, 0], temps))
        greedy = self._fetch(jnp.argmax(ver_logits, axis=-1))   # (B, G)
        self.stats["decode_steps"] += 1
        self.stats["spec_rounds"] += 1
        for s in dec_slots:
            run = self.slots[s]
            v = vlens[s]
            emits = [first[s].tolist()]
            k = 0                          # accepted draft tokens
            while k < v - 1 and int(drafts[s, k]) == emits[-1]:
                k += 1
                emits.append(int(greedy[s, k]))
            self.stats["spec_lane_rounds"] += 1
            self.stats["spec_proposed"] += v - 1
            finished = False
            emitted = 0
            for tok in emits:
                run.ctx += 1
                run.pending = tok
                self._emit(run, tok, first=False)
                emitted += 1
                self.stats["decode_tokens"] += 1
                if self._stopped(run, tok):
                    self._finish(s)        # frees the whole row (and blocks
                    finished = True        # written past the stop point)
                    break
            # counted after the loop: an EOS/budget stop discards the rest of
            # the accepted chain, and the tokens-per-step / acceptance
            # metrics must reflect tokens actually emitted
            self.stats["spec_accepted"] += emitted - 1
            self.stats["spec_emitted"] += emitted
            if finished:
                continue
            rewind_tail(self.alloc, self.block_tables[s], run.ctx,
                        block_size=self.scfg.block_size, trash=self.trash)
            self.draft.commit(s, run.ctx)

    def _fit_chunk_blocks(self, s: int, run: _Run, c: int,
                          allow_preempt: bool) -> int:
        """Shrink ``c`` to what the pool can back, allocating blocks for the
        chunk's span.  With ``allow_preempt`` (nothing else is running this
        step) the lowest-priority/youngest other request is evicted to make
        room."""
        t = self.scfg.block_size
        while True:
            partial_room = (t - run.ctx % t) % t    # space in current block
            cap = partial_room + self.alloc.num_available * t
            c_fit = min(c, cap)
            if c_fit > 0:
                lo = run.ctx // t
                if run.ctx % t != 0 and not self._ensure_writable(s, lo):
                    return 0                # CoW failed: s was preempted
                hi = (run.ctx + c_fit + t - 1) // t
                need = [i for i in range(lo, hi)
                        if self.block_tables[s, i] == self.trash]
                got = self.alloc.alloc(len(need))
                assert got is not None
                for i, blk in zip(need, got):
                    self.block_tables[s, i] = blk
                return c_fit
            if not allow_preempt:
                return 0
            victims = [(r.priority, -r.order, v)
                       for v, r in enumerate(self.slots)
                       if r is not None and v != s]
            if not victims:
                raise RuntimeError(
                    f"paged cache pool exhausted: request {run.req.uid} "
                    f"cannot obtain a block and nothing is left to preempt "
                    f"(num_blocks={self.scfg.num_blocks})")
            self._preempt(min(victims)[2])

    def _alloc_or_preempt(self, n: int, protect: int):
        """Allocate ``n`` blocks, preempting lowest-priority/youngest
        requests until it fits.  If the protected slot itself becomes the
        victim, return None and charge a ``failed_alloc``: any requests
        already evicted this call lost their work for nothing."""
        while True:
            got = self.alloc.alloc(n)
            if got is not None:
                return got
            victims = [(r.priority, -r.order, s)
                       for s, r in enumerate(self.slots) if r is not None]
            if not victims:
                raise RuntimeError("paged cache pool exhausted with no "
                                   "running requests to preempt")
            victim = min(victims)[2]
            self._preempt(victim)
            if victim == protect:
                self.stats["failed_alloc"] += 1
                return None

    def _ensure_writable(self, s: int, bi: int) -> bool:
        """Copy-on-write guard before appending into block-table entry
        ``(s, bi)``: a block that is shared (refcount > 1) or published
        (its codes are matchable cache content) must not be mutated, so the
        writer gets a private copy.  Returns False if the copy's allocation
        preempted ``s`` itself."""
        blk = int(self.block_tables[s, bi])
        if blk == self.trash:
            return True
        if not (self.alloc.is_shared(blk) or self.alloc.is_published(blk)):
            return True
        got = self._alloc_or_preempt(1, protect=s)
        if got is None:
            return False
        self.pool = self._cow_fn(self.pool, jnp.int32(blk), jnp.int32(got[0]))
        self.alloc.decref(blk)
        self.block_tables[s, bi] = got[0]
        self.stats["cow_copies"] += 1
        self.trace.event("cow_copy", track=self.track, lane=s,
                         src=int(blk), dst=int(got[0]))
        return True

    def _preempt(self, s: int) -> None:
        """Evict slot ``s``: drop its block references and re-queue it for
        recompute (prefill over prompt + generated-so-far, vLLM recompute
        policy).  Published blocks survive as cached prefix entries, so the
        recompute usually re-matches them at re-admission."""
        run = self.slots[s]
        assert run is not None
        self._free_row(s)
        self._free_state_slot(run)         # recompute-on-resume, like KV
        if self.draft is not None:
            self.draft.invalidate(s)       # draft lane dies with the slot
        if run.pending is not None and run.req.generated:
            # cached sequence = prompt + generated[:-1]; the pending token is
            # generated[-1] and is re-fed through decode after the re-prefill
            run.target = _with_generated(np.asarray(run.req.prompt),
                                         run.req.generated[:-1])
            run.resume_pending = run.req.generated[-1]
        run.pending = None
        run.ctx = 0
        run.published_upto = 0
        run.state = "prefill"
        run.slot = -1
        self.slots[s] = None
        # aging clock restarts at re-queue: time spent *running* is not
        # waiting, and the wait before the first admission was already
        # absorbed into run.priority there
        run.step_enqueued = self.stats["steps"]
        self.waiting.appendleft(run)
        self.stats["preemptions"] += 1
        self.trace.event("preempt", track=self.track, lane=s,
                         uid=run.req.uid)

    def _free_row(self, s: int) -> None:
        row = self.block_tables[s]
        self.alloc.free([int(b) for b in row if b != self.trash])
        self.block_tables[s, :] = self.trash

    def _free_state_slot(self, run: _Run) -> None:
        if run.state_slot >= 0:
            self.state_alloc.free(run.state_slot)
            run.state_slot = -1

    # -- device-step plumbing --------------------------------------------------
    def _build_args(self, dec_slots: List[int], pf) -> Dict[str, Any]:
        b = self.scfg.max_batch
        m = self.scfg.max_blocks_per_req
        tok_shape = (b, self.cfg.n_codebooks) if self.cfg.n_codebooks else (b,)
        dec_toks = np.zeros(tok_shape, np.int32)
        dec_bt = np.full((b, m), self.trash, np.int32)
        dec_lens = np.zeros((b,), np.int32)
        # inactive decode lanes point at the state pool's trash slot so their
        # garbage state updates land harmlessly off to the side
        dec_sslots = np.full((b,), self.state_trash, np.int32)
        for s in dec_slots:
            run = self.slots[s]
            dec_toks[s] = run.pending
            dec_bt[s] = self.block_tables[s]
            dec_lens[s] = run.ctx
            if run.state_slot >= 0:
                dec_sslots[s] = run.state_slot
        if dec_slots:
            # the kernel attends each lane's ctx tokens plus the one it
            # appends
            self._count_kv_blocks(dec_lens, 1, self._decode_p)

        device = (jnp.asarray(dec_toks), jnp.asarray(dec_bt),
                  jnp.asarray(dec_lens), jnp.asarray(dec_sslots),
                  *self._build_pf_args(pf))
        # summed context of the decode lanes before this step's token
        return {"device": device, "ctx": int(dec_lens.sum())}

    def _count_kv_blocks(self, dec_lens: np.ndarray, appended,
                         p: int) -> None:
        """Count the blocks a decode or verify step's lanes attend to
        (``ceil((ctx + appended) / block_size)`` over the decoding lanes;
        idle lanes have ctx 0 and append nothing) against the block-table
        entries handed to the paged kernel, and the kernel steps that visit
        them when it scores ``p`` blocks a step (``ceil(live / p)`` a
        lane)."""
        bs = self.scfg.block_size
        live = np.where(dec_lens > 0, dec_lens + appended + bs - 1, 0) // bs
        self.stats["decode_kv_blocks_live"] += int(live.sum())
        self.stats["decode_kv_blocks_passed"] += \
            self.scfg.max_batch * self.scfg.max_blocks_per_req
        self.stats["decode_kv_steps_live"] += int((-(-live // p)).sum())

    def _build_pf_args(self, pf):
        """Device args for the prefill half of a fused step (shared by the
        plain and speculative step builders)."""
        m = self.scfg.max_blocks_per_req
        pf_sslot = self.state_trash
        if pf is not None:
            s, ctx, c, c_pad = pf
            run = self.slots[s]
            sl = run.target[..., ctx:ctx + c].astype(np.int32)
            pad = c_pad - c
            widths = [(0, 0)] * (sl.ndim - 1) + [(0, pad)]
            pf_toks = np.pad(sl, widths)[None]
            pf_slot, pf_row, pf_ctx, pf_len = s, self.block_tables[s], ctx, c
            if run.state_slot >= 0:
                pf_sslot = run.state_slot
        else:
            width = (1, self.cfg.n_codebooks, 16) if self.cfg.n_codebooks \
                else (1, 16)
            pf_toks = np.zeros(width, np.int32)
            pf_slot, pf_ctx, pf_len = 0, 0, 0
            pf_row = np.full((m,), self.trash, np.int32)
        return (jnp.asarray(pf_toks), jnp.int32(pf_slot),
                jnp.asarray(pf_row, dtype=jnp.int32), jnp.int32(pf_ctx),
                jnp.int32(pf_len), jnp.int32(pf_sslot))

    def _build_spec_args(self, dec_slots: List[int], vlens: Dict[int, int],
                         drafts: np.ndarray, pf) -> Dict[str, Any]:
        """Device args for a speculative step: verify tokens are column 0 =
        pending, columns 1.. = draft proposals; lanes outside the round get
        vlen 0 (every verify write lands in the trash block)."""
        b, m = self.scfg.max_batch, self.scfg.max_blocks_per_req
        g1 = self.spec.gamma + 1
        dec_toks = np.zeros((b, g1), np.int32)
        dec_bt = np.full((b, m), self.trash, np.int32)
        dec_lens = np.zeros((b,), np.int32)
        dec_vlens = np.zeros((b,), np.int32)
        for s in dec_slots:
            run = self.slots[s]
            dec_toks[s, 0] = run.pending
            dec_toks[s, 1:] = drafts[s, :g1 - 1]
            dec_bt[s] = self.block_tables[s]
            dec_lens[s] = run.ctx
            dec_vlens[s] = vlens[s]
        # the verify kernels walk one block a step
        self._count_kv_blocks(dec_lens, dec_vlens, 1)
        device = (jnp.asarray(dec_toks), jnp.asarray(dec_bt),
                  jnp.asarray(dec_lens), jnp.asarray(dec_vlens),
                  *self._build_pf_args(pf))
        return {"device": device}

    # -- sampling / retirement -------------------------------------------------
    def _sample(self, logits, temps: np.ndarray):
        """Greedy/temperature sampling per batch row (shared with the dense
        engine — see engine.sample_tokens for the RNG/EMA contract)."""
        from repro.serving.engine import sample_tokens
        toks, self._rng, self.scale_state = sample_tokens(
            logits, temps, self._rng, self.scale_state, self.scfg.ema_alpha)
        return toks

    def _emit(self, run: _Run, tok, first: bool) -> None:
        req = run.req
        req.generated.append(tok)
        now = clock()
        if first:
            req.ttft_s = now - run.t_add
            self.stats["first_tokens"] += 1
            self.mreg.observe("ttft", req.ttft_s)
            self.trace.event("first_token", track=self.track, lane=run.slot,
                             uid=req.uid)
        elif run.t_last_tok is not None:
            # TPOT: inter-token gap per request (a preempted request's gap
            # spans its whole recompute — by design, that IS the stall the
            # caller observed)
            self.mreg.observe("tpot", now - run.t_last_tok)
        run.t_last_tok = now
        if req.on_token is not None:
            req.on_token(req, tok)

    def _consume_decode(self, dec_slots: List[int], dec_logits) -> None:
        temps = np.zeros((self.scfg.max_batch,), np.float32)
        for s in dec_slots:
            temps[s] = self.slots[s].req.temperature
        toks_np = self._fetch(self._sample(dec_logits, temps))
        self.stats["decode_steps"] += 1
        for s in dec_slots:
            run = self.slots[s]
            tok = toks_np[s].tolist()
            run.ctx += 1
            run.pending = tok
            self._emit(run, tok, first=False)
            self.stats["decode_tokens"] += 1
            if self._stopped(run, tok):
                self._finish(s)

    def _consume_prefill(self, pf, pf_logits) -> None:
        s, ctx, c, _ = pf
        run = self.slots[s]
        if ctx == 0:
            # this chunk froze a fresh per-slot K affine on device: new scale
            # epoch; any blocks published from here carry the new snapshot
            self._scale_tag += 1
            run.scale_tag = self._scale_tag
            run.snapshot = None
        run.ctx += c
        self.stats["prefill_tokens"] += c
        self.stats["prefill_chunks"] += 1
        if run.score_from >= 0:
            self._score_chunk(run, ctx, c, pf_logits)
        with self.trace.span("publish", track=self.track,
                             step=self._consuming):
            self._publish_full_blocks(s, run)
        if run.ctx < run.target.shape[-1]:
            return                             # more chunks to go
        if run.score_from >= 0:
            self._finish_score(s, run)
            return
        run.state = "decode"
        if run.resume_pending is not None:     # recompute after preemption:
            run.pending = run.resume_pending   # re-feed the in-flight token
            run.resume_pending = None
            self.trace.event("resume", track=self.track, lane=s,
                             uid=run.req.uid)
            return
        temps = np.asarray([run.req.temperature], np.float32)
        tok = self._fetch(self._sample(pf_logits, temps))[0].tolist()
        run.pending = tok
        self._emit(run, tok, first=True)
        if self._stopped(run, tok):
            self._finish(s)

    def _score_chunk(self, run: _Run, ctx: int, c: int, pf_logits) -> None:
        """Teacher-forced scoring of one consumed chunk.

        The chunk covered absolute positions ``[ctx, ctx + c)``; its logits
        row ``r`` sits at position ``ctx + r`` and predicts the target token
        at ``ctx + r + 1``.  Every score-range token whose predecessor row
        lives in this chunk gets its logprob recorded — keyed by absolute
        position, so a preemption's re-prefill simply overwrites the same
        entries (restored donor scales make the recompute deterministic)."""
        s_len = int(run.target.shape[-1])
        t_lo = max(ctx + 1, run.score_from)
        t_hi = min(ctx + c, s_len - 1)         # inclusive
        if t_hi < t_lo:
            return
        rows = self._fetch(pf_logits)[0, t_lo - 1 - ctx:t_hi - ctx]
        golds = np.asarray(run.target[..., t_lo:t_hi + 1])
        from repro.eval.scoring import gold_logprobs
        lps = gold_logprobs(rows, golds)
        for i, t in enumerate(range(t_lo, t_hi + 1)):
            run.score_lps[t] = float(lps[i])

    def _finish_score(self, s: int, run: _Run) -> None:
        """Retire a fully-prefilled scoring request: assemble the per-token
        logprob list (one entry per score token, in order) and finish the
        slot without sampling."""
        s_len = int(run.target.shape[-1])
        run.req.score_logprobs = [run.score_lps[t]
                                  for t in range(run.score_from, s_len)]
        run.req.score_s = clock() - run.t_add
        self.stats["score_requests"] += 1
        self.stats["score_tokens"] += s_len - run.score_from
        self._score_lat_sum += run.req.score_s
        self.mreg.observe("score_latency", run.req.score_s)
        self._finish(s)

    def _publish_full_blocks(self, s: int, run: _Run) -> None:
        """Index every newly-completed full block of the prefill target.
        Blocks are immutable from here on (writes CoW away), so a future
        request with the same token prefix can map them directly."""
        if not self._prefix_on:
            return
        full = min(run.ctx // self.scfg.block_size, len(run.chain))
        # hybrid: a chunk that lands exactly on a published block boundary is
        # the only moment the slot's SSM state equals "the state after those
        # full blocks" — snapshot it so a later prompt can adopt both
        if self._has_ssm and full > 0 and run.ctx == full * self.scfg.block_size:
            self._store_state_snap(run.chain[full - 1], run.state_slot)
        if full <= run.published_upto:
            return
        if run.snapshot is None:
            run.snapshot = snapshot_slot_scales(self.pool, s)
        bs = self.scfg.block_size
        for j in range(run.published_upto, full):
            tokens = np.asarray(run.target[..., j * bs:(j + 1) * bs], np.int32)
            self.alloc.publish(int(self.block_tables[s, j]), run.chain[j],
                               run.scale_tag, run.snapshot,
                               parent=run.chain[j - 1] if j else b"",
                               tokens=tokens)
        run.published_upto = full

    def _stopped(self, run: _Run, tok) -> bool:
        if len(run.req.generated) >= run.req.max_new_tokens:
            return True
        from repro.serving.engine import eos_hit
        return eos_hit(tok, self.scfg.eos_id)

    def _finish(self, s: int) -> None:
        run = self.slots[s]
        run.req.done = True
        self.trace.event("finish", track=self.track, lane=s,
                         uid=run.req.uid,
                         generated=len(run.req.generated or ()))
        self.finished.append(run.req)
        self._free_row(s)
        self._free_state_slot(run)
        if self.draft is not None:
            self.draft.invalidate(s)
        self.slots[s] = None


def _with_generated(prompt: np.ndarray, gen: list) -> np.ndarray:
    """prompt (S,) or (K,S) ++ generated tokens -> the recompute target."""
    if not gen:
        return prompt
    g = np.asarray(gen, dtype=prompt.dtype)
    if prompt.ndim == 2:                       # MusicGen: gen rows are (K,)
        g = g.T
    return np.concatenate([prompt, g], axis=-1)
