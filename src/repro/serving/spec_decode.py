"""Self-speculative quantized decoding: low-bit draft + paged multi-token verify.

The paper's runtime-bitwidth thesis says aggressive low-bit quantization buys
latency headroom with bounded accuracy loss; this module turns that headroom
into wall-clock decode speedup.  A cheaper **draft** of the same checkpoint —
re-quantized weight-only to a lower bitwidth through the existing
``core/methods`` registry, and/or truncated to the first ``draft_layers``
scan repeats — autoregressively proposes ``gamma`` tokens per request from
its own small dense KV state.  The INT8 **target** then verifies all
``gamma + 1`` positions in one batched pass through the paged block pool
(``models.transformer.forward_verify_paged``) and accepts the longest prefix
of draft tokens that matches its own greedy choices.

Greedy verification is *lossless*: every emitted token is the target's own
argmax at a cache state bit-identical to what plain one-token decode would
have produced (the verify forward reuses the exact decode append + attention
ops, position by position), so spec-decode output is token-for-token equal to
plain paged decode — golden-testable like PRs 1-4 — while emitting
``1 + accepted`` tokens per scheduler step instead of one.

The draft/target bitwidth pair is exactly the runtime bitwidth-assignment
knob LLMEasyQuant advertises (ABQ-LLM's arbitrary-bit inference and
FineQuant's weight-only low-bit results motivate INT4 drafts; see PAPERS.md).
``draft_bits=0`` shares the target's weights verbatim — the pure self-draft:
when the target itself serves W8A8 weights, that is the "INT8 self-draft".

Draft state lives in a per-slot **dense** KV cache (the draft's context is
bounded by the request capacity, so paging it would buy nothing).  The draft
lane index *is* the scheduler slot index: the proposer prefills a lane when
its slot's context diverges (`ensure`), advances it ``gamma + 1`` tokens per
round (the final feed ingests the last proposal so a fully-accepted round
leaves the lane aligned), and ``commit`` rewinds the lane length to the
accepted boundary — entries past it are dead weight overwritten by the next
round's appends, mirroring the block-pool tail rewind on the target side.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.apply import (QuantPolicy, dequantize_tree, quantize_tree,
                              tree_nbytes)
from repro.core.qtensor import QTensor
from repro.models.config import ModelConfig
from repro.models.transformer import forward_decode, forward_prefill
from repro.serving.kv_cache import cache_nbytes


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Speculative-decoding knobs (``SchedulerConfig.spec``).

    ``gamma`` draft tokens are proposed per request per scheduler step; the
    target verifies ``gamma + 1`` positions in one fused pass.  The draft is
    the target checkpoint itself, optionally truncated to the first
    ``draft_layers`` scan repeats (0 = all) and/or re-quantized weight-only
    to ``draft_bits`` (0 = share the target's weights verbatim) with
    ``draft_method`` from the ``core/methods`` registry.
    """

    gamma: int = 4
    draft_bits: int = 0                   # 0 = self-draft (share weights)
    draft_method: str = "symmetric"
    draft_layers: int = 0                 # 0 = all scan repeats

    def __post_init__(self):
        assert self.gamma >= 1, "spec decoding needs gamma >= 1"
        assert self.draft_bits in (0, 2, 3, 4, 8), self.draft_bits
        assert self.draft_layers >= 0, self.draft_layers


def spec_unsupported_reason(cfg: ModelConfig) -> Optional[str]:
    """Why ``cfg`` cannot run speculative decoding, or None.

    Same shape as ``scheduler.paged_unsupported_reason`` — a capability
    check, not a silent fallback."""
    if cfg.n_codebooks:
        return (f"multi-codebook decoding (n_codebooks={cfg.n_codebooks}) "
                "proposes per-codebook token tuples; the draft/verify accept "
                "rule is single-stream only")
    if any(s.mixer == "ssm" for s in cfg.layer_pattern):
        return ("SSM state is a running reduction — rejected speculative "
                "positions would need per-position state snapshots to rewind; "
                "serve hybrid configs with spec=None (plain paged decode)")
    if cfg.first_k_dense_replace:
        return ("the draft's dense cache is seeded from the scanned pool "
                "positions only; leading layers have no draft path")
    return None


def ensure_spec_supported(cfg: ModelConfig) -> None:
    reason = spec_unsupported_reason(cfg)
    if reason is not None:
        raise NotImplementedError(
            f"speculative decoding does not support {cfg.name}: {reason}")


# ---------------------------------------------------------------------------
# Draft construction (truncate + re-quantize through the methods registry)
# ---------------------------------------------------------------------------

def _has_qtensor(tree) -> bool:
    return any(isinstance(l, QTensor) for l in jax.tree_util.tree_leaves(
        tree, is_leaf=lambda l: isinstance(l, QTensor)))


def build_draft(params, cfg: ModelConfig, spec: SpecConfig):
    """-> (draft params, draft config).

    Truncation slices the leading scan-repeat axis of every stacked layer
    leaf (QTensor leaves slice through their registered pytree, so an
    already-quantized target truncates for free).  Re-quantization
    dequantizes a mixed tree first, then runs ``core.quantize_tree`` with a
    blanket ``bits_override`` — the same registry path static deployment
    uses, applied to the draft role.
    """
    dcfg, dparams = cfg, params
    if spec.draft_layers:
        if not 0 < spec.draft_layers <= cfg.n_repeats:
            raise ValueError(
                f"draft_layers={spec.draft_layers} out of range for "
                f"{cfg.name} (n_repeats={cfg.n_repeats})")
        dcfg = dataclasses.replace(
            cfg, name=f"{cfg.name}-draft",
            n_layers=spec.draft_layers * cfg.pattern_len)
        dparams = dict(params)
        dparams["layers"] = jax.tree_util.tree_map(
            lambda l: l[:spec.draft_layers], params["layers"])
    if spec.draft_bits:
        fp = dequantize_tree(dparams, dtype=jnp.dtype(cfg.param_dtype)) \
            if _has_qtensor(dparams) else dparams
        policy = QuantPolicy(method=spec.draft_method,
                             bits_override={"*": spec.draft_bits})
        dparams = quantize_tree(fp, policy)
    return dparams, dcfg


# ---------------------------------------------------------------------------
# Jitted draft fns — module-level caches keyed on the draft config, so every
# proposer instance (replicas, bench sweeps) shares one compilation
# ---------------------------------------------------------------------------

_DRAFT_FN_CACHE: Dict[Any, Any] = {}


def _propose_impl(params, cache, t0, *, cfg: ModelConfig, gamma: int):
    """gamma + 1 fused dense decode steps: feed ``t0`` and each greedy draft
    in turn.  The final feed produces no proposal — it ingests the last draft
    token's KV so a fully-accepted round leaves the cache aligned with the
    target (no catch-up step next round)."""
    drafts = []
    tok = t0
    for _ in range(gamma):
        logits, cache = forward_decode(params, tok, cache, cfg)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        drafts.append(tok)
    _, cache = forward_decode(params, tok, cache, cfg)     # ingest last draft
    return jnp.stack(drafts, axis=1), cache


def _propose_fn_for(dcfg: ModelConfig, gamma: int):
    key = ("propose", dcfg, gamma)
    fn = _DRAFT_FN_CACHE.get(key)
    if fn is None:
        fn = jax.jit(partial(_propose_impl, cfg=dcfg, gamma=gamma),
                     donate_argnums=(1,))
        _DRAFT_FN_CACHE[key] = fn
    return fn


def _prefill_fn_for(dcfg: ModelConfig, smax: int):
    key = ("prefill", dcfg, smax)
    fn = _DRAFT_FN_CACHE.get(key)
    if fn is None:
        fn = jax.jit(partial(forward_prefill, cfg=dcfg, smax=smax))
        _DRAFT_FN_CACHE[key] = fn
    return fn


def _insert(batch_cache, one_cache, slot):
    """Insert a B=1 draft cache into lane ``slot`` (same scatter the dense
    engine uses for its slot ring)."""
    def put(b_leaf, o_leaf):
        return jax.lax.dynamic_update_index_in_dim(b_leaf, o_leaf[:, 0],
                                                   slot, 1)
    entries = jax.tree_util.tree_map(put, batch_cache["entries"],
                                     one_cache["entries"])
    length = batch_cache["length"].at[slot].set(one_cache["length"][0])
    return {"entries": entries, "length": length}


def _insert_fn():
    key = ("insert",)
    fn = _DRAFT_FN_CACHE.get(key)
    if fn is None:
        fn = jax.jit(_insert, donate_argnums=(0,))
        _DRAFT_FN_CACHE[key] = fn
    return fn


def _init_batch_cache(one_cache, b: int):
    def alloc(leaf):
        return jnp.zeros((leaf.shape[0], b) + leaf.shape[2:], leaf.dtype)
    entries = jax.tree_util.tree_map(alloc, one_cache["entries"])
    return {"entries": entries, "length": jnp.zeros((b,), jnp.int32)}


def _bootstrap_impl(pool, block_row, slot, ctx, *, cfg: ModelConfig,
                    smax: int):
    """Build one lane's dense draft cache straight from the target's paged
    pool: gather + dequantize the slot's blocks (per-slot frozen K affine,
    per-token V scales), zero the positions past ``ctx`` (trash-block
    garbage), and re-quantize into the dense-cache layout the draft decodes
    against.  For a ``draft_bits=0`` self-draft the pool K/V *is* what the
    target attends to, so the lane starts at least as aligned as a fresh
    dense prefill — at the cost of one gather instead of an O(ctx) forward
    pass."""
    from repro.serving import kv_cache as kvc
    from repro.serving import paged_cache as pgc
    dt = jnp.dtype(cfg.compute_dtype)
    entries = {}
    for i in range(len(cfg.layer_pattern)):
        entry = pool[f"p{i}"]
        k, v = jax.vmap(                       # pool leaves carry a leading
            lambda e: pgc.gqa_gather_prefix(   # scan-repeat axis the paged
                e, block_row, slot, dt))(entry)  # gather is oblivious to
        mask = (jnp.arange(k.shape[1]) < ctx)[None, :, None, None]
        k = jnp.where(mask, k, 0)[:, :smax]
        v = jnp.where(mask, v, 0)[:, :smax]
        entries[f"p{i}"] = jax.vmap(
            lambda kk, vv: kvc.gqa_cache_entry(kk[None], vv[None], smax))(k, v)
    return {"entries": entries,
            "length": jnp.asarray(ctx, jnp.int32)[None]}


def _bootstrap_fn_for(dcfg: ModelConfig, smax: int):
    key = ("bootstrap", dcfg, smax)
    fn = _DRAFT_FN_CACHE.get(key)
    if fn is None:
        fn = jax.jit(partial(_bootstrap_impl, cfg=dcfg, smax=smax))
        _DRAFT_FN_CACHE[key] = fn
    return fn


class DraftProposer:
    """Per-slot draft state + batched gamma-token proposal.

    One lane per scheduler slot.  Host-side ``lens`` is the authoritative
    per-lane context length (written back to the device cache before every
    propose), so rewinding a lane after rejections is an O(1) host update —
    the dead entries past the accepted boundary are overwritten in place by
    the next round's appends, never read (the dense cache masks by length).
    """

    def __init__(self, params, cfg: ModelConfig, spec: SpecConfig, *,
                 max_batch: int, capacity: int, built=None, tracer=None,
                 trace_track: int = 0):
        """``built`` optionally injects another proposer's ``(dparams,
        dcfg)`` pair so N schedulers over the same checkpoint (replica
        fleets) share one draft weight tree instead of re-quantizing it per
        replica; lanes stay private per proposer and the injected tree is
        charged to its owner, not here.  ``tracer``/``trace_track``: the
        owning scheduler's tracer — lane rebuilds are the spec path's
        biggest host cost, so they get lifecycle events."""
        ensure_spec_supported(cfg)
        from repro.obs import NULL_TRACER
        self.trace = tracer if tracer is not None else NULL_TRACER
        self.track = int(trace_track)
        self.spec = spec
        self.gamma = spec.gamma
        self.dparams, self.dcfg = built if built is not None \
            else build_draft(params, cfg, spec)
        # a pure self-draft (no truncation, no re-quantization) shares the
        # target weight tree by reference, and an injected tree belongs to
        # the proposer that built it — either way the weights cost this
        # proposer nothing
        self.shares_weights = self.dparams is params or built is not None
        # propose appends up to gamma + 1 tokens past the request capacity's
        # final context; headroom keeps those scatters in bounds
        self.smax = capacity + spec.gamma + 1
        self.max_batch = max_batch
        self.lens = np.zeros((max_batch,), np.int32)
        self.valid = np.zeros((max_batch,), bool)
        self._cache = None
        self._propose = _propose_fn_for(self.dcfg, self.gamma)
        self._prefill = _prefill_fn_for(self.dcfg, self.smax)
        self._insert = _insert_fn()
        # A pure self-draft (full depth, shared weights) attends to exactly
        # the K/V the target holds in its block pool, so a misaligned lane
        # can be rebuilt by gathering + re-quantizing pool blocks instead of
        # re-running an O(ctx) dense prefill.  Pool entries only exist for
        # attn positions, hence the all-attn requirement (spec decode
        # already rejects SSM; MLA lanes still take the dense-prefill path).
        self.can_bootstrap = (
            spec.draft_bits == 0 and spec.draft_layers == 0
            and all(s.mixer == "attn" for s in cfg.layer_pattern))
        self._bootstrap = _bootstrap_fn_for(self.dcfg, self.smax) \
            if self.can_bootstrap else None
        self.prefills = 0                 # dense lane (re)builds, for metrics
        self.bootstraps = 0               # pool-gather lane rebuilds

    # -- lane lifecycle -------------------------------------------------------
    def aligned(self, slot: int, ctx: int) -> bool:
        """True when lane ``slot`` already mirrors a target context of
        ``ctx`` tokens — the common case, letting the caller skip building
        the full token sequence on the decode hot path."""
        return bool(self.valid[slot]) and int(self.lens[slot]) == ctx

    def ensure(self, slot: int, seq: np.ndarray, ctx: int) -> None:
        """Bring lane ``slot`` up to the target's cached context ``seq[:ctx]``
        (no-op when already aligned).  Misaligned lanes — fresh admissions,
        preemption resumes — pay one dense prefill."""
        if self.aligned(slot, ctx):
            return
        tokens = np.asarray(seq[..., :ctx], np.int32)
        s = int(tokens.shape[-1])
        # same power-of-two bucketing policy as the scheduler's prefill
        # chunks (bounded recompilation); late import avoids the cycle —
        # scheduler imports this module at load time
        from repro.serving.scheduler import _chunk_bucket
        bucket = _chunk_bucket(s, self.smax)
        # RIGHT-pad: positions 0..s-1 stay exact for the real prefix (the
        # engine's left-pad RoPE shift would skew every draft proposal); the
        # pad tail is ignored — the lane's length is pinned to ``s`` below
        toks = np.pad(tokens, (0, bucket - s))[None]
        _, one = self._prefill(self.dparams, jnp.asarray(toks))
        if self._cache is None:
            self._cache = _init_batch_cache(one, self.max_batch)
        self._cache = self._insert(self._cache, one, slot)
        self.lens[slot] = s
        self.valid[slot] = True
        self.prefills += 1
        self.trace.event("draft_prefill", track=self.track, lane=slot, ctx=s)

    def ensure_from_pool(self, slot: int, pool, block_row, ctx: int) -> bool:
        """Bootstrap lane ``slot`` to context ``ctx`` by dequantizing the
        target's pool blocks (PR 6 remainder) — no dense prefill, no token
        replay.  Returns False when this proposer cannot bootstrap (caller
        falls back to ``ensure``).  Only the draft's *acceptance rate* rides
        on lane content, never emitted tokens (greedy verify is lossless),
        and for a self-draft the pool is the best lane content available."""
        if self._bootstrap is None or ctx <= 0:
            return False
        if self.aligned(slot, ctx):
            return True
        one = self._bootstrap(pool, jnp.asarray(block_row, jnp.int32),
                              jnp.int32(slot), jnp.int32(ctx))
        if self._cache is None:
            self._cache = _init_batch_cache(one, self.max_batch)
        self._cache = self._insert(self._cache, one, slot)
        self.lens[slot] = int(ctx)
        self.valid[slot] = True
        self.bootstraps += 1
        self.trace.event("draft_bootstrap", track=self.track, lane=slot,
                         ctx=int(ctx))
        return True

    def invalidate(self, slot: int) -> None:
        """Slot vacated (finish / preemption): the lane's content is dead."""
        self.valid[slot] = False

    def commit(self, slot: int, new_len: int) -> None:
        """Rewind lane ``slot`` to the accepted boundary after a verify
        round (propose advanced it gamma + 1; the target accepted fewer)."""
        self.lens[slot] = new_len

    # -- proposal -------------------------------------------------------------
    def propose(self, slots: List[int], pending: Dict[int, int]) -> np.ndarray:
        """-> (max_batch, gamma) greedy draft tokens; rows outside ``slots``
        are garbage.  Lanes outside ``slots`` append scratch entries past
        their committed length — dead weight their next real append
        overwrites, never read."""
        t0 = np.zeros((self.max_batch,), np.int32)
        for s in slots:
            t0[s] = pending[s]
        self._cache["length"] = jnp.asarray(self.lens)
        drafts, self._cache = self._propose(self.dparams, self._cache,
                                            jnp.asarray(t0))
        return np.asarray(drafts)

    # -- accounting -----------------------------------------------------------
    def nbytes(self) -> int:
        """The spec-decode memory bill: draft weights (zero for a pure
        self-draft — the tree is the target's, shared by reference) plus the
        dense draft KV lanes."""
        total = 0 if self.shares_weights else tree_nbytes(self.dparams)
        if self._cache is not None:
            total += cache_nbytes(self._cache["entries"])
        return total
