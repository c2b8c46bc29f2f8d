"""Paged SimQuant KV cache: block-pool storage + refcounted allocator.

The dense cache in ``kv_cache.py`` pre-allocates ``max_slots x smax`` tokens
per layer — memory scales with the *configured* maximum, not with live
traffic.  This module stores quantized KV entries in fixed-size token blocks
(vLLM-style paged attention, arXiv:2309.06180) so memory scales with live
tokens.  The code bitwidth is owned by a :class:`~repro.serving.codec.
CacheCodec` — ``int8`` (one code per byte, the layout below) or packed
``int4`` (two codes per byte: every ``*_vals`` last dim halves while the
scale rows keep the full dim, which is how readers infer the codec):

  GQA:  k_vals  codes (R, N+1, T, KH*D/pack)  block pool (last = trash)
        v_vals  codes (R, N+1, T, KH*D/pack)
        v_scale f32   (R, KH, T, N+1)      per-token affine V (online)
        v_zero  f32   (R, KH, T, N+1)
        k_scale f32   (R, B,   KH, D)      per-*slot* per-channel K affine,
        k_zero  f32   (R, B,   KH, D)      frozen at the first prefill chunk
  MLA:  c_vals   codes (R, N+1, T, rkv/pack)
        c_scale  f32   (R, T, N+1)         per-token symmetric scale
        kr_vals  codes (R, N+1, T, dr/pack)
        kr_scale f32   (R, T, N+1)

``R`` is the scan-repeat axis (pattern positions nest inside, exactly like
the dense cache); ``N`` is the shared block count, ``T`` the tokens/block,
``B`` the decode-batch width.  The GQA leaves are shaped so that the TPU
stores them row-major, exactly as the paged kernels DMA them: a token's
heads sit side by side in one lane row of the value leaves, and the V
scale/zero rows keep their blocks as lanes (the order the chip would
otherwise pick for a ``(.., T, KH, 1)`` leaf, and then relayout around every
kernel launch); so do the MLA per-token scales.  A request owns a row of a
host-side block table mapping its logical block index -> pool block id;
block ``N`` is a write-off trash block that absorbs stores from padded /
inactive lanes so the jitted step needs no scatter masking.

Quantization math mirrors ``kv_cache.gqa_cache_entry`` / ``gqa_cache_append``
op-for-op (same dtypes, same eps) so a single-chunk paged prefill produces
bit-identical codes to the dense engine — the golden-parity contract the
scheduler tests assert.

Ownership is *shared*, not exclusive: :class:`BlockAllocator` refcounts every
block, keeps a content-hash index over published full prefix blocks, and
parks unreferenced-but-published blocks on an LRU cached list that is
reclaimed under pressure.  One physical block can back many block tables
(prefix sharing); a writer that would mutate a shared or published block
copies it first (``copy_pool_block``).  Because the GQA K affine is
frozen per *slot*, a prefix hit also restores the publisher's scale rows
into the matcher's slot (``snapshot_slot_scales`` /
``restore_slot_scales``) — shared int8 codes then dequantize
bit-identically to the donor's run.  The MLA scales are per token and
travel with their blocks.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.qtensor import int_range, pack_nibbles, unpack_nibbles
from repro.models.config import ModelConfig
from repro.serving.codec import (STORAGE_DTYPE, block_axis, block_index,
                                 get_codec, latent_rows)

TRASH = -1  # host-side marker; resolved to the pool's trash block id on use

# pool keys beside the scanned pattern positions "p{i}": the leading layers'
# entry (leaves stacked over ``first_k_dense_replace``) and the step's
# running counters (``{"moe": uint32 (5,)}``, ``models/moe.py`` COUNTERS)
LEAD = "lead"
STATS = "stats"

# leaf-name partition of a pool entry: BLOCK_LEAVES are indexed by pool block
# id (copied on CoW, shared on a prefix hit) where ``codec.block_index`` says;
# SLOT_SCALE_LEAVES are indexed by decode slot on axis 1 (snapshotted at
# publish / restored on hit, since the frozen K affine travels with the
# request, not the block)
BLOCK_LEAVES = ("k_vals", "v_vals", "v_scale", "v_zero", "c_vals", "c_scale",
                "kr_vals", "kr_scale")
SLOT_SCALE_LEAVES = ("k_scale", "k_zero")


@dataclasses.dataclass(frozen=True)
class PagedCacheConfig:
    block_size: int = 16                 # T — tokens per block
    num_blocks: int = 64                 # N — shared pool (excl. trash block)
    max_batch: int = 8                   # B — decode-batch width (slots)
    max_blocks_per_req: int = 16         # M — block-table row width

    @property
    def trash_block(self) -> int:
        return self.num_blocks

    @property
    def tokens_per_req(self) -> int:
        return self.max_blocks_per_req * self.block_size


# ---------------------------------------------------------------------------
# Pool allocation
# ---------------------------------------------------------------------------

def scanned(pool) -> Dict[str, Any]:
    """The pattern positions' entries, which the layer scan carries."""
    return {k: v for k, v in pool.items() if k not in (LEAD, STATS)}


def init_paged_cache(cfg: ModelConfig, pcfg: PagedCacheConfig,
                     codec="int8") -> Dict[str, Any]:
    """Zero-filled block pool pytree: {"p{i}": leaves (R, ...)} per pattern
    position, plus {LEAD: leaves (first_k, ...)} for leading layers and
    {STATS: ...} counters for MoE patterns.  SSM mixers have no sequence
    axis to page — their fixed-size conv/SSD state lives in the slot pool
    (``state_pool.init_state_pool``), so hybrid patterns simply skip those
    positions here.  ``codec`` picks the code layout: packed codecs shrink
    every ``*_vals`` last dim by the pack factor while scale rows keep the
    full dim (per head)."""
    cd = get_codec(codec)
    npool = pcfg.num_blocks + 1                     # + trash block
    t, b = pcfg.block_size, pcfg.max_batch
    entries: Dict[str, Any] = {}
    positions = [(f"p{i}", spec, cfg.n_repeats)
                 for i, spec in enumerate(cfg.layer_pattern)]
    if cfg.first_k_dense_replace:
        if cfg.lead_spec.mixer == "ssm":
            raise NotImplementedError(
                f"{cfg.name}: leading layers page attention only")
        positions.append((LEAD, cfg.lead_spec, cfg.first_k_dense_replace))
    for key, spec, r in positions:
        if spec.mixer == "attn":
            kh, d = cfg.kv_heads, cfg.hd
            dp = cd.packed_dim(d)
            entries[key] = {
                "k_vals": jnp.zeros((r, npool, t, kh * dp), STORAGE_DTYPE),
                "v_vals": jnp.zeros((r, npool, t, kh * dp), STORAGE_DTYPE),
                "v_scale": jnp.zeros((r, kh, t, npool), jnp.float32),
                "v_zero": jnp.zeros((r, kh, t, npool), jnp.float32),
                "k_scale": jnp.ones((r, b, kh, d), jnp.float32),
                "k_zero": jnp.zeros((r, b, kh, d), jnp.float32),
            }
        elif spec.mixer == "mla":
            rkv, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
            entries[key] = {
                "c_vals": jnp.zeros((r, npool, t, cd.packed_dim(rkv)), STORAGE_DTYPE),
                "c_scale": jnp.ones((r, t, npool), jnp.float32),
                "kr_vals": jnp.zeros((r, npool, t, cd.packed_dim(dr)), STORAGE_DTYPE),
                "kr_scale": jnp.ones((r, t, npool), jnp.float32),
            }
        # ssm: no sequence axis — state_pool.py owns those positions
    if cfg.has_moe:
        entries[STATS] = {"moe": jnp.zeros((5,), jnp.uint32)}
    return entries


def _entry_bits(entry: Dict[str, jax.Array]) -> int:
    """Infer a GQA entry's codec bitwidth from leaf shapes: a packed value
    leaf's last dim is half its K scale rows' (scales always keep the full
    channel dim; the value row holds every kv head's channels)."""
    kh, d = entry["k_scale"].shape[-2:]
    return 8 if entry["k_vals"].shape[-1] == kh * d else 4


class BlockPoolError(RuntimeError):
    """Raised on allocator misuse: double free, negative refcount, or an
    operation against a block in the wrong lifecycle state."""


@dataclasses.dataclass
class PrefixEntry:
    """One published full block in the content-hash prefix index.

    ``tag`` identifies the scale-freeze epoch of the publisher: blocks hold
    int8 codes quantized with the publisher's frozen per-slot K affine, so a
    chain match must stay within one tag — mixing donors would dequantize
    some blocks with the wrong scales.  ``meta`` carries the publisher's
    slot-scale snapshot (restored into the matcher's slot on a hit).
    ``parent`` is the chain digest of the previous block (b"" for block 0)
    and ``tokens`` the block's raw int32 tokens — together they let a new
    request find donors for *partial* (sub-block) prefix reuse: candidates
    share the full-prefix parent, and the common token run with ``tokens``
    is how many cached positions a device copy of the block can seed.

    ``bits``/``half`` track the bit ladder: a demoted entry (``bits == 4``)
    lives as packed int4 codes in half ``half`` of the PACKED physical block
    ``block`` and must be promoted back to a fresh int8 block before use.
    """
    block: int
    tag: int
    meta: Any = None
    parent: bytes = b""
    tokens: Any = None
    bits: int = 8
    half: int = 0


class BlockAllocator:
    """Refcounted pool over the shared blocks, with a prefix-cache index.

    Block lifecycle (all transitions O(1)):

      FREE --alloc--> ACTIVE(ref=1) --incref/acquire--> ACTIVE(ref=n)
      ACTIVE --decref to 0, published--> CACHED (LRU, reclaimable)
      ACTIVE --decref to 0, unpublished--> FREE
      CACHED --acquire--> ACTIVE(ref=1)     (prefix hit revives it)
      CACHED --alloc under pressure--> ACTIVE (LRU entry evicted + recycled)
      CACHED x2 --demote_oldest_pair--> PACKED + FREE   (bit ladder down)
      PACKED half --promote--> ACTIVE(ref=1) on a fresh block (ladder up)

    ``free`` is decref: a block is only recycled when its last reference
    drops, so one physical block can back many block-table rows (prefix
    sharing).  Published blocks outlive their references as CACHED entries
    until memory pressure reclaims them, giving an LRU prefix cache for free.
    Under harder pressure the bit ladder demotes the two LRU-oldest CACHED
    blocks into *one* PACKED physical block of int4 codes (the codec's
    ``demote_pair_blocks`` is the device half), freeing the other — so two
    logical prefix blocks survive in one block of bytes.

    Conservation invariant (checked by ``check()`` and the property tests):
    ``num_free + num_cached + num_active + num_packed == num_blocks``.
    """

    FREE, ACTIVE, CACHED, PACKED = 0, 1, 2, 3

    def __init__(self, num_blocks: int):
        self.num_blocks = num_blocks
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._ref: List[int] = [0] * num_blocks
        self._state: List[int] = [self.FREE] * num_blocks
        self._key_of: List[Optional[bytes]] = [None] * num_blocks
        self._cached: "OrderedDict[int, bytes]" = OrderedDict()  # LRU: old first
        self._index: Dict[bytes, PrefixEntry] = {}
        # bit ladder: PACKED physical block -> [key of half 0, key of half 1]
        self._packed: Dict[int, List[Optional[bytes]]] = {}
        self._packed_lru: "OrderedDict[int, None]" = OrderedDict()
        self.cache_evictions = 0          # cached blocks reclaimed by alloc()
        self.demotions = 0                # logical blocks demoted int8 -> int4
        self.promotions = 0               # logical blocks promoted int4 -> int8

    # -- accounting -----------------------------------------------------------
    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_cached(self) -> int:
        return len(self._cached)

    @property
    def num_packed(self) -> int:
        """Physical blocks holding two demoted int4 halves (bit ladder)."""
        return len(self._packed)

    @property
    def int4_blocks(self) -> int:
        """Logical prefix blocks currently resident as packed int4 halves."""
        return sum(1 for halves in self._packed.values()
                   for key in halves if key is not None)

    @property
    def num_available(self) -> int:
        """Blocks an alloc() can hand out: free + reclaimable cached/packed."""
        return len(self._free) + len(self._cached) + len(self._packed)

    @property
    def num_used(self) -> int:
        """Live (referenced) blocks — excludes reclaimable cached blocks."""
        return self.num_blocks - self.num_available

    @property
    def utilization(self) -> float:
        return self.num_used / max(self.num_blocks, 1)

    @property
    def cached_frac(self) -> float:
        return len(self._cached) / max(self.num_blocks, 1)

    def refcount(self, b: int) -> int:
        return self._ref[b]

    def is_shared(self, b: int) -> bool:
        return self._ref[b] > 1

    def is_published(self, b: int) -> bool:
        key = self._key_of[b]
        e = self._index.get(key) if key is not None else None
        return e is not None and e.block == b

    # -- alloc / refcounting --------------------------------------------------
    def alloc(self, n: int = 1, exclude=()) -> Optional[List[int]]:
        """Allocate ``n`` blocks at refcount 1, or None (all-or-nothing).

        Free blocks are recycled LIFO (cache-warm first); under pressure the
        least-recently-cached prefix blocks are evicted from the index and
        reused, then (bit ladder) the least-recently-packed physical blocks
        — each of those evictions kills up to two demoted prefix entries.
        ``exclude`` blocks are never handed out nor evicted (the promote
        path must not recycle the packed block it is reading from).
        """
        exclude = frozenset(exclude)
        avail = self.num_available
        for b in exclude:
            if self._state[b] != self.ACTIVE:
                avail -= 1
        if n > avail:
            return None
        held: List[int] = []
        out: List[int] = []
        for _ in range(n):
            b = None
            while self._free:
                cand = self._free.pop()
                if cand in exclude:
                    held.append(cand)
                    continue
                b = cand
                break
            if b is None:
                for cand in self._cached:                   # LRU victim
                    if cand not in exclude:
                        b = cand
                        break
                if b is not None:
                    key = self._cached.pop(b)
                    del self._index[key]
                    self._key_of[b] = None
                    self.cache_evictions += 1
            if b is None:
                for cand in self._packed_lru:               # ladder victim
                    if cand not in exclude:
                        b = cand
                        break
                if b is not None:
                    self._evict_packed(b)
            if b is None:
                raise BlockPoolError("alloc accounting out of sync")
            self._state[b] = self.ACTIVE
            self._ref[b] = 1
            out.append(b)
        self._free.extend(reversed(held))
        return out

    def _evict_packed(self, b: int) -> None:
        """Drop a PACKED physical block and every demoted entry it holds."""
        halves = self._packed.pop(b)
        self._packed_lru.pop(b)
        for key in halves:
            if key is not None:
                del self._index[key]
                self.cache_evictions += 1

    def incref(self, b: int) -> None:
        if self._state[b] != self.ACTIVE:
            raise BlockPoolError(f"incref of non-active block {b}")
        self._ref[b] += 1

    def decref(self, b: int) -> None:
        """Drop one reference; at zero the block becomes CACHED if published
        (still matchable, reclaimable LRU) else FREE."""
        if b == TRASH:
            return
        if not 0 <= b < self.num_blocks:
            raise BlockPoolError(f"decref of out-of-range block {b}")
        if self._state[b] != self.ACTIVE or self._ref[b] <= 0:
            raise BlockPoolError(
                f"double free / negative refcount on block {b} "
                f"(state={self._state[b]}, ref={self._ref[b]})")
        self._ref[b] -= 1
        if self._ref[b] > 0:
            return
        key = self._key_of[b]
        if key is not None and self._index.get(key, None) is not None \
                and self._index[key].block == b:
            self._state[b] = self.CACHED
            self._cached[b] = key            # newest at the MRU end
        else:
            self._state[b] = self.FREE
            self._key_of[b] = None
            self._free.append(b)

    def free(self, blocks) -> None:
        """Decref a batch (compat shim for the pre-refcount call sites)."""
        for b in blocks:
            self.decref(b)

    # -- prefix index ---------------------------------------------------------
    def publish(self, b: int, key: bytes, tag: int, meta: Any = None,
                parent: bytes = b"", tokens: Any = None) -> bool:
        """Register a *full, immutable* block under its content-chain key.

        First publisher wins: if ``key`` is already indexed, or ``b`` is
        already published under another key, the call is a no-op (an existing
        entry may be quantized with different frozen scales — see
        ``PrefixEntry.tag``).  Returns True if indexed.
        """
        if self._state[b] != self.ACTIVE:
            raise BlockPoolError(f"publish of non-active block {b}")
        if key in self._index or self._key_of[b] is not None:
            return False
        self._index[key] = PrefixEntry(block=b, tag=tag, meta=meta,
                                       parent=parent, tokens=tokens)
        self._key_of[b] = key
        return True

    def lookup(self, key: bytes) -> Optional[PrefixEntry]:
        return self._index.get(key)

    def children_of(self, parent: bytes) -> List[PrefixEntry]:
        """Published blocks whose chain parent is ``parent`` — the candidate
        donors for a partial (sub-block) match at that chain position."""
        return [e for e in self._index.values() if e.parent == parent]

    def acquire(self, key: bytes) -> Optional[int]:
        """Take a reference on the indexed block for ``key`` (prefix hit):
        revives a CACHED block to ACTIVE(ref=1), increfs an ACTIVE one.
        Demoted (int4) entries cannot be acquired directly — callers must go
        through :meth:`promote` onto a freshly allocated block first."""
        e = self._index.get(key)
        if e is None:
            return None
        if e.bits != 8:
            raise BlockPoolError(f"acquire of demoted entry {key!r}; promote first")
        b = e.block
        if self._state[b] == self.CACHED:
            del self._cached[b]
            self._state[b] = self.ACTIVE
            self._ref[b] = 1
        else:
            self._ref[b] += 1
        return b

    # -- bit ladder -----------------------------------------------------------
    def demote_oldest_pair(self):
        """Demote the two LRU-oldest CACHED blocks into one PACKED block.

        Host bookkeeping only — the caller must mirror it on-device with
        ``codec.demote_pair_blocks(pool, src_a, src_b, dst)`` using the
        returned ids.  The first victim's physical block becomes the packed
        destination (half 0 = first victim, half 1 = second); the second
        victim's block is freed.  Returns ``(key_a, key_b, src_a, src_b,
        dst)`` or None if fewer than two blocks are cached.
        """
        if len(self._cached) < 2:
            return None
        b_a, key_a = self._cached.popitem(last=False)
        b_b, key_b = self._cached.popitem(last=False)
        dst = b_a
        e_a, e_b = self._index[key_a], self._index[key_b]
        e_a.block, e_a.bits, e_a.half = dst, 4, 0
        e_b.block, e_b.bits, e_b.half = dst, 4, 1
        self._key_of[b_a] = None
        self._key_of[b_b] = None
        self._state[dst] = self.PACKED
        self._packed[dst] = [key_a, key_b]
        self._packed_lru[dst] = None
        self._state[b_b] = self.FREE
        self._free.append(b_b)
        self.demotions += 2
        return key_a, key_b, b_a, b_b, dst

    def promote(self, key: bytes, new_block: int):
        """Rebind the demoted entry ``key`` onto ``new_block`` (which must
        come from ``alloc(1, exclude={entry.block})`` — ACTIVE at ref 1, so
        the caller holds the reference exactly as after ``acquire``).

        Returns ``(phys, half)`` for the device half
        (``codec.promote_block(pool, phys, half, new_block)``); when the
        packed block's other half is already gone the physical block is
        freed.
        """
        e = self._index.get(key)
        if e is None or e.bits != 4:
            raise BlockPoolError(f"promote of non-demoted entry {key!r}")
        if self._state[new_block] != self.ACTIVE or self._ref[new_block] != 1:
            raise BlockPoolError(f"promote target {new_block} not freshly allocated")
        phys, half = e.block, e.half
        halves = self._packed[phys]
        halves[half] = None
        e.block, e.bits, e.half = new_block, 8, 0
        self._key_of[new_block] = key
        if halves[0] is None and halves[1] is None:
            del self._packed[phys]
            self._packed_lru.pop(phys)
            self._state[phys] = self.FREE
            self._free.append(phys)
        self.promotions += 1
        return phys, half

    # -- invariants -----------------------------------------------------------
    def check(self) -> None:
        """Assert the conservation invariant and internal consistency (used
        by the property tests; cheap enough to call after every op)."""
        active = [b for b in range(self.num_blocks)
                  if self._state[b] == self.ACTIVE]
        if len(self._free) + len(self._cached) + len(active) \
                + len(self._packed) != self.num_blocks:
            raise BlockPoolError(
                f"conservation violated: free={len(self._free)} "
                f"cached={len(self._cached)} active={len(active)} "
                f"packed={len(self._packed)} != {self.num_blocks}")
        for b in self._free:
            if self._state[b] != self.FREE or self._ref[b] != 0:
                raise BlockPoolError(f"free-list block {b} in bad state")
        for b, key in self._cached.items():
            if self._state[b] != self.CACHED or self._ref[b] != 0:
                raise BlockPoolError(f"cached block {b} in bad state")
            if self._index.get(key, None) is None or self._index[key].block != b:
                raise BlockPoolError(f"cached block {b} not indexed")
        for b in active:
            if self._ref[b] <= 0:
                raise BlockPoolError(f"active block {b} with ref 0")
        if set(self._packed) != set(self._packed_lru):
            raise BlockPoolError("packed set and packed LRU out of sync")
        for b, halves in self._packed.items():
            if self._state[b] != self.PACKED or self._ref[b] != 0:
                raise BlockPoolError(f"packed block {b} in bad state")
            if halves[0] is None and halves[1] is None:
                raise BlockPoolError(f"packed block {b} holds no residents")
            for h, key in enumerate(halves):
                if key is None:
                    continue
                e = self._index.get(key)
                if e is None or e.block != b or e.bits != 4 or e.half != h:
                    raise BlockPoolError(
                        f"packed half {b}/{h} not indexed consistently")
        for key, e in self._index.items():
            if e.bits == 4:
                halves = self._packed.get(e.block)
                if halves is None or halves[e.half] != key:
                    raise BlockPoolError(
                        f"demoted entry {key!r} not back-linked")
            elif self._key_of[e.block] != key:
                raise BlockPoolError(f"index entry {key!r} not back-linked")

    def debug_snapshot(self) -> Dict[str, Any]:
        """JSON-serializable postmortem view of the pool: per-block
        state/refcount/key, free-list depth, LRU orders, and the prefix
        index as parent-linked chains.  Digest keys render as hex; read-only
        (allocator state is untouched)."""
        names = {self.FREE: "FREE", self.ACTIVE: "ACTIVE",
                 self.CACHED: "CACHED", self.PACKED: "PACKED"}
        blocks = []
        for b in range(self.num_blocks):
            key = self._key_of[b]
            blocks.append({
                "block": b, "state": names[self._state[b]],
                "ref": self._ref[b],
                "key": key.hex() if key is not None else None,
            })
        index = []
        for key, e in self._index.items():
            index.append({
                "key": key.hex(), "block": e.block,
                "parent": e.parent.hex() if e.parent else None,
                "tag": e.tag, "bits": e.bits, "half": e.half,
                "has_tokens": e.tokens is not None,
            })
        return {
            "num_blocks": self.num_blocks,
            "num_free": self.num_free,
            "num_active": sum(1 for s in self._state if s == self.ACTIVE),
            "num_cached": self.num_cached,
            "num_packed": self.num_packed,
            "int4_blocks": self.int4_blocks,
            "utilization": self.utilization,
            "cache_evictions": self.cache_evictions,
            "demotions": self.demotions,
            "promotions": self.promotions,
            "free_list": list(self._free),
            "cached_lru": [b for b in self._cached],        # oldest first
            "packed_lru": [b for b in self._packed_lru],
            "packed_halves": {str(b): [k.hex() if k is not None else None
                                       for k in halves]
                              for b, halves in self._packed.items()},
            "blocks": blocks,
            "index": index,
        }


# ---------------------------------------------------------------------------
# Scatter/gather helpers (pure, jit-traceable)
# ---------------------------------------------------------------------------

def _scatter_ids(block_row: jax.Array, start: jax.Array, count: jax.Array,
                 length: int, block_size: int, trash: int):
    """Pool block ids + in-block offsets for ``length`` consecutive tokens
    starting at sequence position ``start``; lanes >= ``count`` -> trash."""
    idx = jnp.arange(length)
    pos = start + idx
    safe = jnp.clip(pos // block_size, 0, block_row.shape[0] - 1)
    bids = jnp.where(idx < count, block_row[safe], trash)
    return bids, pos % block_size


def gqa_chunk_write(entry: Dict[str, jax.Array], k: jax.Array, v: jax.Array, *,
                    slot: jax.Array, block_row: jax.Array, ctx: jax.Array,
                    chunk_len: jax.Array, block_size: int, is_first: bool):
    """Quantize one prefill chunk's K/V (C, KH, D) into the block pool.

    ``is_first`` (static): the first chunk computes the per-channel K range
    over its valid tokens and freezes it into the slot's scale row (KVQuant-
    style); later chunks quantize with the frozen affine, exactly like the
    decode append path.  V always gets fresh per-token scales.
    """
    c = k.shape[0]
    bits = _entry_bits(entry)
    qmin, qmax = int_range(bits)
    valid = (jnp.arange(c) < chunk_len)[:, None, None]
    new = dict(entry)

    if is_first:
        # mirror quantize_keys()/minmax_scale_zero() op-for-op (same dtype
        # promotion + eps) so single-chunk prefill == dense prefill codes
        big = jnp.asarray(jnp.inf, k.dtype)
        xmin = jnp.min(jnp.where(valid, k, big), axis=0)
        xmax = jnp.max(jnp.where(valid, k, -big), axis=0)
        delta = jnp.maximum((xmax - xmin) / (qmax - qmin), 1e-8)   # (KH,D)
        zero = qmin - jnp.round(xmin / delta)
        k_q = jnp.clip(jnp.round(k / delta) + zero, qmin, qmax)
        new["k_scale"] = entry["k_scale"].at[slot].set(delta.astype(jnp.float32))
        new["k_zero"] = entry["k_zero"].at[slot].set(zero.astype(jnp.float32))
    else:
        delta = entry["k_scale"][slot]                             # (KH,D) f32
        zero = entry["k_zero"][slot]
        k_q = jnp.clip(jnp.round(k.astype(jnp.float32) / delta) + zero,
                       qmin, qmax)
    k_q = pack_nibbles(k_q) if bits == 4 else k_q.astype(STORAGE_DTYPE)

    # per-token V affine — mirrors quantize_values()
    vmin = jnp.min(v, axis=-1, keepdims=True)
    vmax = jnp.max(v, axis=-1, keepdims=True)
    v_scale = jnp.maximum((vmax - vmin) / (qmax - qmin), 1e-8)     # (C,KH,1)
    v_zero = qmin - jnp.round(vmin / v_scale)
    v_q = jnp.clip(jnp.round(v / v_scale) + v_zero, qmin, qmax)
    v_q = pack_nibbles(v_q) if bits == 4 else v_q.astype(STORAGE_DTYPE)

    trash = entry["k_vals"].shape[0] - 1
    bids, offs = _scatter_ids(block_row, ctx, chunk_len, c, block_size, trash)
    return _store_tokens(new, bids, offs, k_q, v_q, v_scale, v_zero)


def _store_tokens(entry, bids, offs, k_q, v_q, v_scale, v_zero):
    """Write L tokens' codes (L, KH, D/pack) and V affine (L, KH, 1) at pool
    blocks ``bids`` / offsets ``offs`` (L,): one lane row per token in the
    value leaves, one lane per block in the scale leaves."""
    n, kh = k_q.shape[:2]
    new = dict(entry)
    new["k_vals"] = entry["k_vals"].at[bids, offs].set(k_q.reshape(n, -1))
    new["v_vals"] = entry["v_vals"].at[bids, offs].set(v_q.reshape(n, -1))
    # one scalar per (head, token): a scatter with no window, which XLA runs
    # on the leaf as stored (a window over KH would relayout it heads-minor)
    heads = jnp.arange(kh)[:, None]
    for name, x in (("v_scale", v_scale), ("v_zero", v_zero)):
        new[name] = entry[name].at[heads, offs, bids].set(
            x[..., 0].astype(jnp.float32).T)
    return new


def gqa_paged_append(entry: Dict[str, jax.Array], k_t: jax.Array, v_t: jax.Array,
                     block_tables: jax.Array, lengths: jax.Array, *,
                     block_size: int):
    """Decode append: one token's K/V (B, KH, D) at position ``lengths[b]``.

    f32 math mirrors ``kv_cache.gqa_cache_append`` exactly; slots whose
    block-table entry is the trash block write harmlessly off to the side.
    """
    b = k_t.shape[0]
    bits = _entry_bits(entry)
    qmin, qmax = int_range(bits)
    k_scale, k_zero = entry["k_scale"], entry["k_zero"]            # (B,KH,D)
    k_q = jnp.clip(jnp.round(k_t.astype(jnp.float32) / k_scale) + k_zero,
                   qmin, qmax)
    k_q = pack_nibbles(k_q) if bits == 4 else k_q.astype(STORAGE_DTYPE)

    vmin = jnp.min(v_t, axis=-1, keepdims=True).astype(jnp.float32)
    vmax = jnp.max(v_t, axis=-1, keepdims=True).astype(jnp.float32)
    v_scale = jnp.maximum((vmax - vmin) / (qmax - qmin), 1e-8)
    v_zero = qmin - jnp.round(vmin / v_scale)
    v_q = jnp.clip(jnp.round(v_t.astype(jnp.float32) / v_scale) + v_zero,
                   qmin, qmax)
    v_q = pack_nibbles(v_q) if bits == 4 else v_q.astype(STORAGE_DTYPE)

    bidx = jnp.arange(b)
    safe = jnp.clip(lengths // block_size, 0, block_tables.shape[1] - 1)
    bids = block_tables[bidx, safe]
    offs = lengths % block_size
    return _store_tokens(entry, bids, offs, k_q, v_q, v_scale, v_zero)


def gqa_gather_prefix(entry: Dict[str, jax.Array], block_row: jax.Array,
                      slot: jax.Array, dtype):
    """Dequantize one request's cached prefix: -> k, v (M*T, KH, D)."""
    kh = entry["k_scale"].shape[-2]
    m, t = block_row.shape[0], entry["k_vals"].shape[1]
    k_q = entry["k_vals"][block_row].reshape(m, t, kh, -1)   # (M,T,KH,D/pack)
    v_q = entry["v_vals"][block_row].reshape(m, t, kh, -1)
    if _entry_bits(entry) == 4:
        k_q, v_q = unpack_nibbles(k_q), unpack_nibbles(v_q)
    vs = entry["v_scale"][..., block_row].transpose(2, 1, 0)[..., None]
    vz = entry["v_zero"][..., block_row].transpose(2, 1, 0)[..., None]
    ks = entry["k_scale"][slot]                      # (KH,D)
    kz = entry["k_zero"][slot]
    k = ((k_q.astype(jnp.float32) - kz) * ks).reshape(m * t, *k_q.shape[2:])
    v = ((v_q.astype(jnp.float32) - vz) * vs).reshape(m * t, *v_q.shape[2:])
    return k.astype(dtype), v.astype(dtype)


# -- MLA latent pool ---------------------------------------------------------

def _store_latent(entry: Dict[str, jax.Array], bids, offs, c_kv: jax.Array,
                  kr: jax.Array) -> Dict[str, jax.Array]:
    """Quantize L tokens' latent (L, rkv) and rope key (L, dr) per token
    and write codes and scales at pool blocks ``bids`` / offsets ``offs``
    (L,).  A packed code leaf is half the latent's width."""
    bits = 8 if entry["c_vals"].shape[-1] == c_kv.shape[-1] else 4
    new = dict(entry)
    for name, x in (("c", c_kv), ("kr", kr)):
        q, scale = latent_rows(x, bits)
        q = pack_nibbles(q) if bits == 4 else q.astype(STORAGE_DTYPE)
        new[f"{name}_vals"] = entry[f"{name}_vals"].at[bids, offs].set(q)
        new[f"{name}_scale"] = entry[f"{name}_scale"].at[offs, bids].set(
            scale[:, 0])
    return new


def mla_chunk_write(entry: Dict[str, jax.Array], c_kv: jax.Array, kr: jax.Array, *,
                    block_row: jax.Array, ctx: jax.Array,
                    chunk_len: jax.Array, block_size: int):
    """Quantize one chunk's latent (C, rkv) + rope key (C, dr) into the pool."""
    trash = entry["c_vals"].shape[0] - 1
    bids, offs = _scatter_ids(block_row, ctx, chunk_len, c_kv.shape[0],
                              block_size, trash)
    return _store_latent(entry, bids, offs, c_kv, kr)


def mla_paged_append(entry: Dict[str, jax.Array], c_t: jax.Array, kr_t: jax.Array,
                     block_tables: jax.Array, lengths: jax.Array, *,
                     block_size: int):
    """Decode append of one token's latent (B, rkv) + rope key (B, dr)."""
    bidx = jnp.arange(c_t.shape[0])
    safe = jnp.clip(lengths // block_size, 0, block_tables.shape[1] - 1)
    return _store_latent(entry, block_tables[bidx, safe],
                         lengths % block_size, c_t, kr_t)


def rewind_tail(alloc: "BlockAllocator", block_row: np.ndarray,
                keep_tokens: int, *, block_size: int, trash: int) -> int:
    """Rewind a request's block-table row to ``keep_tokens`` live tokens,
    releasing every tail block past the last kept one (speculative-decoding
    rejection path; also usable for any truncation).

    Only *references* are dropped — the release is a ``decref``, so the
    rewind is CoW-safe by construction: a block another table row still maps
    (shared prefix) just loses this row's reference, and a published block
    survives as a reclaimable CACHED prefix entry.  The conservation
    invariant ``free + cached + active == num_blocks`` therefore holds across
    any propose/accept/reject sequence (property-tested).  The partial block
    containing the new tail is *kept* — its stale codes past ``keep_tokens``
    are overwritten in place by the next append and are never read (attention
    masks by length); writers still CoW away from it if it is shared or
    published, exactly like any other append.

    Returns the number of blocks released.
    """
    keep_blocks = 0 if keep_tokens <= 0 else \
        (keep_tokens + block_size - 1) // block_size
    freed = 0
    for bi in range(keep_blocks, block_row.shape[0]):
        b = int(block_row[bi])
        if b == trash:
            continue
        alloc.decref(b)
        block_row[bi] = trash
        freed += 1
    return freed


# ---------------------------------------------------------------------------
# Copy-on-write / prefix-hit device plumbing
# ---------------------------------------------------------------------------

def copy_pool_block(pool, src, dst):
    """Copy block ``src`` -> ``dst`` across every block-indexed leaf of every
    pattern entry (the device half of copy-on-write).  Slot-scale leaves are
    untouched — the frozen affine belongs to the request, not the block."""
    out = {}
    for pkey, entry in pool.items():
        new = dict(entry)
        for name in BLOCK_LEAVES:
            if name in entry:
                at_dst, at_src = block_index(name, dst), block_index(name, src)
                new[name] = entry[name].at[at_dst].set(entry[name][at_src])
        out[pkey] = new
    return out


def snapshot_slot_scales(pool, slot: int) -> Dict[str, Dict[str, jax.Array]]:
    """Capture slot ``slot``'s frozen scale rows (one small (R, ...) array per
    scale leaf per entry) — stored with a published prefix chain so a future
    hit can dequantize the donor's codes."""
    snap: Dict[str, Dict[str, jax.Array]] = {}
    for pkey, entry in pool.items():
        snap[pkey] = {name: entry[name][:, slot]
                      for name in SLOT_SCALE_LEAVES if name in entry}
    return snap


@jax.jit
def _set_slot_rows(leaves, slot, rows):
    return {pkey: {name: leaf.at[:, slot].set(rows[pkey][name])
                   for name, leaf in entry.items()}
            for pkey, entry in leaves.items()}


def restore_slot_scales(pool, slot: int, snap) -> Dict[str, Any]:
    """Write a snapshot back into slot ``slot``'s scale rows (prefix hit:
    the matcher adopts the donor's frozen affine, so shared int8 blocks and
    its own suffix chunks dequantize/quantize identically).  One jitted
    program for every slot, over the scale leaves alone."""
    leaves = {pkey: {name: pool[pkey][name] for name in rows}
              for pkey, rows in snap.items()}
    out = dict(pool)
    for pkey, new in _set_slot_rows(leaves, jnp.int32(slot), snap).items():
        out[pkey] = {**out[pkey], **new}
    return out


# ---------------------------------------------------------------------------
# Accounting
# ---------------------------------------------------------------------------

def paged_cache_nbytes(pool) -> int:
    """Allocated pool bytes (compare against the dense cache's nbytes)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(pool):
        if hasattr(leaf, "nbytes"):
            total += leaf.nbytes
    return total


def per_block_nbytes(pool) -> int:
    """Bytes one pool block occupies across every block-indexed leaf — the
    unit for the scheduler's effective-capacity accounting (a demoted int4
    block holds a full logical block in half of one of these)."""
    total = 0
    for entry in pool.values():
        for name in BLOCK_LEAVES:
            if name in entry:
                leaf = entry[name]
                total += int(leaf.nbytes) // int(leaf.shape[block_axis(name)])
    return total


def per_device_nbytes(tree) -> int:
    """Max over devices of the bytes one device actually holds for ``tree``.

    For a pool sharded over the ``model`` axis this is what HBM sees per
    chip: the ``kv_heads``-sharded leaves contribute ``nbytes / model`` each,
    replicated leaves contribute in full.  On an unsharded tree every leaf
    has exactly one addressable shard, so this degenerates to
    :func:`paged_cache_nbytes`."""
    per: dict = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards:
            for s in shards:
                d = getattr(s, "device", None)
                per[d] = per.get(d, 0) + int(s.data.nbytes)
        elif hasattr(leaf, "nbytes"):
            per[None] = per.get(None, 0) + int(leaf.nbytes)
    return max(per.values()) if per else 0
