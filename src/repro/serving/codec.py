"""Cache codecs: the one place that owns the block pool's bitwidth.

Every other serving/kernel module is bitwidth-agnostic: pool layouts are
built from a :class:`CacheCodec`, write paths quantize with ``codec.bits``,
and read paths (kernels + oracles) *infer* the codec from shapes — a packed
leaf's last dim is ``dim // pack``, while its scale row keeps the full dim,
so ``vals.shape[-1] != scale.shape[-1]`` means "unpack nibbles first".
``tools/check_codec.py`` enforces that no scoped module hardcodes
``jnp.int8`` pool/state layouts outside this file.

Two codecs ship:

  * ``int8`` — today's layout, one code per byte.  Bit-identical to the
    dense engine (the golden-parity contract).
  * ``int4`` — packed nibbles, two codes per byte: value leaves halve, so
    pool capacity in bytes roughly doubles at a quantization-error cost
    (divergence-gated, never bit-parity-gated).

On top of the codec sits the **bit ladder** (``SchedulerConfig.ladder``):
an *int8* pool under pressure demotes pairs of LRU CACHED prefix blocks
into one physical block of packed int4 codes (freeing the other), and
promotes them back to int8 on a prefix hit.  Demotion is a pure
*code-space* re-quantization — ``c4 = round((c8 + 128) / 17) - 8`` — so the
frozen per-slot affine is untouched and the promote error is bounded by 8
int8 codes (17 = 255/15 exactly).  Per-token V scale/zero rows ride along
as two bf16 halves bit-packed into the one f32 lane the destination block
owns.  Demoted blocks are never read by a kernel: promotion happens before
the block can enter any block table.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.qtensor import int_range, pack_nibbles, unpack_nibbles

# The pool's carrier dtype.  Packed codecs store several codes per carrier
# element; this is the only module allowed to name the concrete dtype.
STORAGE_DTYPE = jnp.int8


@dataclasses.dataclass(frozen=True)
class CacheCodec:
    """How pool blocks store quantized codes.

    ``bits`` is the logical code width used by the quantizers; ``pack`` is
    how many codes share one carrier byte (so a value leaf's last dim is
    ``dim // pack``).
    """
    name: str
    bits: int
    pack: int

    def packed_dim(self, dim: int) -> int:
        if dim % self.pack:
            raise ValueError(
                f"codec {self.name!r} packs {self.pack} codes/byte but "
                f"dim {dim} is not divisible")
        return dim // self.pack


CODECS: Dict[str, CacheCodec] = {
    "int8": CacheCodec(name="int8", bits=8, pack=1),
    "int4": CacheCodec(name="int4", bits=4, pack=2),
}


def get_codec(codec) -> CacheCodec:
    if isinstance(codec, CacheCodec):
        return codec
    try:
        return CODECS[codec]
    except KeyError:
        raise ValueError(f"unknown cache codec {codec!r}; have {sorted(CODECS)}")


def latent_rows(x: jax.Array, bits: int) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-token codes of MLA latent or rope-key rows (..., dim):
    -> (codes f32 (..., dim), scale f32 (..., 1)).  The latent is RMS-normed
    and the rope key is a rotation, so both centre on zero: one absmax scale
    per token and no zero point.  A token's codes depend on that token
    alone, so a later token never clips against an earlier token's range.
    The dense cache and the paged pool both quantize through here."""
    _, qmax = int_range(bits)
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                        1e-8) / qmax
    return jnp.clip(jnp.round(x / scale), -qmax, qmax), scale


# ---------------------------------------------------------------------------
# Bit-ladder primitives (int8 pool only)
# ---------------------------------------------------------------------------

# Pool-entry leaves holding integer codes (block axis 1) vs. the per-token
# f32 affine rows that must survive demotion alongside them (block axis
# last: ``paged_cache`` stores them (R, KH, T, N+1) and (R, T, N+1), blocks
# as lanes).
CODE_LEAVES = ("k_vals", "v_vals", "c_vals", "kr_vals")
PAIR_LEAVES = ("v_scale", "v_zero", "c_scale", "kr_scale")


def block_axis(name: str) -> int:
    """Axis of stacked pool leaf ``name`` that indexes pool blocks."""
    return -1 if name in PAIR_LEAVES else 1


def block_index(name: str, ids):
    """Index expression selecting pool block(s) ``ids`` of leaf ``name``."""
    return (Ellipsis, ids) if name in PAIR_LEAVES else (slice(None), ids)

_BF16_MAX = 3.0e38  # clamp before bf16 cast: keeps packed halves finite, so
                    # the f32 bit-carrier can never form a NaN pattern


def demote_codes(c8: jax.Array) -> jax.Array:
    """int8 codes -> packed int4 nibbles, same affine (code-space requant).

    Maps the unsigned view ``u = c8 + 128`` through ``round(u / 17)``; since
    ``255 = 15 * 17`` the endpoints are exact and the promote error is at
    most 8 codes of the original int8 grid.
    """
    u = c8.astype(jnp.int32) + 128                                  # 0..255
    c4u = jnp.clip(jnp.round(u.astype(jnp.float32) / 17.0), 0, 15)
    return pack_nibbles(c4u.astype(jnp.int32) - 8)                  # [-8, 7]


def promote_codes(packed_row: jax.Array, half: jax.Array) -> jax.Array:
    """Inverse of :func:`demote_codes` for one resident of a packed block.

    ``packed_row`` is the full-width carrier row whose two halves along the
    last dim hold two demoted blocks; ``half`` (traced 0/1) picks one.
    """
    w2 = packed_row.shape[-1] // 2
    sel = jnp.where(half == 0, packed_row[..., :w2], packed_row[..., w2:])
    u = (unpack_nibbles(sel) + 8) * 17                              # 0..255
    return (u - 128).astype(STORAGE_DTYPE)


def promote_codes_full(packed: jax.Array) -> jax.Array:
    """Full-width inverse of :func:`demote_codes` (no halving happened —
    used for in-place demotions like the scheduler's cold state snapshots,
    where one tensor was demoted rather than two packed into one block)."""
    u = (unpack_nibbles(packed) + 8) * 17                           # 0..255
    return (u - 128).astype(STORAGE_DTYPE)


def pack_f32_pair(a: jax.Array, b: jax.Array) -> jax.Array:
    """Two f32 arrays -> one f32 bit-carrier holding both as bf16 halves.

    bf16 keeps the f32 exponent range, so (after a finite clamp) no packed
    word can alias an f32 NaN and get canonicalized in transit; the ~3
    significant digits kept are a divergence-gated ladder cost.
    """
    a16 = jax.lax.bitcast_convert_type(
        jnp.clip(a, -_BF16_MAX, _BF16_MAX).astype(jnp.bfloat16), jnp.uint16)
    b16 = jax.lax.bitcast_convert_type(
        jnp.clip(b, -_BF16_MAX, _BF16_MAX).astype(jnp.bfloat16), jnp.uint16)
    word = a16.astype(jnp.uint32) | (b16.astype(jnp.uint32) << 16)
    return jax.lax.bitcast_convert_type(word, jnp.float32)


def unpack_f32_pair(p: jax.Array, half: jax.Array) -> jax.Array:
    """Recover one bf16 half (as f32) from a :func:`pack_f32_pair` carrier."""
    word = jax.lax.bitcast_convert_type(p, jnp.uint32)
    pick = jnp.where(half == 0, word & 0xFFFF, word >> 16).astype(jnp.uint16)
    return jax.lax.bitcast_convert_type(pick, jnp.bfloat16).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Device halves of the ladder (host bookkeeping lives in BlockAllocator)
# ---------------------------------------------------------------------------

def _demote_pair_impl(pool, src_a, src_b, dst):
    out = {}
    for pkey, entry in pool.items():
        new = dict(entry)
        for name in CODE_LEAVES:
            if name in entry:
                arr = entry[name]
                halves = jnp.concatenate(
                    [demote_codes(arr[:, src_a]), demote_codes(arr[:, src_b])],
                    axis=-1)
                new[name] = arr.at[:, dst].set(halves)
        for name in PAIR_LEAVES:
            if name in entry:
                arr = entry[name]
                new[name] = arr.at[..., dst].set(
                    pack_f32_pair(arr[..., src_a], arr[..., src_b]))
        out[pkey] = new
    return out


def _promote_impl(pool, src, half, dst):
    out = {}
    for pkey, entry in pool.items():
        new = dict(entry)
        for name in CODE_LEAVES:
            if name in entry:
                arr = entry[name]
                new[name] = arr.at[:, dst].set(promote_codes(arr[:, src], half))
        for name in PAIR_LEAVES:
            if name in entry:
                arr = entry[name]
                new[name] = arr.at[..., dst].set(
                    unpack_f32_pair(arr[..., src], half))
        out[pkey] = new
    return out


# src/dst as jnp.int32 scalars so one trace serves every block id; the pool
# is donated (the scheduler rebinds self.pool, mirroring its _COW_FN).
demote_pair_blocks = jax.jit(_demote_pair_impl, donate_argnums=(0,))
promote_block = jax.jit(_promote_impl, donate_argnums=(0,))
