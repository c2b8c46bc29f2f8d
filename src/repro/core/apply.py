"""Execution Runtime Layer: module extraction + quantization dispatch (§2.1).

The paper's workflow phase 1 ("the model is traced and quantizable modules
are identified") maps here to a pytree walk over the params dict: any leaf
whose path matches the policy's patterns (projection/FFN/embedding matrices)
is quantized with the configured backend; everything else (norm gains,
biases, router weights, SSM recurrence params) stays in high precision.

The result is a *mixed pytree* — QTensor leaves where quantized, raw arrays
elsewhere — which flows through jit/pjit like any params pytree, and
``dequantize_tree`` reconstructs fp weights (used by the fake-quant eval
path, while the serving path consumes QTensors natively via the Pallas
w8a8 kernel).
"""
from __future__ import annotations

import dataclasses
import fnmatch
import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .methods.base import get_method
from .qtensor import QTensor

# Leaves whose *path* matches any of these are never quantized regardless of
# policy: small / range-sensitive parameters (paper keeps router + norms
# high-bit in the bitwidth search too).
DEFAULT_EXCLUDE = (
    "*norm*", "*scale*", "*bias*", "*router*", "*gate_w*",  # gate_w = MoE router
    "*A_log*", "*D*", "*dt*", "*conv*",                     # SSM recurrence params
)

DEFAULT_INCLUDE = (
    "*wq*", "*wk*", "*wv*", "*wo*", "*w_in*", "*w_gate*", "*w_out*", "*w_up*",
    "*attn/kv_a", "*attn/kv_b", "*attn/q_a", "*attn/q_b",   # MLA projections
    "*attn/q_proj",
    "*experts*", "*shared*",                                # MoE expert mats
    "*embed*", "*lm_head*",
)


@dataclasses.dataclass(frozen=True)
class QuantPolicy:
    """What to quantize and how (one policy per deployment)."""

    method: str = "symmetric"
    bits_override: Optional[Dict[str, int]] = None   # pattern -> bits (from search)
    include: Sequence[str] = DEFAULT_INCLUDE
    exclude: Sequence[str] = DEFAULT_EXCLUDE
    min_size: int = 4096          # skip tiny leaves (scale overhead dominates)
    quantize_embeddings: bool = False

    def wants(self, path: str, leaf) -> bool:
        if not hasattr(leaf, "ndim") or leaf.ndim < 2:
            return False
        if leaf.size < self.min_size:
            return False
        p = path.lower()
        if any(fnmatch.fnmatch(p, pat) for pat in self.exclude):
            return False
        if not self.quantize_embeddings and ("embed" in p or "lm_head" in p):
            return False
        return any(fnmatch.fnmatch(p, pat) for pat in self.include)

    def bits_for(self, path: str, default: int) -> int:
        if self.bits_override:
            p = path.lower()
            for pat, bits in self.bits_override.items():
                if fnmatch.fnmatch(p, pat.lower()) or pat.lower() == p:
                    return bits
        return default


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def extract_modules(params, policy: QuantPolicy) -> List[Tuple[str, Any]]:
    """Workflow phase 1: list of (path, weight) the policy will quantize."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        ps = _path_str(path)
        if policy.wants(ps, leaf):
            out.append((ps, leaf))
    return out


def quantize_tree(params, policy: QuantPolicy, *,
                  stats: Optional[Dict[str, Any]] = None,
                  calib_x: Optional[Dict[str, jnp.ndarray]] = None):
    """Workflow phase 3: quantize matching leaves, in one pytree pass.

    stats / calib_x: per-path activation stats & calibration inputs for
    calibrated methods (SmoothQuant/AWQ/GPTQ); keyed by tap tag == the path
    of the consuming weight (calibration.py's convention).
    """
    method = get_method(policy.method)

    def visit(path, leaf):
        ps = _path_str(path)
        if not policy.wants(ps, leaf):
            return leaf
        bits = policy.bits_for(ps, method.bits_weight)
        kw = {}
        if method.needs_calibration:
            if stats is not None and ps in stats:
                kw["stats"] = stats[ps]
            if calib_x is not None and ps in calib_x:
                kw["calib_x"] = calib_x[ps]
        # stacked leaves ((layers,) (experts,) d_in, d_out): quantize every
        # (d_in, d_out) slice on its own — vmap the 2D quantizer over each
        # leading dim, so each layer and expert gets its per-output-channel
        # scales
        quant = lambda w: method.quantize_weight(w, bits=bits, **kw)
        for _ in range(leaf.ndim - 2):
            quant = jax.vmap(quant)
        return quant(leaf)

    return jax.tree_util.tree_map_with_path(visit, params)


def dequantize_tree(qparams, dtype=jnp.bfloat16):
    """Reconstruct an fp params pytree from a mixed tree (eval path)."""

    def visit(leaf):
        if isinstance(leaf, QTensor):
            deq = leaf.dequantize(jnp.float32)
            # Grouped layouts (ZeroQuant blockwise) carry an extra group dim;
            # collapse it back: (nG, g, d_out) -> (nG*g, d_out).
            if deq.ndim == 3 and leaf.axis == (1,):
                deq = deq.reshape(-1, deq.shape[-1])
            return deq.astype(dtype)
        return leaf

    return jax.tree_util.tree_map(visit, qparams,
                                  is_leaf=lambda l: isinstance(l, QTensor))


def fake_quantize_tree(params, policy: QuantPolicy, **kw):
    """Quantize+dequantize in place: fp pytree with quantization error baked
    in.  This is the evaluation path used by perplexity benches (phase 4) and
    the bitwidth-search objective."""
    q = quantize_tree(params, policy, **kw)
    deq = dequantize_tree(q, dtype=jnp.float32)
    # Preserve original dtypes/shapes exactly.
    return jax.tree_util.tree_map(
        lambda orig, new: jnp.asarray(new, orig.dtype).reshape(orig.shape)
        if hasattr(orig, "shape") else new,
        params, deq)


def tree_nbytes(qparams) -> int:
    """Packed byte count of a mixed tree (model-size accounting)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(
            qparams, is_leaf=lambda l: isinstance(l, QTensor)):
        if isinstance(leaf, QTensor):
            total += leaf.nbytes_packed()
        elif hasattr(leaf, "nbytes"):
            total += leaf.nbytes
    return total
