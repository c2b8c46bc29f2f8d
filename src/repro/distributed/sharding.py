"""Logical-axis sharding rules (Distributed Controller Layer).

Model code annotates activations with *logical* axes ("batch", "heads",
"ffn", ...).  The launcher binds a mesh + rule table; on CPU smoke tests no
rules are bound and every constraint is a no-op — the same model code runs
everywhere.

Physical mesh axes (production):  ("pod", "data", "model")  or ("data",
"model") single-pod.  Rules map logical -> tuple of mesh axes; axes missing
from the active mesh are dropped, so one rule table serves both meshes.

Parameter shardings (for ``jit(in_shardings=...)``) are derived from param
*path names* by :func:`param_spec` — the same conventions the quantization
policy uses (core/apply.py), so a quantized QTensor pytree shards exactly
like its source weights (scale/zero inherit the reduced spec).
"""
from __future__ import annotations

import contextlib
import re
import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_STATE = threading.local()

# logical axis -> preferred mesh axes (first match present in mesh wins; for
# "batch" all present axes are used jointly).
DEFAULT_RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),
    # Sequence dim of activations shards over `model` (Megatron-SP): every
    # per-token op (norm/proj/FFN) runs S-sharded; cross-token ops (attention
    # kv, SSD scan, MoE grouping) gather explicitly at their boundary.
    "seq": ("model",),
    "kv_seq": (),                # overridden to ("data",) for long-context SP decode
    "heads": ("model",),
    "kv_heads": ("model",),
    "ffn": ("model",),
    "ssm_inner": ("model",),
    "experts": ("data",),        # EP: experts over the data axis
    "moe_groups": ("pod", "data"),  # GShard routing groups = token dim
    "expert_ffn": ("model",),    # TP inside each expert
    "vocab": ("model",),
    "embed": (),                 # activation d_model axis: replicated
    "fsdp": ("data",),           # param d_model axis: ZeRO-sharded over data
    "latent": (),                # MLA latent cache channel axis
    # Megatron-style sequence parallelism for the residual-stream scan carry:
    # the per-layer saved h stack (the dominant train-step temp — the scan
    # transpose keeps a bf16 AND an f32 copy) shards S over the model axis;
    # per-token ops run S-sharded, attention/FFN re-shard on demand.
    "seq_carry": ("model",),
}


def _current():
    return getattr(_STATE, "ctx", None)


@contextlib.contextmanager
def axis_rules(mesh: Mesh, rules: Optional[Dict[str, Tuple[str, ...]]] = None):
    """Bind a mesh + rules; model-code ``constrain`` becomes active."""
    merged = dict(DEFAULT_RULES)
    if rules:
        merged.update(rules)
    prev = _current()
    _STATE.ctx = (mesh, merged)
    try:
        yield
    finally:
        _STATE.ctx = prev


def active_mesh() -> Optional[Mesh]:
    ctx = _current()
    return None if ctx is None else ctx[0]


def resolve(logical: Optional[str]) -> Tuple[str, ...]:
    """Logical name -> mesh axes present in the active mesh."""
    ctx = _current()
    if ctx is None or logical is None:
        return ()
    mesh, rules = ctx
    axes = rules.get(logical, ())
    return tuple(a for a in axes if a in mesh.axis_names)


def spec(*logical_axes) -> P:
    parts = []
    for ax in logical_axes:
        r = resolve(ax)
        if len(r) == 0:
            parts.append(None)
        elif len(r) == 1:
            parts.append(r[0])
        else:
            parts.append(tuple(r))
    return P(*parts)


def constrain(x: jax.Array, *logical_axes) -> jax.Array:
    """with_sharding_constraint against the active rules (no-op when unbound
    or when a dimension is not divisible by its assigned axes)."""
    ctx = _current()
    if ctx is None:
        return x
    mesh, _ = ctx
    assert len(logical_axes) == x.ndim, (
        f"constrain: {len(logical_axes)} axes for rank-{x.ndim} value")
    parts = []
    used = set()                      # a mesh axis may appear only once
    for dim, ax in zip(x.shape, logical_axes):
        r = tuple(a for a in resolve(ax) if a not in used)
        # partial fallback: drop leading axes until the dim divides (e.g. a
        # 16-row dim on a (pod=2, data=16) batch rule shards over data only)
        chosen = None
        for i in range(len(r)):
            cand = r[i:]
            size = int(np.prod([mesh.shape[a] for a in cand]))
            if size > 1 and dim % size == 0:
                chosen = cand
                break
        if chosen:
            parts.append(chosen[0] if len(chosen) == 1 else tuple(chosen))
            used.update(chosen)
        else:
            parts.append(None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*parts)))


# ---------------------------------------------------------------------------
# Parameter partition specs from path conventions
# ---------------------------------------------------------------------------

# (regex on path, logical axes per trailing dim).  The first matching rule
# wins.  Stacked scan layers carry a leading repeat dim -> None is prepended
# automatically when ndim exceeds the rule arity.
_PARAM_RULES = [
    (r"embed",                     ("vocab", None)),
    # head: V over model only — D-over-data conflicts with batch-over-data
    # in the loss matmul and forced full-V logits + f32 full grads (dry-run)
    (r"lm_head|head_cb\d+",        (None, "vocab")),
    # expert dim already consumes the data axis (EP) — no fsdp on top
    (r"experts.*w_(gate|up)",      ("experts", None, "expert_ffn")),
    (r"experts.*w_out",            ("experts", "expert_ffn", None)),
    (r"shared.*w_(gate|up)",       ("fsdp", "expert_ffn")),
    (r"shared.*w_out",             ("expert_ffn", "fsdp")),
    (r"router|gate_w",             (None, None)),
    (r"\bwq\b|wq$|q_b|q_proj",     ("fsdp", "heads")),
    (r"wk|wv|kv_b",                ("fsdp", "kv_heads")),
    (r"\bwo\b|wo$",                ("heads", "fsdp")),
    (r"q_a|wkv_a|kv_a",            ("fsdp", None)),
    (r"b_q|b_k|b_v",               ("heads",)),
    (r"w_(gate|up|in)",            ("fsdp", "ffn")),
    (r"w_out",                     ("ffn", "fsdp")),
    (r"in_proj_(b|c|dt)",          ("fsdp", None)),     # tiny N/H dims: replicate
    (r"in_proj",                   ("fsdp", "ssm_inner")),
    (r"out_proj",                  ("ssm_inner", "fsdp")),
    (r"conv_w_(b|c)|conv_bias_(b|c)", (None, None)),
    (r"conv_w",                    ("ssm_inner", None)),
    (r"conv_bias|gn_gamma",        ("ssm_inner",)),
    (r"A_log|dt_bias|\bD\b|D$",    (None,)),     # tiny per-head params: replicate
    (r"norm|gamma|scale",          (None,)),
]

# Serving (paged-inference) overrides: gather-based tensor parallelism.
# Row-parallel weights keep their contraction dim REPLICATED — the activation
# is all-gathered just before the matmul (data movement only), so every fp
# reduction stays device-local and the sharded engine is bit-identical to the
# unsharded one.  The alternative (Megatron-style partial-sum + psum) floats
# ~1-ulp reassociation diffs into the pool's int8 ``round()`` boundaries,
# which compound into greedy argmax flips — serving's token-parity contract
# forbids that.  Column-parallel projections keep the model-axis sharding:
# each output column sees its full contraction locally.  ``fsdp`` (the data
# axis) is dropped entirely: inside a replica it is the replica axis, not a
# weight-shard axis.
_SERVING_PARAM_OVERRIDES = [
    (r"experts.*w_out",            ("experts", None, None)),
    (r"shared.*w_out",             (None, None)),
    (r"\bwo\b|wo$",                (None, None)),
    (r"w_out",                     (None, None)),
    (r"out_proj",                  (None, None)),
]


def param_logical_axes(path: str, ndim: int,
                       serving: bool = False) -> Tuple[Optional[str], ...]:
    p = path.lower()
    table = (_SERVING_PARAM_OVERRIDES + _PARAM_RULES) if serving \
        else _PARAM_RULES
    for pat, axes in table:
        if re.search(pat, p):
            axes = tuple(axes)
            if serving:
                axes = tuple(None if a == "fsdp" else a for a in axes)
            if len(axes) < ndim:                       # leading scan/stack dims
                axes = (None,) * (ndim - len(axes)) + axes
            return axes[:ndim] if len(axes) >= ndim else axes
    return (None,) * ndim


def param_spec(mesh: Mesh, path: str, shape: Tuple[int, ...],
               serving: bool = False) -> P:
    """PartitionSpec for one parameter; drops non-divisible axes."""
    axes = param_logical_axes(path, len(shape), serving=serving)
    ctx = _current()
    rules = ctx[1] if ctx else DEFAULT_RULES
    parts = []
    for dim, ax in zip(shape, axes):
        if ax is None:
            parts.append(None)
            continue
        cand = tuple(a for a in rules.get(ax, ()) if a in mesh.axis_names)
        size = int(np.prod([mesh.shape[a] for a in cand])) if cand else 1
        if size > 1 and dim % size == 0:
            parts.append(cand[0] if len(cand) == 1 else tuple(cand))
        else:
            parts.append(None)
    return P(*parts)


def blocked_state_spec(mesh: Mesh, param_path: str, shape: Tuple[int, ...]) -> P:
    """Spec for a shape-preserving blocked optimizer-state leaf.

    values/scale have the parameter's dims with the last split into
    (nb, bs) / (nb, 1): the parameter's axes apply to dims [:-1] (the last
    landing on nb) and the trailing block dim stays unsharded.
    """
    axes = param_logical_axes(param_path, len(shape) - 1) + (None,)
    ctx = _current()
    rules = ctx[1] if ctx else DEFAULT_RULES
    parts = []
    for dim, ax in zip(shape, axes):
        if ax is None:
            parts.append(None)
            continue
        cand = tuple(a for a in rules.get(ax, ()) if a in mesh.axis_names)
        size = int(np.prod([mesh.shape[a] for a in cand])) if cand else 1
        if size > 1 and dim % size == 0:
            parts.append(cand[0] if len(cand) == 1 else tuple(cand))
        else:
            parts.append(None)
    return P(*parts)


def mesh_fingerprint(mesh: Optional[Mesh],
                     rules: Optional[Dict[str, Tuple[str, ...]]] = None):
    """Hashable identity of (mesh, rule table) for jit-cache keys.

    Two engines whose meshes differ in axis layout *or* device assignment
    must not share a compiled step (the in/out shardings baked into the
    executable differ), so the fingerprint covers axis names, sizes, the
    flat device ids, and any rule overrides.  ``None`` mesh -> ``None``.
    """
    if mesh is None:
        return None
    dev = tuple(int(d.id) for d in mesh.devices.flat)
    shape = tuple((str(a), int(mesh.shape[a])) for a in mesh.axis_names)
    rule_items = tuple(sorted((k, tuple(v)) for k, v in (rules or {}).items()))
    return (shape, dev, rule_items)


# ---------------------------------------------------------------------------
# Paged pool / SSM state-pool partition specs (serving)
# ---------------------------------------------------------------------------

# leaf name -> logical axes (arity must match the leaf rank *including* the
# leading scan-repeat dim).  GQA block leaves shard the kv-head axis over
# `model` (kv_heads rule); MLA latent leaves are replicated (latent -> ());
# SSM ssd leaves shard the head axis.  Block/slot/token axes never shard —
# the host-side allocator indexes them freely.
_POOL_RULES: Dict[str, Tuple[Optional[str], ...]] = {
    # GQA paged KV pool: codes (R, N+1, T, KH*D), V affine (R, KH, T, N+1)
    "k_vals":  (None, None, None, "kv_heads"),
    "v_vals":  (None, None, None, "kv_heads"),
    "v_scale": (None, "kv_heads", None, None),
    "v_zero":  (None, "kv_heads", None, None),
    "k_scale": (None, None, "kv_heads", None),
    "k_zero":  (None, None, "kv_heads", None),
    # MLA latent pool: codes (R, N+1, T, rkv), per-token scales (R, T, N+1),
    # replicated
    "c_vals":  (None, None, None, None),
    "kr_vals": (None, None, None, None),
    "c_scale": (None, None, None),
    "kr_scale": (None, None, None),
    # SSM state pool
    "conv":      (None, None, None, None),
    "ssd_vals":  (None, None, "heads", None, None),
    "ssd_scale": (None, None, "heads"),
}


# GQA code leaves whose last dim packs the kv heads with their channels
_HEAD_PACKED = ("k_vals", "v_vals")


def pool_spec(mesh: Mesh, name: str, shape: Tuple[int, ...],
              heads: Optional[int] = None) -> P:
    """PartitionSpec for one pool leaf; drops non-divisible axes.  ``heads``
    is the entry's kv-head count: a dim that packs heads with their channels
    splits only into whole heads."""
    axes = _POOL_RULES.get(name, (None,) * len(shape))
    if len(axes) != len(shape):
        axes = (None,) * len(shape)
    ctx = _current()
    rules = ctx[1] if ctx else DEFAULT_RULES
    parts = []
    for dim, ax in zip(shape, axes):
        if ax is None:
            parts.append(None)
            continue
        cand = tuple(a for a in rules.get(ax, ()) if a in mesh.axis_names)
        size = int(np.prod([mesh.shape[a] for a in cand])) if cand else 1
        unit = heads if heads is not None and name in _HEAD_PACKED else dim
        if size > 1 and unit % size == 0:
            parts.append(cand[0] if len(cand) == 1 else tuple(cand))
        else:
            parts.append(None)
    return P(*parts)


def tree_pool_shardings(mesh: Mesh, pool) -> "jax.tree_util.PyTreeDef":
    """NamedSharding pytree for a paged-cache / state-pool dict (pool dicts
    nest as ``{"p0": {"k_vals": ...}}``); a GQA entry's kv-head count is its
    ``k_scale``'s second-to-last dim."""
    def entry(leaves):
        ks = leaves.get("k_scale")
        heads = None if ks is None else int(ks.shape[-2])
        return {name: NamedSharding(mesh, pool_spec(mesh, name, leaf.shape,
                                                    heads))
                for name, leaf in leaves.items()}
    return {pkey: entry(leaves) for pkey, leaves in pool.items()}


def tree_param_shardings(mesh: Mesh, params,
                         serving: bool = False) -> "jax.tree_util.PyTreeDef":
    """NamedSharding pytree matching ``params`` (works on ShapeDtypeStructs
    and on QTensor-containing trees: QTensor fields inherit from the path).

    ``serving=True`` applies the gather-based-TP overrides (row-parallel
    weights replicated on their contraction dim) — the paged engines' bit-
    stability contract requires every matmul reduction to be device-local.
    """
    def visit(path, leaf):
        ps = "/".join(
            str(getattr(k, "key", None) or getattr(k, "idx", None)
                or getattr(k, "name", None) or str(k).lstrip("."))
            for k in path)
        if hasattr(leaf, "shape"):
            return NamedSharding(
                mesh, param_spec(mesh, ps, leaf.shape, serving=serving))
        return NamedSharding(mesh, P())
    return jax.tree_util.tree_map_with_path(visit, params)
