"""Distributed quantization-scale synchronization (paper §3.3, Eq. 7-8, Thm 4).

The paper all-gathers per-shard (delta, z) over NCCL so every rank quantizes
with identical parameters.  TPU/JAX adaptation (DESIGN.md §2): the *raw
statistics* are reduced with ``lax.pmax`` / ``lax.pmean`` over the mesh axes
inside ``shard_map`` — max-of-absmax is the exact global absmax (a strictly
stronger consistency than gather-then-union, with one collective instead of
two).  Thm 4's determinism argument carries over verbatim: psum/pmax are
deterministic collectives, so all shards hold bit-identical (delta, z).

``sync_ema_state`` is the distributed version of Alg. 1: per-shard stats ->
collective reduce -> shared EMA update.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.online import EmaScaleState


def global_absmax(x: jax.Array, axis_names: Sequence[str]) -> jax.Array:
    """Inside shard_map/pjit: exact global absmax across mesh axes."""
    r = jnp.max(jnp.abs(x))
    for ax in axis_names:
        r = jax.lax.pmax(r, ax)
    return r


def global_mean(x: jax.Array, axis_names: Sequence[str]) -> jax.Array:
    m = jnp.mean(x)
    for ax in axis_names:
        m = jax.lax.pmean(m, ax)
    return m


def sync_scale_allgather(delta_local: jax.Array, axis_name: str) -> jax.Array:
    """Paper Eq. 7 literal form: all-gather shards' scales then reduce (max).

    Provided for parity benchmarking against the pmax fast path; both yield
    identical results (tests/distributed assert this)."""
    gathered = jax.lax.all_gather(delta_local, axis_name)     # (P, ...)
    return jnp.max(gathered, axis=0)


def reduce_ema_states(states: Sequence[EmaScaleState], *,
                      mesh: Optional[Mesh] = None,
                      axis: str = "data") -> EmaScaleState:
    """Reduce N replicas' EMA scale states to one shared state.

    The entry point usable *outside* ``shard_map`` — the serving layer's
    replica controller calls it with one :class:`EmaScaleState` per engine
    replica.  Reductions follow Eq. 7-8: ``delta`` takes the max (exact
    global absmax — the same strictly-stronger-than-gather consistency as
    :func:`global_absmax`), ``mu`` the mean, ``step`` the max.

    With a live mesh whose ``axis`` size equals ``len(states)`` the
    reduction runs as the ``pmax``/``pmean`` collective inside ``shard_map``
    (Thm 4 fast path: deterministic collectives, bit-identical result on all
    shards).  Otherwise — the host-side replica case, e.g. a single-device
    test process — a numpy max/mean-reduce produces the same values.
    """
    if not states:
        raise ValueError("reduce_ema_states needs at least one state")
    if len(states) == 1:
        return states[0]
    # per-replica states may be committed to *disjoint* device slices of a
    # 2D serving mesh (each replica samples on its own data-slice) —
    # jnp.stack refuses to mix committed placements, so pull to host first
    # and re-place along the reduce axis for the collective fast path
    d = np.stack([np.asarray(jax.device_get(s.delta)) for s in states])
    m = np.stack([np.asarray(jax.device_get(s.mu)) for s in states])
    if mesh is not None and mesh.shape.get(axis, 1) == len(states):
        d = jax.device_put(d, NamedSharding(mesh, P(axis)))
        m = jax.device_put(m, NamedSharding(mesh, P(axis)))

        @partial(shard_map, mesh=mesh, in_specs=(P(axis), P(axis)),
                 out_specs=(P(), P()), check_vma=False)
        def _reduce(dl, ml):
            return jax.lax.pmax(dl[0], axis), jax.lax.pmean(ml[0], axis)

        delta, mu = _reduce(d, m)
    else:
        delta = jnp.asarray(np.max(np.asarray(d), axis=0))
        mu = jnp.asarray(np.mean(np.asarray(m), axis=0))
    step = max(int(np.asarray(s.step)) for s in states)
    return EmaScaleState(delta=delta, mu=mu,
                         step=jnp.asarray(step, jnp.int32))


def make_synced_quant_step(mesh: Mesh, *, alpha: float = 0.9, bits: int = 8,
                           axes: Tuple[str, ...] = ("data",)):
    """Build a jitted distributed AsyncQuant step over ``mesh``.

    Returns f(x_sharded, state) -> (qvalues int8 sharded like x, new state
    replicated).  x shards along its leading dim over ``axes``.
    """
    from repro.core.online import async_quant_update

    in_spec = (P(axes), P())
    out_spec = (P(axes), P())

    @partial(shard_map, mesh=mesh, in_specs=in_spec, out_specs=out_spec,
             check_vma=False)
    def step(x, state):
        reduce_fn = lambda s: jax.lax.pmax(s, axes)
        q, new_state = async_quant_update(x, state, alpha=alpha, bits=bits,
                                          reduce_fn=reduce_fn)
        return q.values, new_state

    return jax.jit(step)
