"""Elastic scaling: re-mesh + re-shard on node loss (DESIGN.md §4).

JAX is single-controller SPMD: a lost host cannot be papered over inside a
step.  The production recovery loop is

    failure detected -> job restarts on the surviving N' hosts ->
    ``plan_remesh`` picks the best (data, model) factorization for N' chips ->
    checkpoint restored with the *new* shardings (CheckpointManager.restore
    accepts target shardings) -> training resumes at latest step.

``plan_remesh`` keeps the model axis as close to the original as possible
(TP degree is a numerics-neutral choice but shapes must still divide) and
absorbs chip loss into the data axis, preferring batch-divisor-friendly
sizes so global batch is preserved via gradient accumulation.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

from repro.launch.mesh import make_mesh


@dataclasses.dataclass(frozen=True)
class RemeshPlan:
    shape: Tuple[int, ...]
    axis_names: Tuple[str, ...]
    grad_accum: int                  # extra accumulation to keep global batch
    dropped_chips: int

    def describe(self) -> str:
        dims = "x".join(map(str, self.shape))
        return (f"mesh {dims} ({','.join(self.axis_names)}), "
                f"grad_accum={self.grad_accum}, dropped={self.dropped_chips}")


def _divisors_desc(n: int):
    return sorted({d for i in range(1, int(np.sqrt(n)) + 1) if n % i == 0
                   for d in (i, n // i)}, reverse=True)


def plan_remesh(n_available: int, *, old_data: int, old_model: int,
                global_batch: int, model_divisors: Sequence[int] = ()
                ) -> RemeshPlan:
    """Pick (data, model) for n_available chips after failures.

    model_divisors: acceptable TP degrees (e.g. head counts' divisors);
    defaults to divisors of old_model.
    """
    acceptable_tp = list(model_divisors) or _divisors_desc(old_model)
    best = None
    for tp in sorted(acceptable_tp, key=lambda t: abs(t - old_model)):
        if tp <= 0 or tp > n_available:
            continue
        dp = n_available // tp
        if dp == 0:
            continue
        used = dp * tp
        # prefer dp dividing global_batch (else pad batch), maximize usage
        accum = max(1, int(np.ceil((old_data * 1.0) / dp)))
        waste = n_available - used
        score = (waste, abs(tp - old_model), accum)
        if best is None or score < best[0]:
            best = (score, RemeshPlan(shape=(dp, tp), axis_names=("data", "model"),
                                      grad_accum=accum, dropped_chips=waste))
    if best is None:
        raise ValueError(f"cannot form a mesh from {n_available} chips")
    return best[1]


def build_mesh(plan: RemeshPlan, devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    n = int(np.prod(plan.shape))
    return make_mesh(plan.shape, plan.axis_names, devices=devices[:n])
