"""Data-parallel replica serving: sharded block pools + request routing.

The paper's headline system claim is near-linear multi-GPU scaling with
NCCL-synchronized quantization state (§3.3, Thm 4).  This module is that
distributed controller layer over the paged serving stack: N independent
:class:`~repro.serving.scheduler.Scheduler` replicas — one per ``data``-axis
mesh slice when a mesh is live, N host-side replicas otherwise — each owning
a *shard* of the global block budget, behind the familiar single-engine
``add_request`` / ``step`` / ``run`` / ``metrics`` frontend.

  * **Sharded block pools** — the global ``num_blocks`` budget is split
    (near-)evenly across replicas; each replica's allocator, prefix index and
    device pool are private, so replicas never contend and the conservation
    invariant holds per shard (property-tested).
  * **Pluggable routing** — ``round_robin`` (stateless spread),
    ``least_loaded`` (min live-token count: running context + queued prompt
    tokens), and ``prefix_affinity``: the first full prompt block is hashed
    with the *same* blake2b chain digest the scheduler's prefix index uses
    (``_prefix_keys``), so every request sharing a >= 1-block prefix lands
    deterministically on the replica that already published those blocks —
    cross-replica traffic turns into intra-replica prefix hits.
  * **Synced EMA scales** — every ``sync_every`` frontend steps the
    per-replica :class:`EmaScaleState` trackers are reduced to one shared
    ``(delta, z)`` via :func:`repro.distributed.scale_sync.reduce_ema_states`
    (``pmax``/``pmean`` inside ``shard_map`` when a mesh is live, numpy
    max-reduce otherwise) and written back, so all replicas quantize runtime
    activations with identical parameters (Thm 4 consistency).  The sync
    never touches sampling, so greedy outputs are unaffected — the golden
    tests assert a request routed to replica A emits exactly the tokens a
    fresh single-engine baseline emits.
  * **Drain / re-route** — ``drain_replica(i)`` quiesces one replica through
    the scheduler's drain hook and re-routes its not-yet-admitted requests to
    the survivors, the building block for elastic replica counts.
  * **Speculative decoding** — a ``SchedulerConfig.spec`` setting is applied
    per replica (each scheduler owns a draft-proposer lane set; the draft's
    jitted fns are shared through the module-level cache, and ``draft_bits=0``
    self-drafts share the target weights by reference).  ``metrics()``
    aggregates acceptance rate and tokens-per-step as ratios of summed
    counters — weighted by the tokens each replica actually served, never a
    naive mean of per-replica rates.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.obs import SERVING_HISTS, MetricsRegistry, clock
from repro.serving.scheduler import (Scheduler, SchedulerConfig,
                                     _prefix_keys, ensure_paged_supported)

ROUTING_POLICIES = ("round_robin", "least_loaded", "prefix_affinity")


def _replica_submesh(mesh, i: int):
    """Replica ``i``'s device slice: the full ``model`` axis at data-index
    ``i``.  The data axis survives with size 1 so the rule table needs no
    rewriting — size-1 axes are dropped by the divisibility fallback, and
    ``experts`` (EP over ``data``) degenerates to replicated inside one
    replica while ``heads``/``ffn``/``vocab`` still shard over ``model``."""
    ax = list(mesh.axis_names)
    if "data" not in ax:
        return mesh
    dev = np.take(mesh.devices, [i], axis=ax.index("data"))
    return Mesh(dev, mesh.axis_names, axis_types=mesh.axis_types)


@dataclasses.dataclass(frozen=True)
class ReplicaConfig:
    n_replicas: int = 2
    policy: str = "prefix_affinity"      # see ROUTING_POLICIES
    sync_every: int = 8                  # frontend steps between EMA scale
                                         # syncs; 0 disables syncing


def shard_blocks(num_blocks: int, n: int, kind: str = "block") -> List[int]:
    """Split a global budget (near-)evenly: the first ``num_blocks % n``
    replicas get one extra unit.  Used for the KV block budget and — with
    ``kind="state slot"`` — the hybrid SSM state-slot budget."""
    base, rem = divmod(num_blocks, n)
    if base < 1:
        raise ValueError(
            f"cannot shard {num_blocks} {kind}s over {n} replicas; "
            f"every replica needs at least one {kind}")
    return [base + (1 if i < rem else 0) for i in range(n)]


class ReplicatedServeEngine:
    """N data-parallel scheduler replicas behind a single-engine frontend.

    ``params`` is shared by reference (weights are read-only under the jitted
    step; only the per-replica pool is donated), so host memory holds one
    copy of the model no matter how many replicas serve it.  ``mesh`` is
    optional: when given, the EMA scale sync runs as the collective fast path
    over its ``data`` axis; the control plane stays host-side either way.
    """

    def __init__(self, params, cfg, scfg: Optional[SchedulerConfig] = None,
                 rcfg: Optional[ReplicaConfig] = None, mesh=None,
                 tracer=None):
        """``tracer``: optional shared :class:`repro.obs.Tracer`; replica
        ``i`` records on trace track ``i``, so the Chrome-trace export shows
        one process per replica."""
        scfg = scfg or SchedulerConfig()
        rcfg = rcfg or ReplicaConfig()
        if rcfg.policy not in ROUTING_POLICIES:
            raise ValueError(f"unknown routing policy {rcfg.policy!r}; "
                             f"expected one of {ROUTING_POLICIES}")
        if rcfg.n_replicas < 1:
            raise ValueError("n_replicas must be >= 1")
        if mesh is not None and mesh.shape.get("data", 1) != rcfg.n_replicas:
            raise ValueError(
                f"mesh data-axis size {mesh.shape.get('data', 1)} != "
                f"n_replicas {rcfg.n_replicas}")
        # capability gate before any replica is built: an unsupported layout
        # must fail here with the same clear error the single engine gives,
        # not crash inside replica 0's constructor
        ensure_paged_supported(cfg)
        self.cfg = cfg
        self.scfg = scfg
        self.rcfg = rcfg
        self.mesh = mesh
        self.shards = shard_blocks(scfg.num_blocks, rcfg.n_replicas)
        # an explicit global state-slot budget (hybrid SSM patterns) shards
        # the same way the block budget does; the 0-default leaves each
        # replica at its own max_batch worth of slots
        slot_shards = (shard_blocks(scfg.num_state_slots, rcfg.n_replicas,
                                    kind="state slot")
                       if scfg.num_state_slots else
                       [0] * rcfg.n_replicas)
        self.state_slot_shards = slot_shards
        # replica 0 builds the (possibly re-quantized / truncated) draft
        # tree; the rest inject it by reference — one quantization pass and
        # one copy of the draft weights per fleet, not per replica
        self.replicas = []
        draft_built = None
        for i, (nb, ss) in enumerate(zip(self.shards, slot_shards)):
            # with a live mesh each replica is pinned to its own data-axis
            # device slice (a (1, model) submesh): its params are committed
            # tensor-parallel over `model`, its pool kv-head-sharded over the
            # same devices, and its fused step compiles against exactly that
            # slice — replicas stepped via step_launch/step_consume then run
            # concurrently on disjoint devices
            sub = _replica_submesh(mesh, i) if mesh is not None else None
            rep = Scheduler(params, cfg,
                            dataclasses.replace(scfg, num_blocks=nb,
                                                num_state_slots=ss),
                            draft_built=draft_built, mesh=sub,
                            tracer=tracer, trace_track=i)
            if rep.draft is not None and draft_built is None:
                draft_built = (rep.draft.dparams, rep.draft.dcfg)
            self.replicas.append(rep)
        self.routed: Dict[Any, int] = {}     # uid -> replica index
        self._rr = 0                         # round-robin cursor
        self._steps = 0
        self.scale_syncs = 0
        self.tracer = tracer
        self._t_start: Optional[float] = None
        self._t_last = 0.0

    # -- routing --------------------------------------------------------------
    def _affinity_key(self, prompt) -> Optional[bytes]:
        """Chain digest of the first full prompt block — byte-identical to
        key 0 of the scheduler's ``_prefix_keys`` chain, so equal keys here
        imply an index match there."""
        prompt = np.asarray(prompt)
        bs = self.scfg.block_size
        if prompt.shape[-1] < bs:
            return None
        return _prefix_keys(prompt[..., :bs], bs)[0]

    def _route(self, req, exclude: Optional[int] = None) -> int:
        cand = [i for i in range(self.rcfg.n_replicas) if i != exclude]
        if not cand:
            raise ValueError("no replica left to route to")
        policy = self.rcfg.policy
        if policy == "prefix_affinity":
            key = self._affinity_key(req.prompt)
            if key is not None:
                i = int.from_bytes(key[:8], "big") % self.rcfg.n_replicas
                if i != exclude:
                    return i
            # sub-block prompt (nothing to share) or excluded target:
            # fall through to load balancing
            policy = "least_loaded"
        if policy == "least_loaded":
            return min(cand, key=lambda i: (self.replicas[i].live_tokens, i))
        i = cand[self._rr % len(cand)]
        self._rr += 1
        return i

    # -- public API -----------------------------------------------------------
    def _is_live(self, uid) -> bool:
        """True while ``uid`` is queued or running in its routed replica."""
        i = self.routed.get(uid)
        if i is None:
            return False
        rep = self.replicas[i]
        return (any(r.req.uid == uid for r in rep.waiting) or
                any(r is not None and r.req.uid == uid for r in rep.slots))

    def add_request(self, req) -> int:
        """Route and enqueue; returns the chosen replica index.  A live uid
        is routed exactly once — re-submitting it before it finishes is an
        error (the property tests assert no request ever lives in two
        replicas); a finished uid may be reused.  ``routed`` records each
        uid's current (last) home and, like the engines' ``finished`` lists,
        grows with the total requests served."""
        if self._is_live(req.uid):
            raise ValueError(f"request {req.uid} was already routed to "
                             f"replica {self.routed[req.uid]} and is still "
                             f"live")
        i = self._route(req)
        self.replicas[i].add_request(req)    # may raise (capacity) first
        self.routed[req.uid] = i
        return i

    def step(self) -> bool:
        """One frontend iteration: *launch* every replica's fused step before
        consuming any of them (jax dispatch is async, so replicas pinned to
        disjoint device slices overlap their compute instead of serializing
        through this host loop), then sync EMA scale state on the configured
        cadence."""
        if self._t_start is None:
            self._t_start = clock()
        launched = [(r, r.step_launch())
                    for r in self.replicas if r.has_work]
        progressed = False
        for r, ctx in launched:
            progressed = r.step_consume(ctx) or progressed
        self._steps += 1
        if progressed:
            self._t_last = clock()
        if self.rcfg.sync_every and self._steps % self.rcfg.sync_every == 0:
            self.sync_scales()
        return progressed

    def run(self, max_steps: int = 10_000) -> List[Any]:
        steps = 0
        while self.has_work and steps < max_steps:
            self.step()
            steps += 1
        return self.finished

    def drain_replica(self, i: int, max_steps: int = 10_000) -> int:
        """Quiesce replica ``i``: its queued (not yet started) requests are
        re-routed to the other replicas, its in-flight work runs to
        completion.  A request no survivor can hold (shard capacity) stays
        home rather than being lost.  Returns the number of requests
        moved."""
        if self.rcfg.n_replicas < 2:
            raise ValueError("cannot drain the only replica")
        handed = self.replicas[i].drain(max_steps)
        moved = 0
        for req in handed:
            first = self._route(req, exclude=i)
            order = [first] + [k for k in range(self.rcfg.n_replicas)
                               if k != i and k != first]
            for j in order:                  # preferred survivor, then rest
                try:
                    self.replicas[j].add_request(req)
                except ValueError:           # oversized for this shard
                    continue
                self.routed[req.uid] = j
                moved += 1
                break
            else:
                self.replicas[i].add_request(req)   # no survivor can hold it
        return moved

    def sync_scales(self):
        """Reduce per-replica EMA scale states to one shared state and write
        it back (paper Eq. 7-8 over replicas; Thm 4: every replica now
        quantizes runtime activations with identical (delta, z))."""
        from repro.distributed.scale_sync import reduce_ema_states
        shared = reduce_ema_states([r.scale_state for r in self.replicas],
                                   mesh=self.mesh)
        for r in self.replicas:
            # replicated on the replica's own devices: its next sampling step
            # must not mix its submesh with the whole mesh
            r.scale_state = shared if r.mesh is None else jax.device_put(
                shared, NamedSharding(r.mesh, P()))
        self.scale_syncs += 1
        return shared

    # -- introspection --------------------------------------------------------
    @property
    def has_work(self) -> bool:
        return any(r.has_work for r in self.replicas)

    @property
    def finished(self) -> List[Any]:
        return [req for r in self.replicas for req in r.finished]

    @property
    def num_replicas(self) -> int:
        return self.rcfg.n_replicas

    @property
    def stats(self) -> Dict[str, int]:
        """Summed scheduler counters across replicas (frontend parity with
        the single-engine ``stats`` dict)."""
        out: Dict[str, int] = {}
        for r in self.replicas:
            for k, v in r.stats.items():
                out[k] = out.get(k, 0) + v
        return out

    @property
    def scale_state(self):
        """Replica 0's EMA tracker — identical on every replica right after
        a ``sync_scales()`` (the Thm 4 consistency the tests assert)."""
        return self.replicas[0].scale_state

    def metrics(self) -> Dict[str, Any]:
        """Aggregate view plus a ``per_replica`` list of each scheduler's own
        metrics (the bench reports tokens/s and prefix-hit-rate per replica
        from it)."""
        per = [r.metrics() for r in self.replicas]
        # same zero guard as Scheduler.metrics(): before any step ran there
        # is no wall, and `_t_last - 0.0` would fake an epoch-sized one
        if self._t_start is None:
            wall = 0.0
        else:
            wall = max(self._t_last - self._t_start, 1e-9)
        gen = sum(r.stats["decode_tokens"] + r.stats["first_tokens"]
                  for r in self.replicas)
        done = [req for r in self.replicas for req in r.finished]
        hit = sum(r.stats["prefix_hit_tokens"] for r in self.replicas)
        query = sum(r.stats["prefix_query_tokens"] for r in self.replicas)
        # speculative-decoding aggregates are ratios of summed counters —
        # weighted by the tokens each replica actually proposed/emitted.  A
        # naive mean of per-replica rates would let an idle replica's 0/0
        # (or a lightly-loaded one's lucky streak) drag the fleet number
        # away from what the traffic experienced.
        proposed = sum(r.stats["spec_proposed"] for r in self.replicas)
        accepted = sum(r.stats["spec_accepted"] for r in self.replicas)
        emitted = sum(r.stats["spec_emitted"] for r in self.replicas)
        lane_rounds = sum(r.stats["spec_lane_rounds"] for r in self.replicas)
        # teacher-forced scoring aggregates follow the same rule: counters
        # and latencies are summed, the per-request mean is a ratio of the
        # sums — a replica that scored nothing must not drag the average
        score_req = sum(r.stats["score_requests"] for r in self.replicas)
        score_tok = sum(r.stats["score_tokens"] for r in self.replicas)
        score_lat = sum(m["score_latency_s"] for m in per)
        # latency percentiles come from *merged* per-replica histograms —
        # every request weighs once.  Averaging per-replica percentiles (or
        # averages) would weight an idle replica's two requests equally with
        # a loaded replica's two hundred.
        merged = MetricsRegistry.merged([r.mreg for r in self.replicas])
        out = {
            "replicas": self.rcfg.n_replicas,
            "requests_finished": len(done),
            "tokens_per_s": gen / wall if wall else 0.0,
            "wall_s": wall,
            "ttft_avg_s": (float(np.mean([r.ttft_s for r in done]))
                           if done else 0.0),
            "ttft_max_s": (float(np.max([r.ttft_s for r in done]))
                           if done else 0.0),
            "prefix_hit_tokens": hit,
            "prefix_hit_rate": hit / max(query, 1),
            "preemptions": sum(r.stats["preemptions"] for r in self.replicas),
            "spec_rounds": sum(r.stats["spec_rounds"] for r in self.replicas),
            "spec_accept_rate": accepted / max(proposed, 1),
            "spec_tokens_per_step": emitted / max(lane_rounds, 1),
            "spec_draft_nbytes": sum(m["spec_draft_nbytes"] for m in per),
            "cache_nbytes": sum(m["cache_nbytes"] for m in per),
            "state_pool_nbytes": sum(m["state_pool_nbytes"] for m in per),
            # cache codec / bit ladder fleet totals; the weight-bits summary
            # comes from replica 0 (every replica quantized the same params
            # under the same budget, so the assignments are identical)
            "demotions": sum(m["demotions"] for m in per),
            "promotions": sum(m["promotions"] for m in per),
            "int4_blocks": sum(m["int4_blocks"] for m in per),
            "effective_cache_bytes": sum(m["effective_cache_bytes"]
                                         for m in per),
            "state_prefix_hits": sum(m["state_prefix_hits"] for m in per),
            "score_requests": score_req,
            "score_tokens": score_tok,
            "score_latency_s": score_lat,
            "score_latency_avg_s": score_lat / max(score_req, 1),
            "score_tokens_per_s": score_tok / wall if wall else 0.0,
            "weight_bits_min": per[0]["weight_bits_min"],
            "weight_bits_max": per[0]["weight_bits_max"],
            "weight_bits_avg": per[0]["weight_bits_avg"],
            "scale_syncs": self.scale_syncs,
            "per_replica": per,
        }
        out.update(merged.summary(SERVING_HISTS))
        return out

    def export_chrome_trace(self, path: str) -> Dict[str, Any]:
        """Write the fleet's trace as Chrome-trace JSON (requires a shared
        ``tracer`` at construction; each replica is its own process row)."""
        if self.tracer is None:
            raise ValueError("fleet was built without a tracer; pass "
                             "tracer=Tracer() to ReplicatedServeEngine")
        return self.tracer.export_chrome_trace(path)

    def debug_snapshot(self) -> Dict[str, Any]:
        """Per-replica scheduler/allocator postmortem dumps."""
        return {"replicas": [r.debug_snapshot() for r in self.replicas]}
