"""Serving-path benchmark: offered-load, shared-prefix and replica sweeps.

For each offered load (requests injected per engine step) the sweep drives
the paged scheduler end-to-end and reports TTFT, decode throughput, cache
utilization and preemptions — the serving counterpart of the kernel-level
latency tables, giving the paged/chunked-prefill stack a perf trajectory
across PRs.  A dense-engine row at the same traffic anchors the comparison
(memory column = allocated KV-cache bytes).

The shared-prefix sweep replays the many-users-one-system-prompt regime:
every request shares a common prefix, run once with the prefix cache off
(cold) and once on (warm) — the warm row's ``prefix_hit_rate`` and the TTFT
delta are the prefix-caching win.

The replica sweep drives ``ReplicatedServeEngine`` at a fixed offered load
for replica counts {1, 2} (plus 4 in full mode) and reports aggregate and
per-replica tokens/s and prefix-hit-rate — the data-parallel scaling
trajectory (paper Thm 4 regime).  A cold-vs-warm routing pair at 2 replicas
contrasts ``round_robin`` (shared-prefix traffic scattered across pools)
with ``prefix_affinity`` (same chain digest as the prefix index, so shared
prefixes land on the replica that already published them).

The hybrid sweep (``experiments/bench/serving_hybrid.csv``) drives a
Jamba-pattern (attention+SSM) config through the paged engine and the dense
engine at the same traffic: tokens/s side by side, plus the memory column
that motivates the state pool — allocated INT8 state-pool bytes vs the f32
SSD layout the dense slot cache would have paid pre-quantization.

The spec sweep (``experiments/bench/serving_spec.csv``) serves a W8A8
checkpoint on shared-prefix traffic plain and speculatively over
``gamma ∈ {2, 4}`` × draft bitwidth {int8 self-draft (shares the target's
W8A8 weights), int4 weight-only re-quantized}: acceptance rate, mean emitted
tokens per verify step (the >1 signal that speculation actually batches
decode), tokens/s, and the draft memory bill per point.

Run directly:  PYTHONPATH=src python -m benchmarks.bench_serving [--smoke]
``--smoke`` shrinks traffic so the whole bench — replica sweep included —
finishes in ~30 s (tier-1-loop friendly; scheduler step compiles are shared
across engines via the module-level jit cache, so extra engines cost
traffic, not recompiles).
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from benchmarks.common import emit
from repro.models import ModelConfig, init_params
from repro.models.config import LayerSpec
from repro.serving.engine import EngineConfig, PagedServeEngine, Request, ServeEngine
from repro.serving.kv_cache import cache_nbytes
from repro.serving.scheduler import SchedulerConfig

SERVE_CFG = ModelConfig(
    name="serve-bench", vocab_size=512, d_model=128, n_layers=2, n_heads=4,
    n_kv_heads=2, d_ff=512, layer_pattern=(LayerSpec("attn", "dense"),),
    attn_chunk=64)

N_REQUESTS = 16
MAX_NEW = 16
SMAX = 128                       # dense per-slot capacity
SCFG = SchedulerConfig(block_size=16, num_blocks=24, max_batch=4,
                       max_blocks_per_req=8, prefill_chunk=32,
                       token_budget=64)         # 24*16=384 pooled tokens vs
                                                # the dense 4*128=512


def _requests(rng, n, max_new):
    """Mixed-length prompt batch (8..64 tokens)."""
    out = []
    for i in range(n):
        s = int(rng.integers(8, 65))
        out.append(Request(uid=i,
                           prompt=rng.integers(0, 512, size=s).astype(np.int32),
                           max_new_tokens=max_new))
    return out


def _shared_prefix_requests(rng, n, max_new, prefix_len=48, groups=1):
    """Requests round-robined over ``groups`` shared system prefixes, each
    plus a short unique tail.  ``groups=1`` is the classic one-system-prompt
    regime; more groups is the regime where prefix-affinity routing
    concentrates each group's traffic (and its cache hits) on one replica."""
    prefixes = [rng.integers(0, 512, size=prefix_len).astype(np.int32)
                for _ in range(groups)]
    out = []
    for i in range(n):
        tail = rng.integers(0, 512, size=int(rng.integers(4, 17)))
        out.append(Request(
            uid=i,
            prompt=np.concatenate([prefixes[i % groups], tail.astype(np.int32)]),
            max_new_tokens=max_new))
    return out


def _has_work(eng) -> bool:
    if isinstance(eng, PagedServeEngine):
        return eng.scheduler.has_work
    if hasattr(eng, "has_work"):
        return eng.has_work
    return bool(eng.queue or eng.active)


def _drive(eng, reqs, per_step: float):
    """Inject ``per_step`` requests per engine step (offered load), drain."""
    pending = list(reqs)
    credit = 0.0
    t0 = time.perf_counter()
    while pending or _has_work(eng):
        credit += per_step
        while pending and credit >= 1.0:
            eng.add_request(pending.pop(0))
            credit -= 1.0
        if not eng.step() and not pending:
            break
    return time.perf_counter() - t0


def _paged_row(point, eng, wall):
    m = eng.metrics()
    return {
        "point": point,
        "ttft_ms": round(m["ttft_avg_s"] * 1e3, 2),
        "ttft_max_ms": round(m["ttft_max_s"] * 1e3, 2),
        "tokens_per_s": round(m["tokens_per_s"], 2),
        "cache_util_avg": round(m["cache_util_avg"], 3),
        "cache_util_peak": round(m["cache_util_peak"], 3),
        "preemptions": m["preemptions"],
        "prefix_hit_tokens": m["prefix_hit_tokens"],
        "prefix_hit_rate": round(m["prefix_hit_rate"], 3),
        "prefill_chunks": m["prefill_chunks"],
        "cache_bytes": m["cache_nbytes"],
        "wall_s": round(wall, 2),
    }


def run(smoke: bool = False):
    params = init_params(SERVE_CFG, jax.random.PRNGKey(0))
    n = 4 if smoke else N_REQUESTS
    max_new = 4 if smoke else MAX_NEW
    loads = [("high_4rps", 4.0)] if smoke else [("low_0.5rps", 0.5),
                                                ("high_4rps", 4.0)]
    rows = []
    for load_name, per_step in loads:
        rng = np.random.default_rng(7)
        eng = PagedServeEngine(params, SERVE_CFG, SCFG)
        wall = _drive(eng, _requests(rng, n, max_new), per_step)
        rows.append(_paged_row(f"paged_{load_name}", eng, wall))

    # shared-prefix sweep: identical traffic, cache off (cold) vs on (warm)
    for tag, cached in [("cold", False), ("warm", True)]:
        rng = np.random.default_rng(11)
        scfg = dataclasses.replace(SCFG, prefix_cache=cached)
        eng = PagedServeEngine(params, SERVE_CFG, scfg)
        wall = _drive(eng, _shared_prefix_requests(rng, n, max_new), 2.0)
        rows.append(_paged_row(f"shared_prefix_{tag}", eng, wall))

    if not smoke:
        # dense anchor at the high load point
        rng = np.random.default_rng(7)
        eng = ServeEngine(params, SERVE_CFG,
                          EngineConfig(max_slots=SCFG.max_batch, smax=SMAX))
        wall = _drive(eng, _requests(rng, n, max_new), 4.0)
        gen = eng.stats["decode_tokens"] + eng.stats["first_tokens"]
        done = eng.finished
        rows.append({
            "point": "dense_high_4rps",
            "ttft_ms": round(float(np.mean([r.ttft_s for r in done])) * 1e3, 2),
            "ttft_max_ms": round(float(np.max([r.ttft_s for r in done])) * 1e3, 2),
            "tokens_per_s": round(gen / max(wall, 1e-9), 2),
            "cache_util_avg": 1.0,       # dense pays full allocation always
            "cache_util_peak": 1.0,
            "preemptions": 0,
            "prefix_hit_tokens": 0,
            "prefix_hit_rate": 0.0,
            "prefill_chunks": 0,
            "cache_bytes": cache_nbytes(eng._cache),
            "wall_s": round(wall, 2),
        })
    emit(rows, "experiments/bench/serving.csv")   # before the later sweeps:
    rep_rows = _replica_sweep(params, smoke)      # their failure must not
    emit(rep_rows, "experiments/bench/serving_replicas.csv")  # discard these
    hyb_rows = _hybrid_sweep(smoke)
    emit(hyb_rows, "experiments/bench/serving_hybrid.csv")
    spec_rows = _spec_sweep(smoke)
    emit(spec_rows, "experiments/bench/serving_spec.csv")
    ladder_rows = _ladder_sweep(params, smoke)
    emit(ladder_rows, "experiments/bench/serving_ladder.csv")
    shard_rows = _sharded_sweep(smoke)
    emit(shard_rows, "experiments/bench/serving_sharded.csv")
    obs_rows = _obs_sweep(params, smoke)
    emit(obs_rows, "experiments/bench/serving_obs.csv")
    return (rows + rep_rows + hyb_rows + spec_rows + ladder_rows + shard_rows
            + obs_rows)


def _replica_row(point, eng, wall):
    m = eng.metrics()
    per_tps = ";".join(f"{p['tokens_per_s']:.1f}" for p in m["per_replica"])
    per_hit = ";".join(f"{p['prefix_hit_rate']:.3f}" for p in m["per_replica"])
    return {
        "point": point,
        "replicas": m["replicas"],
        "tokens_per_s": round(m["tokens_per_s"], 2),
        "per_replica_tokens_per_s": per_tps,
        "prefix_hit_rate": round(m["prefix_hit_rate"], 3),
        "per_replica_hit_rate": per_hit,
        "ttft_ms": round(m["ttft_avg_s"] * 1e3, 2),
        "preemptions": m["preemptions"],
        "scale_syncs": m["scale_syncs"],
        "wall_s": round(wall, 2),
    }


def _replica_sweep(params, smoke):
    """Fixed offered load, replica counts {1,2[,4]}: per-replica tokens/s +
    prefix-hit-rate, then a cold-vs-warm routing pair at 2 replicas."""
    from repro.serving.replica import ReplicaConfig, ReplicatedServeEngine
    # the 48-block global budget shards evenly over every replica count
    scfg = dataclasses.replace(SCFG, num_blocks=48)
    n = 8 if smoke else 24
    max_new = 4 if smoke else MAX_NEW
    rows = []
    for nrep in ([1, 2] if smoke else [1, 2, 4]):
        rng = np.random.default_rng(13)
        eng = ReplicatedServeEngine(
            params, SERVE_CFG, scfg,
            ReplicaConfig(n_replicas=nrep, policy="prefix_affinity"))
        wall = _drive(eng, _shared_prefix_requests(rng, n, max_new,
                                                   prefix_len=32, groups=4),
                      4.0)
        rows.append(_replica_row(f"replicas_{nrep}_affinity", eng, wall))
    for tag, policy in [("cold_round_robin", "round_robin"),
                        ("warm_affinity", "prefix_affinity")]:
        rng = np.random.default_rng(17)
        eng = ReplicatedServeEngine(
            params, SERVE_CFG, scfg, ReplicaConfig(n_replicas=2, policy=policy))
        wall = _drive(eng, _shared_prefix_requests(rng, n, max_new), 1.0)
        rows.append(_replica_row(f"routing_{tag}", eng, wall))
    return rows


HYBRID_CFG = ModelConfig(
    name="serve-bench-hybrid", vocab_size=512, d_model=128, n_layers=2,
    n_heads=4, n_kv_heads=2, d_ff=512, ssm_state=16, ssm_head_dim=32,
    ssm_chunk=32, attn_chunk=64,
    layer_pattern=(LayerSpec("ssm", "dense"), LayerSpec("attn", "dense")))


def _hybrid_sweep(smoke):
    """Jamba-pattern traffic, paged (state pool) vs dense engine: tokens/s
    and the state-memory story — INT8 pool bytes vs the f32 SSD layout the
    pre-quantization dense cache paid for the same slot count."""
    from repro.serving.state_pool import (dense_f32_state_nbytes,
                                          state_pool_nbytes)
    params = init_params(HYBRID_CFG, jax.random.PRNGKey(1))
    n = 4 if smoke else N_REQUESTS
    max_new = 4 if smoke else MAX_NEW
    scfg = SCFG
    rows = []

    rng = np.random.default_rng(19)
    eng = PagedServeEngine(params, HYBRID_CFG, scfg)
    wall = _drive(eng, _requests(rng, n, max_new), 4.0)
    m = eng.metrics()
    rows.append({
        "point": "hybrid_paged_4rps",
        "tokens_per_s": round(m["tokens_per_s"], 2),
        "ttft_ms": round(m["ttft_avg_s"] * 1e3, 2),
        "preemptions": m["preemptions"],
        "state_slots": m["state_slots"],
        "state_bytes_int8": m["state_pool_nbytes"],
        "state_bytes_f32": dense_f32_state_nbytes(
            HYBRID_CFG, scfg.state_slots + 1),      # + trash slot, like-for-like
        "kv_cache_bytes": m["cache_nbytes"],
        "wall_s": round(wall, 2),
    })

    rng = np.random.default_rng(19)
    dense = ServeEngine(params, HYBRID_CFG,
                        EngineConfig(max_slots=scfg.max_batch, smax=SMAX))
    wall = _drive(dense, _requests(rng, n, max_new), 4.0)
    gen = dense.stats["decode_tokens"] + dense.stats["first_tokens"]
    done = dense.finished
    # the dense cache quantizes SSD state through the same round-trip now;
    # report its actual int8 state bytes plus the f32 bytes it replaced
    ssm_leaves = {k: v for k, v in dense._cache["entries"].items()
                  if "ssd_vals" in v}
    rows.append({
        "point": "hybrid_dense_4rps",
        "tokens_per_s": round(gen / max(wall, 1e-9), 2),
        "ttft_ms": round(float(np.mean([r.ttft_s for r in done])) * 1e3, 2),
        "preemptions": 0,
        "state_slots": scfg.max_batch,
        "state_bytes_int8": cache_nbytes(ssm_leaves),
        "state_bytes_f32": dense_f32_state_nbytes(HYBRID_CFG,
                                                  scfg.max_batch),
        "kv_cache_bytes": cache_nbytes(
            {k: v for k, v in dense._cache["entries"].items()
             if "ssd_vals" not in v}),
        "wall_s": round(wall, 2),
    })
    return rows


def _token_divergence(a, b):
    """Fraction of generated tokens that differ between two runs of the
    same traffic (length mismatches count as divergent positions)."""
    tot = diff = 0
    for uid, toks in a.items():
        other = b.get(uid, [])
        n = max(len(toks), len(other))
        tot += n
        diff += sum(1 for i in range(n)
                    if i >= len(toks) or i >= len(other) or toks[i] != other[i])
    return diff / max(tot, 1)


def _ladder_sweep(params, smoke):
    """Pool-pressure pair (``experiments/bench/serving_ladder.csv``): the
    same grouped shared-prefix traffic on the same undersized pool, ladder
    off vs on.  The off row is the divergence baseline (divergence 0 by
    construction); the on row reports demotions/promotions, resident int4
    halves, the peak *logical* block count (capacity_ratio > 1 is blocks
    that only survived as packed halves), and its token divergence vs the
    off run — the divergence-gated cost of the ladder's 8-code requant
    error on promoted prefixes.  ``run.py``'s ladder gate reads this CSV."""
    n = 18 if smoke else max(N_REQUESTS, 18)
    max_new = 4 if smoke else 8
    # 12 blocks vs a 6 x 48-token prefix working set (18 blocks): the INT8-
    # only pool must evict whole prefixes, the ladder folds them to int4
    # halves instead.  The low watermark keeps demotion a last resort (fold
    # only when nearly dry) so packed halves accumulate.
    base = dataclasses.replace(SCFG, num_blocks=12, max_batch=2,
                               max_blocks_per_req=8, prefill_chunk=16,
                               token_budget=64)

    def traffic():
        return _shared_prefix_requests(np.random.default_rng(31), n, max_new,
                                       prefix_len=48, groups=6)

    # throwaway warm-up engine: the module-level step-fn cache is shared, so
    # both timed rows below see steady-state serving, not compiles
    warm = PagedServeEngine(params, SERVE_CFG, base)
    _drive(warm, traffic(), 1.0)

    rows, outs = [], {}
    for tag, ladder in [("ladder_off", False), ("ladder_on", True)]:
        scfg = dataclasses.replace(base, ladder=ladder, ladder_watermark=0.15)
        eng = PagedServeEngine(params, SERVE_CFG, scfg)
        wall = _drive(eng, traffic(), 1.0)
        m = eng.metrics()
        outs[tag] = {int(r.uid): [int(t) for t in r.generated]
                     for r in eng.finished}
        rows.append({
            "point": tag,
            "ladder": int(ladder),
            "cache_bytes": m["cache_nbytes"],
            "effective_cache_bytes": m["effective_cache_bytes"],
            "capacity_blocks_peak": m["prefix_cache_blocks_peak"],
            "demotions": m["demotions"],
            "promotions": m["promotions"],
            "int4_blocks": m["int4_blocks"],
            "prefix_hit_tokens": m["prefix_hit_tokens"],
            "tokens_per_s": round(m["tokens_per_s"], 2),
            "token_divergence": round(
                _token_divergence(outs[tag], outs["ladder_off"]), 4),
            "wall_s": round(wall, 2),
        })
    off_peak = max(rows[0]["capacity_blocks_peak"], 1)
    for r in rows:
        r["capacity_ratio"] = round(r["capacity_blocks_peak"] / off_peak, 3)
    return rows


# Mesh shapes of the sharded sweep, (data, model).  The meshless
# PagedServeEngine run is the token-parity reference; every mesh row reports
# whether the 2D data x model composition reproduced it token-for-token (the
# gather-based-TP contract), plus per-device pool bytes — the column that
# shrinks as the model axis cuts the kv-head-sharded pool.
SHARDED_MESHES = [(1, 1), (2, 1), (1, 2), (2, 2)]


def _sharded_rows(n_req, max_new):
    """The sweep over the meshes that fit this process's devices."""
    from repro.launch.mesh import make_mesh
    from repro.serving.replica import ReplicaConfig, ReplicatedServeEngine
    scfg = dataclasses.replace(SCFG, num_blocks=48)
    params = init_params(SERVE_CFG, jax.random.PRNGKey(0))

    def traffic():
        return _shared_prefix_requests(np.random.default_rng(29), n_req,
                                       max_new, prefix_len=32, groups=2)

    def outputs(eng):
        return {int(r.uid): [int(t) for t in r.generated]
                for r in eng.finished}

    ref = PagedServeEngine(params, SERVE_CFG, scfg)
    _drive(ref, traffic(), 4.0)
    want = outputs(ref)
    rows = []
    for d, m in SHARDED_MESHES:
        if d * m > len(jax.devices()):
            continue
        eng = ReplicatedServeEngine(
            params, SERVE_CFG, scfg,
            ReplicaConfig(n_replicas=d, policy="round_robin"),
            mesh=make_mesh((d, m), ("data", "model")))
        wall = _drive(eng, traffic(), 4.0)
        mt = eng.metrics()
        per = mt["per_replica"]
        rows.append({
            "point": "mesh_%dx%d" % (d, m),
            "data_shards": d,
            "model_shards": m,
            "tokens_per_s": round(mt["tokens_per_s"], 2),
            "cache_bytes": sum(p["cache_nbytes"] for p in per),
            "cache_bytes_per_device": max(p["cache_nbytes_per_device"]
                                          for p in per),
            "tokens_match": outputs(eng) == want,
            "wall_s": round(wall, 2),
        })
    return rows


def _sharded_sweep(smoke):
    """2D ``data x model`` mesh-shape sweep {1x1, 2x1, 1x2, 2x2}: tokens/s,
    per-device pool bytes, and token parity against the unsharded engine —
    the serving counterpart of the distributed train benches.

    On an accelerator the sweep runs in this process over its devices (a
    child could not reach a chip this process holds).  On the CPU it runs
    in a child that sees 8 host devices, so this process keeps its default
    single-device view (same pattern as tests/serving/test_sharded.py)."""
    import json
    import os
    import subprocess
    import sys
    n = 6 if smoke else N_REQUESTS
    max_new = 4 if smoke else MAX_NEW
    if jax.default_backend() != "cpu":
        return _sharded_rows(n, max_new)
    env = dict(os.environ)
    env.update({"PYTHONPATH": "src",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
                "JAX_PLATFORMS": "cpu"})
    code = ("import json\n"
            "from benchmarks.bench_serving import _sharded_rows\n"
            f"for row in _sharded_rows({n}, {max_new}):\n"
            "    print(json.dumps(row))\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=1800, env=env)
    if r.returncode != 0:
        raise RuntimeError("sharded sweep subprocess failed:\n"
                           + r.stdout + "\n" + r.stderr)
    return [json.loads(line) for line in r.stdout.splitlines()
            if line.startswith("{")]


def _spec_sweep(smoke):
    """Spec-vs-plain decode on shared-prefix traffic: the target serves W8A8
    weights; the int8 self-draft shares them verbatim (near-total acceptance
    -> mean emitted tokens/step well above 1), the int4 draft trades
    acceptance for a 2x-smaller draft.  Same traffic and seed per row, so
    the tokens/s and decode-step deltas are the speculation win."""
    from repro.core import QuantPolicy, quantize_tree
    from repro.serving.spec_decode import SpecConfig
    params = init_params(SERVE_CFG, jax.random.PRNGKey(3))
    qparams = quantize_tree(params, QuantPolicy(method="symmetric",
                                                min_size=2048))
    n = 6 if smoke else N_REQUESTS
    max_new = 32
    # prompts (<= 64 tokens) prefill in one chunk, so the self-draft's dense
    # prefill freezes the same K scales as the target's chunk-1 freeze —
    # the bit-exact regime where acceptance is maximal.  max_batch=1 puts
    # every point in the latency regime speculation targets: at batch 1 the
    # plain engine pays one full decode dispatch per token, while a spec
    # round amortizes its propose+verify pair over ~gamma accepted tokens.
    # (At batch 4 plain splits each dispatch over the whole batch and the
    # self-draft's 2x FLOPs can't pay for themselves on a compute-bound
    # host — that throughput regime is _paged_sweep's job.)  max_new=32
    # keeps decode, not prefill/draft-lane setup, the dominant term.
    scfg = dataclasses.replace(SCFG, prefill_chunk=64, token_budget=96,
                               num_blocks=48, max_batch=1)
    points = [("spec_plain", None)]
    for gamma in (2, 4):
        points.append((f"spec_g{gamma}_int8self",
                       SpecConfig(gamma=gamma, draft_bits=0)))
        points.append((f"spec_g{gamma}_int4",
                       SpecConfig(gamma=gamma, draft_bits=4)))
    rows = []
    for point, spec in points:
        # warm the jit caches with a throwaway engine driving the *same*
        # traffic (the module-level step-fn cache is shared and jit re-traces
        # per decode-batch width / chunk bucket), so the timed wall below is
        # steady-state serving, not compiles — on one CPU device the compile
        # cost would otherwise swamp the tokens/s column
        warm = PagedServeEngine(qparams, SERVE_CFG,
                                dataclasses.replace(scfg, spec=spec))
        _drive(warm, _shared_prefix_requests(np.random.default_rng(23), n,
                                             max_new), 4.0)
        rng = np.random.default_rng(23)
        eng = PagedServeEngine(qparams, SERVE_CFG,
                               dataclasses.replace(scfg, spec=spec))
        wall = _drive(eng, _shared_prefix_requests(rng, n, max_new), 4.0)
        m = eng.metrics()
        rows.append({
            "point": point,
            "gamma": spec.gamma if spec else 0,
            "draft_bits": (spec.draft_bits or 8) if spec else 0,
            "tokens_per_s": round(m["tokens_per_s"], 2),
            "accept_rate": round(m["spec_accept_rate"], 3),
            "tokens_per_step": round(m["spec_tokens_per_step"], 3)
                               if spec else 1.0,
            "decode_steps": m["decode_steps"],
            "ttft_ms": round(m["ttft_avg_s"] * 1e3, 2),
            "draft_bytes": m["spec_draft_nbytes"],
            "wall_s": round(wall, 2),
        })
    return rows


TRACE_PATH = "experiments/bench/serving_trace.json"
# span/event kinds the exported trace must contain (run.py's obs gate):
# one of each proves the tracer is threaded through every scheduler path
TRACE_REQUIRED_KINDS = ("prefill_chunk", "decode_step", "preempt",
                        "spec_round", "demote")


def _obs_sweep(params, smoke):
    """Chrome-trace export (``experiments/bench/serving_obs.csv`` +
    ``serving_trace.json``).

    Ladder-pressure traffic runs traced; the same tracer then also records
    a spec-decode drive and a preemption-forcing burst, so one exported
    trace exhibits every span kind ``run.py``'s obs gate requires (prefill
    chunks, decode steps, a spec round, a preemption, a ladder demotion)."""
    from repro.obs import Tracer, validate_chrome_trace
    from repro.serving.spec_decode import SpecConfig
    n = 18 if smoke else max(N_REQUESTS, 18)
    max_new = 4 if smoke else 8
    # same pool-pressure shape as _ladder_sweep: demotions guaranteed
    base = dataclasses.replace(SCFG, num_blocks=12, max_batch=2,
                               max_blocks_per_req=8, prefill_chunk=16,
                               token_budget=64, ladder=True,
                               ladder_watermark=0.15)
    tr = Tracer()
    eng = PagedServeEngine(params, SERVE_CFG, base, tracer=tr)
    wall = _drive(eng, _shared_prefix_requests(
        np.random.default_rng(31), n, max_new, prefix_len=48, groups=6), 1.0)
    tps = eng.metrics()["tokens_per_s"]

    # spec round: a short speculative drive on the same tracer
    spec_scfg = dataclasses.replace(SCFG, prefill_chunk=64, token_budget=96,
                                    num_blocks=48, max_batch=1,
                                    spec=SpecConfig(gamma=2, draft_bits=0))
    spec_eng = PagedServeEngine(params, SERVE_CFG, spec_scfg, tracer=tr)
    _drive(spec_eng, _shared_prefix_requests(np.random.default_rng(23), 3,
                                             8), 4.0)
    # preemption burst: 3 requests, each needing ceil((56+16-1)/16) = 5
    # blocks, against an 8-block pool at max_batch 2 — eviction guaranteed
    tiny = dataclasses.replace(SCFG, num_blocks=8, max_batch=2,
                               max_blocks_per_req=8, prefill_chunk=16,
                               token_budget=64)
    burst_eng = PagedServeEngine(params, SERVE_CFG, tiny, tracer=tr)
    rng = np.random.default_rng(41)
    burst = [Request(uid=100 + i,
                     prompt=rng.integers(0, 512, size=56).astype(np.int32),
                     max_new_tokens=16) for i in range(3)]
    _drive(burst_eng, burst, 4.0)

    obj = tr.export_chrome_trace(TRACE_PATH)
    errs = validate_chrome_trace(obj)
    kinds = tr.kinds()
    missing = [k for k in TRACE_REQUIRED_KINDS if not kinds.get(k)]
    if errs or missing:
        raise RuntimeError(f"obs sweep: trace schema errors {errs[:3]}, "
                           f"missing span kinds {missing}")
    return [{"point": "obs_on", "tokens_per_s": round(tps, 2),
             "trace_spans": len(tr), "trace_dropped": tr.dropped,
             "trace_valid": int(not errs), "wall_s": round(wall, 2)}]


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny traffic, finishes in <30s")
    for r in run(smoke=ap.parse_args().smoke):
        print(r)
