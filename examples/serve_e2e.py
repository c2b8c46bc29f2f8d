"""End-to-end serving driver (the paper's deployment story).

Trains a small LM briefly, statically quantizes it (SmoothQuant fold +
symmetric W8A8), then serves a stream of batched requests through the
paged-cache engine — continuous batching, chunked prefill, SimQuant INT8 KV
blocks and online EMA scale tracking: the full LLMEasyQuant pipeline on one
box.  ``--dense`` falls back to the legacy slot-ring engine; ``--replicas N``
serves through N data-parallel scheduler replicas with prefix-affinity
routing and synced EMA scales (the paper's multi-worker regime, host-side).
``--spec-gamma G`` turns on self-speculative decoding: a draft of the same
checkpoint (``--draft-bits`` weight-only requantization; 0 shares the W8A8
weights — the INT8 self-draft) proposes G tokens per step and the target
verifies them losslessly, emitting 1 + accepted tokens per decode round.

    PYTHONPATH=src python examples/serve_e2e.py [--requests 12] [--steps 60]
    PYTHONPATH=src python examples/serve_e2e.py --spec-gamma 4
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import QuantPolicy, quantize_tree, tree_nbytes
from repro.core.methods.smoothquant import apply_fold_to_model
from repro.launch.compile_cache import enable_compile_cache
from repro.data import DataConfig, SyntheticLM
from repro.launch.steps import make_train_step
from repro.models import ModelConfig, forward_train, init_params
from repro.models.config import LayerSpec
from repro.optim import AdamWConfig, init_state
from repro.serving.engine import (EngineConfig, PagedServeEngine, Request,
                                  ServeEngine)
from repro.serving.scheduler import SchedulerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--dense", action="store_true",
                    help="use the legacy dense slot-ring engine")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through N data-parallel scheduler replicas "
                         "(prefix-affinity routing, synced EMA scales)")
    ap.add_argument("--spec-gamma", type=int, default=0,
                    help="speculative decoding: draft tokens proposed per "
                         "step (0 = off)")
    ap.add_argument("--draft-bits", type=int, default=0,
                    help="weight-only draft bitwidth (0 = share the target's "
                         "quantized weights — INT8 self-draft)")
    ap.add_argument("--score", action="store_true",
                    help="after serving, teacher-force held-out perplexity "
                         "+ multiple-choice tasks through the same engine "
                         "(scoring mode) and print the quality scorecard")
    args = ap.parse_args()
    enable_compile_cache()
    if args.dense and args.score:
        ap.error("--score needs the paged engine (drop --dense)")
    if args.dense and args.replicas > 1:
        ap.error("--dense and --replicas are mutually exclusive (the dense "
                 "slot-ring engine has no replica frontend)")
    if args.dense and args.spec_gamma:
        ap.error("--spec-gamma needs the paged engine (drop --dense)")

    cfg = ModelConfig(name="serve-demo", vocab_size=512, d_model=128,
                      n_layers=2, n_heads=4, n_kv_heads=2, d_ff=512,
                      layer_pattern=(LayerSpec("attn", "dense"),),
                      attn_chunk=64)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=96, global_batch=8)

    # 1) train briefly
    print(f"[1/4] training {cfg.name} for {args.steps} steps ...")
    params = init_params(cfg, jax.random.PRNGKey(0))
    ocfg = AdamWConfig(lr=2e-3, warmup_steps=10, total_steps=args.steps)
    opt = init_state(params, ocfg)
    step = jax.jit(make_train_step(cfg, ocfg))
    ds = SyntheticLM(dcfg)
    for i in range(args.steps):
        batch = jax.tree_util.tree_map(jnp.asarray, ds.batch_at(i))
        params, opt, metrics = step(params, opt, batch)
    print(f"      final loss {float(metrics['loss']):.3f}")

    # 2) calibrate + SmoothQuant fold + static W8A8
    print("[2/4] calibrating + SmoothQuant fold + W8A8 quantization ...")
    from functools import partial
    fwd = jax.jit(partial(forward_train, cfg=cfg, capture=True))
    taps = {}
    for i in range(2):
        batch = ds.batch_at(10_000 + i)
        _, _, t = fwd(params, jnp.asarray(batch["tokens"][:4]))
        for tag, e in t.items():
            taps[tag] = (e["ch_absmax"] if tag not in taps
                         else jnp.maximum(taps[tag], e["ch_absmax"]))
    folded = apply_fold_to_model(params, taps)
    qparams = quantize_tree(folded, QuantPolicy(method="symmetric", min_size=2048))
    print(f"      model {tree_nbytes(params)/2**20:.2f} -> "
          f"{tree_nbytes(qparams)/2**20:.2f} MiB")

    # 3) serve
    spec = None
    if args.spec_gamma:
        from repro.serving.spec_decode import SpecConfig
        spec = SpecConfig(gamma=args.spec_gamma, draft_bits=args.draft_bits)
    scfg = SchedulerConfig(
        block_size=16, num_blocks=48 * max(args.replicas, 1), max_batch=4,
        max_blocks_per_req=12, prefill_chunk=32, token_budget=64, spec=spec)
    if args.dense:
        print(f"[3/4] serving {args.requests} requests (dense, 4 slots) ...")
        eng = ServeEngine(qparams, cfg, EngineConfig(max_slots=4, smax=160))
    elif args.replicas > 1:
        from repro.serving.replica import ReplicaConfig, ReplicatedServeEngine
        print(f"[3/4] serving {args.requests} requests "
              f"({args.replicas} replicas, prefix-affinity routing) ...")
        eng = ReplicatedServeEngine(qparams, cfg, scfg,
                                    ReplicaConfig(n_replicas=args.replicas))
    else:
        extra = (f", spec-decode gamma={args.spec_gamma} "
                 f"draft_bits={args.draft_bits or 'shared-int8'}"
                 if spec else "")
        print(f"[3/4] serving {args.requests} requests "
              f"(paged INT8 KV blocks, chunked prefill{extra}) ...")
        eng = PagedServeEngine(qparams, cfg, scfg)
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for i in range(args.requests):
        prompt = ds.sample_tokens(1, int(rng.integers(8, 48)), 999 + i)[0, :-1]
        eng.add_request(Request(uid=i, prompt=prompt.astype(np.int32),
                                max_new_tokens=args.new_tokens))
    done = eng.run()
    dt = time.perf_counter() - t0

    # 4) report
    if args.replicas > 1:
        eng.sync_scales()              # final shared (delta, z) on all replicas
    toks = eng.stats["decode_tokens"] + eng.stats.get("first_tokens", len(done))
    print(f"[4/4] served {len(done)} requests, {toks} tokens in {dt:.1f}s "
          f"({toks/dt:.1f} tok/s)")
    slots = 4 * args.replicas if args.replicas > 1 else 4
    print(f"      decode steps: {eng.stats['decode_steps']} "
          f"(continuous batching over {args.requests} requests / "
          f"{slots} slots)")
    print(f"      online EMA scale state: delta={float(eng.scale_state.delta):.3f} "
          f"after {int(eng.scale_state.step)} updates")
    if args.replicas > 1:
        m = eng.metrics()
        per = "; ".join(
            f"r{i}: {p['tokens_per_s']:.1f} tok/s, hit {p['prefix_hit_rate']:.0%}"
            for i, p in enumerate(m["per_replica"]))
        print(f"      {m['replicas']} replicas, {m['scale_syncs']} scale "
              f"syncs, {m['preemptions']} preemptions; {per}")
    elif not args.dense:
        m = eng.metrics()
        print(f"      TTFT avg {m['ttft_avg_s']*1e3:.0f} ms / max "
              f"{m['ttft_max_s']*1e3:.0f} ms; cache util avg "
              f"{m['cache_util_avg']:.0%} peak {m['cache_util_peak']:.0%}; "
              f"preemptions {m['preemptions']}; "
              f"pool {m['cache_nbytes']/2**20:.2f} MiB")
    if spec is not None:                 # single-engine AND replica fleets
        m = eng.metrics()
        print(f"      spec decode: accept rate "
              f"{m['spec_accept_rate']:.0%}, "
              f"{m['spec_tokens_per_step']:.2f} tokens/step over "
              f"{m['spec_rounds']} verify rounds; draft "
              f"{m['spec_draft_nbytes']/2**20:.2f} MiB")
    for r in done[:3]:
        print(f"      req {r.uid}: prompt {len(r.prompt)} toks -> {r.generated[:8]}...")

    if args.score:
        # teacher-forced quality scorecard through the engine that just
        # served: same pools, same codecs, warm prefix cache and all
        from repro.eval.tasks import (DenseScorer, Evaluator, ServingScorer,
                                      default_tasks)
        print("[score] teacher-forced eval through the serving engine ...")
        tasks = default_tasks(dcfg, n_seqs=4, seq_len=80, prompt_len=16,
                              n_items=3)
        served = Evaluator(tasks).evaluate(ServingScorer(eng))
        dense = Evaluator(tasks).evaluate(DenseScorer(params, cfg))
        for name, m in served.items():
            ref = dense[name]
            if "nll" in m:
                print(f"      {name}: nll {m['nll']:.4f} "
                      f"(fp dense {ref['nll']:.4f}, "
                      f"delta {m['nll'] - ref['nll']:+.4f}) "
                      f"ppl {m['ppl']:.2f} over {m['n_tokens']} tokens")
            else:
                print(f"      {name}: accuracy {m['accuracy']:.2f} "
                      f"(fp dense {ref['accuracy']:.2f}, "
                      f"chance {m['chance']:.2f}) over {m['n_items']} items")
        sm = eng.metrics()
        print(f"      scored {sm['score_tokens']} tokens / "
              f"{sm['score_requests']} requests at "
              f"{sm['score_tokens_per_s']:.0f} tok/s "
              f"(avg latency {sm['score_latency_avg_s']*1e3:.0f} ms)")


if __name__ == "__main__":
    main()
