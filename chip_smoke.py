"""Chip smoke run: the paged W8A8 serving path end to end on a TPU.

    python chip_smoke.py [--seed 0]      # one chip
    python chip_smoke.py --chips 4       # sharded serving on a 2x2 mesh only

qwen2-0.5b at its published widths (24 layers, d_model 896, 14 heads over
2 KV heads, head_dim 64, vocab 151936, QKV bias, tied embeddings) with
random weights from ``--seed``.  Phases, in order, in one process:

  1. refuse to run without a TPU (no CPU fallback, no interpret mode);
  2. build the model;
  3. calibrate on 2 synthetic batches, SmoothQuant-fold, W8A8-quantize;
  4. serve 8 requests through ``PagedServeEngine``: 40-400 token prompts
     (most take several 64-token chunks), 4 of them sharing a 128-token
     prefix so the prefix cache hits;
  5. check that the engine's fused step holds the paged-decode,
     prefix-chunk and W8A8 Pallas kernels (``tpu_custom_call``s);
  6. speculative decoding with the int8 self-draft (gamma 4);
  7. teacher-forced scoring against ``eval.scoring.dense_score``;
  8. one JSON line naming the device, after the lines each phase printed.

``--chips 4`` instead serves the same requests through
``ReplicatedServeEngine`` on a (2, 2) data x model mesh (2 replicas, tensor
parallel 2) and compares them with one chip.  A failed phase exits non-zero.
"""
import argparse
import dataclasses
import json
import sys
import time
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

NEW_TOKENS = 16
PREFIX = 128
# Prompt lengths.  Every prefill chunk, before and after the 128-token
# prefix hit, ends in the 64-token bucket (remainder mod 64 is 0 or above
# 32), which bounds the fused-step variants to compile.
PLAIN_LENS = (40, 100, 230, 380)
SHARED_LENS = (170, 250, 300, 360)
SCORE_PROMPT, SCORE_CONT = 100, 92     # 192 tokens = three prefill chunks
# the fused-step kernels that must not give way to jnp oracles
KERNELS = {"paged decode": "paged_kv_decode_attention",
           "prefix-chunk": "paged_prefix_chunk_attention",
           "w8a8 gemm": "w8a8_matmul",
           "fused quant": "fused_quant"}
REQUIRED = ("paged decode", "prefix-chunk", "w8a8 gemm")


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def scheduler_config(**kw):
    from repro.serving.scheduler import SchedulerConfig
    base = SchedulerConfig(block_size=16, num_blocks=512, max_batch=8,
                           max_blocks_per_req=32, prefill_chunk=64,
                           token_budget=128)
    return dataclasses.replace(base, **kw)


def build(cfg, seed: int):
    """Random weights -> calibrated, SmoothQuant-folded, W8A8 params."""
    from repro.core import QuantPolicy, quantize_tree, tree_nbytes
    from repro.core.methods.smoothquant import apply_fold_to_model
    from repro.models import forward_train, init_params
    params = init_params(cfg, jax.random.PRNGKey(seed))
    fp_bytes = tree_nbytes(params)
    fwd = jax.jit(partial(forward_train, cfg=cfg, capture=True))
    rng = np.random.default_rng(seed)
    taps = {}
    for _ in range(2):
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 128)),
                           jnp.int32)
        _, _, t = fwd(params, toks)
        for tag, e in t.items():
            taps[tag] = (e["ch_absmax"] if tag not in taps
                         else jnp.maximum(taps[tag], e["ch_absmax"]))
    qparams = quantize_tree(apply_fold_to_model(params, taps),
                            QuantPolicy(method="symmetric", min_size=2048))
    return qparams, fp_bytes, tree_nbytes(qparams)


def prompts(cfg, seed: int):
    rng = np.random.default_rng(seed + 1)

    def tok(n):
        return rng.integers(0, cfg.vocab_size, n).astype(np.int32)
    prefix = tok(PREFIX)
    plain = [tok(n) for n in PLAIN_LENS]
    shared = [np.concatenate([prefix, tok(n - PREFIX)]) for n in SHARED_LENS]
    return plain, shared


def serve(eng, plain, shared):
    """The plain prompts and the prefix donor first; the other three
    shared-prefix prompts once the donor has prefilled (and so published)
    the prefix blocks.  -> (requests, wall seconds)."""
    from repro.serving.engine import Request
    reqs = [Request(uid=i, prompt=p, max_new_tokens=NEW_TOKENS)
            for i, p in enumerate(plain + shared)]
    n_first = len(plain) + 1
    t0 = time.perf_counter()
    for r in reqs[:n_first]:
        eng.add_request(r)
    donor = reqs[n_first - 1]
    while not donor.generated:
        check(eng.step(), "the prefix donor never produced a token")
    for r in reqs[n_first:]:
        eng.add_request(r)
    eng.run()
    return reqs, time.perf_counter() - t0


def check_served(reqs, cfg, what: str) -> int:
    check(all(r.done for r in reqs),
          f"{what}: {sum(r.done for r in reqs)}/{len(reqs)} requests finished")
    toks = [t for r in reqs for t in r.generated]
    check(all(len(r.generated) == NEW_TOKENS for r in reqs),
          f"{what}: a request stopped short of {NEW_TOKENS} tokens")
    check(all(0 <= t < cfg.vocab_size for t in toks),
          f"{what}: a token outside the vocabulary")
    return len(toks)


def watch_logits(sched):
    """Record, for every sampling call, whether all its logits were finite."""
    flags = []
    sample = sched._sample

    def checked(logits, temps):
        flags.append(jnp.all(jnp.isfinite(logits)))
        return sample(logits, temps)
    sched._sample = checked
    return flags


def lower_step(sched):
    """Lower the engine's fused step (``scheduler._step_fn_for``) for a
    non-first prefill chunk beside a full decode batch."""
    scfg = sched.scfg
    b, m, c = scfg.max_batch, scfg.max_blocks_per_req, scfg.prefill_chunk

    def i32(*shape):
        return jnp.zeros(shape, jnp.int32)
    return sched._step_fn.lower(
        sched.params, sched.pool, sched.spool, i32(b), i32(b, m), i32(b),
        i32(b), i32(1, c), jnp.int32(0), i32(m), jnp.int32(c), jnp.int32(c),
        jnp.int32(0), do_prefill=True, do_decode=True, pf_first=False)


def kernel_calls(hlo_text: str):
    """Count ``tpu_custom_call``s in compiled HLO, by kernel (its jitted
    wrapper's name in the op metadata).  -> (per-kernel counts, total)."""
    calls = [ln for ln in hlo_text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    counts = {k: sum(f"jit({fn})" in ln for ln in calls)
              for k, fn in KERNELS.items()}
    return counts, len(calls)


def timed_compile(sched):
    """Compile the fused step once.  -> (compiled, seconds, whether the
    persistent cache supplied the program: a warm compile)."""
    events = []

    def listen(event, **_):
        events.append(event)
    jax.monitoring.register_event_listener(listen)
    try:
        t = time.perf_counter()
        compiled = lower_step(sched).compile()
        seconds = time.perf_counter() - t
    finally:
        jax.monitoring.unregister_event_listener(listen)
    return compiled, seconds, "/jax/compilation_cache/cache_hits" in events


STEPS = 16      # int8 steps of logit error the scoring check allows


def score_tolerance(logits) -> float:
    """Largest |delta logprob| the int8 KV pool may explain.

    A prefix read back from the pool carries one int8 step (1/255 of a row's
    range) of rounding per K and V element, and bf16 activations carry
    roundings of the same relative size (2^-8).  The K affine frozen on a
    request's first chunk clips later keys by a few steps more.  Allowing
    ``STEPS`` steps of the logit range R, and a log-softmax moves by at most
    twice its largest input change: tol = 2 * STEPS * R / 255."""
    z = np.asarray(logits, np.float64)
    r = float((z.max(axis=-1) - z.min(axis=-1)).max())
    return 2.0 * STEPS * r / 255.0


def run(cfg, seed: int = 0, on_chip: bool = True):
    """Phases 2-7 at ``cfg``'s widths.  ``on_chip=False`` (tests: the CPU
    in interpret mode) lowers and compiles the fused step without asking
    for TPU custom calls."""
    from repro.eval.scoring import dense_logits, dense_score
    from repro.serving.engine import PagedServeEngine, Request
    from repro.serving.spec_decode import SpecConfig

    log(f"[2] build {cfg.name}: {cfg.n_layers} layers, d_model "
        f"{cfg.d_model}, {cfg.n_heads} heads / {cfg.kv_heads} KV heads, "
        f"head_dim {cfg.hd}, vocab {cfg.vocab_size}")
    t = time.perf_counter()
    qparams, fp_bytes, q_bytes = build(cfg, seed)
    log(f"[3] calibrated + SmoothQuant + W8A8: params {fp_bytes} -> "
        f"{q_bytes} bytes in {time.perf_counter() - t:.1f}s")

    eng = PagedServeEngine(qparams, cfg, scheduler_config())
    sched = eng.scheduler
    compiled, seconds, hit = timed_compile(sched)
    log(f"[4] fused step compile {seconds:.2f}s, "
        + ("warm: read from the persistent cache" if hit else
           "cold: not in the persistent cache"))
    flags = watch_logits(sched)
    plain, shared = prompts(cfg, seed)
    reqs, wall = serve(eng, plain, shared)
    n_tok = check_served(reqs, cfg, "serve")
    check(all(bool(f) for f in flags), "non-finite logits while serving")
    m = eng.metrics()
    multi = sum(len(r.prompt) > sched.scfg.prefill_chunk for r in reqs)
    check(m["prefix_hits"] >= len(shared) - 1,
          f"prefix hits {m['prefix_hits']} < {len(shared) - 1}")
    log(f"[4] served {len(reqs)}/{len(reqs)} requests, {n_tok} tokens in "
        f"{wall:.2f}s; {multi} prompts over one chunk, "
        f"{sched.stats['prefill_chunks']} chunks; prefix hits "
        f"{m['prefix_hits']} ({m['prefix_hit_tokens']} tokens); "
        f"{len(flags)} sampled batches, all logits finite")

    counts, total = kernel_calls(compiled.as_text())
    log(f"[5] fused step tpu_custom_calls: {total} "
        + " ".join(f"{k}={v}" for k, v in counts.items()))
    if on_chip:
        missing = [k for k in REQUIRED if not counts[k]]
        check(not missing, f"kernels missing from the fused step: {missing}")

    spec_eng = PagedServeEngine(qparams, cfg, scheduler_config(
        spec=SpecConfig(gamma=4)))
    spec_reqs = [Request(uid=100 + i, prompt=plain[i],
                         max_new_tokens=NEW_TOKENS) for i in range(2)]
    for r in spec_reqs:
        spec_eng.add_request(r)
    spec_eng.run()
    check_served(spec_reqs, cfg, "spec decode")
    sm = spec_eng.metrics()
    match = [a.generated == b.generated for a, b in zip(spec_reqs, reqs)]
    check(sm["spec_rounds"] > 0, "no speculative verify round ran")
    log(f"[6] spec decode gamma=4 int8 self-draft: accept rate "
        f"{sm['spec_accept_rate']:.3f}, {sm['spec_tokens_per_step']:.2f} "
        f"tokens/step over {sm['spec_rounds']} rounds; tokens match the "
        f"plain run: {match}")

    rng = np.random.default_rng(seed + 2)
    pairs = [(rng.integers(0, cfg.vocab_size, SCORE_PROMPT).astype(np.int32),
              rng.integers(0, cfg.vocab_size, SCORE_CONT).astype(np.int32))
             for _ in range(2)]
    score_reqs = [Request(uid=200 + i, prompt=p, score_tokens=c)
                  for i, (p, c) in enumerate(pairs)]
    for r in score_reqs:
        eng.add_request(r)
    eng.run()
    deltas, tol = [], 0.0
    for r, (p, c) in zip(score_reqs, pairs):
        check(r.done and len(r.score_logprobs) == SCORE_CONT,
              f"scoring request {r.uid} did not score every token")
        ref = dense_score(qparams, cfg, p, c)
        deltas.append(np.abs(np.asarray(r.score_logprobs) - ref))
        z = dense_logits(qparams, cfg, jnp.asarray(np.concatenate([p, c]))[None])
        tol = max(tol, score_tolerance(z[0, SCORE_PROMPT - 1:-1]))
    d = np.concatenate(deltas)
    log(f"[7] scoring vs dense_score, {d.size} tokens: max |dlogprob| "
        f"{d.max():.6f}, mean {d.mean():.6f}, tolerance {tol:.6f}")
    check(bool(np.all(np.isfinite(d))), "non-finite scoring logprobs")
    check(float(d.max()) <= tol,
          f"scoring max |dlogprob| {d.max():.6f} above {tol:.6f}")


def first_divergence(want, got):
    """(uid, position) of the first token where two runs differ, or None."""
    for a, b in zip(want, got):
        for i, (x, y) in enumerate(zip(a.generated, b.generated)):
            if x != y:
                return a.uid, i
    return None


def run_sharded(cfg, seed: int = 0):
    """The same requests on one device and on a (2, 2) data x model mesh
    (2 replicas, tensor parallel 2)."""
    from repro.launch.mesh import make_mesh
    from repro.serving.engine import PagedServeEngine
    from repro.serving.replica import ReplicaConfig, ReplicatedServeEngine
    check(len(jax.devices()) >= 4,
          f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    qparams, _, _ = build(cfg, seed)
    plain, shared = prompts(cfg, seed)
    one = PagedServeEngine(qparams, cfg, scheduler_config())
    want, wall_one = serve(one, plain, shared)
    check_served(want, cfg, "one device")
    mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
    fleet = ReplicatedServeEngine(
        qparams, cfg, scheduler_config(num_blocks=2 * 512),
        ReplicaConfig(n_replicas=2, policy="prefix_affinity"), mesh=mesh)
    got, wall_mesh = serve(fleet, plain, shared)
    n_tok = check_served(got, cfg, "2x2 mesh")
    per = fleet.metrics()["per_replica"]
    devices = sorted({d.id for rep in fleet.replicas
                      for leaf in jax.tree_util.tree_leaves(rep.pool)
                      for d in leaf.sharding.device_set})
    log(f"[4x] one device {wall_one:.2f}s, 2x2 mesh {wall_mesh:.2f}s, "
        f"{n_tok} tokens")
    for i, p in enumerate(per):
        log(f"[4x] replica {i}: pool {p['cache_nbytes']} bytes, "
            f"{p['cache_nbytes_per_device']} per device, prefix hits "
            f"{p['prefix_hits']}")
    log(f"[4x] pool shards on devices {devices}")
    div = first_divergence(want, got)
    log(f"[4x] tokens first diverge at (uid, position) {div}"
        if div else "[4x] tokens identical to one device")
    check(len(devices) == 4, f"pool shards on {len(devices)} devices, not 4")
    check(all(2 * p["cache_nbytes_per_device"] == p["cache_nbytes"]
              for p in per), "per-device pool bytes did not halve")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {backend!r}",
              file=sys.stderr)
        raise SystemExit(2)
    from repro.configs.qwen2_0_5b import FULL
    from repro.launch.compile_cache import enable_compile_cache
    log(f"[1] {jax.devices()[0].device_kind} x {len(jax.devices())}, "
        f"compile cache {enable_compile_cache()}")
    if args.chips == 4:
        run_sharded(FULL, args.seed)
    else:
        run(FULL, args.seed)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
