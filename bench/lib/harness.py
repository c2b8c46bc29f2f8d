"""One run of one cell: build, warm up, fill, measure, check.

The system under test is the paged W8A8 serving engine
(``repro.serving.engine.PagedServeEngine``), driven through ``add_request``
and ``step`` exactly as a server would drive it.  Everything else here is
the benchmark's own: the weights (drawn by the configuration's reference
module), the traffic, the clocks, the reduction of spans and traces, and
the comparison with the plain reference that decides ``correct``.
"""
from __future__ import annotations

import gc
import json
import resource
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from functools import partial
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from . import registry, window

CAP_S = 60.0            # serving after the window, for first tokens due in it


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclass
class Cell:
    """A workload and the files it names."""
    name: str
    config_name: str
    config: dict
    mix: dict
    limits: dict
    ref: Any
    base: Path

    @classmethod
    def load(cls, workload: dict, base: Path = registry.BENCH) -> "Cell":
        cfg = registry.config(workload["config"], base)
        return cls(workload["name"], workload["config"], cfg,
                   registry.traffic(workload["traffic"], base),
                   registry.limits(workload["name"], base),
                   registry.reference(cfg["reference"], base), base)

    def model_config(self):
        from repro.models.config import LayerSpec, ModelConfig
        kw = self.ref.program_config(self.config["model"])
        kw["layer_pattern"] = tuple(LayerSpec(*p) for p in kw["layer_pattern"])
        return ModelConfig(name=self.config_name, **kw)

    def scheduler_config(self):
        """The configuration's scheduler, its pool sized by the one rule:
        every slot can hold a full block table."""
        from repro.serving.scheduler import SchedulerConfig
        s = dict(self.config["scheduler"])
        s["num_blocks"] = s["max_batch"] * s["max_blocks_per_req"]
        return SchedulerConfig(eos_id=-1, **s)


# -- build --------------------------------------------------------------------

def build(cell: Cell, mcfg, seed: int):
    """Weights from the seed, one calibration batch, SmoothQuant fold and
    W8A8.  -> (served params, {phase: seconds})."""
    import jax
    import jax.numpy as jnp
    from repro.core import QuantPolicy, quantize_tree
    from repro.core.methods.smoothquant import apply_fold_to_model
    from repro.models import forward_train
    q = cell.config["quant"]
    t = time.perf_counter()
    weights = cell.ref.make_weights(cell.config["model"], seed)
    rows, cols = q["calib_batch"]
    toks = np.random.default_rng([seed, 7]).integers(
        0, mcfg.vocab_size, (rows, cols)).astype(np.int32)
    _, _, taps = jax.jit(partial(forward_train, cfg=mcfg, capture=True))(
        weights, jnp.asarray(toks))
    absmax = {tag: e["ch_absmax"] for tag, e in taps.items()}
    jax.block_until_ready(absmax)
    t_build = time.perf_counter() - t
    policy = QuantPolicy(method=q["method"], min_size=q["min_size"])
    alpha = q["smoothquant_alpha"]

    def quantize(w, a):
        return quantize_tree(apply_fold_to_model(w, a, alpha), policy)
    t = time.perf_counter()
    params = jax.jit(quantize, donate_argnums=(0,))(weights, absmax)
    jax.block_until_ready(params)
    del weights
    return params, {"build": t_build, "quantize": time.perf_counter() - t}


def step_variants(scfg) -> List[tuple]:
    """Every fused-step variant a serving loop can reach:
    (do_prefill, do_decode, pf_first, chunk bucket)."""
    from repro.serving.scheduler import _chunk_bucket
    buckets = sorted({_chunk_bucket(c, scfg.prefill_chunk)
                      for c in range(1, scfg.prefill_chunk + 1)})
    out = [(False, True, True, 16)]
    for dec in (False, True):
        for first in (True, False):
            out += [(True, dec, first, b) for b in buckets]
    return out


def warm(engine) -> int:
    """Run each fused-step variant once on dummy rows that write only the
    pool's trash block, and the sampler on each logits shape, so that every
    program the loop uses is compiled (or read from the persistent cache)
    before the window.  -> variants run."""
    import jax
    import jax.numpy as jnp
    from repro.core.online import EmaScaleState
    from repro.serving.engine import sample_tokens
    from repro.serving.paged_cache import snapshot_slot_scales
    sched = engine.scheduler
    scfg = sched.scfg
    b, m = scfg.max_batch, scfg.max_blocks_per_req
    dec = (jnp.asarray(np.zeros((b,), np.int32)),
           jnp.asarray(np.full((b, m), sched.trash, np.int32)),
           jnp.asarray(np.zeros((b,), np.int32)),
           jnp.asarray(np.full((b,), sched.state_trash, np.int32)))
    rng = jax.random.PRNGKey(0)
    variants = step_variants(scfg)
    for do_pf, do_dec, first, c_pad in variants:
        if do_pf:
            pf = (jnp.asarray(np.zeros((1, c_pad), np.int32)), jnp.int32(0),
                  jnp.asarray(np.full((m,), sched.trash, np.int32),
                              dtype=jnp.int32),
                  jnp.int32(0), jnp.int32(c_pad), jnp.int32(sched.state_trash))
        else:
            pf = sched._build_pf_args(None)
        pf_logits, dec_logits, sched.pool, sched.spool = sched._step_fn(
            sched.params, sched.pool, sched.spool, *dec, *pf,
            do_prefill=do_pf, do_decode=do_dec, pf_first=first,
            pf_score=False)
        for logits in (pf_logits, dec_logits):
            if hasattr(logits, "shape"):
                toks, _, _ = sample_tokens(
                    logits, np.zeros((logits.shape[0],), np.float32), rng,
                    EmaScaleState.init(), scfg.ema_alpha)
                np.asarray(toks)
    jax.block_until_ready(snapshot_slot_scales(sched.pool, 0))
    return len(variants)


# -- serve --------------------------------------------------------------------

class CompileWatch:
    """Times of JAX's compile and compile-cache events."""

    NAMES = ("/jax/core/compile/backend_compile_duration",
             "/jax/compilation_cache/cache_hits",
             "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax
        self.events: List[tuple] = []
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name in self.NAMES:
            self.events.append((time.perf_counter(), name))

    def _duration(self, name, _secs, **_):
        self._event(name)

    def between(self, t0: float, t1: float) -> Dict[str, int]:
        out = {n.rsplit("/", 1)[1]: 0 for n in self.NAMES}
        for t, n in self.events:
            if t0 <= t < t1:
                out[n.rsplit("/", 1)[1]] += 1
        return out


@dataclass
class Served:
    records: List[window.Record]
    requests: Dict[int, Any]
    t0: float
    t1: float
    t_fill: float
    t_end: float
    counters0: Dict[str, int] = field(default_factory=dict)
    counters1: Dict[str, int] = field(default_factory=dict)
    steps: List[tuple] = field(default_factory=list)
    collections: List[tuple] = field(default_factory=list)


def _annotate(on: bool, name: str):
    import contextlib
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def serve(engine, traffic, mix: dict, seconds: float,
          profile: Optional[Callable] = None) -> Served:
    """Offer the load of ``traffic`` (a generator's object, see
    ``lib/traffic.py``), open the window in steady state, measure for
    ``seconds``, then serve on (up to ``CAP_S``) until every request that
    was due or sent in the window has its first token.  Steady state is
    ``ramp_s`` seconds after the load starts where the mix sets it, and
    otherwise the moment every request of the first wave has its first
    token.

    ``profile(start: bool)`` starts and stops a device trace over the first
    ``trace_s`` seconds of the window."""
    from repro.serving.engine import Request
    clock = time.perf_counter
    records: Dict[int, window.Record] = {}
    requests: Dict[int, Any] = {}
    finished: List[int] = []
    tr = profile is not None

    def on_token(req, _tok):
        rec = records[req.uid]
        rec.tokens.append(clock())
        if len(rec.tokens) == rec.max_new:
            finished.append(req.uid)

    def submit(spec, start):
        records[spec.index] = window.Record(
            spec.index, start, clock(), len(spec.prompt), spec.max_new_tokens)
        req = Request(uid=spec.index, prompt=spec.prompt,
                      max_new_tokens=spec.max_new_tokens, on_token=on_token)
        requests[spec.index] = req
        engine.add_request(req)

    sched = engine.scheduler
    steps: List[tuple] = []         # (start, end, prefilled, involuntary
                                    # context switches) of each step
    collections: List[tuple] = []   # (start, end, generation) of each gc
    gc_start = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            gc_start[0] = clock()
        else:
            collections.append((gc_start[0], clock(), info["generation"]))
    gc.callbacks.append(on_gc)
    t_load = clock()
    first_wave = traffic.first_wave()
    for spec in first_wave:
        submit(spec, clock())
    first_wave = [records[spec.index] for spec in first_wave]
    arrivals = traffic.arrivals()
    nxt = next(arrivals, None)
    t0 = t1 = None
    trace_until = None          # while tracing, when to stop
    counters0: Dict[str, int] = {}
    stop = None
    while True:
        now = clock()
        if t0 is None:
            steady = (now >= t_load + float(mix["ramp_s"]) if "ramp_s" in mix
                      else all(r.first is not None for r in first_wave))
            if steady:
                t0 = now
                t1 = t0 + seconds
                counters0 = dict(sched.stats)
                if profile is not None:
                    profile(True)
                    trace_until = t0 + float(mix.get("trace_s", 3.0))
        elif trace_until is not None and now >= trace_until:
            profile(False)
            trace_until = None
        if t1 is not None and now >= t1:
            if stop is None:
                stop = t1 + CAP_S
            due = [r for r in records.values() if t0 <= r.start < t1]
            if now >= stop or all(r.first is not None for r in due):
                break
        with _annotate(tr, "bench.offer"):
            while finished:
                for spec in traffic.after(finished.pop(0)):
                    submit(spec, clock())
            while nxt is not None and t_load + nxt.due_s <= now:
                submit(nxt, t_load + nxt.due_s)
                nxt = next(arrivals, None)
        if sched.has_work:
            chunks = sched.stats["prefill_chunks"]
            switched = resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw
            a = clock()
            with _annotate(tr, "bench.step"):
                engine.step()
            steps.append((a, clock(), sched.stats["prefill_chunks"] > chunks,
                          resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw
                          - switched))
        else:
            wait = 0.05 if nxt is None else t_load + nxt.due_s - clock()
            with _annotate(tr, "bench.idle"):
                time.sleep(max(0.0, min(wait, 0.05)))
    if trace_until is not None:
        profile(False)
    gc.callbacks.remove(on_gc)
    return Served(list(records.values()), requests, t0, t1, t0 - t_load,
                  clock(),
                  counters0, dict(sched.stats), steps, collections)


# -- check --------------------------------------------------------------------

def sample_for_check(served: Served, limits: dict, seed: int) -> List[int]:
    """Finished requests to compare: the longest (prompt and output), then
    others drawn from the seed until ``sample_tokens`` served tokens."""
    done = [r for r in served.records
            if r.max_new and len(r.tokens) == r.max_new]
    if not done:
        return []
    done.sort(key=lambda r: r.index)
    longest = max(done, key=lambda r: (r.prompt_len + r.max_new, r.index))
    rng = np.random.default_rng([seed, 11])
    rest = [done[i] for i in rng.permutation(len(done))
            if done[i] is not longest]
    pick, n = [longest], longest.max_new
    for r in rest:
        if n >= limits["sample_tokens"] or len(pick) >= limits["max_requests"]:
            break
        pick.append(r)
        n += r.max_new
    return [r.index for r in pick]


def _gap_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gap(z, nxt, n):
        """Widest gap by which the tokens ``nxt`` lie below each row's best,
        over the rows < n."""
        best = z.max(-1)
        at = jnp.take_along_axis(z, nxt[:, None], -1)[:, 0]
        return jnp.where(jnp.arange(z.shape[0]) < n, best - at, 0.0).max()

    @jax.jit
    def first(z):
        return z.argmax(-1).astype(jnp.int32)
    return gap, first


def check(cell: Cell, served: Served, picks: List[int], seed: int,
          controls=()) -> Dict[str, Any]:
    """Compare the served tokens of ``picks`` with the plain reference:
    the widest gap by which a served token's reference logit lies below the
    reference's best at that position.  Each of ``controls`` (the
    reference's lower-precision variants) reads the same gap for the tokens
    it would put first at the same positions."""
    import jax.numpy as jnp
    ref, model = cell.ref, cell.config["model"]
    weights = ref.make_weights(model, seed)
    gap, first = _gap_fn()
    widest = {c: 0.0 for c in (None, *controls)}
    per = []
    t = time.perf_counter()
    for i in picks:
        req = served.requests[i]
        gen = np.asarray(req.generated, np.int32)
        seq = np.concatenate([np.asarray(req.prompt, np.int32), gen[:-1]])
        row0 = len(req.prompt) - 1
        z = ref.logits(weights, model, seq, row0)
        nxt = np.zeros((z.shape[0],), np.int32)
        nxt[:len(gen)] = gen
        one = {"uid": i, "prompt": len(req.prompt), "served": len(gen),
               "gap": float(gap(z, jnp.asarray(nxt), len(gen))),
               "s": time.perf_counter() - t}
        for c in controls:
            zc = ref.logits(weights, model, seq, row0, control=c)
            one[c] = float(gap(z, first(zc), len(gen)))
            del zc
        t = time.perf_counter()
        per.append(one)
        for c in widest:
            widest[c] = max(widest[c], one["gap" if c is None else c])
    return {"widest_logit_gap": widest.pop(None), "controls": widest,
            "requests": per,
            "served_tokens": sum(p["served"] for p in per)}


def judge(gap: float, compared: int, limit: float) -> bool:
    """``correct``: some served tokens were compared, and the widest gap
    lies within the limit."""
    return compared > 0 and gap <= limit


# -- the run ------------------------------------------------------------------

def device_info() -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def run(workload: dict, seed: int, seconds: float, trace: bool,
        t_process: float, end_to_end: List[dict],
        layer_metrics: List[dict], base: Path = registry.BENCH,
        controls=(), keep_trace: Optional[str] = None) -> Dict[str, Any]:
    """One run of ``workload``, reporting the cell's ``end_to_end`` metrics
    (or with ``trace`` its ``layer_metrics``); returns the result line's
    object.  ``controls`` also reads each named lower-precision control of
    the reference on the same sample, under ``controls``."""
    import jax
    from repro.obs.trace import Tracer
    from repro.serving.engine import PagedServeEngine
    cell = Cell.load(workload, base)
    mcfg = cell.model_config()
    scfg = cell.scheduler_config()
    split: Dict[str, float] = {"process": time.perf_counter() - t_process}
    params, times = build(cell, mcfg, seed)
    split.update(times)
    t = time.perf_counter()
    tracer = Tracer(capacity=1 << 20, jax_profiler=True) if trace else None
    engine = PagedServeEngine(params, mcfg, scfg, tracer=tracer)
    del params
    split["engine"] = time.perf_counter() - t
    t = time.perf_counter()
    n_variants = warm(engine)
    split["warm"] = time.perf_counter() - t
    watch = CompileWatch()

    trace_dir = None
    if trace:
        trace_dir = keep_trace or tempfile.mkdtemp(prefix="bench-trace-")

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # annotations only, no Python calls
        opts.host_tracer_level = 2

        def profile(start: bool):
            if start:
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            else:
                jax.profiler.stop_trace()
    else:
        profile = None
    traffic = registry.generator(cell.mix["generator"], base).make(
        cell.mix, seed, mcfg.vocab_size)
    served = serve(engine, traffic, cell.mix, seconds, profile)
    split["fill"] = served.t_fill
    setup_s = served.t0 - t_process
    summary = window.summarize(served.records, served.t0, served.t1,
                                served.t_end)
    late = window.lateness(served.records, served.t0, served.t1)
    compiles = watch.between(served.t0, served.t1)
    dev = device_info()
    stats = jax.devices()[0].memory_stats() or {}
    log(f"setup split (s): " + ", ".join(f"{k} {v:.3f}"
                                         for k, v in split.items())
        + f"; {n_variants} step variants warmed; setup_s {setup_s:.3f}")
    log(f"memory: bytes_in_use {stats.get('bytes_in_use')} "
        f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
        f"cache_nbytes {engine.cache_nbytes()} "
        f"bytes_limit {stats.get('bytes_limit')}")
    log(f"window: {served.t1 - served.t0:.3f}s, served on "
        f"{served.t_end - served.t1:.3f}s after it: {json.dumps(summary)}")
    log(f"generator lateness: {json.dumps(late)}")
    log(f"compiles inside the window: {json.dumps(compiles)}")
    log("steps and stalls in the window: " + json.dumps(window.stalls(
        served.steps, served.collections, served.t0, served.t1)))

    view = None
    if trace:
        from .view import RunView
        view = RunView.make(cell, scfg, served, tracer, trace_dir,
                            dev["kind"])
        if keep_trace is None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    # free the program's state before the reference runs
    engine.scheduler.pool = engine.scheduler.spool = None
    engine.scheduler.params = None
    del engine
    gc.collect()
    picks = sample_for_check(served, cell.limits, seed)
    result: Dict[str, Any] = {"attempted": summary["attempted"],
                              "failed": summary["failed"]}
    if trace:
        metrics, breakdown, busy_s, window_s = view.per_layer(layer_metrics)
        dev.update(busy_s=busy_s, window_s=window_s)
        result["metrics"] = metrics
        result["breakdown"] = breakdown
    else:
        values = dict(summary, setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in end_to_end}
    result["device"] = dev
    t = time.perf_counter()
    verdict = check(cell, served, picks, seed, controls)
    limit = float(cell.limits["widest_logit_gap"]["limit"])
    gap = verdict["widest_logit_gap"]
    n_cmp = verdict["served_tokens"]
    log(f"reference over {len(picks)} requests, {n_cmp} served tokens in "
        f"{time.perf_counter() - t:.3f}s: " + json.dumps(verdict["requests"]))
    result["correct"] = judge(gap, n_cmp, limit)
    if controls:
        # each control through the same decision: it has to read not correct
        result["controls"] = {c: {"gap": g, "correct": judge(g, n_cmp, limit)}
                              for c, g in verdict["controls"].items()}
        for c, v in result["controls"].items():
            log(f"control {c}: widest gap {v['gap']!r} limit {limit!r} "
                f"correct {v['correct']}")
    # the numbers compared, each beside its limit: last in the line
    result["checks"] = {"widest_logit_gap": {"value": gap, "limit": limit},
                        "served_tokens_compared": {"value": n_cmp,
                                                   "min": 1}}
    log(f"check served_tokens_compared {n_cmp} min 1")
    log(f"check widest_logit_gap {gap!r} limit {limit!r}")
    return result
