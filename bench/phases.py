"""Where a cell's step time goes, by the engine's own phases, on the chip.

    python3 bench/phases.py --workload <cell> --seeds 1,2,3 --seconds 51 \
        [--untraced 1]

For each seed it runs the cell as ``run.py --trace 1`` does (build, warm
up, fill, a window of ``--seconds`` with the engine's tracer on and a
device trace of the window's first seconds) and prints one JSON line:

  * ``metrics``: the cell's per-layer metrics, as ``run.py`` reports them;
  * ``coverage``: over the window's step calls, the share of each call's
    wall that its ``schedule`` + ``device_step`` + ``consume`` spans cover
    (least and median);
  * ``align``: the offset and worst residual by which ``lib/align.py``
    maps the tracer's spans onto the device trace;
  * ``exposed``: device-idle ms per traced step inside each host phase
    (``schedule``, ``device_step``, ``publish``, ``retire``, and
    ``device_wait`` for contrast), and their host-phase sum over the mean
    ``bench.step`` wall beside ``step_idle_share``;
  * ``stalls``: every step call longer than ``STALL_S``, with the time
    each phase held inside it;
  * ``untraced`` (with ``--untraced 1``): the same seed served again with
    tracing off; each step ordinal does the same work in both runs, so
    the walls of the steps after the profiled seconds compare one to one.

The benchmark's own runs never run this.  It needs a TPU.
"""
import argparse
import gc
import json
import shutil
import statistics
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench.lib import align, harness, registry  # noqa: E402

TOP = ("schedule", "device_step", "consume")
PHASES = ("schedule", "stage", "device_step", "consume", "device_wait",
          "publish", "retire")
EXPOSED = ("schedule", "device_step", "publish", "retire", "device_wait")
AFTER_PROFILE_S = 6.0     # stopping the profiler holds the host ~5 s
STALL_S = 0.5             # a step call this long is a stall


def by_step(spans) -> dict:
    """Phase spans grouped by their step ordinal."""
    out: dict = {}
    for s in spans:
        if s.kind in PHASES and s.args and "step" in s.args:
            out.setdefault(s.args["step"], []).append(s)
    return out


def step_walls(served) -> dict:
    """Wall of each step call, by step ordinal (the harness's step calls
    are the engine's steps, one ordinal each, from 1)."""
    return {i + 1: (a, b) for i, (a, b, *_rest) in enumerate(served.steps)}


def coverage(walls: dict, groups: dict, t0: float, t1: float) -> dict:
    shares = []
    for k, (a, b) in walls.items():
        if t0 <= a < t1 and k in groups and b > a:
            held = sum(s.dur for s in groups[k] if s.kind in TOP)
            shares.append(held / (b - a))
    if not shares:
        return {}
    return {"steps": len(shares), "min": min(shares),
            "median": statistics.median(shares)}


def stalls(walls: dict, groups: dict, t0: float, t1: float) -> list:
    out = []
    for k, (a, b) in walls.items():
        if t0 <= a < t1 and b - a > STALL_S:
            held: dict = {}
            for s in groups.get(k, []):
                held[s.kind] = held.get(s.kind, 0.0) + 1e3 * s.dur
            out.append({"step": k, "at_s": a - t0, "wall_ms": 1e3 * (b - a),
                        "phases_ms": held})
    return out


def exposed(view) -> dict:
    """The clocks' alignment, and device-idle ms per traced step inside
    each host phase."""
    fitted = align.fit(view.spans, view.trace.spans.get("paged_step", []))
    if fitted is None:
        return {}
    _, worst, paired = fitted
    out = {"align": {"worst_us": worst / 1e3, "pairs": len(paired)}}
    got = align.idle_by_kind(view, EXPOSED)
    if got is None:
        return out
    idle, n = got
    per = {k: v / 1e6 / n for k, v in idle.items()}
    host = sum(v for k, v in per.items() if k != "device_wait")
    steps = view.steps
    mean_step = (sum(b - a for a, b in steps) / len(steps) / 1e6
                 if steps else None)
    out.update(idle_ms_per_step=per, host_ms=host,
               mean_bench_step_ms=mean_step,
               host_share_of_step=(100.0 * host / mean_step
                                   if mean_step else None))
    return out


def after_profile(walls: dict, t0: float, t1: float, trace_s: float) -> dict:
    return {k: b - a for k, (a, b) in walls.items()
            if t0 + trace_s + AFTER_PROFILE_S <= a < t1}


def compare(on: dict, off: dict) -> dict:
    both = sorted(set(on) & set(off))
    if not both:
        return {}
    ratio = [on[k] / off[k] for k in both]
    return {"steps": len(both),
            "on_mean_ms": 1e3 * statistics.fmean(on[k] for k in both),
            "off_mean_ms": 1e3 * statistics.fmean(off[k] for k in both),
            "on_p50_ms": 1e3 * statistics.median(on[k] for k in both),
            "off_p50_ms": 1e3 * statistics.median(off[k] for k in both),
            "ratio_median": statistics.median(ratio)}


def _free(engine) -> None:
    engine.scheduler.pool = engine.scheduler.spool = None
    engine.scheduler.params = None
    gc.collect()


def measure(workload: dict, seed: int, seconds: float, per_layer: list,
            untraced: bool = False, base: Path = registry.BENCH) -> dict:
    import jax
    from repro.obs.trace import Tracer
    from repro.serving.engine import PagedServeEngine
    from bench.lib.view import RunView
    cell = harness.Cell.load(workload, base)
    mcfg, scfg = cell.model_config(), cell.scheduler_config()
    params, _ = harness.build(cell, mcfg, seed)
    tracer = Tracer(capacity=1 << 20, jax_profiler=True)
    engine = PagedServeEngine(params, mcfg, scfg, tracer=tracer)
    harness.warm(engine)
    trace_dir = tempfile.mkdtemp(prefix="phases-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2

    def profile(start: bool):
        if start:
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        else:
            jax.profiler.stop_trace()
    make = registry.generator(cell.mix["generator"], base).make
    served = harness.serve(engine, make(cell.mix, seed, mcfg.vocab_size),
                           cell.mix, seconds, profile)
    n_steps = engine.scheduler.stats["steps"]
    kind = jax.devices()[0].device_kind
    view = RunView.make(cell, scfg, served, tracer, trace_dir, kind)
    shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {k: v["value"] for k, v in view.per_layer(per_layer)[0].items()}
    _free(engine)
    del engine
    groups = by_step(tracer.events)
    walls = step_walls(served)
    t0, t1 = served.t0, served.t1
    trace_s = float(cell.mix.get("trace_s", 3.0))
    out = {"workload": workload["name"], "seed": seed, "metrics": metrics,
           "steps_match": n_steps == len(served.steps),
           "coverage": coverage(walls, groups, t0, t1),
           "exposed": exposed(view),
           "stalls": stalls(walls, groups, t0, t1)}
    if untraced:
        engine = PagedServeEngine(params, mcfg, scfg)
        harness.warm(engine)
        off = harness.serve(engine, make(cell.mix, seed, mcfg.vocab_size),
                            cell.mix, seconds)
        out["untraced"] = compare(
            after_profile(walls, t0, t1, trace_s),
            {k: b - a for k, (a, b) in step_walls(off).items()})
        out["untraced"]["steps_match"] = \
            engine.scheduler.stats["steps"] == len(off.steps)
        _free(engine)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--untraced", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    import jax
    if jax.default_backend() != "tpu":
        print("phases: needs a TPU", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = registry.benchmark(ROOT)
    workload = registry.cell(bench, args.workload)
    per_layer = registry.metrics_for(bench["per_layer"], args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(measure(workload, seed, args.seconds, per_layer,
                                 bool(args.untraced))),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
