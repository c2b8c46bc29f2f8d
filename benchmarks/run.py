"""Benchmark harness entry point: one function per paper table/figure.

``python -m benchmarks.run [--only NAME]`` prints ``name,us_per_call,derived``
CSV rows per the repo contract, and each bench also writes its full CSV
under experiments/bench/.
"""
from __future__ import annotations

import argparse
import csv
import inspect
import sys
import time
import traceback

import jax

from repro.launch.compile_cache import enable_compile_cache

BENCHES = [
    ("perplexity_table1_4", "benchmarks.bench_perplexity"),
    ("throughput_table2", "benchmarks.bench_throughput"),
    ("comparison_table3", "benchmarks.bench_comparison_matrix"),
    ("latency_table5", "benchmarks.bench_latency_breakdown"),
    ("weight_dists_fig1", "benchmarks.bench_weight_dists"),
    ("scaling_fig8", "benchmarks.bench_scaling"),
    ("kernels", "benchmarks.bench_kernels"),
    ("serving_paged", "benchmarks.bench_serving"),
    ("scorecard", "benchmarks.bench_scorecard"),
]


def spec_regression_gate(path: str = "experiments/bench/serving_spec.csv"):
    """Return an error string if the spec sweep lost its headline win.

    The spec sweep's reason to exist is that the int8 self-draft at
    gamma=4 turns near-total acceptance into wall-clock speedup over plain
    paged decode.  If ``spec_g4_int8self`` ever fails to strictly beat
    ``spec_plain`` in tokens/s, the speculation machinery regressed (slower
    verify launch, extra per-round dispatches, draft-lane churn) even though
    every correctness test still passes — so the bench run itself goes red.
    """
    try:
        with open(path) as f:
            rows = {r["point"]: r for r in csv.DictReader(f)}
        plain = float(rows["spec_plain"]["tokens_per_s"])
        spec = float(rows["spec_g4_int8self"]["tokens_per_s"])
    except (OSError, KeyError, ValueError) as e:
        return f"spec gate: cannot read {path} ({e!r})"
    if spec <= plain:
        return (f"spec gate: spec_g4_int8self {spec} tokens/s does not beat "
                f"spec_plain {plain} tokens/s ({path})")
    return None


def ladder_gate(path: str = "experiments/bench/serving_ladder.csv"):
    """Return an error string if the bit ladder lost its capacity win or
    blew its divergence budget.

    The ladder's contract (ISSUE 8): with the ladder *off* the engine is
    bit-identical to the pre-codec scheduler, so the off row's token
    divergence must be exactly 0; with the ladder *on* under pool pressure
    the peak reusable prefix capacity (cached int8 blocks + demoted int4
    halves) must reach >= 1.5x the INT8-only run, paid for by a *bounded*
    token divergence — the 8-code promote requant may drift tokens, but a
    divergence above 0.25 means the requant (or the promote plumbing) broke,
    not just wobbled.
    """
    try:
        with open(path) as f:
            rows = {r["point"]: r for r in csv.DictReader(f)}
        off, on = rows["ladder_off"], rows["ladder_on"]
        off_div = float(off["token_divergence"])
        on_div = float(on["token_divergence"])
        ratio = float(on["capacity_ratio"])
        demotions = int(on["demotions"])
    except (OSError, KeyError, ValueError) as e:
        return f"ladder gate: cannot read {path} ({e!r})"
    if off_div != 0.0:
        return (f"ladder gate: ladder-off run diverged from baseline "
                f"({off_div}) — the codec refactor broke bit-identity ({path})")
    if demotions == 0:
        return (f"ladder gate: pressure sweep produced no demotions — the "
                f"ladder never engaged, capacity claim untested ({path})")
    if ratio < 1.5:
        return (f"ladder gate: effective prefix-cache capacity ratio {ratio} "
                f"< 1.5x INT8-only ({path})")
    if on_div > 0.25:
        return (f"ladder gate: ladder-on token divergence {on_div} exceeds "
                f"the 0.25 bound ({path})")
    return None


def sharded_parity_gate(path: str = "experiments/bench/serving_sharded.csv"):
    """Return an error string if any mesh shape diverged from the unsharded
    engine.

    Gather-based TP's entire contract is that the 2D ``data x model`` mesh
    composition is a pure layout change: every sweep row carries a
    ``tokens_match`` column comparing its greedy output token-for-token
    against the meshless reference run.  Any ``False`` means a cross-shard
    reduction crept back into a serving matmul (fp reassociation crossing
    the pool quantizers' round() boundaries) — a correctness regression the
    unit suite can miss if the drift lands between its golden checkpoints.
    """
    try:
        with open(path) as f:
            rows = list(csv.DictReader(f))
        if not rows:
            return f"sharded gate: {path} is empty"
    except OSError as e:
        return f"sharded gate: cannot read {path} ({e!r})"
    bad = [r["point"] for r in rows
           if str(r.get("tokens_match", "")).lower() != "true"]
    if bad:
        return (f"sharded gate: sharded-vs-unsharded token divergence at "
                f"{bad} ({path})")
    return None


def scorecard_gate(out_dir: str = "experiments/scorecard"):
    """Return an error string if the serving-path quality scorecard broke.

    The scorecard's contract: quantized serving quality is *measured*, not
    assumed.  Red when (a) the artifact set is missing, schema-invalid, or
    thinner than the acceptance grid (>= 2 methods x {int8, int4} x ladder
    on/off plus the dense reference), (b) the symmetric-int8 serving NLL
    drifts from the fp32 dense reference beyond 0.05 nats — observed drift
    on the bench checkpoint is ~3e-4, so a trip means real quality loss in
    the W8A8 serving path, not noise — or (c) turning the bit ladder ON
    costs more than 0.05 nats over the same config with the ladder off
    (the demote/promote requant is supposed to be near-free for quality).
    """
    from repro.eval.scorecard import load_artifacts
    arts, errors = load_artifacts(out_dir)
    if errors:
        return "scorecard gate: invalid artifacts: " + "; ".join(errors[:4])
    required = {"fp32_dense"}
    for m in ("symmetric", "zeropoint"):
        required |= {f"{m}-int8", f"{m}-int8-ladder", f"{m}-int4"}
    missing = sorted(required - set(arts))
    if missing:
        return f"scorecard gate: missing artifacts {missing} ({out_dir})"
    fp = arts["fp32_dense"]["quality"]["nll"]
    int8 = arts["symmetric-int8"]["quality"]["nll"]
    if abs(int8 - fp) > 0.05:
        return (f"scorecard gate: symmetric-int8 serving NLL {int8:.4f} "
                f"deviates from fp32 dense {fp:.4f} by {abs(int8 - fp):.4f} "
                f"> 0.05 nats ({out_dir})")
    for m in ("symmetric", "zeropoint"):
        off = arts[f"{m}-int8"]["quality"]["nll"]
        on = arts[f"{m}-int8-ladder"]["quality"]["nll"]
        if on - off > 0.05:
            return (f"scorecard gate: {m} ladder-on NLL {on:.4f} regresses "
                    f"{on - off:.4f} > 0.05 nats past ladder-off {off:.4f} "
                    f"({out_dir})")
    return None


def obs_trace_gate(path: str = "experiments/bench/serving_obs.csv",
                      trace_path: str = "experiments/bench/serving_trace.json"):
    """Return an error string if the exported trace broke.

    The exported Chrome trace must schema-validate and contain every span
    kind the instrumentation promises (prefill chunk, decode step,
    preemption, spec round, ladder demotion) — a missing kind means some
    scheduler path silently lost its spans.  What tracing costs is measured
    on the chip (``bench/phases.py``), not here: a host-CPU tokens/s ratio
    says nothing about it."""
    import json
    from benchmarks.bench_serving import TRACE_REQUIRED_KINDS
    from repro.obs import validate_chrome_trace
    try:
        with open(path) as f:
            rows = {r["point"]: r for r in csv.DictReader(f)}
        dropped = int(rows["obs_on"]["trace_dropped"])
    except (OSError, KeyError, ValueError) as e:
        return f"obs gate: cannot read {path} ({e!r})"
    try:
        with open(trace_path) as f:
            obj = json.load(f)
    except (OSError, ValueError) as e:
        return f"obs gate: cannot load {trace_path} ({e!r})"
    errs = validate_chrome_trace(obj)
    if errs:
        return f"obs gate: trace schema errors: {errs[:4]} ({trace_path})"
    kinds = {e.get("name") for e in obj["traceEvents"]}
    missing = [k for k in TRACE_REQUIRED_KINDS if k not in kinds]
    if missing:
        return (f"obs gate: exported trace is missing span kinds {missing} "
                f"({trace_path})")
    if dropped and dropped > len(obj["traceEvents"]):
        return (f"obs gate: ring dropped {dropped} spans — more than it "
                f"kept; raise Tracer capacity for the sweep ({path})")
    return None


def pallas_interpret_gate():
    """Smoke-mode gate on a CPU host: re-run the paged kernel parity subset
    with REPRO_FORCE_PALLAS=1 (pallas kernels in interpret mode), so the
    bench loop exercises the real kernel bodies — not just the jnp oracles
    the default CPU path falls back to.  It runs pytest in a child process,
    which on an accelerator host could not reach the chip this process
    holds; there the benches already run the compiled kernels."""
    import os
    import subprocess
    env = dict(os.environ)
    env.update({"PYTHONPATH": "src", "REPRO_FORCE_PALLAS": "1"})
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q",
         "tests/kernels/test_paged_suite.py"],
        capture_output=True, text=True, timeout=1800, env=env)
    if r.returncode != 0:
        return ("pallas interpret gate: paged kernel parity subset failed "
                "under REPRO_FORCE_PALLAS=1\n" + r.stdout[-2000:])
    return None


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None)
    p.add_argument("--smoke", action="store_true",
                   help="pass smoke=True to benches that support it "
                        "(tiny workloads, tier-1-loop friendly)")
    args = p.parse_args()
    enable_compile_cache()

    print("name,us_per_call,derived")
    failures = 0
    ran = []
    for name, module in BENCHES:
        if args.only and args.only not in name:
            continue
        ran.append(name)
        t0 = time.time()
        try:
            mod = __import__(module, fromlist=["run"])
            kwargs = {}
            if args.smoke and "smoke" in inspect.signature(mod.run).parameters:
                kwargs["smoke"] = True
            rows = mod.run(**kwargs)
            dt = (time.time() - t0) * 1e6
            derived = ";".join(
                f"{r.get('method', r.get('kernel', r.get('point', '?')))}="
                f"{r.get('ppl', r.get('tokens_per_s', r.get('total_ms', r.get('us_per_call', r.get('mem_ratio', '')))))}"
                for r in rows[:6])
            print(f"{name},{dt:.0f},{derived}")
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"{name},-1,FAILED")
    if "serving_paged" in ran:
        # perf regression gate on the freshly written spec-sweep CSV (only
        # when that bench actually ran — --only runs must not judge a stale
        # file): speculation must still pay for itself in wall-clock
        err = spec_regression_gate()
        if err:
            failures += 1
            print(err, file=sys.stderr)
        # capacity + divergence gate on the freshly written ladder sweep:
        # ladder off must stay bit-identical, ladder on must buy >= 1.5x
        # prefix capacity within the divergence budget
        err = ladder_gate()
        if err:
            failures += 1
            print(err, file=sys.stderr)
        # correctness gate on the freshly written sharded-mesh sweep: any
        # mesh shape whose greedy tokens diverge from the unsharded engine
        # turns the bench run red
        err = sharded_parity_gate()
        if err:
            failures += 1
            print(err, file=sys.stderr)
        # the exported Chrome trace must be schema-valid with every
        # promised span kind present
        err = obs_trace_gate()
        if err:
            failures += 1
            print(err, file=sys.stderr)
    if "scorecard" in ran:
        # quality regression gate on the freshly written scorecard artifacts:
        # int8 serving NLL must track the fp dense reference and the bit
        # ladder must stay quality-neutral (runs under --smoke too — the
        # smoke sweep writes the full acceptance grid)
        err = scorecard_gate()
        if err:
            failures += 1
            print(err, file=sys.stderr)
    if args.smoke and "kernels" in ran and jax.default_backend() == "cpu":
        err = pallas_interpret_gate()
        if err:
            failures += 1
            print(err, file=sys.stderr)
    if failures:
        # stdout is the CSV contract (often piped to a file): repeat the
        # verdict on stderr so a red run is visible there too, and exit
        # nonzero so CI never mistakes a raising bench for a pass
        print(f"benchmarks.run: {failures} bench(es) FAILED", file=sys.stderr)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
