"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, limits and per-layer metrics are
found by name from ``BENCHMARK.json`` and the files under ``bench/``.  The
run builds the served model from the seed, warms up every program the
cell's traffic uses, brings the traffic to steady state (all of that is
``setup_s``), measures for ``--seconds``, checks what was served against
the plain reference, and prints one JSON object as the last line of
standard output.  ``--trace 1`` reports the per-layer metrics instead of the
end-to-end ones, from the engine's spans and counters and a device trace
of the window's first seconds.

It needs a TPU: without one, or with fewer chips than the cell asks for,
it exits non-zero and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="keep the device trace in this directory")
    args = ap.parse_args()

    from bench.lib import registry
    bench = registry.benchmark(ROOT)
    workload = registry.cell(bench, args.workload)

    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"bench: needs a TPU, JAX found {backend!r}", file=sys.stderr)
        return 3
    if jax.device_count() < workload["chips"]:
        print(f"bench: {args.workload} needs {workload['chips']} chips, "
              f"JAX found {jax.device_count()}", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    # small programs too: every later run reads them back
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"bench: {args.workload} seed {args.seed} on "
          f"{jax.devices()[0].device_kind} x {jax.device_count()}, compile "
          f"cache {cache}", file=sys.stderr, flush=True)

    from bench.lib import harness
    result = harness.run(
        workload, args.seed, args.seconds, bool(args.trace), T_PROCESS,
        registry.metrics_for(bench["end_to_end"], args.workload),
        registry.metrics_for(bench["per_layer"], args.workload),
        keep_trace=args.keep_trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
