"""Readings for a cell's limits, on the chip: the program's widest logit gap
and its controls' on several seeds, in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed it runs the cell as ``run.py`` does (build, warm up, fill,
a window of ``--seconds``) and compares the same sample of served requests
with the float32 reference three ways: the served tokens (the number the
cell's check compares), and the tokens that the reference computed one
precision step lower would put first at the same positions (``int4``:
weights, activations and KV cache in int4; ``int4-weights``: the weights
alone).  Each control goes through the run's own decision against the
cell's limit.  One JSON line per seed; the exit code is 1 where any control
reads correct on any seed.  The benchmark's own runs never run this.
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

CONTROLS = ("int4", "int4-weights")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    import jax
    if jax.default_backend() != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 3
    from bench.lib import harness, registry
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = registry.benchmark(ROOT)
    workload = registry.cell(bench, args.workload)
    mine = registry.metrics_for(bench["end_to_end"], args.workload)
    passed = []
    for seed in (int(s) for s in args.seeds.split(",")):
        r = harness.run(workload, seed, args.seconds, False,
                        time.perf_counter(), mine, [], controls=CONTROLS)
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "gap": r["checks"]["widest_logit_gap"]["value"],
                          "controls": r["controls"],
                          "attempted": r["attempted"]}), flush=True)
        passed += [(seed, c) for c, v in r["controls"].items()
                   if v["correct"]]
    if passed:
        print(f"control: read correct on {passed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
