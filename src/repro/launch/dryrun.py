"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and record memory/cost/collective analysis.

  PYTHONPATH=src python -m repro.launch.dryrun --mesh both \
      [--arch qwen3-32b] [--shape train_4k] [--out experiments/dryrun]

Success criteria (assignment): ``.lower().compile()`` succeeds for the
16x16 single-pod mesh AND the (2,16,16) multi-pod mesh for every applicable
cell; ``memory_analysis()`` proves fit; ``cost_analysis()`` feeds §Roofline.

One JSON record per cell is written to --out (resumable sweep).
"""
import argparse
import json
import os
import time
import traceback
from functools import partial

import jax
import numpy as np

from repro.configs import ASSIGNED, get_config
from repro.distributed.sharding import axis_rules
from repro.launch import hlo_analysis as ha
from repro.launch.mesh import make_production_mesh, mesh_chip_count
from repro.launch.steps import (SHAPES, cell_is_applicable, input_specs,
                                make_prefill_step, make_serve_step,
                                make_train_step, shape_kind)
from repro.optim import AdamWConfig


def make_ocfg(cfg) -> AdamWConfig:
    # INT8 Adam moments for >10B-param archs: the quantized-optimizer trick
    # that makes llama4-maverick train_4k fit one pod (DESIGN.md §6).
    return AdamWConfig(quantized_state=cfg.param_count() > 10e9)


def train_microbatches(cfg, mesh=None, global_batch: int = 256) -> int:
    """Gradient-accumulation factor for the train_4k cell (memory fit).

    Capped so each microbatch still shards over the full (pod, data) batch
    extent — a non-divisible micro batch silently replicates (dry-run
    finding: 10x flops on the multi-pod MoE cell)."""
    n = cfg.param_count()
    mb = 16 if n > 100e9 else (4 if n > 20e9 else 1)
    if mesh is not None:
        import numpy as np
        bsz = int(np.prod([mesh.shape[a] for a in mesh.axis_names
                           if a in ("pod", "data")]))
        mb = min(mb, max(global_batch // bsz, 1))
    return mb


def lower_cell(arch: str, shape: str, mesh, *, rules=None, save_hlo=None,
               block_len: int = 0):
    """Lower + compile one cell.  Returns a result record dict."""
    cfg = get_config(arch)
    if block_len:
        cfg = type(cfg)(**{**cfg.__dict__, "attn_chunk": block_len})
    kind = shape_kind(shape)
    chips = mesh_chip_count(mesh)
    rec = dict(arch=arch, shape=shape, kind=kind, chips=chips,
               mesh=dict(zip(mesh.axis_names, [mesh.shape[a] for a in mesh.axis_names])),
               params=cfg.param_count(), active_params=cfg.active_param_count())

    t0 = time.time()
    with axis_rules(mesh, rules):
        ocfg = make_ocfg(cfg)
        specs = input_specs(cfg, shape, mesh, ocfg)
        if kind == "train":
            import jax.numpy as jnp
            mb = train_microbatches(cfg, mesh, SHAPES[shape]["batch"])
            rec["microbatches"] = mb
            # >100B params: bf16 grad accumulation (memory fit; DESIGN.md §6)
            acc_dt = jnp.bfloat16 if cfg.param_count() > 100e9 else jnp.float32
            fn = make_train_step(cfg, ocfg, microbatches=mb, accum_dtype=acc_dt)
            lowered = jax.jit(fn, donate_argnums=(0, 1)).lower(
                specs["params"], specs["opt_state"], specs["batch"])
        elif kind == "prefill":
            fn = make_prefill_step(cfg, SHAPES[shape]["seq"])
            lowered = jax.jit(fn).lower(specs["params"], specs["batch"])
        else:
            fn = make_serve_step(cfg)
            lowered = jax.jit(fn, donate_argnums=(2,)).lower(
                specs["params"], specs["tokens"], specs["cache"])
        rec["lower_s"] = round(time.time() - t0, 2)

        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

    mem = compiled.memory_analysis()
    rec["memory"] = dict(
        argument_bytes=int(getattr(mem, "argument_size_in_bytes", 0)),
        output_bytes=int(getattr(mem, "output_size_in_bytes", 0)),
        temp_bytes=int(getattr(mem, "temp_size_in_bytes", 0)),
        code_bytes=int(getattr(mem, "generated_code_size_in_bytes", 0)),
    )
    # Aliased (donated) args don't add; per-device HBM demand:
    rec["memory"]["hbm_per_device"] = (
        rec["memory"]["argument_bytes"] + rec["memory"]["temp_bytes"]
        + rec["memory"]["output_bytes"])

    hlo_text = compiled.as_text()
    terms, coll = ha.roofline_from_compiled(compiled, chips, hlo_text)
    s = SHAPES[shape]
    mf = ha.model_flops(cfg, kind, s["seq"], s["batch"])
    rec["roofline"] = dict(
        flops_per_device=terms.flops_per_device,
        bytes_per_device=terms.bytes_per_device,
        wire_bytes_per_device=terms.wire_bytes_per_device,
        compute_s=terms.compute_s,
        memory_s=terms.memory_s,
        collective_s=terms.collective_s,
        dominant=terms.dominant,
        step_time_bound_s=terms.step_time_s,
        model_flops_total=mf,
        useful_flops_ratio=(mf / (terms.flops_per_device * chips)
                            if terms.flops_per_device else 0.0),
        roofline_fraction=terms.roofline_fraction(mf),
        collective_counts=coll.counts,
        collective_bytes_by_kind={k: float(v) for k, v in coll.bytes_by_kind.items()},
    )
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo_text)
    return rec


def fake_host_devices(n: int = 512) -> None:
    """Give the CPU backend ``n`` devices for the production meshes.  Call
    before the first jax backend query (jax fixes the device count then);
    importing this module leaves ``XLA_FLAGS`` alone."""
    flag = f"--xla_force_host_platform_device_count={n}"
    os.environ["XLA_FLAGS"] = f"{os.environ.get('XLA_FLAGS', '')} {flag}".strip()


def main():
    fake_host_devices()
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None, help="one arch id (default: all)")
    p.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    p.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    p.add_argument("--out", default="experiments/dryrun")
    p.add_argument("--save-hlo", action="store_true")
    p.add_argument("--skip-existing", action="store_true")
    args = p.parse_args()

    os.makedirs(args.out, exist_ok=True)
    archs = [args.arch] if args.arch else ASSIGNED
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    n_ok = n_skip = n_fail = 0
    for multi in meshes:
        mesh = make_production_mesh(multi_pod=multi)
        tag = "multi" if multi else "single"
        for arch in archs:
            cfg = get_config(arch)
            for shape in shapes:
                name = f"{arch}__{shape}__{tag}"
                path = os.path.join(args.out, name + ".json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[skip-existing] {name}")
                    continue
                ok, why = cell_is_applicable(cfg, shape)
                if not ok:
                    with open(path, "w") as f:
                        json.dump(dict(arch=arch, shape=shape, mesh=tag,
                                       skipped=True, reason=why), f, indent=1)
                    print(f"[skipped] {name}: {why}")
                    n_skip += 1
                    continue
                print(f"[lower+compile] {name} ...", flush=True)
                try:
                    hlo_path = (os.path.join(args.out, name + ".hlo.txt")
                                if args.save_hlo else None)
                    rec = lower_cell(arch, shape, mesh, save_hlo=hlo_path)
                    rec["mesh_tag"] = tag
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=1)
                    r = rec["roofline"]
                    print(f"  ok: lower {rec['lower_s']}s compile {rec['compile_s']}s | "
                          f"hbm/dev {rec['memory']['hbm_per_device']/2**30:.2f} GiB | "
                          f"terms c/m/coll = {r['compute_s']:.4f}/{r['memory_s']:.4f}/"
                          f"{r['collective_s']:.4f} s -> {r['dominant']}", flush=True)
                    n_ok += 1
                except Exception as e:
                    n_fail += 1
                    with open(path + ".fail", "w") as f:
                        f.write(traceback.format_exc())
                    print(f"  FAIL: {type(e).__name__}: {e}", flush=True)
    print(f"\ndone: {n_ok} ok, {n_skip} skipped, {n_fail} failed")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
