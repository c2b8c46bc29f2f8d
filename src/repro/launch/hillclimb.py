"""Perf hillclimb harness: lower a cell under named config variants and
record the roofline-term deltas (hypothesis -> change -> measure loop).

  PYTHONPATH=src python -m repro.launch.hillclimb --cell mamba2_train \
      [--variant bf16_ssd] [--out experiments/perf]

Variants are expressed as (sharding-rule overrides, ModelConfig field
overrides, env toggles) so each measurement is one flag away from the
baseline — the log in EXPERIMENTS.md §Perf references these names.
"""
import argparse
import dataclasses
import json
import os
import time

import jax

from repro.configs import get_config, _MODULES
from repro.distributed.sharding import axis_rules
from repro.launch import hlo_analysis as ha
from repro.launch.dryrun import fake_host_devices, lower_cell
from repro.launch.mesh import make_production_mesh

# variant := dict(rules=..., cfg=..., env=...)
CELLS = {
    "minicpm3_prefill": dict(arch="minicpm3-4b", shape="prefill_32k"),
    "mamba2_train": dict(arch="mamba2-370m", shape="train_4k"),
    "llama4_train": dict(arch="llama4-maverick-400b-a17b", shape="train_4k"),
    "qwen3_decode": dict(arch="qwen3-32b", shape="decode_32k"),
}

VARIANTS = {
    "baseline": dict(),
    # -- memory-term levers --------------------------------------------------
    "remat_dots": dict(cfg=dict(remat_policy="dots_nobatch")),
    "remat_everything": dict(cfg=dict(remat_policy="everything")),
    "ssd_chunk_512": dict(cfg=dict(ssm_chunk=512)),
    "ssd_chunk_128": dict(cfg=dict(ssm_chunk=128)),
    "attn_chunk_512": dict(cfg=dict(attn_chunk=512)),
    "attn_chunk_2048": dict(cfg=dict(attn_chunk=2048)),
    "attn_chunk_4096": dict(cfg=dict(attn_chunk=4096)),
    "qg_bf16_chunk4096": dict(cfg=dict(attn_chunk=4096),
                              env=dict(REPRO_FLASH_QG_BF16="1")),
    "bf16_ssd": dict(env=dict(REPRO_SSD_BF16="1")),
    "flash_decode_ref": dict(env=dict(REPRO_FLASH_DECODE="1")),
    "w8a8_weights": dict(env=dict(REPRO_SERVE_W8A8="1")),
    "w8a8_flash": dict(env=dict(REPRO_SERVE_W8A8="1", REPRO_FLASH_DECODE="1")),
    "w8a8_nofsdp": dict(env=dict(REPRO_SERVE_W8A8="1"), rules=dict(fsdp=())),
    "w8a8_nofsdp_bf16deq": dict(env=dict(REPRO_SERVE_W8A8="1",
                                         REPRO_DECODE_BF16_DEQ="1"),
                                rules=dict(fsdp=())),
    # -- collective-term levers ----------------------------------------------
    "no_fsdp": dict(rules=dict(fsdp=())),
    "no_fsdp_mb8": dict(rules=dict(fsdp=()), microbatches=8),
    "no_fsdp_mb4": dict(rules=dict(fsdp=()), microbatches=4),
    "no_fsdp_mb2": dict(rules=dict(fsdp=()), microbatches=2),
    "mb4_only": dict(microbatches=4),
    "mb8_only": dict(microbatches=8),
    "no_fsdp_mb1": dict(rules=dict(fsdp=()), microbatches=1),
    "seq_carry_off": dict(rules=dict(seq_carry=(), seq=())),
}


def measure(cell: str, variant: str, out_dir: str):
    spec = CELLS[cell]
    var = VARIANTS[variant]
    for k, v in (var.get("env") or {}).items():
        os.environ[k] = v
    arch = spec["arch"]
    mod = _MODULES[arch]
    orig = mod.FULL
    try:
        if var.get("cfg"):
            mod.FULL = dataclasses.replace(orig, **var["cfg"])
        if var.get("microbatches"):
            import repro.launch.dryrun as dr
            orig_mb = dr.train_microbatches
            dr.train_microbatches = lambda cfg, mesh=None, global_batch=256, _n=var["microbatches"]: _n
        mesh = make_production_mesh()
        rec = lower_cell(arch, spec["shape"], mesh, rules=var.get("rules"))
        rec["cell"] = cell
        rec["variant"] = variant
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, f"{cell}__{variant}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        r = rec["roofline"]
        print(f"{cell} / {variant}: hbm {rec['memory']['hbm_per_device']/2**30:.2f} GiB | "
              f"c/m/coll {r['compute_s']:.4f}/{r['memory_s']:.4f}/{r['collective_s']:.4f} "
              f"| dom {r['dominant']} | frac {r['roofline_fraction']:.4f}")
        return rec
    finally:
        mod.FULL = orig
        if var.get("microbatches"):
            import repro.launch.dryrun as dr
            dr.train_microbatches = orig_mb
        for k in (var.get("env") or {}):
            os.environ.pop(k, None)


def main():
    fake_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True, choices=list(CELLS))
    ap.add_argument("--variant", default="baseline", choices=list(VARIANTS))
    ap.add_argument("--out", default="experiments/perf")
    args = ap.parse_args()
    measure(args.cell, args.variant, args.out)


if __name__ == "__main__":
    main()
