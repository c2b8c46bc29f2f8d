"""Fault-tolerance demo: train, 'lose' chips, re-mesh, resume from checkpoint.

Runs with 8 emulated host devices; the first phase trains on a (4, 2) mesh,
then we simulate losing 3 devices and resume on the re-planned mesh with the
checkpoint re-sharded onto it (DESIGN.md §4 elastic path).

    PYTHONPATH=src python examples/elastic_restart.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.data import DataConfig, SyntheticLM
from repro.distributed import axis_rules, plan_remesh, build_mesh
from repro.distributed.sharding import param_spec
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_train_step
from repro.models import ModelConfig, init_params
from repro.models.config import LayerSpec
from repro.optim import AdamWConfig, init_state

CKPT = "experiments/elastic_demo"


def shardings_for(mesh, params):
    def visit(path, leaf):
        ps = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        return NamedSharding(mesh, param_spec(mesh, ps, leaf.shape))
    return jax.tree_util.tree_map_with_path(visit, params)


def train_steps(mesh, params, opt, cfg, ocfg, ds, start, n):
    step_fn = jax.jit(make_train_step(cfg, ocfg))
    with axis_rules(mesh):
        for i in range(start, start + n):
            batch = jax.tree_util.tree_map(jnp.asarray, ds.batch_at(i))
            params, opt, metrics = step_fn(params, opt, batch)
        print(f"  steps {start}..{start+n-1}: loss {float(metrics['loss']):.3f}")
    return params, opt


def main():
    cfg = ModelConfig(name="elastic-demo", vocab_size=256, d_model=128,
                      n_layers=2, n_heads=4, n_kv_heads=2, d_ff=256,
                      layer_pattern=(LayerSpec("attn", "dense"),), attn_chunk=32)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=64, global_batch=8)
    ocfg = AdamWConfig(lr=1e-3, warmup_steps=5, total_steps=40)
    ds = SyntheticLM(dcfg)
    mgr = CheckpointManager(CKPT, keep=2)

    print("[phase 1] mesh (4 data x 2 model) — 8 chips")
    mesh1 = make_mesh((4, 2), ("data", "model"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    params = jax.device_put(params, shardings_for(mesh1, params))
    opt = init_state(params, ocfg)
    params, opt = train_steps(mesh1, params, opt, cfg, ocfg, ds, 0, 10)
    mgr.save(10, params)
    print("  checkpointed at step 10")

    print("[phase 2] simulated failure: only 5 chips survive")
    plan = plan_remesh(5, old_data=4, old_model=2, global_batch=8)
    print(f"  remesh plan: {plan.describe()}")
    mesh2 = build_mesh(plan)

    template = init_params(cfg, jax.random.PRNGKey(0))
    restored = mgr.restore(10, template,
                           shardings=shardings_for(mesh2, template))
    opt2 = init_state(restored, ocfg)
    print("  restored + re-sharded onto the new mesh; resuming")
    restored, opt2 = train_steps(mesh2, restored, opt2, cfg, ocfg, ds, 10, 10)
    mgr.save(20, restored)
    print(f"[done] latest checkpoint: step {mgr.latest_step()} "
          f"(trained across two different meshes)")


if __name__ == "__main__":
    main()
