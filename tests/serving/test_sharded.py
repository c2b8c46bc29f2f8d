"""Tensor/expert-parallel sharded serving goldens (ISSUE 7 tentpole).

Contract: serving on a 2D ``data x model`` mesh — tensor-parallel
attention/MLP inside each replica, expert-parallel MoE, kv-head-sharded
block pools — emits token-for-token identical greedy output to the
unsharded engine, for GQA, MLA, MoE and hybrid-SSM configs, through forced
preemption/resume and speculative decoding.  Multi-device cases run in a
subprocess with XLA_FLAGS=8 host devices so the main test process keeps the
default single-device view (same pattern as tests/distributed).
"""
import subprocess
import sys
import textwrap


def _run_subprocess(code: str, extra_env=None):
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "JAX_PLATFORMS": "cpu", "HOME": "/root"}
    if extra_env:
        env.update(extra_env)
    r = subprocess.run(
        [sys.executable, "-c", COMMON + textwrap.dedent(code)],
        capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


# Shared scaffolding for every scenario: tiny configs, bucket-exact prompts
# (prefill_chunk == block_size keeps chunk boundaries identical between the
# baseline and the meshed engine), and an output-dict helper.
COMMON = """
import dataclasses
import jax
import numpy as np
from repro.launch.mesh import make_mesh
from repro.models import ModelConfig, init_params
from repro.models.config import LayerSpec
from repro.serving.engine import PagedServeEngine, Request
from repro.serving.scheduler import SchedulerConfig

SCFG = SchedulerConfig(block_size=16, num_blocks=24, max_batch=4,
                       max_blocks_per_req=8, prefill_chunk=16,
                       token_budget=128)
PROMPTS = [(np.arange(16 * (1 + i % 2), dtype=np.int32) * (3 + 2 * i)) % 128
           for i in range(4)]

def reqs(n=4, max_new=8):
    return [Request(uid=i, prompt=PROMPTS[i % len(PROMPTS)].copy(),
                    max_new_tokens=max_new) for i in range(n)]

def outputs(eng):
    return {r.uid: r.generated for r in eng.finished}

def serve_paged(params, cfg, scfg=None, mesh=None, n=4, max_new=8):
    eng = PagedServeEngine(params, cfg, scfg or SCFG, mesh=mesh)
    for r in reqs(n, max_new):
        eng.add_request(r)
    eng.run()
    return eng
"""


def test_sharded_gqa_tp_parity_and_pool_shrink():
    """GQA on a (1, 2) model-parallel mesh: token parity with the unsharded
    engine, and the kv-head-sharded pool really halves per-device bytes."""
    out = _run_subprocess("""
        CFG = ModelConfig(name="t", vocab_size=128, d_model=64, n_layers=2,
                          n_heads=4, n_kv_heads=2, d_ff=128, attn_chunk=16)
        params = init_params(CFG, jax.random.PRNGKey(0))
        base = serve_paged(params, CFG)
        mesh = make_mesh((1, 2), ("data", "model"))
        tp = serve_paged(params, CFG, mesh=mesh)
        assert outputs(base) == outputs(tp), "TP perturbed greedy output"
        mb, mt = base.metrics(), tp.metrics()
        assert mb["cache_nbytes"] == mt["cache_nbytes"]
        assert mb["cache_nbytes_per_device"] == mb["cache_nbytes"]
        # int8 k/v codes shard over kv_heads; per-slot scales too -> the
        # per-device pool footprint drops to ~half of the logical pool
        assert mt["cache_nbytes_per_device"] <= 0.6 * mt["cache_nbytes"], mt
        print("GQA_TP_OK")
    """)
    assert "GQA_TP_OK" in out


def test_sharded_replicated_2x2_spec_preempt_parity():
    """The full 2D composition: 2 data-parallel replicas x 2-way tensor
    parallel, speculative decoding on, with a forced mid-stream preemption
    at the same emitted-token count in both runs — still token-for-token
    equal to the host-side (meshless) replica fleet."""
    out = _run_subprocess("""
        from repro.serving.replica import ReplicaConfig, ReplicatedServeEngine
        from repro.serving.spec_decode import SpecConfig
        CFG = ModelConfig(name="t", vocab_size=128, d_model=64, n_layers=2,
                          n_heads=4, n_kv_heads=2, d_ff=128, attn_chunk=16)
        params = init_params(CFG, jax.random.PRNGKey(0))
        scfg = dataclasses.replace(SCFG, max_batch=2, num_blocks=48,
                                   spec=SpecConfig(gamma=3))

        def serve(mesh):
            eng = ReplicatedServeEngine(
                params, CFG, scfg,
                ReplicaConfig(n_replicas=2, policy="round_robin"), mesh=mesh)
            for r in reqs(4, 10):
                eng.add_request(r)
            fired = False
            while any(rep.has_work for rep in eng.replicas):
                eng.step()
                r0 = eng.replicas[0].slots[0]
                if not fired and r0 is not None and r0.state == "decode" \\
                        and len(r0.req.generated) >= 2:
                    eng.replicas[0]._preempt(0)
                    fired = True
            assert fired, "preemption never fired"
            for rep in eng.replicas:
                rep.alloc.check()
            return eng

        base = serve(None)
        mesh = make_mesh((2, 2), ("data", "model"))
        tp = serve(mesh)
        assert outputs(base) == outputs(tp), "2D mesh perturbed greedy output"
        mt = tp.metrics()
        assert mt["spec_tokens_per_step"] > 1.0
        assert mt["preemptions"] >= 1
        per = mt["per_replica"][0]
        assert per["cache_nbytes_per_device"] <= 0.6 * per["cache_nbytes"]
        print("SPEC_2X2_OK")
    """)
    assert "SPEC_2X2_OK" in out


def test_sharded_mla_tp_parity():
    """MLA on a (1, 2) mesh: queries shard over heads, the latent cache
    stays replicated (there is no kv_heads axis to cut) — parity must hold
    and the pool footprint must NOT shrink."""
    out = _run_subprocess("""
        CFG = ModelConfig(name="mla", vocab_size=128, d_model=64, n_layers=2,
                          n_heads=4, d_ff=128, q_lora_rank=32, kv_lora_rank=16,
                          qk_nope_head_dim=16, qk_rope_head_dim=8,
                          v_head_dim=16,
                          layer_pattern=(LayerSpec("mla", "dense"),),
                          attn_chunk=16)
        params = init_params(CFG, jax.random.PRNGKey(1))
        base = serve_paged(params, CFG)
        mesh = make_mesh((1, 2), ("data", "model"))
        tp = serve_paged(params, CFG, mesh=mesh)
        assert outputs(base) == outputs(tp), "MLA TP perturbed greedy output"
        mt = tp.metrics()
        assert mt["cache_nbytes_per_device"] == mt["cache_nbytes"]
        print("MLA_TP_OK")
    """)
    assert "MLA_TP_OK" in out


def test_sharded_moe_parity():
    """MoE on a (1, 2) mesh (expert_ffn tensor-parallel; the expert axis
    degenerates to replicated on a size-1 data axis): token parity holds."""
    out = _run_subprocess("""
        CFG = ModelConfig(name="moe", vocab_size=128, d_model=64, n_layers=2,
                          n_heads=4, n_kv_heads=2, d_ff=128, n_experts=4,
                          n_experts_active=2, capacity_factor=8.0,
                          layer_pattern=(LayerSpec("attn", "moe"),),
                          attn_chunk=16)
        params = init_params(CFG, jax.random.PRNGKey(2))
        base = serve_paged(params, CFG)
        mesh = make_mesh((1, 2), ("data", "model"))
        tp = serve_paged(params, CFG, mesh=mesh)
        assert outputs(base) == outputs(tp), "MoE TP perturbed greedy output"
        print("MOE_TP_OK")
    """)
    assert "MOE_TP_OK" in out


def test_sharded_hybrid_ssm_parity():
    """Jamba-pattern hybrid (SSM + attention interleaved) on a (1, 2) mesh:
    the attention pool shards over kv_heads, the SSD state pool over heads,
    conv state stays replicated — plain paged decode parity holds with a
    forced preemption/resume."""
    out = _run_subprocess("""
        CFG = ModelConfig(name="hyb", vocab_size=128, d_model=64, n_layers=2,
                          n_heads=4, n_kv_heads=2, d_ff=128, ssm_state=16,
                          ssm_head_dim=32, ssm_chunk=32, attn_chunk=16,
                          layer_pattern=(LayerSpec("ssm", "dense"),
                                         LayerSpec("attn", "dense")))
        params = init_params(CFG, jax.random.PRNGKey(3))

        def serve(mesh):
            eng = PagedServeEngine(params, CFG, SCFG, mesh=mesh)
            for r in reqs(3, 8):
                eng.add_request(r)
            fired = False
            while eng.scheduler.has_work:
                eng.step()
                r0 = eng.scheduler.slots[0]
                if not fired and r0 is not None and r0.state == "decode" \\
                        and len(r0.req.generated) >= 2:
                    eng.scheduler._preempt(0)
                    fired = True
            assert fired, "preemption never fired"
            eng.scheduler.alloc.check()
            return eng

        base = serve(None)
        mesh = make_mesh((1, 2), ("data", "model"))
        tp = serve(mesh)
        assert outputs(base) == outputs(tp), "hybrid TP perturbed output"
        print("HYB_TP_OK")
    """)
    assert "HYB_TP_OK" in out


def test_sharded_gqa_pallas_shard_map_parity():
    """REPRO_FORCE_PALLAS=1 variant: the paged attention kernels run in
    interpret mode under the per-shard head-slice shard_map routing — the
    sharded kernel path must agree token-for-token with the unsharded kernel
    path (each shard computes exactly its aligned q/kv head block)."""
    out = _run_subprocess("""
        CFG = ModelConfig(name="t", vocab_size=128, d_model=64, n_layers=2,
                          n_heads=4, n_kv_heads=2, d_ff=128, attn_chunk=16)
        params = init_params(CFG, jax.random.PRNGKey(0))
        base = serve_paged(params, CFG, n=2, max_new=6)
        mesh = make_mesh((1, 2), ("data", "model"))
        tp = serve_paged(params, CFG, mesh=mesh, n=2, max_new=6)
        assert outputs(base) == outputs(tp), "pallas shard_map diverged"
        print("PALLAS_TP_OK")
    """, extra_env={"REPRO_FORCE_PALLAS": "1"})
    assert "PALLAS_TP_OK" in out


def test_gqa_pool_shards_whole_heads_only():
    """The GQA code leaves pack KH heads into their last dim (KH*D): they
    shard over `model` only where the kv heads divide, like the V scales'
    KH axis — never into part-heads, even when KH*D divides."""
    from jax.sharding import AbstractMesh, PartitionSpec as P
    from repro.distributed.sharding import tree_pool_shardings
    from repro.models import ModelConfig
    from repro.serving.paged_cache import PagedCacheConfig, init_paged_cache
    cfg = ModelConfig(name="t", vocab_size=64, d_model=64, n_layers=2,
                      n_heads=4, n_kv_heads=2, d_ff=128)
    pool = init_paged_cache(cfg, PagedCacheConfig(block_size=4, num_blocks=6,
                                                  max_batch=2))
    for model, split in ((2, "model"), (4, None)):
        mesh = AbstractMesh((1, model), ("data", "model"))
        specs = {k: v.spec for k, v in tree_pool_shardings(mesh, pool)["p0"].items()}
        assert specs["k_vals"] == specs["v_vals"] == P(None, None, None, split)
        assert specs["v_scale"] == specs["v_zero"] == P(None, split, None, None)
        assert specs["k_scale"] == P(None, None, split, None)
