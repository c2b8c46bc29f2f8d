"""Tiny-mesh dry-run smoke: the full 512-device sweep is a benchmark-scale
run; here we prove the machinery (specs -> lower -> compile -> analysis) on a
(2,2)/(2,2,2) mesh inside a subprocess with 8 host devices."""
import subprocess
import sys
import textwrap


def _run(code: str) -> str:
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
             "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


def test_tiny_mesh_train_and_decode():
    out = _run("""
        import dataclasses, jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.distributed.sharding import axis_rules
        from repro.launch.steps import (input_specs, make_train_step,
                                        make_serve_step, SHAPES)
        from repro.launch import hlo_analysis as ha
        from repro.launch.mesh import make_mesh
        from repro.optim import AdamWConfig

        # shrink the shape table for the tiny run
        import repro.launch.steps as steps
        steps.SHAPES = {
            "train_4k": dict(seq=64, batch=8, kind="train"),
            "decode_32k": dict(seq=64, batch=8, kind="decode"),
        }

        for multi in (False, True):
            mesh = (make_mesh((2, 2, 2), ("pod", "data", "model"))
                    if multi else make_mesh((2, 2), ("data", "model")))
            for arch in ("qwen3-1.7b", "phi3.5-moe-42b-a6.6b"):
                cfg = get_smoke_config(arch)
                with axis_rules(mesh):
                    ocfg = AdamWConfig()
                    specs = input_specs(cfg, "train_4k", mesh, ocfg)
                    step = make_train_step(cfg, ocfg)
                    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
                        specs["params"], specs["opt_state"], specs["batch"]
                    ).compile()
                    mem = compiled.memory_analysis()
                    assert mem.temp_size_in_bytes > 0
                    terms, coll = ha.roofline_from_compiled(
                        compiled, 8 if multi else 4)
                    assert terms.flops_per_device > 0
                    assert terms.bytes_per_device > 0

                    sspecs = input_specs(cfg, "decode_32k", mesh)
                    serve = make_serve_step(cfg)
                    c2 = jax.jit(serve, donate_argnums=(2,)).lower(
                        sspecs["params"], sspecs["tokens"], sspecs["cache"]
                    ).compile()
                    assert c2.memory_analysis().temp_size_in_bytes >= 0
                print("OK", arch, "multi" if multi else "single")
        print("TINY_DRYRUN_PASS")
    """)
    assert "TINY_DRYRUN_PASS" in out
