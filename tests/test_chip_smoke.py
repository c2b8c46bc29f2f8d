"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
phases run end to end at toy widths on the CPU — Pallas kernels in
interpret mode, the sharded path on 4 host devices.  Each case runs in a
child process, so this process keeps its single-device view and caches."""
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

# qwen2-0.5b's head layout (14 heads over 2 KV heads) at toy width
TOY = """
import dataclasses
import chip_smoke
from repro.configs.qwen2_0_5b import SMOKE
TOY = dataclasses.replace(SMOKE, n_heads=14, n_kv_heads=2, head_dim=8)
"""


def _python(args, **env):
    r = subprocess.run(
        [sys.executable, *args], cwd=ROOT, capture_output=True, text=True,
        timeout=900, env=dict(os.environ, JAX_PLATFORMS="cpu",
                              PYTHONPATH=str(ROOT / "src"), **env))
    return r


def test_refuses_without_tpu():
    r = _python(["chip_smoke.py"])
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_phases_at_toy_width_in_interpret_mode():
    r = _python(["-c", TOY + "chip_smoke.run(TOY, on_chip=False)"],
                REPRO_FORCE_PALLAS="1")
    assert r.returncode == 0, r.stdout + r.stderr
    out = r.stdout
    assert "[4] served 8/8 requests" in out
    assert "[6] spec decode" in out and "[7] scoring" in out


def test_sharded_path_on_four_host_devices():
    r = _python(["-c", TOY + "chip_smoke.run_sharded(TOY)"],
                REPRO_FORCE_PALLAS="1",
                XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "pool shards on devices [0, 1, 2, 3]" in r.stdout
    assert "tokens identical to one device" in r.stdout


def test_compile_cache_dir():
    """The entry points' cache: JAX_COMPILATION_CACHE_DIR when it is set,
    else a fixed .jax_cache/ at the repository root."""
    code = ("import jax\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache(), jax.config.jax_compilation_cache_dir)")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, timeout=300,
                       capture_output=True, text=True,
                       env=dict(env, JAX_PLATFORMS="cpu",
                                PYTHONPATH=str(ROOT / "src")))
    assert r.stdout.split() == [str(ROOT / ".jax_cache")] * 2, r.stderr
    r = _python(["-c", code], JAX_COMPILATION_CACHE_DIR=str(ROOT / "elsewhere"))
    assert r.stdout.split() == [str(ROOT / "elsewhere")] * 2, r.stderr
