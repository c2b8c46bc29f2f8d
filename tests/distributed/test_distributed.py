"""Distributed layer: scale sync (Thm 4), compression, elastic, watchdog.

Multi-device cases run in a subprocess with XLA_FLAGS=8 host devices so the
main test process keeps the default single-device view (assignment note).
"""
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed import plan_remesh, Watchdog
from repro.distributed.compression import compress_decompress, init_error_state


def _run_subprocess(code: str):
    r = subprocess.run(
        [sys.executable, "-c",
         "from repro.launch.mesh import make_mesh\n" + textwrap.dedent(code)],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
             "JAX_PLATFORMS": "cpu", "HOME": "/root"})
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


def test_scale_sync_consistency_8dev():
    """Thm 4: all shards end with identical (delta, z); pmax == allgather-max."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.distributed.scale_sync import (global_absmax,
                                                  sync_scale_allgather,
                                                  make_synced_quant_step)
        from repro.core.online import EmaScaleState
        mesh = make_mesh((8,), ("data",))
        x = jax.random.normal(jax.random.PRNGKey(0), (64, 32)) * \\
            jnp.arange(1, 65)[:, None]          # shard-dependent ranges

        @partial(shard_map, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                 check_vma=False)
        def both(xs):
            local = jnp.max(jnp.abs(xs))
            via_pmax = global_absmax(xs, ("data",))
            via_ag = sync_scale_allgather(local, "data")
            return jnp.stack([via_pmax, via_ag])[None].repeat(xs.shape[0], 0)

        res = np.asarray(both(x))
        true = float(jnp.max(jnp.abs(x)))
        assert np.allclose(res[:, 0], true), (res[:, 0], true)
        assert np.allclose(res[:, 0], res[:, 1])       # Eq.7 == pmax path

        step = make_synced_quant_step(mesh)
        q, state = step(x, EmaScaleState.init())
        assert q.shape == x.shape and q.dtype == jnp.int8
        assert int(state.step) == 1
        print("SCALE_SYNC_OK", float(state.delta))
    """)
    assert "SCALE_SYNC_OK" in out


def test_reduce_ema_states_mesh_matches_host_8dev():
    """The replica controller's EMA-state reduce: the shard_map pmax/pmean
    fast path and the numpy host fallback agree bit-for-bit (Thm 4: the
    shared (delta, z) is identical no matter where the reduce runs)."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.online import EmaScaleState
        from repro.distributed.scale_sync import reduce_ema_states
        mesh = make_mesh((8,), ("data",))
        states = [EmaScaleState(delta=jnp.asarray(1.0 + i),
                                mu=jnp.asarray(float(i)),
                                step=jnp.asarray(i + 1, jnp.int32))
                  for i in range(8)]
        a = reduce_ema_states(states, mesh=mesh)      # collective fast path
        b = reduce_ema_states(states)                 # numpy fallback
        assert float(a.delta) == float(b.delta) == 8.0   # max-reduce
        assert float(a.mu) == float(b.mu) == 3.5          # mean
        assert int(a.step) == int(b.step) == 8
        print("REDUCE_EMA_OK")
    """)
    assert "REDUCE_EMA_OK" in out


def test_int8_allreduce_8dev():
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.compression import make_int8_allreduce
        mesh = make_mesh((8,), ("data",))
        g = jax.random.normal(jax.random.PRNGKey(0), (64, 128))
        ar = make_int8_allreduce(mesh)
        out = np.asarray(ar(g))
        # every shard row must equal the global mean of shard-means
        per_shard = np.asarray(g).reshape(8, 8, 128).mean(axis=0)
        got = out.reshape(8, 8, 128)
        for i in range(8):
            rel = np.abs(got[i] - per_shard).max() / (np.abs(per_shard).max() + 1e-9)
            assert rel < 0.02, rel
        print("INT8_AR_OK")
    """)
    assert "INT8_AR_OK" in out


def test_error_feedback_convergence():
    """Quantized-gradient SGD with error feedback reaches the fp optimum."""
    target = jax.random.normal(jax.random.PRNGKey(0), (128,))

    def run(compressed: bool):
        w = jnp.zeros((128,))
        err = init_error_state({"w": w})
        for _ in range(200):
            g = 2 * (w - target)
            if compressed:
                out, err = compress_decompress({"w": g}, err)
                g = out["w"]
            w = w - 0.05 * g
        return float(jnp.mean((w - target) ** 2))

    assert run(True) < 1e-3
    assert run(True) < 10 * run(False) + 1e-6


def test_plan_remesh_after_failures():
    plan = plan_remesh(224, old_data=16, old_model=16, global_batch=256)
    assert plan.shape[0] * plan.shape[1] <= 224
    assert plan.shape[1] in (16, 8, 4, 2, 1)       # acceptable TP degrees
    assert plan.dropped_chips < 32
    # degenerate: lost almost everything
    plan2 = plan_remesh(3, old_data=16, old_model=16, global_batch=256)
    assert plan2.shape[0] * plan2.shape[1] <= 3


def test_watchdog_straggler_detection():
    wd = Watchdog(window=16, threshold=2.0, patience=2)
    for i in range(10):
        wd.step_begin()
        time.sleep(0.005)
        wd.step_end(i)
    assert not wd.should_restart
    # inject two slow steps
    for i in (10, 11):
        wd.step_begin()
        time.sleep(0.05)
        rec = wd.step_end(i)
        assert rec.straggler
    assert wd.should_restart
    s = wd.summary()
    assert s["stragglers"] >= 2 and s["steps"] == 12


def test_watchdog_hang_timer():
    fired = []
    wd = Watchdog(hang_timeout=0.05, on_hang=lambda: fired.append(1))
    wd.step_begin()
    time.sleep(0.15)
    wd.step_end(0)
    assert fired
