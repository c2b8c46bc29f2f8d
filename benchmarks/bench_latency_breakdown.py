"""Paper Table 5 / Eq. 12: per-layer decode latency decomposition.

    T_total = T_load + T_quant + T_gemm + T_comm + T_sync

TPU adaptation of the instrumentation (DESIGN.md §2): on the CPU host we
measure the analogous component kernels at one layer's decode shapes —
  T_load  ~ streaming the (quantized vs fp) KV cache + weights (memcpy-bound)
  T_quant ~ the fused dynamic-quantization kernel (Alg. 1)
  T_gemm  ~ INT8 vs FP32 GEMM at the layer's projection shapes
  T_comm  ~ scale/activation exchange (loopback: measured as the EMA-state
            update + scale broadcast machinery; 0 collectives on 1 device)
  T_sync  ~ device synchronization (block_until_ready on a trivial op)
The reproduction target is the paper's structural claims: quantization
shifts time from Load+GEMM into a small Quant term (Table 5's 24.1->10.8 ms
Load and 38.4->19.5 ms GEMM at <5 ms Quant).

All component timings flow through the obs tracer's span machinery
(``common.timeit``).  The serving-side split of a step into the scheduler's
phases is measured on the chip, from the engine's own spans
(``bench/phases.py`` and the benchmark's per-layer metrics).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.online import EmaScaleState, async_quant_update
from repro.core.qtensor import quantize_symmetric
from repro.kernels import ref

from .common import emit, timeit

# one-layer decode workload (batch of 64 decode tokens, GPT-2-medium-ish layer)
B, D, F, S, KH, HD = 64, 1024, 4096, 2048, 8, 128


def run():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (B, D), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (D, F), jnp.float32)
    qw = quantize_symmetric(w, 8, axis=(0,))
    kcache_fp = jax.random.normal(jax.random.PRNGKey(2), (B, S, KH, HD), jnp.bfloat16)
    kcache_q = jnp.asarray(np.random.randint(-128, 127, (B, S, KH, HD)), jnp.int8)

    # T_load: one pass over cache + weights (sum forces the read)
    t_load_fp = timeit(jax.jit(lambda c, ww: (c.astype(jnp.float32).sum(),
                                              ww.sum())), kcache_fp, w)
    t_load_q = timeit(jax.jit(lambda c, ww: (c.astype(jnp.float32).sum(),
                                             ww.sum())), kcache_q, qw.values)

    # T_quant: fused dynamic activation quantization
    t_quant = timeit(jax.jit(ref.fused_quant_ref), x)

    # T_gemm: fp32 vs int8 GEMM at (B, D) x (D, F)
    t_gemm_fp = timeit(jax.jit(lambda a, b: a @ b), x, w)
    q_x, s_x = ref.fused_quant_ref(x)
    t_gemm_q = timeit(jax.jit(ref.w8a8_matmul_ref), q_x, s_x, qw.values,
                      qw.scale.reshape(1, -1))

    # T_comm: scale-metadata maintenance (Alg. 1 EMA update; single device)
    state = EmaScaleState.init()
    t_comm = timeit(jax.jit(lambda xx, st: async_quant_update(xx, st)[1].delta),
                    x, state)

    # T_sync: barrier latency
    one = jnp.ones(())
    t_sync = timeit(jax.jit(lambda a: a + 1), one)

    ms = lambda t: round(t * 1e3, 3)
    rows = [
        dict(method="fp32", load_ms=ms(t_load_fp), quant_ms=0.0,
             gemm_ms=ms(t_gemm_fp), comm_ms=0.0, sync_ms=ms(t_sync),
             total_ms=ms(t_load_fp + t_gemm_fp + t_sync)),
        dict(method="int8_sym(W8A8)", load_ms=ms(t_load_q), quant_ms=ms(t_quant),
             gemm_ms=ms(t_gemm_q), comm_ms=ms(t_comm), sync_ms=ms(t_sync),
             total_ms=ms(t_load_q + t_quant + t_gemm_q + t_comm + t_sync)),
    ]
    # derived structural checks (paper: load and gemm shrink, quant is small)
    rows.append(dict(method="ratio_q_over_fp",
                     load_ms=round(t_load_q / t_load_fp, 3),
                     quant_ms="-",
                     gemm_ms=round(t_gemm_q / t_gemm_fp, 3),
                     comm_ms="-", sync_ms="-",
                     total_ms=round(rows[1]["total_ms"] / rows[0]["total_ms"], 3)))
    emit(rows, "experiments/bench/latency_breakdown.csv")
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
