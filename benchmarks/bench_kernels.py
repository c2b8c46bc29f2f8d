"""Kernel microbench: oracle-path timings + structural kernel facts.

Pallas interpret mode is a correctness tool, not a perf tool, on CPU — so
wall times here are the jnp oracle paths (what the CPU actually runs), and
for each Pallas kernel we additionally report its STRUCTURAL numbers:
VMEM working set per grid step and bytes touched, which are the quantities
the TPU roofline cares about (DESIGN.md §2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.methods.simquant import quantize_kv
from repro.core.qtensor import quantize_symmetric
from repro.kernels import ref
from repro.models.attention import flash_attention

from .common import emit, timeit


def _paged_pool(b, kh, d, n, t, seed=0):
    """Pool leaves in the stored layout: code rows (N, T, KH*D), V affine
    (KH, T, N) with blocks as lanes."""
    rs = np.random.RandomState(seed)
    k_vals = jnp.asarray(rs.randint(-128, 128, (n, t, kh * d)), jnp.int8)
    v_vals = jnp.asarray(rs.randint(-128, 128, (n, t, kh * d)), jnp.int8)
    k_scale = jnp.asarray(rs.uniform(0.01, 0.05, (b, kh, d)), jnp.float32)
    k_zero = jnp.asarray(rs.uniform(-2, 2, (b, kh, d)), jnp.float32)
    v_scale = jnp.asarray(rs.uniform(0.01, 0.05, (kh, t, n)), jnp.float32)
    v_zero = jnp.asarray(rs.uniform(-2, 2, (kh, t, n)), jnp.float32)
    return k_vals, k_scale, k_zero, v_vals, v_scale, v_zero


def paged_suite_rows(smoke: bool = False):
    """Paged kernel suite: single-launch verify vs gamma+1 per-position
    decode launches, and the block-table chunk-prefill read vs the XLA
    dense prefix gather it replaced — oracle-path wall times on CPU (the
    float path the Pallas kernels reproduce bitwise), ctx in {256, 1024}."""
    rows = []
    b, h, kh, d, t, gamma, c = 4, 8, 4, 64, 16, 4, 64
    iters = 2 if smoke else 5
    key = jax.random.PRNGKey(0)
    for ctx in (256, 1024):
        m = ctx // t
        n = b * m + 1
        pool = _paged_pool(b, kh, d, n, t)
        tables = jnp.asarray(
            np.random.RandomState(1).permutation(n - 1)[:b * m].reshape(b, m),
            jnp.int32)
        lengths = jnp.full((b,), ctx - gamma - 1, jnp.int32)

        # -- spec-decode verify: one launch vs gamma+1 decode launches ------
        q = jax.random.normal(key, (b, gamma + 1, h, d))
        t_one = timeit(jax.jit(ref.paged_kv_verify_attention_ref),
                       q, *pool, tables, lengths, iters=iters)

        def per_position(q, k_vals, k_scale, k_zero, v_vals, v_scale,
                         v_zero, tables, lengths):
            outs = [ref.paged_kv_decode_attention_ref(
                        q[:, j], k_vals, k_scale, k_zero, v_vals, v_scale,
                        v_zero, tables, lengths + j + 1)
                    for j in range(gamma + 1)]
            return jnp.stack(outs, axis=1)

        t_per = timeit(jax.jit(per_position), q, *pool, tables, lengths,
                       iters=iters)
        rows.append(dict(kernel="verify_single_launch", ctx=ctx,
                         us_per_call=round(t_one * 1e6, 1),
                         us_baseline=round(t_per * 1e6, 1),
                         baseline="gamma+1_decode_launches",
                         speedup=round(t_per / max(t_one, 1e-12), 2)))

        # -- chunk prefill: pool read by block table vs XLA dense gather ----
        k_vals, k_scale, k_zero, v_vals, v_scale, v_zero = pool
        block_row = tables[0]
        qc = jax.random.normal(key, (1, c, h, d))
        k_chunk = jax.random.normal(jax.random.PRNGKey(1), (1, c, kh, d))
        v_chunk = jax.random.normal(jax.random.PRNGKey(2), (1, c, kh, d))
        ctx_arr = jnp.asarray(ctx, jnp.int32)
        args = (qc, k_vals, k_scale[0], k_zero[0], v_vals, v_scale, v_zero,
                k_chunk, v_chunk, block_row, ctx_arr)
        t_new = timeit(jax.jit(ref.paged_prefix_chunk_attention_ref), *args,
                       iters=iters)

        def gather_chunk(q, k_vals, k_scale, k_zero, v_vals, v_scale,
                         v_zero, k_chunk, v_chunk, block_row, ctx):
            # the replaced path: dense-gather + dequantize the whole prefix,
            # concatenate the chunk, run masked flash attention over it
            f32 = jnp.float32
            rows = lambda x: x[block_row].reshape(m, t, kh, d).astype(f32)
            lanes = lambda x: x[..., block_row].transpose(2, 1, 0)[..., None]
            k_pre = ((rows(k_vals) - k_zero.astype(f32))
                     * k_scale.astype(f32)).reshape(m * t, kh, d)
            v_pre = ((rows(v_vals) - lanes(v_zero))
                     * lanes(v_scale)).reshape(m * t, kh, d)
            k_cat = jnp.concatenate([k_pre[None], k_chunk.astype(f32)], axis=1)
            v_cat = jnp.concatenate([v_pre[None], v_chunk.astype(f32)], axis=1)
            pre_pos = jnp.arange(m * t)
            pre_pos = jnp.where(pre_pos < ctx, pre_pos, 2 ** 30)
            pos = ctx + jnp.arange(c)
            return flash_attention(q, k_cat, v_cat, q_positions=pos,
                                   kv_positions=jnp.concatenate([pre_pos, pos]),
                                   chunk=c)

        t_old = timeit(jax.jit(gather_chunk), *args, iters=iters)
        rows.append(dict(kernel="chunk_prefill_pool_read", ctx=ctx,
                         us_per_call=round(t_new * 1e6, 1),
                         us_baseline=round(t_old * 1e6, 1),
                         baseline="xla_dense_gather",
                         speedup=round(t_old / max(t_new, 1e-12), 2)))
    emit(rows, "experiments/bench/kernels_paged.csv")
    return rows


def run(smoke: bool = False):
    rows = []
    key = jax.random.PRNGKey(0)

    # fused dynamic quantization at serving shapes
    for m, k in ((256, 1024), (1024, 4096)):
        x = jax.random.normal(key, (m, k))
        t = timeit(jax.jit(ref.fused_quant_ref), x)
        rows.append(dict(kernel="fused_quant", shape=f"{m}x{k}",
                         us_per_call=round(t * 1e6, 1),
                         vmem_block_kb=round((256 * k * 4) / 1024, 1),
                         bytes_touched=m * k * 5))        # read f32? no: bf16+int8+scale

    # W8A8 GEMM vs fp32 GEMM
    for m, k, n in ((256, 1024, 1024), (512, 2048, 2048)):
        x = jax.random.normal(key, (m, k))
        w = jax.random.normal(jax.random.PRNGKey(1), (k, n))
        qw = quantize_symmetric(w, 8, axis=(0,))
        q_x, s_x = ref.fused_quant_ref(x)
        t_q = timeit(jax.jit(ref.w8a8_matmul_ref), q_x, s_x, qw.values,
                     qw.scale.reshape(1, -1))
        t_f = timeit(jax.jit(lambda a, b: a @ b), x, w)
        rows.append(dict(kernel="w8a8_matmul", shape=f"{m}x{k}x{n}",
                         us_per_call=round(t_q * 1e6, 1),
                         vmem_block_kb=round((256 * 256 * (1 + 1 + 4)) / 1024, 1),
                         bytes_touched=int(m * k + k * n + m * n * 4)))
        rows.append(dict(kernel="fp32_matmul(ref)", shape=f"{m}x{k}x{n}",
                         us_per_call=round(t_f * 1e6, 1),
                         vmem_block_kb="-",
                         bytes_touched=int(4 * (m * k + k * n + m * n))))

    # quantized-cache decode attention (the SimQuant hot path)
    for b, s, h, kh, d in ((8, 2048, 8, 8, 64), (4, 8192, 8, 2, 64)):
        q = jax.random.normal(key, (b, h, d))
        kk = jax.random.normal(jax.random.PRNGKey(1), (b, s, kh, d))
        vv = jax.random.normal(jax.random.PRNGKey(2), (b, s, kh, d))
        qk, qv = quantize_kv(kk, vv)
        length = jnp.full((b,), s, jnp.int32)
        t = timeit(jax.jit(ref.kv_decode_attention_ref), q, qk.values, qk.scale,
                   qk.zero, qv.values, qv.scale, qv.zero, length, iters=3)
        rows.append(dict(kernel="kv_decode_attention", shape=f"b{b}s{s}h{h}kh{kh}",
                         us_per_call=round(t * 1e6, 1),
                         vmem_block_kb=round((512 * d * 2 + h // kh * d * 4) / 1024, 1),
                         bytes_touched=int(2 * b * s * kh * d)))
    emit(rows, "experiments/bench/kernels.csv")
    rows.extend(paged_suite_rows(smoke))
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
