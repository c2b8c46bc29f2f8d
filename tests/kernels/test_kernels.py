"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(assignment requirement: per-kernel allclose against ref.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.methods.simquant import quantize_kv
from repro.core.qtensor import quantize_symmetric
from repro.kernels import ref
from repro.kernels.fused_quant import fused_quant
from repro.kernels.kv_decode_attention import (kv_decode_attention,
                                               paged_kv_decode_attention)
from repro.kernels.w8a8_matmul import w8a8_matmul

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("m,k", [(64, 128), (192, 320), (130, 96), (8, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_quant_matches_ref(m, k, dtype):
    x = (jax.random.normal(KEY, (m, k)) * 3).astype(dtype)
    q, s = fused_quant(x, block_m=64, interpret=True)
    qr, sr = ref.fused_quant_ref(x)
    # bf16 inputs: the f32 scale can differ in the last ulp between kernel
    # and oracle, flipping codes sitting exactly on a rounding boundary
    max_code_diff = int(jnp.max(jnp.abs(q.astype(jnp.int32) - qr.astype(jnp.int32))))
    assert max_code_diff <= (1 if dtype == jnp.bfloat16 else 0)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)


@pytest.mark.parametrize("m,k,n", [(128, 256, 128), (64, 192, 96), (100, 130, 70)])
@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
def test_w8a8_matches_ref(m, k, n, out_dtype):
    x = jax.random.normal(KEY, (m, k)) * 2
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n))
    q_x, s_x = ref.fused_quant_ref(x)
    qw = quantize_symmetric(w, 8, axis=(0,))
    out = w8a8_matmul(q_x, s_x, qw.values, qw.scale, out_dtype=out_dtype,
                      block_m=64, block_n=64, block_k=64, interpret=True)
    outr = ref.w8a8_matmul_ref(q_x, s_x, qw.values, qw.scale, out_dtype)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(outr, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_w8a8_accuracy_vs_fp32():
    """End-to-end fused path ~1% relative error vs fp32 GEMM (paper W8A8)."""
    x = jax.random.normal(KEY, (256, 512))
    w = jax.random.normal(jax.random.PRNGKey(1), (512, 256))
    qw = quantize_symmetric(w, 8, axis=(0,))
    out = ref.quant_gemm_fused_ref(x, qw.values, qw.scale.reshape(1, -1))
    rel = float(jnp.linalg.norm(out - x @ w) / jnp.linalg.norm(x @ w))
    assert rel < 0.02, rel


@pytest.mark.parametrize("b,s,h,kh,d", [(2, 96, 8, 4, 32), (1, 64, 4, 1, 64),
                                        (3, 128, 6, 2, 16)])
@pytest.mark.parametrize("chunk", [32, 48])
def test_kv_decode_attention_sweep(b, s, h, kh, d, chunk):
    q = jax.random.normal(KEY, (b, h, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, kh, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kh, d))
    qk, qv = quantize_kv(k, v)
    length = jnp.asarray(np.random.RandomState(0).randint(1, s + 1, size=b),
                         jnp.int32)
    out = kv_decode_attention(q, qk.values, qk.scale, qk.zero,
                              qv.values, qv.scale, qv.zero, length,
                              chunk=chunk, interpret=True)
    outr = ref.kv_decode_attention_ref(q, qk.values, qk.scale, qk.zero,
                                       qv.values, qv.scale, qv.zero, length)
    np.testing.assert_allclose(np.asarray(out), np.asarray(outr),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("b,h,kh,d,n,t,m", [(2, 8, 4, 32, 10, 16, 4),
                                            (3, 4, 1, 64, 6, 8, 3),
                                            (1, 6, 2, 16, 5, 4, 5),
                                            (3, 4, 2, 16, 300, 4, 6)])
def test_paged_kv_decode_attention_sweep(b, h, kh, d, n, t, m):
    """Gather-by-block-table Pallas kernel vs the dense-gather oracle, on the
    stored pool layout: code rows (N, T, KH*D), V affine (KH, T, N) whose
    lanes are the blocks (n = 300 spans three 128-lane tiles, the last one
    partial)."""
    q = jax.random.normal(KEY, (b, h, d))
    k_pool = jax.random.normal(jax.random.PRNGKey(1), (1, n * t, kh, d))
    v_pool = jax.random.normal(jax.random.PRNGKey(2), (1, n * t, kh, d))
    qk, qv = quantize_kv(k_pool, v_pool)
    k_vals = qk.values.reshape(n, t, kh * d)
    v_vals = qv.values.reshape(n, t, kh * d)
    v_scale = qv.scale.reshape(n, t, kh).T
    v_zero = qv.zero.reshape(n, t, kh).T
    # per-slot frozen K affine (slightly different per batch row)
    k_scale = (jnp.broadcast_to(qk.scale[0], (b, kh, d))
               * jnp.linspace(0.9, 1.1, b)[:, None, None])
    k_zero = jnp.broadcast_to(qk.zero[0], (b, kh, d))
    rs = np.random.RandomState(0)
    tables = jnp.asarray(rs.randint(0, n, size=(b, m)), jnp.int32)
    lengths = jnp.asarray(rs.randint(1, m * t + 1, size=(b,)), jnp.int32)
    out = paged_kv_decode_attention(q, k_vals, k_scale, k_zero,
                                    v_vals, v_scale, v_zero,
                                    tables, lengths, interpret=True)
    outr = ref.paged_kv_decode_attention_ref(q, k_vals, k_scale, k_zero,
                                             v_vals, v_scale, v_zero,
                                             tables, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(outr),
                               rtol=3e-5, atol=3e-5)


def test_kv_decode_quantization_fidelity():
    """INT8-cache attention close to the fp attention (the SimQuant claim)."""
    b, s, h, kh, d = 2, 128, 8, 4, 64
    q = jax.random.normal(KEY, (b, h, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, kh, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kh, d))
    qk, qv = quantize_kv(k, v)
    length = jnp.full((b,), s, jnp.int32)
    out_q = ref.kv_decode_attention_ref(q, qk.values, qk.scale, qk.zero,
                                        qv.values, qv.scale, qv.zero, length)
    # fp oracle via the same math with identity quantization
    ones = jnp.ones_like(qk.scale)
    zeros = jnp.zeros_like(qk.zero)
    out_fp = ref.kv_decode_attention_ref(
        q, k.transpose(0, 1, 2, 3), ones, zeros,
        v, jnp.ones_like(qv.scale), jnp.zeros_like(qv.zero), length)
    rel = float(jnp.linalg.norm(out_q - out_fp) / jnp.linalg.norm(out_fp))
    assert rel < 0.03, rel


def test_qdot_dispatch_paths():
    """ops.qdot: fp, W8A8, grouped, weight-only int4 all agree with fp ref."""
    from repro.core import QuantPolicy, quantize_tree
    from repro.kernels.ops import qdot
    x = jax.random.normal(KEY, (32, 128), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (128, 64))
    ref_out = x @ w
    for method, tol in [("symmetric", 0.05), ("zeroquant", 0.05),
                        ("gptq", 0.25), ("awq", 0.25)]:
        qt = quantize_tree({"wq": w}, QuantPolicy(method=method, min_size=16))
        out = qdot(x, qt["wq"], out_dtype=jnp.float32)
        rel = float(jnp.linalg.norm(out - ref_out) / jnp.linalg.norm(ref_out))
        assert rel < tol, (method, rel)
