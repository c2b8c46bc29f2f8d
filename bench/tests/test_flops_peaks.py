"""FLOP and byte counts against hand counts for qwen2-0.5b, and the peak
table."""
import jax
import pytest

from bench.lib import flops, registry
from bench.lib.peaks import peak

D, H, KH, HD, F, V = 896, 14, 2, 64, 4864, 151936


def _one_layer():
    cfg = dict(registry.config("qwen2-0.5b")["model"], num_hidden_layers=1)
    ref = registry.reference("qwen_dense")
    return jax.eval_shape(lambda: ref.make_weights(cfg, 0))


def test_matmul_weights_one_qwen2_layer():
    hand = D * H * HD + 2 * D * KH * HD + H * HD * D + 3 * D * F
    assert hand == 14_909_440
    assert flops.matmul_weights(_one_layer()) == hand


def test_model_flops_hand_count():
    w = 14_909_440
    # one prefill chunk of 64 tokens after 128 of context, one logits row
    ctx = flops.chunk_context(128, 64)
    assert ctx == sum(128 + j + 1 for j in range(64))
    got = flops.model_flops(w, 1, H, HD, D, V, 64, ctx, 1)
    assert got == 2 * w * 64 + 4 * H * HD * ctx + 2 * D * V


def test_w8a8_call_counts():
    ops, nbytes = flops.w8a8_call(128, 1024, 4864)
    assert ops == 2 * 128 * 1024 * 4864
    assert nbytes == (128 * 1024 + 1024 * 4864 + 4 * 128 + 4 * 4864
                      + 4 * 128 * 4864)


def test_peak_table():
    p = peak("TPU v5 lite")
    assert p["int8_ops"] == 393e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peak("TPU v9 imaginary")
