"""The dropless expert layer with an expert share (DeepSeek-V3 routing):
the shares of a layer sum to the uncut layer, a skewed router drops no
token, and its counters equal a recount on the host — in float32 and
through the W8A8 grouped GEMM."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import QuantPolicy, quantize_tree
from repro.core.qtensor import QTensor
from repro.models.config import LayerSpec, ModelConfig
from repro.models.layers import swiglu_apply
from repro.models.moe import moe_dropless, moe_init, route

# 64 router outputs, top-6, 2 shared experts: the whole layer holds all 64
UNCUT = ModelConfig(name="moe", vocab_size=64, d_model=64, n_layers=2,
                    n_heads=4, d_ff=128, n_experts=64, n_experts_active=6,
                    moe_d_ff=32, n_shared_experts=2, moe_score="sigmoid",
                    moe_score_bias=True, moe_routed_scale=2.446,
                    capacity_factor=0.0,
                    layer_pattern=(LayerSpec("attn", "moe"),))
SHARES = 8


def _share(cfg, p, i):
    """Share i of 8: experts [8 i, 8 i + 8) of the 64-wide router."""
    e = cfg.n_experts // SHARES
    scfg = dataclasses.replace(cfg, n_experts=e, n_routed_experts=64,
                               expert_offset=e * i)
    sp = dict(p, experts=jax.tree_util.tree_map(
        lambda a: a[e * i:e * (i + 1)], p["experts"]))
    return scfg, sp


def _quant(p):
    return quantize_tree(p, QuantPolicy(min_size=0))


@pytest.fixture(scope="module")
def layer():
    p = moe_init(jax.random.PRNGKey(0), UNCUT)
    x = jax.random.normal(jax.random.PRNGKey(1), (40, 64), jnp.float32)
    return p, x


@pytest.mark.parametrize("quantized", [False, True])
def test_shares_sum_to_the_uncut_layer(layer, quantized):
    p, x = layer
    if quantized:
        p = _quant(p)
    with jax.default_matmul_precision("highest"):
        whole, _ = moe_dropless(p, x, UNCUT)
        outs, held = [], 0
        for i in range(SHARES):
            scfg, sp = _share(UNCUT, p, i)
            out, counts = moe_dropless(sp, x, scfg)
            outs.append(np.asarray(out, np.float64))
            held += int(counts[1])
        # every share computes the shared experts alike: counted once
        shared = np.asarray(swiglu_apply(p["shared"], x), np.float64)
    total = sum(o - shared for o in outs) + shared
    np.testing.assert_allclose(total, np.asarray(whole), atol=2e-5,
                               rtol=2e-5)
    assert held == x.shape[0] * UNCUT.n_experts_active


def _recount(p, x, cfg, valid):
    """Routing and counters recomputed in numpy."""
    logits = np.asarray(x, np.float64) @ np.asarray(p["gate_w"], np.float64)
    scores = 1.0 / (1.0 + np.exp(-logits))
    idx = np.argsort(-(scores + np.asarray(p["gate_bias"])), axis=-1,
                     kind="stable")[:, :cfg.n_experts_active]
    loc = idx - cfg.expert_offset
    held = (loc >= 0) & (loc < cfg.n_experts) & valid[:, None]
    rows = np.bincount(loc[held], minlength=cfg.n_experts)
    return [int(valid.sum()) * cfg.n_experts_active, int(held.sum()),
            int(rows.max()), 1, int((rows > 0).sum())]


@pytest.mark.parametrize("share", [0, 3, 7])
def test_counters_equal_a_host_recount(layer, share):
    p, x = layer
    scfg, sp = _share(UNCUT, _quant(p), share)
    valid = np.arange(x.shape[0]) < 33              # 7 pad rows
    with jax.default_matmul_precision("highest"):
        _, counts = moe_dropless(sp, x, scfg, valid=jnp.asarray(valid))
    assert np.asarray(counts).tolist() == _recount(p, x, scfg, valid)


@pytest.mark.parametrize("quantized", [False, True])
def test_skewed_router_drops_no_token(layer, quantized):
    """A bias that sends every token to one held expert: the expert gets
    all 40 rows (more than one row tile), and each token's output is the
    per-token sum of its experts."""
    p, x = layer
    cfg, sp = _share(UNCUT, p, 2)
    bias = np.zeros((64,), np.float32)
    bias[cfg.expert_offset + 5] = 100.0
    sp = dict(sp, gate_bias=jnp.asarray(bias))
    if quantized:
        sp = _quant(sp)
    with jax.default_matmul_precision("highest"):
        out, counts = moe_dropless(sp, x, cfg)
        w, idx = route(sp, x, cfg)
    assert int(counts[2]) == x.shape[0]             # busiest expert: all

    def mats(j):
        return {k: (v.dequantize() if isinstance(v, QTensor) else v)[j]
                for k, v in sp["experts"].items()}
    want = np.asarray(swiglu_apply(sp["shared"], x), np.float64)
    w, idx = np.asarray(w), np.asarray(idx)
    for t in range(x.shape[0]):
        for s in range(cfg.n_experts_active):
            j = idx[t, s] - cfg.expert_offset
            if 0 <= j < cfg.n_experts:
                with jax.default_matmul_precision("highest"):
                    y = swiglu_apply(mats(j), x[t:t + 1])[0]
                want[t] += w[t, s] * np.asarray(y, np.float64)
    # W8A8 rounds activations to int8 per row: 1% of the output's scale
    tol = 1e-2 if quantized else 1e-5
    scale = np.abs(want).max()
    assert np.abs(np.asarray(out) - want).max() < tol * scale


@pytest.mark.parametrize("change", [
    {"moe_score": "sigmoid"}, {"moe_score_bias": True},
    {"moe_routed_scale": 2.0}, {"n_routed_experts": 16},
    {"n_routed_experts": 16, "expert_offset": 8}])
def test_capacity_dispatch_rejects_deepseek_router(change):
    """The capacity dispatch routes a softmax over the held experts alone:
    a config asking for anything else must say so at construction, and
    the same config runs dropless."""
    base = dict(name="cap", vocab_size=64, d_model=64, n_layers=1,
                n_heads=4, d_ff=128, n_experts=8, n_experts_active=2,
                layer_pattern=(LayerSpec("attn", "moe"),))
    with pytest.raises(ValueError, match="capacity_factor=0"):
        ModelConfig(**base, **change)
    ModelConfig(**base, **change, capacity_factor=0.0)
