"""The quantization recipe names the MLA and MoE leaves the model makes:
after ``quantize_tree`` every MLA projection and every expert and shared
expert matrix is a QTensor, the router and its bias are not, and each
MLA / MoE include pattern matches some leaf."""
import fnmatch

import jax
import pytest

from repro.core import QuantPolicy, quantize_tree
from repro.core.apply import DEFAULT_INCLUDE, _path_str
from repro.core.qtensor import QTensor
from repro.models import init_params
from repro.models.config import LayerSpec, ModelConfig

BASE = dict(vocab_size=128, d_model=64, n_layers=3, n_heads=4, d_ff=128,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16,
            v_head_dim=16, n_experts=8, n_routed_experts=16,
            n_experts_active=6, moe_d_ff=32, n_shared_experts=2,
            moe_score="sigmoid", moe_score_bias=True, capacity_factor=0.0,
            first_k_dense_replace=1,
            layer_pattern=(LayerSpec("mla", "moe"),))
MLA = ("q_proj", "q_a", "q_b", "kv_a", "kv_b", "wo")
MLA_MOE_PATTERNS = [p for p in DEFAULT_INCLUDE
                    if any(k in p for k in ("attn/", "experts", "shared"))]


@pytest.mark.parametrize("q_lora_rank", [0, 16])
def test_mla_and_expert_projections_are_quantized(q_lora_rank):
    cfg = ModelConfig(name="t", q_lora_rank=q_lora_rank, **BASE)
    params = init_params(cfg, jax.random.PRNGKey(0))
    q = quantize_tree(params, QuantPolicy(min_size=0))
    leaves = jax.tree_util.tree_flatten_with_path(
        q, is_leaf=lambda l: isinstance(l, QTensor))[0]
    seen = set()
    for path, leaf in leaves:
        ps = _path_str(path)
        name = ps.rsplit("/", 1)[-1]
        if "/attn/" in ps and name in MLA:
            assert isinstance(leaf, QTensor), ps
            seen.add(name)
        elif "/experts/" in ps or "/shared/" in ps:
            assert isinstance(leaf, QTensor), ps
            seen.add(ps.split("/")[-2])
        elif name in ("gate_w", "gate_bias"):
            assert not isinstance(leaf, QTensor), ps
            seen.add(name)
    lora = {"q_a", "q_b"} if q_lora_rank else {"q_proj"}
    assert seen == {"kv_a", "kv_b", "wo", "experts", "shared", "gate_w",
                    "gate_bias"} | lora


def test_every_mla_and_moe_pattern_names_a_leaf():
    paths = []
    for rank in (0, 16):
        cfg = ModelConfig(name="t", q_lora_rank=rank, **BASE)
        params = init_params(cfg, jax.random.PRNGKey(0))
        paths += [_path_str(p).lower() for p, _ in
                  jax.tree_util.tree_flatten_with_path(params)[0]]
    assert MLA_MOE_PATTERNS
    for pat in MLA_MOE_PATTERNS:
        assert any(fnmatch.fnmatch(p, pat) for p in paths), pat
