"""A tiny Moonlight (DeepSeek-V3 block) cell for CPU tests, in the
registry ``tiny.make`` writes under a temporary directory: MLA without
q-LoRA, one leading dense layer, then MoE layers holding 8 experts (ids
4-11) of a 16-wide router, top-6 and 2 shared experts.

The program's widest gap reads 0.21-1.26 over nine seeds and the int4
control's 1.55-3.11 (int4 weights alone 0.85-2.13; CPU), so ``LIMIT`` 1.4
sits between them.  The program's gap is wide at these widths because a
token's expert choice flips on rounding where its 6th and 7th scores lie
close (16 router outputs, a 64-wide residual), and a flip swaps a sixth
of the routed sum; the unquantized program with its int8 latent pool
already reads 0.18-0.51 in float32 and 0.09-1.07 in bfloat16 (CPU, three
seeds).  The first prefill chunk holds a whole prompt of the tiny mixes
(8-60 tokens): the latent's per-channel affine is frozen on it, and the
16 tokens of a smaller first chunk bound later tokens' latents so poorly
at this width that clipping alone reads 2.8 (float32 weights)."""
import json
import shutil

from bench.lib import registry
from bench.tests import tiny

MODEL = {"hidden_size": 64, "num_attention_heads": 4,
         "q_lora_rank": None, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 16,
         "v_head_dim": 16, "intermediate_size": 128,
         "moe_intermediate_size": 32, "n_routed_experts": 8,
         "router_experts": 16, "first_held_expert": 4,
         "num_experts_per_tok": 6, "n_shared_experts": 2,
         "routed_scaling_factor": 2.446, "scoring_func": "sigmoid",
         "topk_method": "noaux_tc", "norm_topk_prob": True,
         "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
         "first_k_dense_replace": 1, "num_hidden_layers": 4,
         "vocab_size": 512, "rope_theta": 50000, "rms_norm_eps": 1e-05,
         "tie_word_embeddings": False, "hidden_act": "silu"}
CONFIG = {"reference": "deepseek_moe", "reduced": ["n_routed_experts"],
          "model": MODEL,
          "quant": {"method": "symmetric", "min_size": 1024,
                    "smoothquant_alpha": 0.5, "calib_batch": [2, 32]},
          "scheduler": {"block_size": 16, "max_blocks_per_req": 8,
                        "max_batch": 4, "prefill_chunk": 64,
                        "token_budget": 68}}
LIMIT = 1.4


def make(base, limit: float = LIMIT):
    """Write the tiny MoE cell ``tiny-moe.closed`` under ``base`` (beside
    ``tiny.make``'s); -> its workload."""
    tiny.make(base)
    shutil.copy(registry.BENCH / "reference" / "deepseek_moe.py",
                base / "reference" / "deepseek_moe.py")
    (base / "configs" / "tiny-moe.json").write_text(json.dumps(CONFIG))
    cell = "tiny-moe.closed"
    (base / "checks" / f"{cell}.json").write_text(json.dumps(
        {"widest_logit_gap": {"limit": limit}, "sample_tokens": 40,
         "max_requests": 3}))
    return {"name": cell, "config": "tiny-moe", "traffic": "closed",
            "chips": 1}
