"""A tiny cell for CPU tests: the qwen2 layout at toy widths, in a registry
of its own under a temporary directory.  At these widths the program's
widest gap reads 0.10-0.18 and the int4 control's 0.56-0.69 (CPU, three
seeds), so the tiny limit 0.3 sits between them."""
import json
import shutil

from bench.lib import registry

MODEL = {"hidden_size": 256, "intermediate_size": 512,
         "num_attention_heads": 4, "num_hidden_layers": 2,
         "num_key_value_heads": 2, "head_dim": 64, "rms_norm_eps": 1e-06,
         "rope_theta": 1000000.0, "tie_word_embeddings": True,
         "vocab_size": 4096, "qkv_bias": True}
CONFIG = {"reference": "qwen_dense", "reduced": [], "model": MODEL,
          "quant": {"method": "symmetric", "min_size": 1024,
                    "smoothquant_alpha": 0.5, "calib_batch": [2, 32]},
          "scheduler": {"block_size": 16, "max_blocks_per_req": 8,
                        "max_batch": 4, "prefill_chunk": 16,
                        "token_budget": 20}}
LENGTHS = {"prompt": {"median": 24, "sigma": 0.5, "min": 8, "max": 60},
           "output": {"median": 12, "sigma": 0.5, "min": 4, "max": 40},
           "deck": 64}
MIXES = {"closed": dict(generator="lognormal", loop="closed", clients=4,
                        **LENGTHS),
         "open": dict(generator="lognormal", loop="open", rate_per_s=20.0,
                      ramp_s=0.5, trace_s=0.5, **LENGTHS)}


def make(base, limit: float = 0.3):
    """Write the tiny registry under ``base``; -> its workloads."""
    for d in ("configs", "traffic", "checks", "reference"):
        (base / d).mkdir(parents=True, exist_ok=True)
    shutil.copy(registry.BENCH / "reference" / "qwen_dense.py",
                base / "reference" / "qwen_dense.py")
    for d in ("layer_metrics", "generators"):
        shutil.copytree(registry.BENCH / d, base / d, dirs_exist_ok=True)
    (base / "configs" / "tiny.json").write_text(json.dumps(CONFIG))
    out = {}
    for name, mix in MIXES.items():
        (base / "traffic" / f"{name}.json").write_text(json.dumps(mix))
        cell = f"tiny.{name}"
        (base / "checks" / f"{cell}.json").write_text(json.dumps(
            {"widest_logit_gap": {"limit": limit}, "sample_tokens": 40,
             "max_requests": 3}))
        out[name] = {"name": cell, "config": "tiny", "traffic": name,
                     "chips": 1}
    return out
