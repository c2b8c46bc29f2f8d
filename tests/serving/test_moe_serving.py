"""A Moonlight-shaped (DeepSeek-V3 block) W8A8 model served through the
paged engine: the scheduler's MoE counters equal a recount from its own
token counts, reach the host in the step's one transfer, and the decode
spans carry the batch's summed context."""
import numpy as np
import pytest

from repro.core import QuantPolicy, quantize_tree
from repro.models.config import LayerSpec, ModelConfig
from repro.obs import Tracer
from repro.serving.engine import PagedServeEngine, Request
from repro.serving.scheduler import SchedulerConfig, paged_unsupported_reason


@pytest.fixture(scope="module")
def served():
    from bench.lib import registry
    from bench.tests import tiny_moe
    ref = registry.reference("deepseek_moe")
    kw = ref.program_config(tiny_moe.MODEL)
    kw["layer_pattern"] = tuple(LayerSpec(*p) for p in kw["layer_pattern"])
    cfg = ModelConfig(name="tiny-moe", **kw)
    assert paged_unsupported_reason(cfg) is None
    params = quantize_tree(ref.make_weights(tiny_moe.MODEL, 5),
                           QuantPolicy(min_size=1024))
    scfg = SchedulerConfig(num_blocks=32, max_batch=4, max_blocks_per_req=8,
                           prefill_chunk=16, token_budget=20)
    tracer = Tracer(capacity=1 << 14)
    eng = PagedServeEngine(params, cfg, scfg, tracer=tracer)
    rng = np.random.default_rng(0)
    for i, n in enumerate((40, 9, 23)):
        eng.add_request(Request(uid=i, prompt=rng.integers(0, 512, n),
                                max_new_tokens=6))
    eng.run()
    return cfg, eng.scheduler, tracer


def test_counters_equal_a_recount(served):
    cfg, sched, _ = served
    st = sched.stats
    layers = cfg.n_layers - cfg.first_k_dense_replace
    tokens = st["prefill_tokens"] + st["decode_tokens"]
    assert st["moe_pairs_routed"] == cfg.n_experts_active * layers * tokens
    assert st["moe_rows_held_steps"] == layers * (st["prefill_chunks"]
                                                  + st["decode_steps"])
    assert 0 < st["moe_pairs_held"] < st["moe_pairs_routed"]
    assert 0 < st["moe_rows_peak"] <= st["moe_pairs_held"]
    assert 0 < st["moe_experts_hit"] \
        <= cfg.n_experts * st["moe_rows_held_steps"]


def test_decode_spans_carry_the_summed_context(served):
    _, sched, tracer = served
    steps = [e for e in tracer.events if e.kind == "decode_step"]
    assert steps
    for s in steps:
        # every lane has its prompt in the cache before it decodes
        assert s.args["ctx"] >= 9 * s.args["batch"]
