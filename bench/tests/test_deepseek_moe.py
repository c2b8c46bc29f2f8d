"""The DeepSeek-V3-block reference (Moonlight): it agrees with the
program's own float32 forward pass on the same weights, its controls are
coarser than it, and a whole run of the tiny MoE cell through the paged
W8A8 engine (prefill, then decode through the int8 latent pool) agrees
with it within the tiny cell's limit while the int4 control does not."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import harness, registry
from bench.tests import tiny_moe

SEED = 2**31 + 17


@pytest.fixture(scope="module")
def setup():
    from repro.models import forward_train
    from repro.models.config import LayerSpec, ModelConfig
    ref = registry.reference("deepseek_moe")
    kw = ref.program_config(tiny_moe.MODEL)
    kw["layer_pattern"] = tuple(LayerSpec(*p) for p in kw["layer_pattern"])
    mcfg = ModelConfig(name="tiny-moe", dtype="float32", **kw)
    w = ref.make_weights(tiny_moe.MODEL, 3)
    toks = np.random.default_rng(0).integers(0, 512, 90).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        zp, _, _ = forward_train(
            jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w),
            jnp.asarray(toks)[None], mcfg)
    return ref, w, toks, np.asarray(zp[0]), mcfg


def test_reference_matches_dense_forward(setup):
    ref, w, toks, zp, _ = setup
    z = np.asarray(ref.logits(w, tiny_moe.MODEL, toks, 0))[:len(toks)]
    assert np.abs(z - zp).max() < 1e-4 * np.abs(zp).max()


def test_rows_from_first(setup):
    ref, w, toks, zp, _ = setup
    z = np.asarray(ref.logits(w, tiny_moe.MODEL, toks, 40))
    assert z.shape[0] % ref.ROWS == 0
    np.testing.assert_allclose(z[:50], zp[40:], atol=1e-4, rtol=1e-4)


def test_controls_differ(setup):
    ref, w, toks, zp, _ = setup
    for control in ("int4", "int4-weights"):
        z = np.asarray(ref.logits(w, tiny_moe.MODEL, toks, 0,
                                  control=control))[:90]
        assert np.abs(z - zp).max() > 1e-3 * np.abs(zp).max()


def test_weights_hold_the_share(setup):
    """The weights hold the configuration's experts of a wider router, and
    the program's parameter count counts them."""
    ref, w, _, _, mcfg = setup
    m = ref.dims(tiny_moe.MODEL)
    moe = w["layers"]["p0"]["moe"]
    assert moe["experts"]["w_gate"].shape[1] == m["e"] == 8
    assert moe["gate_w"].shape[-1] == m["er"] == 16
    assert mcfg.n_experts == 8 and mcfg.router_width == 16
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(w))
    assert mcfg.param_count() == n


def test_config_states_the_source_keys_it_serves():
    """The configuration file states the source's config.json keys at its
    top level, as served here; ``model``, which the reference and the
    program read, holds the same values plus the held share's two keys,
    and each key it changed from the source is in ``reduced``."""
    cfg = registry.config("moonlight-16b-a3b")
    share = {"router_experts", "first_held_expert"}
    model = {k: v for k, v in cfg["model"].items() if k not in share}
    assert {k: cfg.get(k, "absent") for k in model} == model
    assert cfg["model"]["router_experts"] == \
        cfg["published"]["n_routed_experts"] == 64
    assert sorted(cfg["published"]) == sorted(cfg["reduced"])

def test_paged_w8a8_run_agrees_and_int4_does_not(tmp_path, monkeypatch):
    """A traced run of the tiny MoE cell: the served tokens lie within
    ``LIMIT`` of the reference's best, the int4 control's do not, and
    the MoE counters reach the per-layer metrics."""
    from bench.lib import peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    cell = tiny_moe.make(tmp_path)
    bench = registry.benchmark(registry.BENCH.parent)
    r = harness.run(cell, SEED, 1.0, True, time.perf_counter(),
                    bench["end_to_end"], bench["per_layer"], base=tmp_path,
                    controls=("int4",))
    assert r["correct"] is True
    assert r["controls"]["int4"]["correct"] is False
    assert r["failed"] == 0
    m = r["metrics"]
    # 8 of 16 router outputs held: about half the pairs land here
    assert 20 < m["moe_held_share"]["value"] < 80
    assert m["moe_peak_load"]["value"] >= 1.0
    assert 0 < m["step_mfu.moe"]["value"] < 100
    assert "expert_gemm_roofline" not in m      # no device trace on the CPU
