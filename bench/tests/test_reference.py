"""The plain reference agrees with the program's own dense float32 forward
pass on the same weights (a check of the reference, not of the program's
serving path), and its controls are coarser than it."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import registry
from bench.tests import tiny


@pytest.fixture(scope="module")
def setup():
    from repro.models import forward_train
    from repro.models.config import LayerSpec, ModelConfig
    ref = registry.reference("qwen_dense")
    out = {}
    for name, extra in (("qwen2", {"qkv_bias": True}),
                        ("qwen3", {"qkv_bias": False, "qk_norm": True})):
        model = dict(tiny.MODEL, **extra)
        kw = ref.program_config(model)
        kw["layer_pattern"] = tuple(LayerSpec(*p)
                                    for p in kw["layer_pattern"])
        mcfg = ModelConfig(name=name, dtype="float32", **kw)
        w = ref.make_weights(model, 3)
        toks = np.random.default_rng(0).integers(0, 4096, 90).astype(np.int32)
        with jax.default_matmul_precision("highest"):
            zp, _, _ = forward_train(
                jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w),
                jnp.asarray(toks)[None], mcfg)
        out[name] = (ref, model, w, toks, np.asarray(zp[0]))
    return out


@pytest.mark.parametrize("name", ["qwen2", "qwen3"])
def test_reference_matches_dense_forward(setup, name):
    ref, model, w, toks, zp = setup[name]
    z = np.asarray(ref.logits(w, model, toks, 0))[:len(toks)]
    assert np.abs(z - zp).max() < 1e-4 * np.abs(zp).max()


@pytest.mark.parametrize("name", ["qwen2", "qwen3"])
def test_rows_from_first(setup, name):
    ref, model, w, toks, zp = setup[name]
    z = np.asarray(ref.logits(w, model, toks, 40))
    assert z.shape[0] % ref.ROWS == 0
    np.testing.assert_allclose(z[:50], zp[40:], atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("control", ["int4", "int4-weights"])
def test_controls_differ(setup, control):
    ref, model, w, toks, zp = setup["qwen2"]
    z = np.asarray(ref.logits(w, model, toks, 0, control=control))[:90]
    assert np.abs(z - zp).max() > 1e-3 * np.abs(zp).max()
