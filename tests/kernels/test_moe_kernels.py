"""The DeepSeek-V3 block's kernels in interpret mode against their
oracles, at the edges: the grouped expert GEMM (groups of exactly one row
tile, one row past it, an expert with no rows, tiles past the used ones,
several output-column tiles) bitwise; paged MLA decode (a lane of one
token, lanes at and one past a block boundary, a lane filling its table,
blocks scattered through the pool) within float rounding, since it
streams an online softmax where the oracle takes one softmax.  And the
fused serving step of a tiny MoE model holds no float copy of an expert
weight."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import expert_gemm as eg
from repro.kernels import ref
from repro.kernels.paged_attention import mla_paged_decode_attention
from test_paged_suite import _mla_pool

TM = 32


def _layout(sizes):
    """Row tiles for groups of ``sizes`` rows, each padded to TM."""
    tiles = [-(-s // TM) for s in sizes]
    te = np.repeat(np.arange(len(sizes)), tiles).astype(np.int32)
    used = len(te)
    total = used + 2                                 # two unused tiles
    te = np.concatenate([te, np.full(total - used, len(sizes) - 1,
                                     np.int32)])
    return jnp.asarray(te), jnp.asarray([used], jnp.int32), total * TM


@pytest.mark.parametrize("sizes,k,n,block", [
    ([TM, 0, TM + 1, 3], 256, 256, None),          # whole N in one block
    ([1, 2 * TM, 0, 0, 5], 128, 384, 128 * 128),   # three column tiles
])
def test_expert_gemm_matches_oracle(sizes, k, n, block, monkeypatch):
    if block is not None:
        monkeypatch.setattr(eg, "WEIGHT_BLOCK_BYTES", block)
    assert eg.column_tile(k, n) == (n if block is None else 128)
    te, used, r = _layout(sizes)
    rs = np.random.RandomState(len(sizes))
    e = len(sizes)
    x = jnp.asarray(rs.randint(-128, 128, (r, k)), jnp.int8)
    xs = jnp.asarray(rs.uniform(0.01, 0.1, (r, 1)), jnp.float32)
    w = jnp.asarray(rs.randint(-128, 128, (e, k, n)), jnp.int8)
    ws = jnp.asarray(rs.uniform(0.01, 0.1, (e, 1, n)), jnp.float32)
    got = eg.expert_gemm(x, xs, w, ws, te, used, tm=TM, interpret=True)
    want = ref.expert_gemm_ref(x, xs, w, ws, te, used, tm=TM)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    rows = int(used[0]) * TM
    assert not np.asarray(got)[rows:].any()
    # row 0 is expert 0's: a plain int8 product
    one = (np.asarray(x[0], np.int64) @ np.asarray(w[0], np.int64)
           ).astype(np.float32) * np.asarray(xs[0]) * np.asarray(ws[0, 0])
    np.testing.assert_allclose(np.asarray(got)[0], one, rtol=1e-6)


@pytest.mark.parametrize("n", [24, 300])
def test_mla_decode_kernel_matches_oracle(n):
    """Lanes at a block edge, one past it, a whole table and a partial last
    block; at 300 blocks the scales span three 128-lane tiles, the last
    one partial."""
    b, h, rkv, dr, dn, dv, t, m = 5, 4, 32, 16, 16, 16, 16, 6
    c_vals, c_scale, kr_vals, kr_scale = _mla_pool(b, rkv, dr, n, t)
    rs = np.random.RandomState(7)
    trash = n - 1
    lengths = np.array([1, 16, 17, m * t, 40], np.int32)   # tokens attended
    tables = np.full((b, m), trash, np.int32)
    ids = rs.permutation(n - 1)
    at = 0
    for i, length in enumerate(lengths):
        live = -(-length // t)
        tables[i, :live] = ids[at:at + live] if at + live <= n - 1 \
            else rs.choice(n - 1, live, replace=False)
        at += live
    q_nope = jax.random.normal(jax.random.PRNGKey(1), (b, h, dn))
    q_rope = jax.random.normal(jax.random.PRNGKey(2), (b, h, dr))
    w_uk = jax.random.normal(jax.random.PRNGKey(3), (rkv, h, dn)) * 0.2
    w_uv = jax.random.normal(jax.random.PRNGKey(4), (rkv, h, dv)) * 0.2
    bt, ln = jnp.asarray(tables), jnp.asarray(lengths)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope, w_uk)
    o_lat = mla_paged_decode_attention(
        q_lat, q_rope, c_vals, c_scale, kr_vals, kr_scale, bt, ln,
        qk_nope_dim=dn, interpret=True)
    got = jnp.einsum("bhr,rhd->bhd", o_lat, w_uv)
    want = ref.mla_paged_decode_attention_ref(
        q_nope, q_rope, w_uk, w_uv, c_vals, c_scale, kr_vals, kr_scale, bt,
        ln)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # lane 0 attends one token: its output is that token's value
    c0 = (np.asarray(c_vals[tables[0, 0], 0], np.float32)
          * np.asarray(c_scale[0, tables[0, 0]]))
    np.testing.assert_allclose(np.asarray(o_lat)[0],
                               np.broadcast_to(c0, (h, rkv)), rtol=1e-5,
                               atol=1e-5)


def test_moe_step_keeps_expert_weights_int8():
    """The compiled decode step of a tiny Moonlight-shaped W8A8 engine:
    the expert codes enter it as int8, and no float array of an expert
    weight's shape exists in it."""
    from bench.lib import registry
    from bench.tests import tiny_moe
    from repro.core import QuantPolicy, quantize_tree
    from repro.models.config import LayerSpec, ModelConfig
    from repro.serving.engine import PagedServeEngine
    from repro.serving.scheduler import SchedulerConfig
    refm = registry.reference("deepseek_moe")
    kw = refm.program_config(tiny_moe.MODEL)
    kw["layer_pattern"] = tuple(LayerSpec(*p) for p in kw["layer_pattern"])
    cfg = ModelConfig(name="tiny-moe", **kw)
    params = quantize_tree(refm.make_weights(tiny_moe.MODEL, 1),
                           QuantPolicy(min_size=1024))
    scfg = SchedulerConfig(num_blocks=32, max_batch=4, max_blocks_per_req=8,
                           prefill_chunk=16, token_budget=20)
    sched = PagedServeEngine(params, cfg, scfg).scheduler
    b, mm = scfg.max_batch, scfg.max_blocks_per_req
    dec = (jnp.zeros((b,), jnp.int32),
           jnp.full((b, mm), sched.trash, jnp.int32),
           jnp.ones((b,), jnp.int32), jnp.zeros((b,), jnp.int32))
    hlo = sched._step_fn.lower(
        sched.params, sched.pool, sched.spool, *dec,
        *sched._build_pf_args(None), do_prefill=False, do_decode=True,
        pf_first=True).compile().as_text()
    e, d, f = cfg.n_experts, cfg.d_model, cfg.expert_d_ff
    shapes = (f"{e},{d},{f}", f"{e},{f},{d}")
    assert any(f"s8[{s}]" in hlo or re.search(rf"s8\[\d+,{s}\]", hlo)
               for s in shapes)
    for s in shapes:
        assert not re.search(rf"(f32|bf16|f16)\[(\d+,)?{s}\]", hlo), s


def test_mla_chunk_row_tiles_change_no_float(monkeypatch):
    """Where the chunk's query rows do not fit VMEM in one program, the
    MLA chunk kernel splits them into tiles (Moonlight: 16 heads x 128
    positions); each row's softmax is one-shot either way, so the tiled
    launch is bitwise the whole one, chunk rows past a tile edge
    included."""
    import importlib
    pa = importlib.import_module("repro.kernels.paged_attention")
    c, h, rkv, dr, n, t, m = 8, 4, 32, 16, 12, 16, 4
    pool = _mla_pool(1, rkv, dr, n, t)
    keys = jax.random.split(jax.random.PRNGKey(9), 4)
    q_lat = jax.random.normal(keys[0], (1, c, h, rkv))
    q_rope = jax.random.normal(keys[1], (1, c, h, dr))
    c_chunk = jax.random.normal(keys[2], (1, c, rkv))
    kr_chunk = jax.random.normal(keys[3], (1, c, dr))
    row = jnp.asarray([3, 7, 1, n - 1], jnp.int32)
    args = (q_lat, q_rope, *pool, c_chunk, kr_chunk, row, jnp.int32(40))
    run = lambda: np.asarray(pa.mla_paged_prefix_chunk_attention(
        *args, qk_nope_dim=16, interpret=True))
    whole = run()
    cols = m * t + c
    assert pa.mla_chunk_tile(c, h, cols, rkv, dr) == c
    tight = pa.mla_chunk_vmem_bytes(2, h, cols, rkv, dr)
    monkeypatch.setattr(pa, "VMEM_LIMIT_BYTES", tight)
    assert pa.mla_chunk_tile(c, h, cols, rkv, dr) == 2
    pa.mla_paged_prefix_chunk_attention.clear_cache()
    np.testing.assert_array_equal(run(), whole)
