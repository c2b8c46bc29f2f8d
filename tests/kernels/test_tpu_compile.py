"""Compile every main-path Pallas kernel for a v5e at real widths.

Interpret mode checks neither the TPU tiling rules nor the VMEM limit; the
TPU compiler, which is installed here, does — for a chip that is described
and not attached.  Widths: qwen2-0.5b (14 heads over 2 KV heads,
head_dim 64, d_ff 4864, vocab 151936), qwen3-1.7b (16 over 8, head_dim
128, d_model 2048, d_ff 6144), the GQA decode cells' slots, tables and
pools, minicpm3-4b's MLA latent (rank 256,
rope 32, 40 heads) and Moonlight-16B-A3B's (rank 512, rope 64, nope 128,
16 heads; 8 held experts of width 1408 at d_model 2048; 64 slots with
160-block tables over a 10,240-block pool).  The topology is described
inside a fixture, never at import: only one process may load the TPU
library at a time.
"""
import importlib
import math
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import minicpm3_4b, qwen2_0_5b, qwen3_1_7b
from repro.models.config import LayerSpec, ModelConfig
from repro.models.moe import ROW_TILE
from repro.serving import paged_cache as pgc
from repro.serving.scheduler import (SchedulerConfig, _decode_vmem_bytes,
                                     _kernel_vmem_bytes, ensure_kernels_fit)
from repro.serving.spec_decode import SpecConfig

kd = importlib.import_module("repro.kernels.kv_decode_attention")
pa = importlib.import_module("repro.kernels.paged_attention")
fq = importlib.import_module("repro.kernels.fused_quant")
wm = importlib.import_module("repro.kernels.w8a8_matmul")
eg = importlib.import_module("repro.kernels.expert_gemm")

I8, I32, F32, BF16 = jnp.int8, jnp.int32, jnp.float32, jnp.bfloat16
B, N, T, G, C = 8, 512, 16, 5, 64        # batch, pool blocks, block, verify, chunk
WIDTHS = {"qwen2": (14, 2, 64), "qwen3": (16, 8, 128)}   # heads, kv heads, hd
RKV, DR, DN, HM = 256, 32, 64, 40                          # minicpm3 MLA
MOON = dict(rkv=512, dr=64, dn=128, h=16, d=2048, f=1408, e=8, b=64, m=160,
            n=10240 + 1, c=128)
MOONLIGHT = ModelConfig(
    name="moonlight-16b-a3b", vocab_size=163840, d_model=2048, n_layers=27,
    n_heads=16, d_ff=11264, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, n_experts=8, n_routed_experts=64,
    n_experts_active=6, moe_d_ff=1408, n_shared_experts=2,
    moe_score="sigmoid", moe_score_bias=True, moe_routed_scale=2.446,
    capacity_factor=0.0, first_k_dense_replace=1,
    layer_pattern=(LayerSpec("mla", "moe"),))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def shape(topo):
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dt=F32: jax.ShapeDtypeStruct(dims, dt,
                                                     sharding=one_chip)


def _gqa_pool(S, kh, d, bits, per_slot, b=B, n=N):
    dp = d if bits == 8 else d // 2
    scale = (b, kh, d) if per_slot else (kh, d)
    return (S((n, T, kh * dp), I8), S(scale), S(scale),
            S((n, T, kh * dp), I8), S((kh, T, n)), S((kh, T, n)))


def _decode(S, model, bits, m=32, b=B, n=N):
    h, kh, d = WIDTHS[model]
    return (kd.paged_kv_decode_attention, S((b, h, d)),
            *_gqa_pool(S, kh, d, bits, True, b, n), S((b, m), I32),
            S((b,), I32))


def _verify(S, model, bits, m=32):
    h, kh, d = WIDTHS[model]
    return (pa.paged_kv_verify_attention, S((B, G, h, d)),
            *_gqa_pool(S, kh, d, bits, True), S((B, m), I32), S((B,), I32))


def _chunk(S, model, bits, m=32, c=C):
    h, kh, d = WIDTHS[model]
    return (pa.paged_prefix_chunk_attention, S((1, c, h, d)),
            *_gqa_pool(S, kh, d, bits, False), S((1, c, kh, d)),
            S((1, c, kh, d)), S((m,), I32), S((), I32))


def _mla_verify(S, bits, m=32):
    rp, drp = (RKV, DR) if bits == 8 else (RKV // 2, DR // 2)

    def fn(*a):
        return pa.mla_paged_verify_attention(*a, qk_nope_dim=DN)
    return (fn, S((B, G, HM, RKV)), S((B, G, HM, DR)), S((N, T, rp), I8),
            S((T, N)), S((N, T, drp), I8), S((T, N)), S((B, m), I32),
            S((B,), I32))


def _mla_chunk(S, bits, m=32, c=32):
    rp, drp = (RKV, DR) if bits == 8 else (RKV // 2, DR // 2)

    def fn(*a):
        return pa.mla_paged_prefix_chunk_attention(*a, qk_nope_dim=DN)
    return (fn, S((1, c, HM, RKV)), S((1, c, HM, DR)), S((N, T, rp), I8),
            S((T, N)), S((N, T, drp), I8), S((T, N)), S((1, c, RKV)),
            S((1, c, DR)), S((m,), I32), S((), I32))


def _moon_chunk(S, m=MOON["m"], c=MOON["c"]):
    rkv, dr, h = MOON["rkv"], MOON["dr"], MOON["h"]

    def fn(*a):
        return pa.mla_paged_prefix_chunk_attention(*a, qk_nope_dim=MOON["dn"])
    return (fn, S((1, c, h, rkv)), S((1, c, h, dr)), S((N, T, rkv), I8),
            S((T, N)), S((N, T, dr), I8), S((T, N)), S((1, c, rkv)),
            S((1, c, dr)), S((m,), I32), S((), I32))


def _moon_decode(S, m=MOON["m"]):
    rkv, dr, h, b, n = (MOON[k] for k in ("rkv", "dr", "h", "b", "n"))

    def fn(*a):
        return pa.mla_paged_decode_attention(*a, qk_nope_dim=MOON["dn"])
    return (fn, S((b, h, rkv)), S((b, h, dr)), S((n, T, rkv), I8),
            S((T, n)), S((n, T, dr), I8), S((T, n)), S((b, m), I32),
            S((b,), I32))


def _expert(S, tokens, k, n):
    """The grouped GEMM over a step's rows: every (token, expert) pair of
    top-6 may land here, each held expert's group padded to the tile."""
    e = MOON["e"]
    r = ROW_TILE * -(-(tokens * 6 + e * (ROW_TILE - 1)) // ROW_TILE)

    def fn(*a):
        return eg.expert_gemm(*a, tm=ROW_TILE)
    return (fn, S((r, k), I8), S((r, 1)), S((e, k, n), I8), S((e, 1, n)),
            S((r // ROW_TILE,), I32), S((1,), I32))


def _dense_decode(S, model, s=517):
    h, kh, d = WIDTHS[model]
    return (kd.kv_decode_attention, S((B, h, d)), S((B, s, kh, d), I8),
            S((B, 1, kh, d)), S((B, 1, kh, d)), S((B, s, kh, d), I8),
            S((B, s, kh, 1)), S((B, s, kh, 1)), S((B,), I32))


def _quant(S, dt):
    return fq.fused_quant, S((512, 4864), dt)


def _gemm(S, m, k, n):
    return (wm.w8a8_matmul, S((m, k), I8), S((m, 1)), S((k, n), I8),
            S((1, n)))


# the decode cells' launches: slots, table width and pool blocks (+ trash)
DECODE_CELLS = {"qwen2_batch_decode": ("qwen2", 32, 256, 8193),
                "qwen3_long_prompt": ("qwen3", 6, 256, 1537)}

CASES = {
    **{f"paged_decode_{mo}_int{b}": (_decode, mo, b)
       for mo in WIDTHS for b in (8, 4)},
    **{f"paged_decode_{c}_int{bits}": (_decode, mo, bits, m, b, n)
       for c, (mo, b, m, n) in DECODE_CELLS.items() for bits in (8, 4)},
    **{f"verify_{mo}_int{b}": (_verify, mo, b)
       for mo in WIDTHS for b in (8, 4)},
    **{f"prefix_chunk_{mo}_int{b}": (_chunk, mo, b)
       for mo in WIDTHS for b in (8, 4)},
    **{f"mla_verify_int{b}": (_mla_verify, b) for b in (8, 4)},
    **{f"mla_prefix_chunk_int{b}": (_mla_chunk, b) for b in (8, 4)},
    **{f"dense_decode_{mo}": (_dense_decode, mo) for mo in WIDTHS},
    "fused_quant_f32_4864": (_quant, F32),
    "fused_quant_bf16_4864": (_quant, BF16),
    "w8a8_decode_qwen2_up": (_gemm, 8, 896, 4864),
    "w8a8_decode_qwen2_down": (_gemm, 8, 4864, 896),
    "w8a8_prefill_qwen2_vocab": (_gemm, 512, 896, 151936),
    "w8a8_decode_qwen3_up": (_gemm, 8, 2048, 6144),
    "expert_gemm_moonlight_decode_up": (_expert, 64, 2048, 1408),
    "expert_gemm_moonlight_decode_down": (_expert, 64, 1408, 2048),
    "expert_gemm_moonlight_prefill_up": (_expert, 128, 2048, 1408),
    "mla_decode_moonlight": (_moon_decode,),
    "mla_prefix_chunk_moonlight": (_moon_chunk,),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, shape):
    build, *args = CASES[case]
    fn, *shapes = build(shape, *args)
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


# Either side of the VMEM line of each one-shot kernel: the engine's build-
# time estimate (ensure_kernels_fit) and the compiler must agree.
LINE = [
    # kernel, config, scheduler fields, blocks accepted, blocks refused
    ("prefix-chunk", qwen2_0_5b.FULL, dict(prefill_chunk=64), 256, 512),
    ("prefix-chunk", qwen3_1_7b.FULL, dict(prefill_chunk=128), 256, 512),
    ("verify", qwen2_0_5b.FULL,
     dict(prefill_chunk=16, spec=SpecConfig(gamma=4)), 512, 1024),
    # the MLA chunk kernel splits its query rows into tiles that fit, so
    # its line is where one chunk position's rows no longer do
    ("mla prefix-chunk", minicpm3_4b.FULL, dict(prefill_chunk=32), 256, 1024),
    ("mla prefix-chunk", MOONLIGHT, dict(prefill_chunk=128), 160, 512),
]


def _launch(kernel, cfg, scfg, blocks, S):
    if kernel == "mla prefix-chunk" and cfg is MOONLIGHT:
        return _moon_chunk(S, blocks, scfg.prefill_chunk)
    if kernel == "mla prefix-chunk":
        return _mla_chunk(S, 8, blocks, scfg.prefill_chunk)
    model = "qwen2" if cfg.hd == 64 else "qwen3"
    assert WIDTHS[model] == (cfg.n_heads, cfg.kv_heads, cfg.hd)
    if kernel == "prefix-chunk":
        return _chunk(S, model, 8, blocks, scfg.prefill_chunk)
    return _verify(S, model, 8, blocks)


@pytest.mark.parametrize("kernel,cfg,fields,ok_blocks,bad_blocks", LINE,
                         ids=[f"{k}-{c.name}" for k, c, *_ in LINE])
def test_vmem_guard_agrees_with_compiler(kernel, cfg, fields, ok_blocks,
                                         bad_blocks, shape):
    for blocks, fits in ((ok_blocks, True), (bad_blocks, False)):
        scfg = SchedulerConfig(block_size=T, max_blocks_per_req=blocks,
                               **fields)
        need = _kernel_vmem_bytes(cfg, scfg, blocks)[kernel]
        assert (need <= pa.VMEM_LIMIT_BYTES) == fits, (blocks, need)
        fn, *shapes = _launch(kernel, cfg, scfg, blocks, shape)
        if fits:
            jax.jit(fn).lower(*shapes).compile()
        else:
            with pytest.raises(ValueError, match="VMEM"):
                ensure_kernels_fit(cfg, scfg)
            with pytest.raises(Exception, match="(?i)vmem"):
                jax.jit(fn).lower(*shapes).compile()


def test_mla_decode_guard_agrees_with_compiler(shape):
    """The MLA decode kernel streams one block at a time: the engine's
    estimate does not grow with the table, lies under the limit, and the
    compiler takes the cell's table and one twice as long."""
    needs = set()
    for blocks in (MOON["m"], 2 * MOON["m"]):
        scfg = SchedulerConfig(block_size=T, max_blocks_per_req=blocks,
                               max_batch=MOON["b"], prefill_chunk=16)
        needs.add(_kernel_vmem_bytes(MOONLIGHT, scfg, blocks)["mla decode"])
        fn, *shapes = _moon_decode(shape, blocks)
        jax.jit(fn).lower(*shapes).compile()
    assert len(needs) == 1 and needs.pop() <= pa.VMEM_LIMIT_BYTES


# The GQA decode kernel holds a layer's V scale and zero leaves whole, so
# its line is in pool blocks: the engine's estimate and the compiler agree
# on a pool just under it and one just over it, at each decode cell's
# widths and slots.
DECODE_LINE = [
    # config, slots, pool blocks accepted, pool blocks refused
    (qwen2_0_5b.FULL, 32, 250_000, 262_144),
    (qwen3_1_7b.FULL, 6, 58_000, 61_440),
]


@pytest.mark.parametrize("cfg,slots,ok_blocks,bad_blocks", DECODE_LINE,
                         ids=[c.name for c, *_ in DECODE_LINE])
def test_decode_guard_agrees_with_compiler(cfg, slots, ok_blocks,
                                           bad_blocks, shape):
    model = "qwen2" if cfg.hd == 64 else "qwen3"
    for blocks, fits in ((ok_blocks, True), (bad_blocks, False)):
        scfg = SchedulerConfig(block_size=T, num_blocks=blocks,
                               max_batch=slots, max_blocks_per_req=256,
                               prefill_chunk=16)
        need = _decode_vmem_bytes(cfg, scfg, blocks)
        assert (need <= kd.DECODE_VMEM_LIMIT_BYTES) == fits, (blocks, need)
        fn, *shapes = _decode(shape, model, 8, 256, slots, blocks + 1)
        if fits:
            ensure_kernels_fit(cfg, scfg)
            jax.jit(fn).lower(*shapes).compile()
        else:
            with pytest.raises(ValueError, match="num_blocks"):
                ensure_kernels_fit(cfg, scfg)
            with pytest.raises(Exception, match="(?i)vmem"):
                jax.jit(fn).lower(*shapes).compile()


# One scanned layer body of the paged step at qwen2-0.5b batch-decode's
# shapes: 24 layers, 8,192 blocks + trash of 16 tokens, 32 slots with
# 256-block tables, prefill chunk 64.  The pool leaves must reach the
# kernels as stored: a copy or transpose the size of one layer's pool leaf
# is a relayout of the whole pool around every attention launch.
POOL = pgc.PagedCacheConfig(block_size=T, num_blocks=8192, max_batch=32,
                            max_blocks_per_req=256)
_HLO_OP = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\w+\[([\d,]*)\]"
                     r"\S*\s+([\w\-]+)\(")


def _pool_relayouts(hlo: str, pool) -> list:
    """Copy / transpose instructions of the optimized HLO whose result has
    the element count of one layer of a GQA pool leaf."""
    sizes = {math.prod(leaf.shape[1:]) for name, leaf in pool.items()
             if name in pgc.BLOCK_LEAVES}
    hits = []
    for line in hlo.splitlines():
        m = _HLO_OP.match(line)
        if m is None:
            continue
        name, dims, opcode = m.groups()
        relayout = any(x.startswith(("copy", "transpose"))
                       for x in (name, opcode))
        count = math.prod(int(x) for x in dims.split(",") if x)
        if relayout and count in sizes:
            hits.append(line.strip()[:160])
    return hits


def _layer_pool(S):
    cfg = qwen2_0_5b.FULL
    pool = jax.eval_shape(lambda: pgc.init_paged_cache(cfg, POOL))["p0"]
    return cfg, {name: S(leaf.shape, leaf.dtype) for name, leaf in pool.items()}


def _decode_layers(cfg, pool, S):
    b, m = POOL.max_batch, POOL.max_blocks_per_req

    def layers(pool, q, k_t, v_t, tables, lengths):
        def body(q, entry):
            entry = pgc.gqa_paged_append(entry, k_t, v_t, tables, lengths,
                                         block_size=T)
            out = kd.paged_kv_decode_attention(
                q, entry["k_vals"], entry["k_scale"], entry["k_zero"],
                entry["v_vals"], entry["v_scale"], entry["v_zero"], tables,
                lengths + 1)
            return out, entry
        return jax.lax.scan(body, q, pool)
    kv = S((b, cfg.kv_heads, cfg.hd))
    return layers, (pool, S((b, cfg.n_heads, cfg.hd)), kv, kv,
                    S((b, m), I32), S((b,), I32))


def _chunk_layers(cfg, pool, S):
    c, m = 64, POOL.max_blocks_per_req

    def layers(pool, q, k, v, row, slot, ctx):
        def body(q, entry):
            entry = pgc.gqa_chunk_write(entry, k, v, slot=slot, block_row=row,
                                        ctx=ctx, chunk_len=jnp.int32(c),
                                        block_size=T, is_first=False)
            out = pa.paged_prefix_chunk_attention(
                q, entry["k_vals"], entry["k_scale"][slot],
                entry["k_zero"][slot], entry["v_vals"], entry["v_scale"],
                entry["v_zero"], k[None], v[None], row, ctx)
            return out, entry
        return jax.lax.scan(body, q, pool)
    kv = S((c, cfg.kv_heads, cfg.hd))
    return layers, (pool, S((1, c, cfg.n_heads, cfg.hd)), kv, kv,
                    S((m,), I32), S((), I32), S((), I32))


@pytest.mark.parametrize("body", ["decode", "chunk"])
def test_scanned_layer_reads_pool_in_place(body, shape):
    cfg, pool = _layer_pool(shape)
    build = _decode_layers if body == "decode" else _chunk_layers
    fn, args = build(cfg, pool, shape)
    hlo = jax.jit(fn, donate_argnums=(0,)).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert _pool_relayouts(hlo, pool) == []
