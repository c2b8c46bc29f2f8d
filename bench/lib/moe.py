"""Operations and bytes of the MoE and MLA layers (the DeepSeek-V3 block),
counted from shapes and from the program's counters.

Rooflines count the work any implementation must do, never a kernel's own
padding or walk:

  * the grouped expert GEMM (``expert_gemm``), per launch: the int8 codes
    and float32 scales of each held expert that has rows read once, the
    routed rows' int8 activations and scales read and their float32
    outputs written, and 2 x rows x K x N operations; the experts and rows
    are the means per MoE layer call over the window (``moe_experts_hit``
    and ``moe_pairs_held`` over ``moe_rows_held_steps``);
  * paged MLA decode (``mla_paged_decode_attention``): the live latent
    and rope-key bytes of the traced decode steps, int8 codes and one
    float32 scale each per token (each lane's context
    plus the token it appends, from the ``decode_step`` spans' ``ctx`` and
    ``batch``), for every layer, mapped onto the trace by ``align.py``.

Model FLOPs for ``step_mfu.moe``: per token processed, 2 x the matrices
outside the routed experts (leading layers, MLA projections, shared
experts, router), plus 6 x d_model x expert width per held (token, expert)
pair from the counters, plus MLA's absorbed attention, 2 x heads x
(2 kv_lora_rank + rope dim) per context token and layer, plus 2 x d_model
x vocab per logits row.
"""
from __future__ import annotations

from typing import Optional

from . import align, hlo, xtrace

GEMM = "expert_gemm"
MLA_DECODE = "mla_paged_decode_attention"


def per_call(counters: dict):
    """(held experts with rows, rows) per MoE layer call in the window, or
    None where the program counts neither."""
    calls = counters.get("moe_rows_held_steps", 0)
    if not calls or "moe_experts_hit" not in counters:
        return None
    return (counters["moe_experts_hit"] / calls,
            counters.get("moe_pairs_held", 0) / calls)


def gemm_cost(op: hlo.Op, experts: float, rows: float):
    """(operations, bytes) of one grouped-GEMM launch; K and N from its
    (E, K, N) int8 codes, the launch's 3-D int8 operand."""
    (_, k, n), = [d for dt, d in op.args if dt == "s8" and len(d) == 3]
    ops = 2.0 * rows * k * n
    nbytes = experts * (k * n + 4 * n) + rows * (k + 4) + 4 * rows * n
    return ops, nbytes


def _launches(run, kernel: str):
    for o in run.ops_in_window():
        p = hlo.parse(o.name)
        if p is not None and p.base == kernel:
            yield o, p


def expert_gemm_roofline(run) -> Optional[float]:
    got = per_call(run.counters)
    if got is None:
        return None
    least = spent = 0.0
    for o, p in _launches(run, GEMM):
        ops, nbytes = gemm_cost(p, *got)
        least += max(ops / run.peaks["int8_ops"],
                     nbytes / run.peaks["hbm_bytes_per_s"])
        spent += o.dur / 1e9
    return 100.0 * least / spent if spent else None


def mla_decode_roofline(run) -> Optional[float]:
    m = run.cell.ref.dims(run.cell.config["model"])
    if run.plane is None or "r" not in m:
        return None
    fitted = align.fit(run.spans, run.trace.spans.get("paged_step", []))
    if fitted is None:
        return None
    traced = {s.args["step"] for s in fitted[2]}
    tokens = steps = 0
    for s in run.spans:
        if (s.kind == "decode_step" and s.args.get("step") in traced
                and "ctx" in s.args):
            tokens += int(s.args["ctx"]) + int(s.args["batch"])
            steps += 1
    spent = [o.dur / 1e9 for o, _ in _launches(run, MLA_DECODE)]
    if not steps or not spent:
        return None
    per_token = max(2.0 * m["h"] * (2 * m["r"] + m["dr"])
                    / run.peaks["bf16_flops"],
                    (m["r"] + m["dr"] + 8) / run.peaks["hbm_bytes_per_s"])
    least = tokens * per_token / steps                  # per step and layer
    return 100.0 * least / (sum(spent) / len(spent))


def dense_weights(shapes) -> int:
    """K x N summed over every layer matrix outside the routed experts."""
    import jax
    total = 0
    for tree in (shapes.get("lead", {}), shapes["layers"]):
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            keys = [str(getattr(k, "key", "")) for k in path]
            if "experts" not in keys and len(leaf.shape) == 3:
                n, k, m = leaf.shape
                total += n * k * m
    return total


def step_mfu(run) -> Optional[float]:
    """``view.step_mfu`` with the routed experts counted by their held
    pairs and attention in MLA's absorbed form."""
    import jax
    if "moe_pairs_held" not in run.counters:
        return None
    m = run.cell.ref.dims(run.cell.config["model"])
    shapes = jax.eval_shape(
        lambda: run.cell.ref.make_weights(run.cell.config["model"], 0))
    t0, t1 = run.served.t0, run.served.t1
    tokens = context = rows = 0
    for s in run.spans:
        if s.kind == "prefill_chunk":
            c, ctx = int(s.args["tokens"]), int(s.args["ctx"])
            tokens += c
            context += c * ctx + c * (c + 1) // 2
            rows += 1
    for r in run.served.records:
        for k, t in enumerate(r.tokens):
            if k and t0 <= t < t1:
                tokens += 1
                context += r.prompt_len + k
                rows += 1
    wall = xtrace.union_length(
        (s.ts, s.ts + s.dur) for s in run.spans
        if s.kind in ("schedule", "decode_step", "prefill_chunk"))
    if not tokens or wall <= 0:
        return None
    f = (2.0 * dense_weights(shapes) * tokens
         + 6.0 * m["d"] * m["fe"] * run.counters["moe_pairs_held"]
         + 2.0 * m["h"] * (2 * m["r"] + m["dr"]) * context * m["layers"]
         + 2.0 * m["d"] * m["v"] * rows)
    return 100.0 * f / (wall * run.peaks["int8_ops"])
