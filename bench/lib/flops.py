"""Operations and bytes, counted from shapes.

Model FLOPs for ``step_mfu``: per token processed, 2 x the matmul weights
of the layers (every stacked ``(layers, K, N)`` matrix of the weight
dictionary) plus 4 x heads x head_dim x the token's context per layer
(scores and the weighted sum), and 2 x d_model x vocab for each row whose
logits are computed.  Embedding lookups, norms, rotary and softmax are not
counted.

A W8A8 GEMM call ``(M, K) x (K, N)`` does 2 M K N integer operations and
moves its int8 operands, its float32 scales and its float32 output.
"""
from __future__ import annotations


def matmul_weights(shapes) -> int:
    """Sum of K * N over the stacked layer matrices, for all layers."""
    import jax
    total = 0
    for leaf in jax.tree_util.tree_leaves(shapes["layers"]):
        if len(leaf.shape) == 3:
            n, k, m = leaf.shape
            total += n * k * m
    return total


def model_flops(weights: int, layers: int, heads: int, head_dim: int,
                d_model: int, vocab: int, tokens: int, context: int,
                logit_rows: int) -> float:
    """FLOPs of ``tokens`` processed with summed attention context
    ``context`` (over tokens, per layer) and ``logit_rows`` logit rows."""
    return (2.0 * weights * tokens
            + 4.0 * heads * head_dim * context * layers
            + 2.0 * d_model * vocab * logit_rows)


def chunk_context(ctx: int, c: int) -> int:
    """Summed context of a prefill chunk of ``c`` tokens after ``ctx``:
    token j attends to ctx + j + 1 positions."""
    return c * ctx + c * (c + 1) // 2


def w8a8_call(m: int, k: int, n: int) -> tuple:
    """(operations, bytes) of one (M, K) x (K, N) W8A8 GEMM call."""
    ops = 2 * m * k * n
    nbytes = m * k + k * n + 4 * m + 4 * n + 4 * m * n
    return ops, nbytes
