"""Pallas TPU kernels for the paper's fused hot paths + jnp oracles.

  fused_quant          — row-wise dynamic INT8 quantization (paper Alg. 1)
  w8a8_matmul          — INT8xINT8 MXU GEMM + fused rescale (paper Alg. 2)
  kv_decode_attention  — flash-decode over the SimQuant INT8 KV cache
  ops                  — dispatch layer (qdot / decode_attention)
  ref                  — pure-jnp oracles, the correctness contract
"""
from . import ops, ref
from .fused_quant import fused_quant
from .w8a8_matmul import w8a8_matmul
from .kv_decode_attention import kv_decode_attention
from .ops import qdot, decode_attention

__all__ = [
    "ops", "ref", "fused_quant", "w8a8_matmul", "kv_decode_attention",
    "qdot", "decode_attention",
]
