"""The grouped expert GEMM launches' least time at the chip's int8 peak
or HBM bandwidth over their device time in the traced window
(``lib/moe.py``)."""
from bench.lib.moe import expert_gemm_roofline as read  # noqa: F401
