"""The W8A8 GEMM launches' least time at the chip's int8 peak or HBM
bandwidth over their device time in the traced window (``lib/view.py``)."""
from bench.lib.view import kernel_roofline


def read(run):
    return kernel_roofline(run, "w8a8_matmul")
