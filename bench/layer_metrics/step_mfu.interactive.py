"""Model FLOPs of the tokens the window's steps processed over the summed
step wall time times the chip's int8 peak (``lib/view.py``)."""
from bench.lib.view import step_mfu as read  # noqa: F401
