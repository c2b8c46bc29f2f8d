"""Model FLOPs of the window's tokens, the routed experts counted by their
held pairs and attention in MLA's absorbed form, over the summed step wall
time times the chip's int8 peak (``lib/moe.py``)."""
from bench.lib.moe import step_mfu as read  # noqa: F401
