"""Paged MLA decode's least time, the live latent bytes of the traced
decode steps at HBM bandwidth, over its mean launch time (``lib/moe.py``)."""
from bench.lib.moe import mla_decode_roofline as read  # noqa: F401
