"""Attention kernels' share of their roofline (bench.readers.attn_roofline)."""
from bench.readers import attn_roofline as read  # noqa: F401
