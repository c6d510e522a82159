"""Geometric mean roofline share of the L2 problems (bench.readers.l2_roofline)."""
from bench.readers import l2_roofline as read  # noqa: F401
