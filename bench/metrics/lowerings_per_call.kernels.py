"""Lowerings per run_program call (bench.program_spans.lowerings_per_call)."""
from bench.program_spans import lowerings_per_call as read  # noqa: F401
