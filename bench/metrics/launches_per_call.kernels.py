"""Device launches per run_program call (bench.program_spans.launches_per_call)."""
from bench.program_spans import launches_per_call as read  # noqa: F401
