"""Share of the device's idle time that lowering inside run_program covers (bench.program_spans.lowering_idle_share)."""
from bench.program_spans import lowering_idle_share as read  # noqa: F401
