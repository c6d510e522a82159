"""Model flops utilization of serving (bench.readers.serve_mfu)."""
from bench.readers import serve_mfu as read  # noqa: F401
