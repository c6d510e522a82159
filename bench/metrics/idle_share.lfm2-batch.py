"""Device idle share of the traced window (bench.readers.idle_share)."""
from bench.readers import idle_share as read  # noqa: F401
