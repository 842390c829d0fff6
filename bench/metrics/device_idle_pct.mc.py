"""Device idle share of the traced window (profiler trace)."""
from bench.metrics._device import idle_pct as read  # noqa: F401
