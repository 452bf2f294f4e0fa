"""Device time under `moe_experts` per decode step, in this cell:
benchmark/span_readings.py, `moe_experts_ms`."""
from benchmark.span_readings import moe_experts_ms as read  # noqa: F401
