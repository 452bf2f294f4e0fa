"""Device time under `moe_router`, `moe_dispatch` and `moe_combine` per decode
step, in this cell:
benchmark/span_readings.py, `moe_dispatch_ms`."""
from benchmark.span_readings import moe_dispatch_ms as read  # noqa: F401
