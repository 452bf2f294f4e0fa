"""The fused decode step's time in this cell: benchmark/readings.py,
`decode_step_ms`."""
from benchmark.readings import decode_step_ms as read  # noqa: F401
