"""Executions of the kernel `flash_fwd` per train step, in this cell:
benchmark/span_readings.py, `flash_fwd_calls_per_step`."""
from benchmark.span_readings import flash_fwd_calls_per_step as read  # noqa: F401
