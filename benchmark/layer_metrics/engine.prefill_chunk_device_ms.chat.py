"""Median device time of one execution of `jit__prefill`, in this cell:
benchmark/span_readings.py, `prefill_chunk_device_ms`."""
from benchmark.span_readings import prefill_chunk_device_ms as read  # noqa: F401
