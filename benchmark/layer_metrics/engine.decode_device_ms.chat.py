"""Median device time of one execution of the decode program, in this cell:
benchmark/span_readings.py, `decode_device_ms`."""
from benchmark.span_readings import decode_device_ms as read  # noqa: F401
