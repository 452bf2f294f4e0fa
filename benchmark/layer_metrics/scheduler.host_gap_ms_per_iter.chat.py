"""Device-idle time inside `serve.iteration` and outside the two fetch spans,
per iteration, in this cell:
benchmark/span_readings.py, `host_gap_ms`."""
from benchmark.span_readings import host_gap_ms as read  # noqa: F401
