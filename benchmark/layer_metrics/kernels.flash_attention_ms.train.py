"""Device time under the scope `flash_attention` per train step, in this cell:
benchmark/span_readings.py, `flash_attention_ms`."""
from benchmark.span_readings import flash_attention_ms as read  # noqa: F401
