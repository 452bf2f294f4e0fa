"""Device time under the scope `decode_attention` per decode step, in this cell:
benchmark/span_readings.py, `decode_attention_ms`."""
from benchmark.span_readings import decode_attention_ms as read  # noqa: F401
