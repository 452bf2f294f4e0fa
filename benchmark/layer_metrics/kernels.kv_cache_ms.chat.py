"""Device time under `kv_cache_update` and under `decode_layers` outside its
inner scopes, per decode step, in this cell:
benchmark/span_readings.py, `kv_cache_ms`."""
from benchmark.span_readings import kv_cache_ms as read  # noqa: F401
