"""Share of its roofline at which `decode_attention` ran, in this cell:
benchmark/readings.py, `decode_attention_roofline`."""
from benchmark.readings import decode_attention_roofline as read  # noqa: F401
